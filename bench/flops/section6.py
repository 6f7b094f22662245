"""Operations one INTERACT iteration needs per agent on the Section-6
problem, counted from the shapes (multiply and add count as 2).

Sizes: d inputs, h hidden units in each of the two tanh layers, C classes,
n_in / n_out inner / outer samples, P = d*h + h + h*h + h backbone
parameters, m agents.  Matrix products only; elementwise work (tanh,
softmax, the CG vector updates) is left out, so the count is a floor of
what any implementation must do.

Per agent and iteration (eqs. 5-10 of arXiv 2207.13283):

* features of the inner split, computed once and shared by v, the CG
  products and the cross term:  F_in = 2 n_in (d h + h h)
* head logits on them (H_in = 2 n_in h C) and v = grad_y g, whose weight
  gradient is one more head product:  2 H_in
* CG: each H_yy product holds the features fixed and costs a tangent
  logit product and its transpose, 2 H_in; ``cg_iters`` of them
* cross term H_xy z = grad_x <grad_y g, z>: two head-sized products
  (2 H_in) and the backward through the backbone, 2 n_in h h for the
  second layer's weight and input gradients each and 2 n_in d h for the
  first layer's weight gradient:  2 H_in + 2 F2_in + F1_in
* grad_x,y f on the outer split: forward F1 + F2 + H, backward 2 H for
  the head, 2 F2 for the second layer and F1 for the first layer's
  weights:  2 F1_out + 3 F2_out + 3 H_out
* the two mixes of x and u: each M @ (m x P) is 2 m m P, per agent 2 m P.
"""
from __future__ import annotations


def interact_agent_step(conf: dict, num_agents: int) -> float:
    d, h, c = conf["d_in"], conf["hidden"], conf["classes"]
    n = conf["n_per_agent"]
    n_out = int(conf["outer_frac"] * n)
    n_in = n - n_out
    cg = conf["hypergrad"]["cg_iters"]
    f1 = lambda k: 2.0 * k * d * h
    f2 = lambda k: 2.0 * k * h * h
    hd = lambda k: 2.0 * k * h * c
    params = d * h + h + h * h + h
    inner = (f1(n_in) + f2(n_in) + 2 * hd(n_in) + cg * 2 * hd(n_in)
             + 2 * hd(n_in) + 2 * f2(n_in) + f1(n_in))
    outer = 2 * f1(n_out) + 3 * f2(n_out) + 3 * hd(n_out)
    mix = 2 * (2.0 * num_agents * params)
    return inner + outer + mix
