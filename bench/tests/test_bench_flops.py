"""The analytic operation counts against hand counts at small shapes."""
from flops import section6


def test_section6_hand_count():
    # d=2, h=3, C=2, n=10 (n_out=3, n_in=7), 1 CG product, m=2 agents.
    # F1(n)=2n*2*3=12n, F2(n)=2n*3*3=18n, H(n)=2n*3*2=12n.
    # inner: F1+F2 = 210, v: 2H = 168, CG: 1*2H = 168,
    #        cross: 2H + 2F2 + F1 = 168 + 252 + 84 = 504      -> 1050
    # outer: 2F1 + 3F2 + 3H = 72 + 162 + 108                -> 342
    # mixes: P = 2*3+3+3*3+3 = 21, 2 * (2 m P) = 168
    conf = dict(d_in=2, hidden=3, classes=2, n_per_agent=10,
                outer_frac=0.3, hypergrad={"cg_iters": 1})
    assert section6.interact_agent_step(conf, 2) == 1050 + 342 + 168


def test_section6_grows_with_cg_iterations():
    conf = dict(d_in=2, hidden=3, classes=2, n_per_agent=10,
                outer_frac=0.3, hypergrad={"cg_iters": 1})
    more = dict(conf, hypergrad={"cg_iters": 4})
    # each CG product is 2 H(n_in) = 168
    assert (section6.interact_agent_step(more, 2)
            - section6.interact_agent_step(conf, 2)) == 3 * 168
