"""Required operations of the INTERACT iterations run in the traced
window (``bench/flops/section6.py``) over the traced window's length and
the chips' bf16 peak.  The eq.-11 checks and the CG iterations past
convergence are work the count leaves out; the cell's products run in
float32 at ``HIGHEST`` (six bf16 passes), so a share of the bf16 peak
cannot pass about 17%."""
from flops import section6


def read(ctx):
    w, red = ctx["window"], ctx["trace"]
    if not w.get("agent_steps") or ctx["peaks"] is None or not red.window_s:
        return None
    m = ctx["params"]["num_agents"]
    flops = section6.interact_agent_step(ctx["config"], m) * w["agent_steps"]
    return 100.0 * flops / (red.window_s * ctx["chips"]
                            * ctx["peaks"]["bf16_flops_per_s"])
