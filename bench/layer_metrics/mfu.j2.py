"""Required operations of the window's agent steps
(``bench/flops/jamba2.py``) over the traced window's length, the chips
and their bf16 peak.  Recomputation (per-layer remat) and the attention
blocks above the diagonal are work the count leaves out."""
from flops import jamba2


def read(ctx):
    w, red = ctx["window"], ctx["trace"]
    if not w.get("agent_steps") or ctx["peaks"] is None or not red.window_s:
        return None
    flops = jamba2.interact_agent_step(ctx["config"], ctx["params"])
    return 100.0 * flops * w["agent_steps"] / (
        red.window_s * ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
