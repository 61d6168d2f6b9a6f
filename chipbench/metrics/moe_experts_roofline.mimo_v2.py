"""moe_experts_roofline.mimo_v2 (%): as `moe_experts_roofline`, for a
`mimo_v2` configuration's three SwiGLU stacks of [held, hidden, expert
width]: the least time the chip could take for the window's routed-expert
products (the three matrices of each expert touched read once a call,
each pair's rows in and out; decode rows by the chunk counters, prompts
by their admissions' counters) over the device time of the instructions
under the `moe.experts` scope, decode and prefill together. Layer:
kernels. Source: device trace. Moves serve_tokens_per_s. Bound by memory
bandwidth in decode."""
from chipbench import flops_mimo_v2 as fl
from chipbench import spans_mimo_v2 as counters
from chipbench import trace
from chipbench.peaks import least_seconds

SCOPE = "moe.experts"


def read(view):
    cfg = view.cfg
    spent = trace.scope_seconds(view.summary, SCOPE)
    if spent <= 0.0 or "hybrid_layer_pattern" not in cfg:
        return None
    counts = counters.window_counts(view)
    if counts is None:
        return None
    work, moved = fl.expert_calls(cfg, counts["pairs_here"],
                                  counts["touched"])
    return 100.0 * least_seconds(work, moved, view.peak) / spent
