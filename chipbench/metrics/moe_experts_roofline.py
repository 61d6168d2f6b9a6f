"""moe_experts_roofline (%): the least time the chip could take for the
window's routed-expert products (the weights of the experts touched
read once a call, each pair's rows in and out; decode rows by the chunk
counters, prompts by their admissions' counters) over the device time
of the instructions under the `moe.experts` scope, decode and prefill
together. Layer: kernels. Source: device trace. Moves
serve_tokens_per_s. Bound by memory bandwidth in decode."""
from chipbench import flops_nemotron_h as fl
from chipbench import spans_nemotron_h as counters
from chipbench import trace
from chipbench.peaks import least_seconds

SCOPE = "moe.experts"


def read(view):
    cfg = view.cfg
    spent = trace.scope_seconds(view.summary, SCOPE)
    if spent <= 0.0 or "mamba_num_heads" not in cfg:
        return None
    z = fl.sizes(cfg)
    counts = counters.window_counts(view, z["n_m"])
    if counts is None:
        return None
    work, moved = fl.expert_calls(cfg, counts["pairs_here"],
                                  counts["touched"])
    return 100.0 * least_seconds(work, moved, view.peak) / spent
