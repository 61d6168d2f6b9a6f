"""moe_experts_train_roofline (%): the least time the chip could take for
the train window's grouped expert products (gate, up and down, each
forward, dx and dw, over the pairs the program counted; the held experts'
weights read for the forward and for dx, their gradients written, each
pair's rows in and out) over the device time of the instructions under
the `moe.experts` scope, backward included. Layer: kernels. Source:
device trace. Moves train_tokens_per_s. Bound by compute at a thousand
rows an expert."""
from chipbench import flops_lfm2, spans_lfm2, trace_ad
from chipbench.peaks import least_seconds

SCOPE = "moe.experts"


def read(view):
    if view.cfg.get("model_type") != "lfm2_moe":
        return None
    spent = trace_ad.scope_seconds(view.summary, SCOPE)
    pairs = spans_lfm2.pairs_per_step(view)
    if spent <= 0.0 or pairs is None:
        return None
    steps = view.observed["steps"]
    work, moved = flops_lfm2.expert_train_calls(
        view.cfg, pairs * steps, flops_lfm2.kinds(view.cfg)["sparse"] * steps)
    return 100.0 * least_seconds(work, moved, view.peak) / spent
