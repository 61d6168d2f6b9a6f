"""flash_attention_roofline.lfm2 (%): the least time the chip could take
for the train window's attention calls of an `lfm2_moe` configuration
(`flops.flash_attention_call` at its heads and head size: one layer's
causal attention, forward and backward, times the ATTENTION layers and
steps; `flash_attention_roofline` multiplies by every layer) over the
device time of the instructions under the `attn` scope. Layer: kernels.
Source: device trace. Moves train_tokens_per_s."""
from chipbench import flops, flops_lfm2, trace_ad
from chipbench.peaks import least_seconds

SCOPE = "attn"


def read(view):
    if view.cfg.get("model_type") != "lfm2_moe":
        return None
    o = view.observed
    spent = trace_ad.scope_seconds(view.summary, SCOPE)
    if spent <= 0.0:
        return None
    work, moved = flops.flash_attention_call(view.cfg, o["batch"], o["seq"])
    calls = flops_lfm2.kinds(view.cfg)["attention"] * o["steps"]
    return 100.0 * least_seconds(work * calls, moved * calls, view.peak) \
        / spent
