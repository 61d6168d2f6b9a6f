"""flash_attention_roofline (%): the least time the chip could take for
the train window's attention calls (chipbench's FLOPs and bytes for one
layer's causal attention, forward and backward, times layers and steps)
over the device time of the instructions under the block's `attn` scope
(the flash call inside `decoder.N/attn`). Layer: kernels. Source: device
trace. Moves train_tokens_per_s. Bound by compute at these shapes."""
from chipbench import flops, trace
from chipbench.peaks import least_seconds

SCOPE = "attn"


def read(view):
    o = view.observed
    spent = trace.scope_seconds(view.summary, SCOPE)
    if spent <= 0.0:
        return None
    work, moved = flops.flash_attention_call(view.cfg, o["batch"], o["seq"])
    calls = view.cfg["num_hidden_layers"] * o["steps"]
    return 100.0 * least_seconds(work * calls, moved * calls, view.peak) \
        / spent
