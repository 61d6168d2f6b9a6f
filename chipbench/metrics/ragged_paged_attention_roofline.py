"""ragged_paged_attention_roofline (%): the least time the chip could
take for the serve window's decode attention (K and V of the live
lengths read once, Q read and the output written, per layer) over the
device time of the instructions under the `decode.attend` scope. Layer:
kernels. Source: device trace. Moves serve_tokens_per_s. Bound by memory
bandwidth."""
from chipbench import flops, trace
from chipbench.peaks import least_seconds

SCOPE = "decode.attend"


def read(view):
    o = view.observed
    spent = trace.scope_seconds(view.summary, SCOPE)
    if spent <= 0.0:
        return None
    work, moved = flops.paged_attention_calls(
        view.cfg, o["decode_rows"], o["decode_context"])
    layers = view.cfg["num_hidden_layers"]
    return 100.0 * least_seconds(work * layers, moved * layers, view.peak) \
        / spent
