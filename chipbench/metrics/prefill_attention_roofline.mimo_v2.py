"""prefill_attention_roofline.mimo_v2 (%): the least time the chip could
take for the attention of the prompts prefilled in the serve window in a
`mimo_v2` configuration (the causal triangle in full layers, the band of
`sliding_window` keys in window layers, K rows 192 and V rows 128 wide)
over the device time of the instructions under the `prefill.attend`
scope. Layer: kernels. Source: device trace, the prompts from the
harness's count. Moves serve_tokens_per_s. Bound by compute."""
from chipbench import flops_mimo_v2 as fl
from chipbench import trace
from chipbench.peaks import least_seconds

SCOPE = "prefill.attend"


def read(view):
    o, cfg = view.observed, view.cfg
    spent = trace.scope_seconds(view.summary, SCOPE)
    if spent <= 0.0 or "hybrid_layer_pattern" not in cfg \
            or not o["prefill_tokens"]:
        return None
    work, moved = fl.prefill_attention(
        cfg, o["prefill_tokens"], o["prefill_pairs"],
        fl.window_pairs(cfg, o["prefills"], o["prefill_tokens"], 0))
    return 100.0 * least_seconds(work, moved, view.peak) / spent
