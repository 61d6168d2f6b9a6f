"""paged_attention_roofline.mimo_v2 (%): the least time the chip could
take for the serve window's decode attention in a `mimo_v2`
configuration (full layers: K and V of the live length read once, at
the published 192 + 128 wide rows of 4 KV heads; window layers: at most
the last `sliding_window` keys, 8 KV heads; q read and o written a row)
over the device time of the instructions under the `decode.attend` scope
and the scopes by layer kind beneath it. A pool that keeps K rows in
whole lanes reads more than this counts, and shows as a shortfall.
Layer: kernels. Source: device trace, the lengths attended from the
harness's count of the window's decode rows. Moves serve_tokens_per_s.
Bound by memory bandwidth."""
from chipbench import flops_mimo_v2 as fl
from chipbench import trace
from chipbench.peaks import least_seconds

SCOPES = ("decode.attend", "decode.attend.full", "decode.attend.window")


def read(view):
    o, cfg = view.observed, view.cfg
    spent = sum(trace.scope_seconds(view.summary, s) for s in SCOPES)
    if spent <= 0.0 or "hybrid_layer_pattern" not in cfg:
        return None
    work, moved = fl.decode_attention(
        cfg, o["decode_rows"], o["decode_context"],
        fl.window_pairs(cfg, 0, 0, o["decode_rows"]))
    return 100.0 * least_seconds(work, moved, view.peak) / spent
