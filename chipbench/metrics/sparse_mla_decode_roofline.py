"""sparse_mla_decode_roofline (%): the least time the chip could take
for the serve window's decode attention over the chosen latent rows in
the absorbed form (each chosen row read once at the published 1,152 B,
278,528 FLOP a chosen pair; the absorbed query read and the latent
output written a row), over the device time of the instructions under
the `decode.attend` scope and `decode.attend.sparse` beneath it: the
attention kernel. The rows' gather in front of it is XLA's and outside
this time. Layer: kernels. Source: device trace; the chosen rows from
the chunk counter `latent_rows_read`. Moves serve_tokens_per_s."""
from chipbench import flops_deepseek_v32 as fl
from chipbench import spans_deepseek_v32 as counters
from chipbench import trace
from chipbench.peaks import least_seconds

SCOPES = ("decode.attend", "decode.attend.sparse")


def read(view):
    o, cfg = view.observed, view.cfg
    spent = sum(trace.scope_seconds(view.summary, s) for s in SCOPES)
    if spent <= 0.0 or "index_topk" not in cfg:
        return None
    c = counters.latent_counts(view)
    if c is None:
        return None
    work, moved = fl.decode_attention(cfg, o["decode_rows"], c["rows_read"])
    return 100.0 * least_seconds(work, moved, view.peak) / spent
