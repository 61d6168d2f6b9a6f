"""lightning_indexer_roofline (%): the least time the chip could take
for the indexer's scores of the serve window (64 heads x 128 a query,
every causal key, at the published widths: a decode row reads its whole
context's keys, a prompt each of its keys once, both write a float32
score a key) over the device time of the instructions under the
`prefill.index` and `decode.index` scopes: the scoring kernels. The
exact top-k that follows them is XLA's and outside this time. Layer:
kernels. Source: device trace; the keys from the harness's count of the
window's prompts and decode rows. Moves serve_tokens_per_s."""
from chipbench import flops_deepseek_v32 as fl
from chipbench import trace
from chipbench.peaks import least_seconds


def read(view):
    o, cfg = view.observed, view.cfg
    if "index_topk" not in cfg:
        return None
    phases = ((trace.scope_seconds(view.summary, "prefill.index"),
               o["prefill_tokens"], o["prefill_pairs"], o["prefill_tokens"]),
              (trace.scope_seconds(view.summary, "decode.index"),
               o["decode_rows"], o["decode_context"], o["decode_context"]))
    spent = sum(p[0] for p in phases)
    if spent <= 0.0:
        return None
    least = 0.0
    for secs, queries, pairs, keys in phases:
        if secs > 0.0:
            least += least_seconds(*fl.indexer(cfg, queries, pairs, keys),
                                   view.peak)
    return 100.0 * least / spent
