"""serve.mfu.deepseek_v32 (%): the serving loop's share of the chip's
peak for a `deepseek_v32` configuration: the cell's one share of the
whole step. Layer: entry points. Source: `flops_deepseek_v32`'s forward
work of the prompt tokens prefilled and the output tokens decoded in the
traced window (matmuls by layer kind; the indexer at every causal key;
attention at the keys chosen, expanded in prefill and absorbed in
decode; the head where a token is sampled; the routed experts by the
pairs that met an expert held here, as the chunks' and the admissions'
counters give them), over the window's seconds and the table's bf16
peak. Moves serve_tokens_per_s."""
from chipbench import flops_deepseek_v32 as fl
from chipbench import spans_deepseek_v32 as counters


def read(view):
    o, cfg = view.observed, view.cfg
    if "index_topk" not in cfg:
        return None
    c = counters.latent_counts(view)
    if c is None:
        return None
    work = fl.forward_flops(
        cfg, o["prefill_tokens"] + o["decode_rows"],
        o["prefill_pairs"] + o["decode_context"],
        fl.chosen_pairs(cfg, o["prefills"], o["prefill_tokens"]),
        c["rows_read"], o["prefills"] + o["decode_rows"], c["pairs_here"])
    return 100.0 * work / o["window_s"] / view.peak["bf16_flops_per_s"]
