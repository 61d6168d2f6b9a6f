"""serve.mfu.mimo_v2 (%): the serving loop's share of the chip's peak
for a `mimo_v2` configuration: the cell's one share of the whole step.
Layer: entry points. Source: `flops_mimo_v2`'s forward work of the prompt
tokens prefilled and the output tokens decoded in the traced window
(matmuls by layer kind, attention at the lengths attended: the whole
context in full layers, at most the window in window layers; the head
where a token is sampled; the routed experts by the pairs that met an
expert held here, as the chunks' and the admissions' counters give them),
over the window's seconds and the table's bf16 peak. Moves
serve_tokens_per_s."""
from chipbench import flops_mimo_v2 as fl
from chipbench import spans_mimo_v2 as counters


def read(view):
    o, cfg = view.observed, view.cfg
    if "hybrid_layer_pattern" not in cfg:
        return None
    counts = counters.window_counts(view)
    if counts is None:
        return None
    work = fl.forward_flops(
        cfg, o["prefill_tokens"] + o["decode_rows"],
        o["prefill_pairs"] + o["decode_context"],
        fl.window_pairs(cfg, o["prefills"], o["prefill_tokens"],
                        o["decode_rows"]),
        o["prefills"] + o["decode_rows"], counts["pairs_here"])
    return 100.0 * work / o["window_s"] / view.peak["bf16_flops_per_s"]
