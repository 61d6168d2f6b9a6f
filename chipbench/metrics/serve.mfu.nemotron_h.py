"""serve.mfu.nemotron_h (%): the serving loop's share of the chip's peak
for a `nemotron_h` configuration: the cell's one share of the whole
step. Layer: entry points. Source: `flops_nemotron_h`'s forward work of
the prompt tokens prefilled and the output tokens decoded in the traced
window (matmuls by block kind, the recurrence, attention at the lengths
attended, the head where a token is sampled, the routed experts by the
pairs that met an expert held here, as the chunks' and the admissions'
counters give them), over the window's
seconds and the table's bf16 peak. Moves serve_tokens_per_s."""
from chipbench import flops_nemotron_h as fl
from chipbench import spans_nemotron_h as counters


def read(view):
    o, cfg = view.observed, view.cfg
    if "mamba_num_heads" not in cfg:
        return None
    z = fl.sizes(cfg)
    counts = counters.window_counts(view, z["n_m"])
    if counts is None:
        return None
    work = fl.forward_flops(
        cfg, o["prefill_tokens"] + o["decode_rows"],
        o["prefill_pairs"] + o["decode_context"],
        o["prefills"] + o["decode_rows"], counts["pairs_here"])
    return 100.0 * work / o["window_s"] / view.peak["bf16_flops_per_s"]
