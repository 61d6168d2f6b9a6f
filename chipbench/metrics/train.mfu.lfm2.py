"""train.mfu.lfm2 (%): the whole train step's share of the chip's peak
for an `lfm2_moe` configuration. Layer: entry points. Source:
`chipbench/flops_lfm2.py` (forward plus backward of the work required
here: matmuls by block kind, the routed experts by the pairs the program
counted, causal attention in the attention blocks only, the tied head;
no recomputation) times the steps the traced window finished, over the
window's seconds and the table's bf16 peak. Moves train_tokens_per_s."""
from chipbench import flops_lfm2, spans_lfm2


def read(view):
    if view.cfg.get("model_type") != "lfm2_moe":
        return None
    pairs = spans_lfm2.pairs_per_step(view)
    if pairs is None:
        return None
    o = view.observed
    work = flops_lfm2.train_flops_per_step(
        view.cfg, o["batch"], o["seq"], pairs) * o["steps"]
    return 100.0 * work / o["window_s"] / view.peak["bf16_flops_per_s"]
