"""train.mfu (%): the whole train step's share of the chip's peak.
Layer: entry points. Source: chipbench's FLOP count (forward plus
backward, no recomputation, no embedding lookup, causal attention) times
the steps the traced window finished, over the window's seconds and the
table's bf16 peak. Moves train_tokens_per_s."""
from chipbench import flops


def read(view):
    o = view.observed
    work = flops.train_flops_per_step(view.cfg, o["batch"], o["seq"]) \
        * o["steps"]
    return 100.0 * work / o["window_s"] / view.peak["bf16_flops_per_s"]
