"""What a train step's device-side counters say about the window, from
the metadata of the `train_step:call` spans (`moe_pairs_here`,
`moe_experts_touched`, `moe_max_load` of the newest step that had
finished when the call was made). None where the program recorded no
such span or no such counters (another family, or an older commit)."""
from __future__ import annotations

from chipbench import spans


def window_steps(view):
    """[(pairs computed here, held experts that got a row, largest load
    of one expert)], one entry a step whose counters came home inside the
    window (each step's once), or None."""
    found = spans.in_window(view) or []
    seen = {}
    for s in found:
        meta = s["meta"]
        if s["name"] == "train_step:call" and "moe_pairs_here" in meta:
            seen[int(meta["counters_step"])] = (
                int(meta["moe_pairs_here"]),
                int(meta["moe_experts_touched"]), int(meta["moe_max_load"]))
    return list(seen.values()) or None


def pairs_per_step(view):
    steps = window_steps(view)
    return sum(p for p, _, _ in steps) / len(steps) if steps else None
