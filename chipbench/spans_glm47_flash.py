"""What the dense latent engine's chunk counters say about the traced
window, from the metadata of the `serve:commit` spans (a chunk's
`attn_rows`, `attn_pairs`, `latent_rows_read`: the query rows, the (row,
key) pairs and the latent rows ONE main layer's decode attention
computed and read, every step the device ran, a slot's rows read once a
step; `drafted`, `accepted`: the MTP drafts of the committed verify
passes). None where the program recorded no such span (another engine,
or an older commit)."""
from __future__ import annotations

from chipbench import spans

KEYS = ("attn_rows", "attn_pairs", "latent_rows_read")


def dense_counts(view):
    """{key: sum over the window's commits} for `KEYS`, and `drafted`,
    `accepted` where the commits carry them."""
    found = spans.in_window(view) or []
    commits = [s["meta"] for s in found if s["name"] == "serve:commit"
               and "attn_pairs" in s["meta"]]
    if not commits or not sum(int(m["attn_rows"]) for m in commits):
        return None
    out = {k: sum(int(m[k]) for m in commits) for k in KEYS}
    for k in ("drafted", "accepted"):
        if all(k in m for m in commits):
            out[k] = sum(int(m[k]) for m in commits)
    return out
