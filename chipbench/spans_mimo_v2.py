"""What the window engine's counters say about the traced window, from
the metadata of the `serve:commit` spans (a chunk's `moe_pairs_here`,
`moe_experts_touched`, `moe_max_load`, `attn_rows`, `attn_tokens_full`,
`attn_tokens_window`) and of the `serve:admit` spans (a prompt's
`moe_pairs_here`, `moe_experts_touched`, beside its `prompt_tokens`).
None where the program recorded no such span (another engine, or an
older commit)."""
from __future__ import annotations

from chipbench import spans
from chipbench.spans_nemotron_h import _scaled


def window_counts(view):
    """{"pairs_here", "touched"}: token-expert pairs that the window's
    decode rows and prompts brought to experts held here, and (expert,
    call) visits whose weights had to be read; "full_keys", "ring_keys":
    the keys the decode rows attended in ONE full and ONE window layer;
    all counted by the program. And "chunks": per decode chunk (largest
    load, pairs, visits)."""
    o = view.observed
    found = spans.in_window(view) or []
    counted = {name: [s["meta"] for s in found if s["name"] == name
                      and "moe_pairs_here" in s["meta"]]
               for name in ("serve:commit", "serve:admit")}
    commits = [m for m in counted["serve:commit"] if "attn_rows" in m]
    admits = counted["serve:admit"]
    rows = sum(int(m["attn_rows"]) for m in commits)
    prompt = sum(int(m["prompt_tokens"]) for m in admits)
    if not rows or (o["prefill_tokens"] and not prompt):
        return None
    pairs, touched, full_keys, ring_keys = _scaled(
        commits, ("moe_pairs_here", "moe_experts_touched",
                  "attn_tokens_full", "attn_tokens_window"),
        rows, o["decode_rows"])
    if prompt:
        p, t = _scaled(admits, ("moe_pairs_here", "moe_experts_touched"),
                       prompt, o["prefill_tokens"])
        pairs, touched = pairs + p, touched + t
    return {
        "pairs_here": pairs, "touched": touched,
        "full_keys": full_keys, "ring_keys": ring_keys,
        "chunks": [(int(m["moe_max_load"]), int(m["moe_pairs_here"]),
                    int(m["moe_experts_touched"])) for m in commits]}
