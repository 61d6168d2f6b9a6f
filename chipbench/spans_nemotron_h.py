"""What the hybrid engine's counters say about the window, from the
metadata of the `serve:commit` spans (a chunk's `moe_pairs_here`,
`moe_experts_touched`, `moe_max_load`, `ssm_rows`) and of the
`serve:admit` spans (a prompt's `moe_pairs_here`, `moe_experts_touched`,
beside its `prompt_tokens`). None where the program recorded no such
span (another engine, or an older commit)."""
from __future__ import annotations

from chipbench import spans


def _scaled(metas, keys, have, want):
    """The sums of `keys` over `metas`, brought from the `have` rows the
    spans cover to the `want` rows the harness counted (both ends of the
    window lie on iteration boundaries, as the spans do, so the factor
    is 1 or close to it)."""
    return [want / have * sum(int(m[k]) for m in metas) for k in keys]


def window_counts(view, state_layers):
    """{"pairs_here", "touched"}: token-expert pairs that the window's
    decode rows and prompts brought to experts held here, and (expert,
    call) visits whose weights had to be read, both counted by the
    program. And "chunks": per decode chunk (largest load, pairs,
    visits)."""
    o = view.observed
    found = spans.in_window(view) or []
    counted = {name: [s["meta"] for s in found if s["name"] == name
                      and "moe_pairs_here" in s["meta"]]
               for name in ("serve:commit", "serve:admit")}
    commits, admits = counted["serve:commit"], counted["serve:admit"]
    rows = sum(int(m["ssm_rows"]) for m in commits) / state_layers
    prompt = sum(int(m["prompt_tokens"]) for m in admits)
    if not rows or (o["prefill_tokens"] and not prompt):
        return None
    keys = ("moe_pairs_here", "moe_experts_touched")
    pairs, touched = _scaled(commits, keys, rows, o["decode_rows"])
    if prompt:
        p, t = _scaled(admits, keys, prompt, o["prefill_tokens"])
        pairs, touched = pairs + p, touched + t
    return {
        "pairs_here": pairs, "touched": touched,
        "chunks": [(int(m["moe_max_load"]), int(m["moe_pairs_here"]),
                    int(m["moe_experts_touched"])) for m in commits]}
