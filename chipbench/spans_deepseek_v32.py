"""What the latent engine's counters say about the traced window, from
the metadata of the `serve:commit` spans (a chunk's `moe_pairs_here`,
`moe_experts_touched`, `moe_max_load`, `attn_rows`, `index_keys`,
`latent_rows_read`) and of the `serve:admit` spans (a prompt's
`moe_pairs_here`, `moe_experts_touched`, beside its `prompt_tokens`).
None where the program recorded no such span (another engine, or an
older commit)."""
from __future__ import annotations

from chipbench import spans
from chipbench.spans_nemotron_h import _scaled


def latent_counts(view):
    """{"pairs_here", "touched"}: token-expert pairs that the window's
    decode rows and prompts brought to experts held here, and (expert,
    call) visits whose weights had to be read; "index_keys",
    "rows_read": the keys ONE layer's indexer scored for the decode rows
    and the latent rows ONE layer's attention read for them; "rows": the
    decode rows the commits counted; all counted by the program and
    brought to the harness's rows."""
    o = view.observed
    found = spans.in_window(view) or []
    commits = [s["meta"] for s in found if s["name"] == "serve:commit"
               and "latent_rows_read" in s["meta"]]
    admits = [s["meta"] for s in found if s["name"] == "serve:admit"
              and "moe_pairs_here" in s["meta"]]
    rows = sum(int(m["attn_rows"]) for m in commits)
    prompt = sum(int(m["prompt_tokens"]) for m in admits)
    if not rows or (o["prefill_tokens"] and not prompt):
        return None
    pairs, touched, keys, read = _scaled(
        commits, ("moe_pairs_here", "moe_experts_touched", "index_keys",
                  "latent_rows_read"), rows, o["decode_rows"])
    if prompt:
        p, t = _scaled(admits, ("moe_pairs_here", "moe_experts_touched"),
                       prompt, o["prefill_tokens"])
        pairs, touched = pairs + p, touched + t
    return {"pairs_here": pairs, "touched": touched, "index_keys": keys,
            "rows_read": read, "rows": rows}
