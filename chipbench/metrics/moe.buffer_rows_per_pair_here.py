"""moe.buffer_rows_per_pair_here (ratio): rows of the sorted buffers the
expert layers worked on per token-expert pair computed here, over the
window's decode chunks and prompts: `moe_rows_buffered` over
`moe_pairs_here`, each kind of span's sums brought to the rows the
harness counted as `spans_nemotron_h` brings them. A buffer with a row
for every pair routed anywhere reads 1 / (the held share), about 4; 1
would be a buffer of the computed pairs alone. Layer: expert layer.
Source: the counters in the `serve:commit` and `serve:admit` spans'
metadata. Moves serve_tokens_per_s (the gathers, `relu^2` and the combine
run over every buffered row). None where the program counts no buffer
rows (an older commit)."""
from chipbench import flops_nemotron_h as fl
from chipbench import spans
from chipbench.spans_nemotron_h import _scaled

KEYS = ("moe_rows_buffered", "moe_pairs_here")


def read(view):
    o, cfg = view.observed, view.cfg
    if "mamba_num_heads" not in cfg:
        return None
    found = spans.in_window(view) or []
    metas = {name: [s["meta"] for s in found if s["name"] == name
                    and "moe_rows_buffered" in s["meta"]]
             for name in ("serve:commit", "serve:admit")}
    commits, admits = metas["serve:commit"], metas["serve:admit"]
    rows = sum(int(m["ssm_rows"]) for m in commits) / fl.sizes(cfg)["n_m"]
    prompt = sum(int(m["prompt_tokens"]) for m in admits)
    if not rows or (o["prefill_tokens"] and not prompt):
        return None
    buffered, pairs = _scaled(commits, KEYS, rows, o["decode_rows"])
    if prompt:
        b, p = _scaled(admits, KEYS, prompt, o["prefill_tokens"])
        buffered, pairs = buffered + b, pairs + p
    return buffered / pairs if pairs else None
