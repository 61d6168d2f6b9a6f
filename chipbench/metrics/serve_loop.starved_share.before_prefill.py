"""serve_loop.starved_share.before_prefill (%): `serve:starved` spans
that a prefill's dispatch ended (`before` = `prefill` or
`warm_prefill`), over the window: the device waited for an admission's
host work. Layer: serve loop. Source: program spans. Moves
serve_tokens_per_s."""
from chipbench import spans_serve_loop


def read(view):
    return spans_serve_loop.starved_share(view,
                                          ("prefill", "warm_prefill"))
