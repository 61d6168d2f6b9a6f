"""serve_loop.starved_share.before_chunk (%): `serve:starved` spans
that a decode chunk's dispatch ended (`before` = `chunk`), over the
window: the device waited for the first-token read, the join, the state
upload and the chunk's dispatch. Layer: serve loop. Source: program
spans. Moves serve_tokens_per_s."""
from chipbench import spans_serve_loop


def read(view):
    return spans_serve_loop.starved_share(view, ("chunk",))
