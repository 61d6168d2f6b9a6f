"""serve_loop.live_slot_share (%): tokens committed over the decode rows
the device computed (`serve:commit`'s `tokens` / (slots x `steps`)): an
empty slot, a slot past its budget inside a chunk and a trimmed step all
count against it. Layer: serve loop. Source: program spans. Moves
serve_tokens_per_s."""
from chipbench import spans_serve_loop


def read(view):
    return spans_serve_loop.live_slot_share(view)
