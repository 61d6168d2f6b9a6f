"""serve_loop.starved_share (%): the share of the window in which the
serve loop knew the device's queue empty (`serve:starved`: from a
blocking read that left nothing dispatched behind it to the next device
call's return). Layer: serve loop. Source: program spans. Moves
serve_tokens_per_s."""
from chipbench import spans_serve_loop


def read(view):
    return spans_serve_loop.starved_share(view)
