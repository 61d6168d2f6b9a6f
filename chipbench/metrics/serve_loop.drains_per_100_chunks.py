"""serve_loop.drains_per_100_chunks (count): pipeline drains
(`eng.pipeline_drains`: a batch-composition change that forced the
device state to be uploaded anew) per 100 decode chunks dispatched in
the window. Layer: serve loop. Source: program counters. Moves
serve_tokens_per_s."""


def read(view):
    o = view.observed
    return 100.0 * o["drains"] / o["chunks"] if o["chunks"] else None
