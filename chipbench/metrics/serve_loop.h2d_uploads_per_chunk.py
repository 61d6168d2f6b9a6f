"""serve_loop.h2d_uploads_per_chunk (count): host-to-device uploads of
decode batch state (`eng.h2d_uploads`) per decode chunk dispatched
(`eng.chunk_dispatches`) in the window. Layer: serve loop. Source:
program counters. Moves serve_tokens_per_s."""


def read(view):
    o = view.observed
    return o["h2d_uploads"] / o["chunks"] if o["chunks"] else None
