"""device.idle_unaccounted_share.serve (%): the device's idle share of
the traced window less `serve_loop.starved_share`: idle that the loop
cannot account for (a result's way back to the host before a read
returns, gaps between queued programs, reads that did not block). Below
0 where the loop's stretches cover more than the trace's idle (a stretch
runs to the dispatch's return, the device starts a little before it).
Layer: device. Source: device trace. Moves serve_tokens_per_s."""
from chipbench import spans_serve_loop


def read(view):
    starved = spans_serve_loop.starved_share(view)
    if starved is None:
        return None
    return 100.0 * view.summary.idle_share - starved
