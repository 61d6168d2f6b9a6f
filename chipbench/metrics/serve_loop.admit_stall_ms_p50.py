"""serve_loop.admit_stall_ms_p50 (ms): median duration of the
`serve:admit` spans in the window: how long one admission (block
allocation, prefill dispatch, the wait for its first token) holds the
loop. Layer: serve loop. Source: program spans. Moves
serve_tokens_per_s."""
import statistics

from chipbench import spans


def read(view):
    found = spans.in_window(view)
    stalls = spans.durations(found, "serve:admit") if found else []
    return 1e3 * statistics.median(stalls) if stalls else None
