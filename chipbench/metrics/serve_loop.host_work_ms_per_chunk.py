"""serve_loop.host_work_ms_per_chunk (ms): the loop thread's time in the
window that is neither blocked on the device (`serve:wait_chunk`,
`serve:wait_first_token`) nor in the caller's hook (`serve:feed`), per
decode chunk dispatched (`serve:chunk`): what the host does for one
chunk. The window, not the sum of `serve:iteration`, is the whole: the
trace starts and ends inside the hook, in the middle of an iteration.
Layer: serve loop. Source: program spans. Moves serve_tokens_per_s."""
from chipbench import spans


def read(view):
    found = spans.in_window(view)
    if not found:
        return None
    chunks = len(spans.durations(found, "serve:chunk"))
    if not chunks:
        return None
    t0, t1 = view.window
    waited = sum(spans.seconds(found, name) for name in
                 ("serve:wait_chunk", "serve:wait_first_token", "serve:feed"))
    return 1e3 * ((t1 - t0) - waited) / chunks
