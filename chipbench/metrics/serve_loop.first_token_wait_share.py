"""serve_loop.first_token_wait_share (%): the share of the window the
loop spends blocked on a prefill's first token
(`serve:wait_first_token`). Layer: serve loop. Source: program spans.
Moves serve_tokens_per_s."""
from chipbench import spans


def read(view):
    found = spans.in_window(view)
    if not found or not spans.durations(found, "serve:wait_first_token"):
        return None
    t0, t1 = view.window
    return 100.0 * spans.seconds(found, "serve:wait_first_token") / (t1 - t0)
