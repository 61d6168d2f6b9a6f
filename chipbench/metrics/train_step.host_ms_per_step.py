"""train_step.host_ms_per_step (ms): mean duration of the
`train_step:call` spans in the window: the host's part of a step, from
the call to the return of its not yet computed loss. Layer: entry
points. Source: program spans. Moves train_tokens_per_s."""
from chipbench import spans


def read(view):
    found = spans.in_window(view)
    calls = spans.durations(found, "train_step:call") if found else []
    return 1e3 * sum(calls) / len(calls) if calls else None
