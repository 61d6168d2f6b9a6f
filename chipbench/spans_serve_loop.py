"""What the serve loop says of the device's queue and of its own work,
from its spans inside the window (PR 39): `serve:starved` (the stretch
in which the loop knows the device has nothing to run; `before`: the
kind of device call that ended it), `serve:commit`'s `tokens` and
`steps`, and an admission's `serve:reserve`, `serve:prefill_inputs` and
`serve:prefill`. Every function returns None where the program recorded
no such span (an older commit's tree)."""
from __future__ import annotations

from chipbench import spans


def starved_share(view, before=None):
    """Percent of the window under `serve:starved` spans, clipped to it;
    with `before` (a tuple of kinds) only those that such a call
    ended."""
    found = spans.in_window(view) or []
    starved = [s for s in found if s["name"] == "serve:starved"]
    if not starved:
        return None
    if before is not None:
        starved = [s for s in starved if s["meta"].get("before") in before]
    t0, t1 = view.window
    return 100.0 * sum(s["end"] - s["start"] for s in starved) / (t1 - t0)


def live_slot_share(view):
    """Percent of the decode rows the device computed (`steps` x the
    engine's slots, over the window's `serve:commit` spans) that became
    a live request's token (`tokens`)."""
    found = spans.in_window(view) or []
    commits = [s["meta"] for s in found if s["name"] == "serve:commit"
               and "steps" in s["meta"]]
    rows = view.observed["slots"] * sum(int(m["steps"]) for m in commits)
    if not rows:
        return None
    return 100.0 * sum(int(m["tokens"]) for m in commits) / rows


def admit_host_ms(view):
    """Milliseconds of the loop thread's own work to bring one prompt to
    the device: (`serve:reserve` + `serve:prefill_inputs` +
    `serve:prefill`) over the count of `serve:admit`."""
    found = spans.in_window(view) or []
    admits = len(spans.durations(found, "serve:admit"))
    if not admits or not spans.durations(found, "serve:reserve"):
        return None
    worked = sum(spans.seconds(found, name) for name in
                 ("serve:reserve", "serve:prefill_inputs", "serve:prefill"))
    return 1e3 * worked / admits
