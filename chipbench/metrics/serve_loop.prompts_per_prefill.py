"""serve_loop.prompts_per_prefill (count): admissions in the window
(`serve:admit` spans) over prefill programs dispatched in it
(`serve:prefill` spans): how many prompts a prefill program takes. 1.0
where every prompt has a program of its own; above it where a scan's
staged prompts are packed. Layer: serve loop. Source: program spans.
Moves serve_tokens_per_s."""
from chipbench import spans


def read(view):
    found = spans.in_window(view)
    if not found:
        return None
    admits = len(spans.durations(found, "serve:admit"))
    prefills = len(spans.durations(found, "serve:prefill"))
    return admits / prefills if admits and prefills else None
