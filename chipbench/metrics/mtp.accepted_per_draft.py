"""mtp.accepted_per_draft (%): drafts the MTP layer made on the device
that greedy verification accepted (and the slot's budget took), over the
drafts proposed, in the window: one draft a live slot a verify pass.
Seeded random weights accept about what chance gives; a trained model
most. Layer: speculative decoding. Source: program counters
(`dec.spec_stats`, fed from the tokens each chunk sends home, at the
window's two ends). Moves serve_tokens_per_s."""


def read(view):
    o = view.observed
    if not o.get("drafted"):
        return None
    return 100.0 * o["accepted"] / o["drafted"]
