"""mtp.device_share (%): device time of the instructions under the
`decode.mtp` and `prefill.mtp` scopes over the device's busy time in the
traced window: the MTP layer's kernels (its latent attention and its
grouped expert products, which are most of the weights it streams). The
trace names kernels only, so its XLA work (the input projection, the
projections of its attention, its shared expert and router, its pass
over the head it shares) is not in it, and the reading is low by that
work's time. In `glm47_flash_l6_mtp1` that work streams about 714 MB of
weights a verify pass (the head 634.4 MB, the attention's projections
43.5 MB, the shared expert 18.9 MB, `eh_proj` 16.8 MB), at least 0.87 ms
at the chip's 819 GB/s: at least 2.2 points of a 40 ms pass, and a
trimmed head or projection moves this number by none of it. Layer:
speculative decoding. Source: device trace. Moves serve_tokens_per_s."""
from chipbench import trace

SCOPES = ("decode.mtp", "prefill.mtp")


def read(view):
    spent = sum(trace.scope_seconds(view.summary, s) for s in SCOPES)
    if spent <= 0.0 or view.summary.busy_s <= 0.0:
        return None
    return 100.0 * spent / view.summary.busy_s
