"""serve.mfu (%): the serving loop's share of the chip's peak. Layer:
entry points. Source: chipbench's forward FLOPs of the prompt tokens
prefilled and the output tokens decoded in the traced window (the output
head only where a token is sampled, attention at the lengths attended),
over the window's seconds and the table's bf16 peak. Moves
serve_tokens_per_s."""
from chipbench import flops


def read(view):
    o = view.observed
    work = flops.forward_flops(
        view.cfg, o["prefill_tokens"] + o["decode_rows"],
        o["prefill_pairs"] + o["decode_context"],
        head_tokens=o["prefills"] + o["decode_rows"])
    return 100.0 * work / o["window_s"] / view.peak["bf16_flops_per_s"]
