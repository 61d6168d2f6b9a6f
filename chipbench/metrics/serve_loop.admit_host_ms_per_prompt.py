"""serve_loop.admit_host_ms_per_prompt (ms): the loop thread's own work
to bring one prompt to the device: (`serve:reserve` +
`serve:prefill_inputs` + `serve:prefill`) / count of `serve:admit` in
the window. Layer: serve loop. Source: program spans. Moves
serve_tokens_per_s."""
from chipbench import spans_serve_loop


def read(view):
    return spans_serve_loop.admit_host_ms(view)
