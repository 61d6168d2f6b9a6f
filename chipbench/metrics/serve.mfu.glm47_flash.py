"""serve.mfu.glm47_flash (%): the serving loop's share of the chip's peak
for a `glm4_moe_lite` configuration: the cell's one share of the whole
step. Layer: entry points. Source: `flops_glm47_flash`'s forward work of
the MAIN model for the prompt tokens prefilled and the output tokens
served in the traced window (matmuls by layer kind, four routed experts
a token an expert layer, attention at every causal key, expanded in
prefill and absorbed in decode, the head where a token is sampled), over
the window's seconds and the table's bf16 peak. The MTP layer's work and
the rows of rejected drafts are overhead and not counted. Moves
serve_tokens_per_s."""
from chipbench import flops_glm47_flash as fl


def read(view):
    o, cfg = view.observed, view.cfg
    if cfg.get("model_type") != "glm4_moe_lite":
        return None
    work = fl.forward_flops(
        cfg, o["prefill_tokens"] + o["decode_rows"], o["prefill_pairs"],
        o["decode_context"], o["prefills"] + o["decode_rows"])
    return 100.0 * work / o["window_s"] / view.peak["bf16_flops_per_s"]
