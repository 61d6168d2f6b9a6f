"""mla_paged_decode_roofline (%): the least time the chip could take for
the decode attention the serve window's chunks ran in the absorbed form
over every cached latent row (each of a slot's rows read once a step and
layer at the published 1,152 B, for all its query rows; 43,520 FLOP a
(row, key) pair; the absorbed query read and the latent output written a
row), over the device time of the instructions under the
`decode.attend.dense` scope: `mla_paged_decode_attention` in the main
layers, which reads the pool through the block table (the MTP layer's
calls run under `decode.mtp`). Layer: kernels. Source: device trace;
rows, pairs and rows read from the chunk counters of the
`serve:commit` spans (ONE main layer's, every step the device ran).
Moves serve_tokens_per_s."""
from chipbench import flops_glm47_flash as fl
from chipbench import spans_glm47_flash as counters
from chipbench import trace
from chipbench.peaks import least_seconds


def read(view):
    cfg = view.cfg
    spent = trace.scope_seconds(view.summary, "decode.attend.dense")
    if spent <= 0.0 or cfg.get("model_type") != "glm4_moe_lite":
        return None
    c = counters.dense_counts(view)
    if c is None:
        return None
    work, moved = fl.decode_attention(cfg, cfg["num_hidden_layers"],
                                      c["attn_rows"],
                                      c["attn_pairs"], c["latent_rows_read"])
    return 100.0 * least_seconds(work, moved, view.peak) / spent
