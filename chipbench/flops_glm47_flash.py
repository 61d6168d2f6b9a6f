"""Operations and bytes a `glm4_moe_lite` configuration needs, from shapes
alone (the dense member of the latent family: no indexer, every causal
latent row attended; `flops_deepseek_v32` counts the sparse one).

Only required work counts, at the PUBLISHED widths whatever the program
stores (a latent row is 576 wide here even where a pool keeps it in
640): matmul parameters every token meets without the input embedding
table (the Wkvb up-projection once a token, in either form), the routed
experts by the token-expert pairs of the tokens served, the attention at
every causal key, in the form each phase needs least (heads expanded in
prefill, 2 nh (dn + dr + dv) = 20,480 FLOP a pair; absorbed in decode,
2 nh (kvr + dr + kvr) = 43,520 FLOP a pair, a slot's latent rows read
once at 1,152 B for all its query rows), the output head where a token
is sampled. The MTP layer and rows a verify pass rejected are overhead,
not the model's work: `forward_flops` leaves them out.

`cfg` is a configuration file's dict (chipbench/configs/<config>.json).
"""
from __future__ import annotations

BF16 = 2  # bytes


def sizes(cfg):
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return {"layers": layers, "n_dense": dense, "n_sparse": layers - dense,
            "held": cfg["n_routed_experts"],
            "router_width": cfg.get("published", {}).get(
                "n_routed_experts", cfg["n_routed_experts"]),
            "mtp": cfg.get("num_nextn_predict_layers", 0)}


def matmul_params(cfg):
    """Matmul parameters that every token meets in one layer's attention
    ("attn": MLA's five projections), in the dense MLP, in a router, in
    the shared expert, in one routed expert, in the head, and in the MTP
    layer's input projection ("eh")."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    fe = cfg["moe_intermediate_size"]
    return {"attn": h * qr + qr * nh * (dn + dr) + h * (kvr + dr)
            + kvr * nh * (dn + dv) + nh * dv * h,
            "dense": 3 * h * cfg["intermediate_size"],
            "router": h * sizes(cfg)["router_width"],
            "shared": 3 * h * fe * cfg["n_shared_experts"],
            "expert": 3 * h * fe,
            "head": h * cfg["vocab_size"],
            "eh": 2 * h * h}


def parameters(cfg):
    """Every parameter held here: the matmuls, both tables, the norms,
    the routers' choice bias, and the MTP layer (an expert layer, its
    input projection and three norms)."""
    z, p, h = sizes(cfg), matmul_params(cfg), cfg["hidden_size"]
    norms = 2 * h + cfg["q_lora_rank"] + cfg["kv_lora_rank"]
    sparse = p["attn"] + norms + p["router"] + z["router_width"] \
        + p["shared"] + z["held"] * p["expert"]
    return (z["n_dense"] * (p["attn"] + norms + p["dense"])
            + z["n_sparse"] * sparse + 2 * p["head"] + h
            + z["mtp"] * (sparse + p["eh"] + 3 * h))


def pair_flops(cfg, absorbed):
    """Attention a (query, key) pair, every head: expanded (q . k over dn
    + dr, p v over dv) or absorbed (scores against the whole latent row,
    p c over c)."""
    nh, kvr = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    return 2 * nh * (kvr + dr + kvr if absorbed else dn + dr + dv)


def latent_row_bytes(cfg):
    """A latent row as published: c and k_pe in bf16."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * BF16


def forward_flops(cfg, tokens, prefill_pairs, decode_pairs, head_tokens):
    """The main model's forward over `tokens` tokens: `prefill_pairs` /
    `decode_pairs` causal (query, key) pairs of ONE layer in prefill /
    in decode, `head_tokens` tokens that need the head. Every token meets
    `num_experts_per_tok` experts an expert layer; all of them are held
    here (a configuration holding a share is refused)."""
    z, p = sizes(cfg), matmul_params(cfg)
    if z["held"] != z["router_width"]:
        raise ValueError("the routed pairs are counted for a configuration "
                         "that holds every expert")
    every = z["layers"] * p["attn"] + z["n_dense"] * p["dense"] \
        + z["n_sparse"] * (p["router"] + p["shared"]
                           + cfg["num_experts_per_tok"] * p["expert"])
    return (2 * every * tokens
            + z["layers"] * (pair_flops(cfg, False) * prefill_pairs
                             + pair_flops(cfg, True) * decode_pairs)
            + 2 * p["head"] * head_tokens)


def decode_attention(cfg, layers, rows, pairs, rows_read):
    """The absorbed decode kernel over `layers` layers' calls: `rows`
    query rows, `pairs` (row, key) pairs and `rows_read` latent rows read
    (a slot's once for all its rows) in ONE layer: (flops, bytes). A row
    reads its absorbed query (kvr + dr a head) and writes its latent
    output (kvr a head)."""
    nh, kvr, dr = cfg["num_attention_heads"], cfg["kv_lora_rank"], \
        cfg["qk_rope_head_dim"]
    moved = layers * (rows_read * latent_row_bytes(cfg)
                      + rows * nh * (2 * kvr + dr) * BF16)
    return layers * pair_flops(cfg, True) * pairs, moved
