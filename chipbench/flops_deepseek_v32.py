"""Operations and bytes a `deepseek_v32` configuration needs, from shapes
alone (the counterpart of `flops.py`, whose Llama layer has K and V a
head and attends the whole context; neither holds here).

Only required work counts, at the PUBLISHED widths whatever the program
stores (a latent row is 576 wide here even where a pool keeps it in
640): matmul parameters every token meets without the input embedding
table (the Wkvb up-projection once a token, in either form), the routed
experts by the token-expert pairs that really met an expert held here,
the indexer at every causal key, the attention at the keys the indexer
chose, min(t + 1, index_topk) a query, in the form each phase needs
least (heads expanded in prefill, 81,920 FLOP a pair at the published
widths; absorbed in decode, 278,528 FLOP a pair, reading 1,152 B a
chosen row), the output head over the vocabulary held here where a token
is sampled. A kernel's bytes are what the algorithm has to move.

`cfg` is a configuration file's dict (chipbench/configs/<config>.json).
"""
from __future__ import annotations

BF16, F32 = 2, 4  # bytes


def sizes(cfg):
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return {"layers": layers, "n_dense": dense, "n_sparse": layers - dense,
            "held": cfg["n_routed_experts"],
            "router_width": cfg.get("published", {}).get(
                "n_routed_experts", cfg["n_routed_experts"]),
            "topk": cfg["index_topk"]}


def matmul_params(cfg):
    """Matmul parameters that every token meets in one layer's attention
    ("attn": MLA's five projections), in its indexer ("index"), in the
    dense MLP, in a router, in the shared expert, in one routed expert,
    and in the head."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
    fe = cfg["moe_intermediate_size"]
    return {"attn": h * qr + qr * nh * (dn + dr) + h * (kvr + dr)
            + kvr * nh * (dn + dv) + nh * dv * h,
            "index": qr * ih * idim + h * idim + h * ih,
            "dense": 3 * h * cfg["intermediate_size"],
            "router": h * sizes(cfg)["router_width"],
            "shared": 3 * h * fe * cfg["n_shared_experts"],
            "expert": 3 * h * fe,
            "head": h * cfg["vocab_size"]}


def parameters(cfg):
    """Every parameter held here: the matmuls, both tables, the norms
    (the indexer's LayerNorm with its bias) and the routers' choice
    bias."""
    z, p, h = sizes(cfg), matmul_params(cfg), cfg["hidden_size"]
    norms = 2 * h + cfg["q_lora_rank"] + cfg["kv_lora_rank"] \
        + 2 * cfg["index_head_dim"]
    return (z["layers"] * (p["attn"] + p["index"] + norms)
            + z["n_dense"] * p["dense"]
            + z["n_sparse"] * (p["router"] + z["router_width"] + p["shared"]
                               + z["held"] * p["expert"])
            + 2 * p["head"] + h)


def chosen_pairs(cfg, prefills, prefill_tokens):
    """Query-key pairs ONE layer's attention of `prefills` prompts of
    `prefill_tokens` tokens in all attends: min(t + 1, k) a query, for
    prompts at least k long (every prompt of the cell is)."""
    k = sizes(cfg)["topk"]
    return prefills * (k * (k + 1) // 2) + k * (prefill_tokens - prefills * k)


def index_pair_flops(cfg):
    """The indexer's work a (query, key) pair: a product a head and its
    weighted sum."""
    return 2 * cfg["index_n_heads"] * cfg["index_head_dim"]


def prefill_pair_flops(cfg):
    """Attention a chosen pair in the expanded form: q . k over dn + dr
    and p v over dv, every head."""
    return 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])


def decode_pair_flops(cfg):
    """Attention a chosen pair in the absorbed form: scores against the
    whole latent row and p c over c, every head."""
    kvr, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return 2 * cfg["num_attention_heads"] * (kvr + dr + kvr)


def latent_row_bytes(cfg):
    """A chosen row as published: c and k_pe in bf16."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * BF16


def index_key_bytes(cfg):
    return cfg["index_head_dim"] * BF16


def forward_flops(cfg, tokens, index_pairs, prefill_chosen, decode_chosen,
                  head_tokens, expert_pairs):
    """Forward pass over `tokens` tokens. `index_pairs`: (query, causal
    key) pairs of ONE layer; `prefill_chosen` / `decode_chosen`: chosen
    pairs ONE layer's attention attends in prefill / in decode;
    `head_tokens`: tokens that need the output head; `expert_pairs`:
    token-expert pairs computed here, summed over the expert layers."""
    z, p = sizes(cfg), matmul_params(cfg)
    every = z["layers"] * (p["attn"] + p["index"]) \
        + z["n_dense"] * p["dense"] \
        + z["n_sparse"] * (p["router"] + p["shared"])
    return (2 * every * tokens + 2 * p["expert"] * expert_pairs
            + z["layers"] * (index_pair_flops(cfg) * index_pairs
                             + prefill_pair_flops(cfg) * prefill_chosen
                             + decode_pair_flops(cfg) * decode_chosen)
            + 2 * p["head"] * head_tokens)


def indexer(cfg, queries, index_pairs, keys_read):
    """The indexer's scores over all layers: (flops, bytes). A query
    reads its heads' q and weights once; `keys_read` key rows are read
    (a decode row its whole context, a prompt each of its keys once); a
    query writes its scores, 4 B a key."""
    z = sizes(cfg)
    q_bytes = cfg["index_n_heads"] * (cfg["index_head_dim"] * BF16 + F32)
    moved = z["layers"] * (queries * q_bytes + keys_read * index_key_bytes(cfg)
                           + index_pairs * F32)
    return z["layers"] * index_pair_flops(cfg) * index_pairs, moved


def decode_attention(cfg, rows, chosen):
    """Absorbed decode attention of `rows` rows over `chosen` chosen
    latent rows in ONE layer, over all layers: (flops, bytes). A chosen
    row is read once at its published width; a row reads its absorbed
    query (kvr + dr a head) and writes its latent output (kvr a
    head)."""
    z = sizes(cfg)
    nh, kvr, dr = cfg["num_attention_heads"], cfg["kv_lora_rank"], \
        cfg["qk_rope_head_dim"]
    moved = z["layers"] * (chosen * latent_row_bytes(cfg)
                           + rows * nh * (2 * kvr + dr) * BF16)
    return z["layers"] * decode_pair_flops(cfg) * chosen, moved


def prefill_attention(cfg, tokens, chosen):
    """Expanded prefill attention of `tokens` prompt tokens with `chosen`
    chosen pairs in ONE layer, over all layers: (flops, bytes). Each
    token's q and latent row are read and its o written once a layer."""
    z = sizes(cfg)
    nh, dn, dr, dv = cfg["num_attention_heads"], cfg["qk_nope_head_dim"], \
        cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    moved = z["layers"] * tokens * (nh * (dn + dr + dv) * BF16
                                    + latent_row_bytes(cfg))
    return z["layers"] * prefill_pair_flops(cfg) * chosen, moved
