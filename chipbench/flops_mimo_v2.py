"""Operations and bytes a `mimo_v2` configuration needs, from shapes
alone (the counterpart of `flops.py`, which counts a Llama layer: one
kind of attention with one head count and one head size, one MLP width,
the whole context in every layer; none of which holds here).

Only required work counts, at the PUBLISHED widths whatever the program
stores (a K row is 192 wide here even where a pool keeps it in 256):
matmul parameters by layer kind without the input embedding table, the
routed experts by the token-expert pairs that really met an expert held
here, attention at the lengths attended (the whole context in a full
layer, at most `sliding_window` keys in a window layer), the output head
over the vocabulary held here where a token is sampled. A kernel's bytes
are what the algorithm has to move.

`cfg` is a configuration file's dict (chipbench/configs/<config>.json).
"""
from __future__ import annotations

BF16, F32 = 2, 4  # bytes
FULL, WINDOW = "full", "window"


def sizes(cfg):
    pattern, freq = cfg["hybrid_layer_pattern"], cfg["moe_layer_freq"]
    return {"n_full": sum(1 for k in pattern if k == 0),
            "n_window": sum(1 for k in pattern if k != 0),
            "n_sparse": sum(1 for k in freq if k != 0),
            "n_dense": sum(1 for k in freq if k == 0),
            "held": cfg["n_routed_experts"],
            "router_width": cfg.get("published", {}).get(
                "n_routed_experts", cfg["n_routed_experts"]),
            "window": cfg["sliding_window"],
            "kv_heads": {FULL: cfg["num_key_value_heads"],
                         WINDOW: cfg["swa_num_key_value_heads"]}}


def matmul_params(cfg):
    """Matmul parameters that every token meets in one layer's attention
    of each kind ("full", "window"), in a router, in the dense MLP, in
    one routed expert, and in the head."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    dk, dv = cfg["head_dim"], cfg["v_head_dim"]
    attn = lambda nkv: h * nh * dk + h * nkv * (dk + dv) + nh * dv * h
    z = sizes(cfg)
    return {FULL: attn(z["kv_heads"][FULL]),
            WINDOW: attn(z["kv_heads"][WINDOW]),
            "router": h * z["router_width"],
            "dense": 3 * h * cfg["intermediate_size"],
            "expert": 3 * h * cfg["moe_intermediate_size"],
            "head": h * cfg["vocab_size"]}


def parameters(cfg):
    """Every parameter held here: the matmuls, both tables, the norms,
    the sinks and the routers' choice bias."""
    z, p, h = sizes(cfg), matmul_params(cfg), cfg["hidden_size"]
    layers = z["n_full"] + z["n_window"]
    sinks = cfg["num_attention_heads"] * (
        z["n_window"] * bool(cfg["add_swa_attention_sink_bias"])
        + z["n_full"] * bool(cfg["add_full_attention_sink_bias"]))
    return (z["n_full"] * p[FULL] + z["n_window"] * p[WINDOW]
            + z["n_dense"] * p["dense"]
            + z["n_sparse"] * (p["router"] + z["router_width"]
                               + z["held"] * p["expert"])
            + 2 * p["head"] + (2 * layers + 1) * h + sinks)


def window_pairs(cfg, prefills, prefill_tokens, decode_rows):
    """Query-key pairs of ONE window layer: a prompt of P >= window
    tokens has W (W + 1) / 2 + (P - W) W of them, a decode row behind it
    W (every prompt of the cell is at least a window long)."""
    w = cfg["sliding_window"]
    return prefills * (w * (w + 1) // 2) + w * (prefill_tokens - prefills * w) \
        + w * decode_rows


def forward_flops(cfg, tokens, full_pairs, ring_pairs, head_tokens,
                  expert_pairs):
    """Forward pass over `tokens` tokens. `full_pairs` / `ring_pairs`:
    query-key pairs of ONE full / ONE window layer; `head_tokens`:
    tokens that need the output head; `expert_pairs`: token-expert pairs
    computed here, summed over the expert layers."""
    z, p = sizes(cfg), matmul_params(cfg)
    every = z["n_full"] * p[FULL] + z["n_window"] * p[WINDOW] \
        + z["n_sparse"] * p["router"] + z["n_dense"] * p["dense"]
    per_pair = 2 * cfg["num_attention_heads"] \
        * (cfg["head_dim"] + cfg["v_head_dim"])
    return (2 * every * tokens + 2 * p["expert"] * expert_pairs
            + per_pair * (z["n_full"] * full_pairs
                          + z["n_window"] * ring_pairs)
            + 2 * p["head"] * head_tokens)


def kv_row_bytes(cfg, kind):
    """K and V bytes of one token in one layer of `kind`, as
    published."""
    return sizes(cfg)["kv_heads"][kind] \
        * (cfg["head_dim"] + cfg["v_head_dim"]) * BF16


def decode_attention(cfg, rows, full_keys, ring_keys):
    """Decode attention of `rows` rows that attended `full_keys` keys in
    ONE full layer and `ring_keys` in ONE window layer, over all layers:
    (flops, bytes). A row reads its keys' K and V once, its q, and writes
    its o."""
    z = sizes(cfg)
    nh, dk, dv = cfg["num_attention_heads"], cfg["head_dim"], cfg["v_head_dim"]
    layers = z["n_full"] + z["n_window"]
    keys = z["n_full"] * full_keys + z["n_window"] * ring_keys
    moved = z["n_full"] * full_keys * kv_row_bytes(cfg, FULL) \
        + z["n_window"] * ring_keys * kv_row_bytes(cfg, WINDOW) \
        + layers * rows * nh * (dk + dv) * BF16
    return 2 * nh * (dk + dv) * keys, moved


def prefill_attention(cfg, tokens, full_pairs, ring_pairs):
    """Prefill attention of `tokens` prompt tokens with `full_pairs`
    query-key pairs in ONE full layer (the causal triangle) and
    `ring_pairs` in ONE window layer (the band), over all layers:
    (flops, bytes). Each token's q, K and V are read and its o written
    once a layer."""
    z = sizes(cfg)
    nh, dk, dv = cfg["num_attention_heads"], cfg["head_dim"], cfg["v_head_dim"]
    moved = tokens * sum(
        z[n] * (nh * (dk + dv) * BF16 + kv_row_bytes(cfg, kind))
        for n, kind in (("n_full", FULL), ("n_window", WINDOW)))
    return 2 * nh * (dk + dv) * (z["n_full"] * full_pairs
                                 + z["n_window"] * ring_pairs), moved


def expert_calls(cfg, pairs, touched):
    """The three grouped products of the routed experts summed over
    calls: `pairs` token-expert pairs computed, `touched` (expert, call)
    visits whose three matrices had to be read: (flops, bytes). A pair
    also moves its hidden row in twice (gate, up), the two products'
    rows out, their gated row in, and its output row out."""
    h, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    row = 2 * h * BF16 + 2 * fe * F32 + fe * BF16 + h * F32
    return 6 * h * fe * pairs, 3 * h * fe * BF16 * touched + row * pairs
