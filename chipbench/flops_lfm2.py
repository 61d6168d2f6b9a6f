"""Operations and bytes an `lfm2_moe` configuration needs, from shapes
alone (the counterpart of `flops.py`, which counts a Llama layer:
attention in every layer, one dense MLP, no expert).

Only required work counts: matmul parameters by block kind without the
embedding lookup (the tied table counts once, as the output head), the
routed experts by the token-expert pairs that really met an expert held
here, causal attention in the attention blocks only. Nothing recomputed;
the convolution's taps and the gates are elementwise and are left out. A
grouped product's bytes are what the algorithm has to move: the weights
of the experts held once going forward and once for dx, their gradient
written once, each pair's rows in and out.

`cfg` is a configuration file's dict (chipbench/configs/<config>.json).
"""
from __future__ import annotations

from chipbench import flops

BF16 = 2  # bytes


def kinds(cfg):
    types = cfg["layer_types"]
    dense = cfg["num_dense_layers"]
    return {"conv": types.count("conv"),
            "attention": types.count("full_attention"),
            "dense": min(dense, len(types)),
            "sparse": max(len(types) - dense, 0)}


def matmul_params(cfg):
    """Matmul parameters of one operator or feed-forward part of each
    kind that every token meets, of one routed expert and of the head."""
    d, hd = cfg["hidden_size"], flops.head_dim(cfg)
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    router = cfg.get("published", {}).get("num_experts", cfg["num_experts"])
    return {"conv": 4 * d * d,
            "attention": 2 * d * nh * hd + 2 * d * nkv * hd,
            "dense": 3 * d * cfg["intermediate_size"],
            "router": d * router,
            "expert": 3 * d * cfg["moe_intermediate_size"],
            "head": d * cfg["vocab_size"]}


def forward_flops(cfg, tokens, attn_pairs, expert_pairs):
    """Forward pass over `tokens` tokens. `attn_pairs`: query-key pairs
    of ONE attention block; `expert_pairs`: token-expert pairs computed
    here, summed over the sparse layers."""
    n, p = kinds(cfg), matmul_params(cfg)
    every = (n["conv"] * p["conv"] + n["attention"] * p["attention"]
             + n["dense"] * p["dense"] + n["sparse"] * p["router"]
             + p["head"])
    return (2 * every * tokens + 2 * p["expert"] * expert_pairs
            + n["attention"] * flops.attn_flops_per_pair(cfg) * attn_pairs)


def train_flops_per_step(cfg, batch, seq, expert_pairs):
    """Forward plus backward (twice the forward), no recomputation."""
    return 3 * forward_flops(cfg, batch * seq,
                             batch * flops.causal_pairs(seq), expert_pairs)


def expert_train_calls(cfg, pairs, layers):
    """The grouped products of `layers` sparse layers of a train step,
    forward and backward (gate, up and down; each forward, dx and dw)
    over `pairs` token-expert pairs in all: (flops, bytes). Every held
    expert's weights are read for the forward and for dx and their
    gradient is written; a product K -> N moves a pair's rows K in and N
    out forward, N in and K out for dx, K and N in for dw."""
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    stack = 3 * cfg["num_experts"] * d * fe * BF16
    rows = 3 * 3 * (d + fe) * BF16
    return 3 * 2 * 3 * d * fe * pairs, 3 * stack * layers + rows * pairs
