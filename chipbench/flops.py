"""Operations and bytes the algorithm needs, from shapes alone.

Only required work counts: matmul parameters without the input embedding
table (a lookup does no matmul), attention at its causal half, nothing
recomputed. A kernel's bytes are what the algorithm has to move for the
call (for paged attention: K and V of the live lengths once, Q and the
output), not what an implementation happens to fetch.

`cfg` is a configuration file's dict (chipbench/configs/<config>.json).
"""
from __future__ import annotations

BF16 = 2  # bytes


def head_dim(cfg):
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_matmul_params(cfg):
    h, hd = cfg["hidden_size"], head_dim(cfg)
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = h * nh * hd + 2 * h * nkv * hd + nh * hd * h
    mlp = 3 * h * cfg["intermediate_size"]
    return attn + mlp


def matmul_params(cfg):
    """Parameters that take part in a matmul for every token: the blocks
    and the output head. The input embedding table is left out."""
    return (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def all_params(cfg):
    h = cfg["hidden_size"]
    per_layer = layer_matmul_params(cfg) + 2 * h
    embed = cfg["vocab_size"] * h
    head = 0 if cfg.get("tie_word_embeddings") else cfg["vocab_size"] * h
    return cfg["num_hidden_layers"] * per_layer + embed + head + h


def causal_pairs(seq):
    """Query-key pairs a causal sequence of `seq` tokens needs."""
    return seq * (seq + 1) // 2


def attn_flops_per_pair(cfg):
    """QK^T and PV for one query-key pair, all heads of one layer."""
    return 4 * cfg["num_attention_heads"] * head_dim(cfg)


def forward_flops(cfg, tokens, pairs, head_tokens=None):
    """Forward pass over `tokens` tokens whose attention covers `pairs`
    query-key pairs per layer. `head_tokens`: how many of them need the
    output head (a prefill needs its last row only); default all."""
    if head_tokens is None:
        head_tokens = tokens
    body = cfg["num_hidden_layers"] * layer_matmul_params(cfg)
    return (2 * body * tokens
            + 2 * cfg["hidden_size"] * cfg["vocab_size"] * head_tokens
            + cfg["num_hidden_layers"] * attn_flops_per_pair(cfg) * pairs)


def train_flops_per_step(cfg, batch, seq):
    """Forward plus backward (twice the forward), no recomputation."""
    return 3 * forward_flops(cfg, batch * seq, batch * causal_pairs(seq))


def flash_attention_call(cfg, batch, seq):
    """One layer's causal attention over [batch, seq], forward and
    backward together: (flops, bytes). Forward: QK^T and PV. Backward:
    dV, dP, dQ, dK (the recomputed QK^T is the kernel's choice and does
    not count). Bytes: forward reads Q, K, V and writes O; backward reads
    Q, K, V, O, dO and writes dQ, dK, dV; K and V at their own head count."""
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   head_dim(cfg))
    pairs = batch * causal_pairs(seq)
    fwd = 4 * nh * hd * pairs
    q_bytes = batch * seq * nh * hd * BF16
    kv_bytes = batch * seq * nkv * hd * BF16
    fwd_bytes = 2 * q_bytes + 2 * kv_bytes
    bwd_bytes = 4 * q_bytes + 4 * kv_bytes
    return 3 * fwd, fwd_bytes + bwd_bytes


def paged_attention_calls(cfg, rows, context_tokens):
    """Decode attention of one layer summed over calls: `rows` query rows
    in all (one per live slot per step) attending to `context_tokens`
    cached positions in all: (flops, bytes). K and V of the live lengths
    are read once; Q is read and the output written."""
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   head_dim(cfg))
    flops = 4 * nh * hd * context_tokens
    bytes_moved = (2 * nkv * hd * BF16 * context_tokens
                   + 2 * nh * hd * BF16 * rows)
    return flops, bytes_moved
