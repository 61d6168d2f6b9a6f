"""Operations and bytes a `nemotron_h` configuration needs, from shapes
alone (the counterpart of `flops.py`, which counts a Llama layer from
`intermediate_size` x `num_hidden_layers` and would read this family
wrong by an order of magnitude).

Only required work counts: matmul parameters by block kind without the
input embedding table, the recurrence's 6 operations per state element
and token, the routed experts by the token-expert pairs that really met
an expert held here, attention at the lengths attended in the one kind
of block that has it, the output head over the vocabulary held here. A
kernel's bytes are what the algorithm has to move: a decode row reads
and writes its whole recurrent state, an expert product reads the
weights of the experts it touched once.

`cfg` is a configuration file's dict (chipbench/configs/<config>.json).
"""
from __future__ import annotations

BF16, F32 = 2, 4  # bytes


def sizes(cfg):
    heads, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    d_in = heads * hd
    state = cfg["ssm_state_size"]
    pattern = cfg["hybrid_override_pattern"]
    return {"d_in": d_in, "heads": heads, "hd": hd, "state": state,
            "conv_dim": d_in + 2 * cfg["n_groups"] * state,
            "n_m": pattern.count("M"), "n_a": pattern.count("*"),
            "n_e": pattern.count("E"),
            "held": cfg["n_routed_experts"],
            "router_width": cfg.get("published", {}).get(
                "n_routed_experts", cfg["n_routed_experts"])}


def matmul_params(cfg):
    """Matmul parameters of one block of each kind that every token
    meets ({"M", "*", "E"}; "E" without the routed experts), of one
    routed expert and of the head."""
    z, h = sizes(cfg), cfg["hidden_size"]
    nh, nkv, ad = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    lat, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    fs = cfg["moe_shared_expert_intermediate_size"]
    return {
        "M": h * (2 * z["d_in"] + 2 * cfg["n_groups"] * z["state"]
                  + z["heads"]) + z["d_in"] * h,
        "*": 2 * h * nh * ad + 2 * h * nkv * ad,
        "E": h * z["router_width"] + 2 * h * lat + 2 * h * fs,
        "expert": 2 * lat * f,
        "head": h * cfg["vocab_size"]}


def ssm_flops_per_token(cfg):
    """One Mamba block, one token: decay, outer product and sum into
    every state element (3 multiplies, 1 add), then `S C` (a multiply
    and an add): 6 per element; the conv's taps beside it."""
    z = sizes(cfg)
    return 6 * z["d_in"] * z["state"] \
        + 2 * cfg["conv_kernel"] * z["conv_dim"]


def forward_flops(cfg, tokens, attn_pairs, head_tokens, expert_pairs):
    """Forward pass over `tokens` tokens. `attn_pairs`: query-key pairs
    of ONE attention block; `head_tokens`: tokens that need the output
    head; `expert_pairs`: token-expert pairs computed here, summed over
    the expert blocks."""
    z, p = sizes(cfg), matmul_params(cfg)
    every = z["n_m"] * p["M"] + z["n_a"] * p["*"] + z["n_e"] * p["E"]
    return (2 * every * tokens
            + z["n_m"] * ssm_flops_per_token(cfg) * tokens
            + 2 * p["expert"] * expert_pairs
            + z["n_a"] * 4 * cfg["num_attention_heads"] * cfg["head_dim"]
            * attn_pairs
            + 2 * p["head"] * head_tokens)


def state_bytes_per_row(cfg):
    """Bytes one decode row moves in one Mamba block's state update:
    the float32 SSM state read and written, the conv's rows read and
    written."""
    z = sizes(cfg)
    return 2 * z["d_in"] * z["state"] * F32 \
        + 2 * (cfg["conv_kernel"] - 1) * z["conv_dim"] * BF16


def state_bytes_per_admission(cfg):
    """Bytes one admission moves in one Mamba block's rows of the state
    pools: the prefill's state and conv rows written over the slot's."""
    return state_bytes_per_row(cfg) // 2


def state_pool_shapes(cfg, slots):
    """The two per-slot state pools as the profiler prints an array's
    type: the SSM state [M blocks, slots, heads, head dim, state]
    float32 and the conv rows [M blocks, slots, taps - 1, conv dim]
    bfloat16."""
    z = sizes(cfg)
    return (f"f32[{z['n_m']},{slots},{z['heads']},{z['hd']},{z['state']}]",
            f"bf16[{z['n_m']},{slots},{cfg['conv_kernel'] - 1},"
            f"{z['conv_dim']}]")


def expert_calls(cfg, pairs, touched):
    """The two grouped products of the routed experts summed over
    calls: `pairs` token-expert pairs computed, `touched` (expert,
    call) visits whose weights had to be read: (flops, bytes). A pair
    also moves its latent row in and out and its hidden row out and in."""
    lat, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    row = lat * BF16 + f * F32 + f * BF16 + lat * F32
    return 4 * lat * f * pairs, 2 * lat * f * BF16 * touched + row * pairs
