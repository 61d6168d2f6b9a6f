"""The system under test for `mimo_v2` configurations: builds the
program's own `MimoV2ForCausalLM` and `PagedDecoder` from a configuration
file and hands them the benchmark's seeded weights.

The reference's leaves and the program's parameters carry the same names
and shapes ([in, out] matrices, [experts held, in, out] stacks), so the
seeded arrays become the parameters as they are: no second copy of 7 GB
on the device. Only this module (and the driver loops in
`chipbench/kinds/`) imports the program.
"""
from __future__ import annotations

import gc

# at import, so that a program without this family fails the cell at
# once (ImportError, before any weight is made) rather than after set-up
from paddle_tpu.models import mimo_v2 as program
from paddle_tpu.models.paged_decode import PagedDecoder


def program_config(cfg):
    """The program's configuration from a configuration file's dict: the
    published keys under their own names; the router keeps its published
    width and the file's `n_routed_experts` says how many experts are
    held here, from `experts_first`."""
    published = cfg.get("published", {})
    dtype = {"bfloat16": "bfloat16", "float32": "float32"}[cfg["torch_dtype"]]
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "hybrid_layer_pattern", "moe_layer_freq", "num_attention_heads",
            "num_key_value_heads", "head_dim", "v_head_dim",
            "swa_num_attention_heads", "swa_num_key_value_heads",
            "swa_head_dim", "swa_v_head_dim", "sliding_window",
            "partial_rotary_factor", "rope_theta", "swa_rope_theta",
            "attention_value_scale", "add_swa_attention_sink_bias",
            "add_full_attention_sink_bias", "num_experts_per_tok",
            "norm_topk_prob", "routed_scaling_factor", "layernorm_epsilon",
            "max_position_embeddings")
    return program.MimoV2Config(
        **{k: cfg[k] for k in keys},
        n_routed_experts=published.get("n_routed_experts",
                                       cfg["n_routed_experts"]),
        experts_held=(cfg.get("experts_first", 0), cfg["n_routed_experts"]),
        dtype=dtype)


def build_model(cfg, weights):
    """The program's MimoV2ForCausalLM at the configuration's sizes; its
    parameters ARE the benchmark's seeded arrays (the model checks names,
    shapes and types)."""
    return program.MimoV2ForCausalLM(program_config(cfg), arrays=weights)


def build_decoder(cfg, traffic, weights):
    """`PagedDecoder` with the traffic's slots, block and pool, and with
    pipelined admission: a prompt's prefill is up to eight programs and
    several prompts are admitted in one scan, so the loop dispatches them
    all before it reads the first of their first tokens."""
    model = build_model(cfg, weights)
    dec = PagedDecoder(model, max_len=traffic["max_len"],
                       block_size=traffic["block"],
                       num_blocks=traffic["pool_blocks"],
                       max_slots=traffic["slots"],
                       prefill_chunk=traffic.get("prefill_chunk"),
                       pipelined_admission=True)
    del model
    gc.collect()
    return dec
