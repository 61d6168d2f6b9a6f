"""The system under test for `glm4_moe_lite` configurations: builds the
program's own `Glm4MoeLiteForCausalLM` and `PagedDecoder` from a
configuration file and hands them the benchmark's seeded weights.

The reference's leaves and the program's parameters carry the same names
and shapes ([in, out] matrices, [experts held, in, out] stacks, the MTP
layer's under `mtp.`), so the seeded arrays become the parameters as
they are: no second copy of 9 GB on the device. Only this module (and
the traffic loops in `chipbench/kinds/`) imports the program.
"""
from __future__ import annotations

import gc

# at import, so that a program without this family fails the cell at
# once (ImportError, before any weight is made) rather than after set-up
from paddle_tpu.models import glm4_moe_lite as program
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
            "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "num_experts_per_tok", "n_group", "topk_group",
            "n_shared_experts", "routed_scaling_factor", "norm_topk_prob",
            "rms_norm_eps", "rope_theta", "rope_scaling",
            "max_position_embeddings", "num_nextn_predict_layers",
            "partial_rotary_factor")
    return program.Glm4MoeLiteConfig(
        **{k: cfg[k] for k in keys},
        n_routed_experts=published.get("n_routed_experts",
                                       cfg["n_routed_experts"]),
        experts_held=(cfg.get("experts_first", 0), cfg["n_routed_experts"]),
        dtype=dtype)


def build_model(cfg, weights):
    """The program's Glm4MoeLiteForCausalLM at the configuration's sizes;
    its parameters ARE the benchmark's seeded arrays (the model checks
    names, shapes and types)."""
    return program.Glm4MoeLiteForCausalLM(program_config(cfg),
                                          arrays=weights)


def build_decoder(cfg, traffic, weights):
    """`PagedDecoder` with the traffic's slots, block and pool, and with
    pipelined admission: a prompt's prefill is up to 11 programs and
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
