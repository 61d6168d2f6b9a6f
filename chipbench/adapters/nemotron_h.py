"""The system under test for `nemotron_h` configurations: builds the
program's own `NemotronHForCausalLM` and `PagedDecoder` from a
configuration file and hands them the benchmark's seeded weights.

The reference's leaves and the program's parameters carry the same
names and shapes ([in, out] matrices, [experts held, in, out] stacks), so
the seeded arrays become the parameters as they are: no second copy of
9 GB on the device. Only this module (and the driver loops in
`chipbench/kinds/`) imports the program.
"""
from __future__ import annotations

import gc

# at import, so that a program without this family fails the cell at
# once (ImportError, before any weight is made) rather than after set-up
from paddle_tpu.models import nemotron_h as program
from paddle_tpu.models.paged_decode import PagedDecoder


def program_config(cfg):
    """The program's configuration from a configuration file's dict: the
    published keys under their own names; the router keeps its published
    width and the file's `n_routed_experts` says how many experts are
    held here, from `experts_first`."""
    published = cfg.get("published", {})
    dtype = {"bfloat16": "bfloat16", "float32": "float32"}[cfg["torch_dtype"]]
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "hybrid_override_pattern", "num_attention_heads",
            "num_key_value_heads", "head_dim", "mamba_num_heads",
            "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel",
            "chunk_size", "num_experts_per_tok", "moe_latent_size",
            "moe_intermediate_size", "moe_shared_expert_intermediate_size",
            "routed_scaling_factor", "norm_topk_prob", "layer_norm_epsilon",
            "max_position_embeddings")
    return program.NemotronHConfig(
        **{k: cfg[k] for k in keys},
        n_routed_experts=published.get("n_routed_experts",
                                       cfg["n_routed_experts"]),
        experts_held=(cfg.get("experts_first", 0), cfg["n_routed_experts"]),
        dtype=dtype)


def build_model(cfg, weights):
    """The program's NemotronHForCausalLM at the configuration's sizes;
    its parameters ARE the benchmark's seeded arrays (the model checks
    names, shapes and types)."""
    return program.NemotronHForCausalLM(program_config(cfg), arrays=weights)


def build_decoder(cfg, traffic, weights):
    """`PagedDecoder` with the traffic's slots, block and pool, and
    with pipelined admission: two thirds of this cell's window are
    waits for first tokens (a prefill streams 7 GB of experts whatever
    its length), and with one prefill in flight at a time every pause of
    the host's there is the device's too (PERF.md, PR 31)."""
    model = build_model(cfg, weights)
    dec = PagedDecoder(model, max_len=traffic["max_len"],
                       block_size=traffic["block"],
                       num_blocks=traffic["pool_blocks"],
                       max_slots=traffic["slots"],
                       pipelined_admission=True)
    del model
    gc.collect()
    return dec
