"""A `nemotron_h` size that a test run can hold: the same code paths as
the cell (a pattern with every block kind, grouped B and C, a conv of 4
taps, GQA, a router four times as wide as the experts held, a shared
expert, paged cache beside the recurrent state, decode chunks of 8) at
toy widths. The weights' spread is 0.16 = 1.28 / sqrt(64), what the
cell's normal(0, 0.02) is to its 4096-wide rows, so that pre-activations
(and with them relu^2 experts and conv inputs) have the cell's
magnitudes and a fault in a mechanism shows among a hundred served
tokens, as it does at the cell's size."""
import jax

from chipbench.tests import tiny

CFG = dict(reference="nemotron_h", adapter="nemotron_h", vocab_size=256,
           hidden_size=64, num_hidden_layers=5,
           hybrid_override_pattern="MEM*E", num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
           mamba_head_dim=8, ssm_state_size=16, n_groups=2, conv_kernel=4,
           chunk_size=8, n_routed_experts=4, experts_first=4,
           num_experts_per_tok=4, moe_latent_size=32,
           moe_intermediate_size=48,
           moe_shared_expert_intermediate_size=96,
           routed_scaling_factor=5.0, norm_topk_prob=True,
           layer_norm_epsilon=1e-5, max_position_embeddings=256,
           time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4,
           initializer_range=0.16,
           torch_dtype="bfloat16", reduced=["n_routed_experts"],
           published={"n_routed_experts": 16}, assumed={})
SERVE = dict(tiny.SERVE)


def run(traffic, limits, seed=7, seconds=1.0, cfg=CFG):
    """The rest of a run after the look for a chip, on the CPU."""
    from chipbench import run as harness
    end_to_end = [{"name": "serve_tokens_per_s", "unit": "tokens/s"},
                  {"name": "setup_s", "unit": "s"}]
    return harness.run_cell(cfg, traffic, limits, end_to_end, [], seed,
                            seconds, 0, jax.devices()[:1], tiny.PEAK)
