"""An `lfm2_moe` size that a test run can hold: the same code paths as
the cell (both operator kinds, a leading dense layer, GQA with QK-norm,
a router four times as wide as the experts held, top-2, the sorted
grouped products forward and backward, AdamW with bf16 moments) at toy
widths. The weights' spread is 0.16, what the cell's normal(0, 0.02) is
to its 2048-wide rows, so that pre-activations have the cell's
magnitudes."""
import jax

from chipbench.tests import tiny

CFG = dict(reference="lfm2", adapter="lfm2", vocab_size=256,
           hidden_size=64, intermediate_size=96, num_hidden_layers=4,
           layer_types=["conv", "full_attention", "conv", "full_attention"],
           num_dense_layers=1, num_attention_heads=4,
           num_key_value_heads=2, conv_L_cache=3, conv_bias=False,
           num_experts=4, experts_first=4, num_experts_per_tok=2,
           moe_intermediate_size=48, norm_topk_prob=True,
           routed_scaling_factor=1.0, use_expert_bias=True, norm_eps=1e-5,
           rope_theta=1000000.0, max_position_embeddings=256,
           initializer_range=0.16, torch_dtype="bfloat16",
           reduced=["num_experts"], published={"num_experts": 16},
           assumed={})
TRAIN = dict(tiny.TRAIN, check_steps=2)


def run(traffic=TRAIN, limits=None, seed=7, seconds=1.0, cfg=CFG):
    """The rest of a run after the look for a chip, on the CPU."""
    from chipbench import run as harness
    end_to_end = [{"name": "train_tokens_per_s", "unit": "tokens/s"},
                  {"name": "setup_s", "unit": "s"}]
    return harness.run_cell(cfg, traffic, limits or {}, end_to_end, [], seed,
                            seconds, 0, jax.devices()[:1], tiny.PEAK)
