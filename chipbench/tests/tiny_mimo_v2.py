"""A `mimo_v2` size that a test run can hold: the same code paths as the
cell (full and window layers with their own KV head counts, K rows wider
than V rows, a partial rotary term with a base a kind, sinks, a leading
dense layer, a router four times as wide as the experts held, paged
blocks beside the rings, a prompt prefilled in several chunks, decode
chunks of 8) at toy widths. Prompts and budgets run several windows and
blocks long. The weights' spread is 0.16 = 1.28 / sqrt(64), what the
cell's normal(0, 0.02) is to its 4096-wide rows, so that a fault in a
mechanism shows among a hundred served tokens, as it does at the cell's
size."""
import jax

from chipbench.tests import tiny

CFG = dict(reference="mimo_v2", adapter="mimo_v2", vocab_size=256,
           hidden_size=64, intermediate_size=128, moe_intermediate_size=48,
           num_hidden_layers=5, hybrid_layer_pattern=[0, 1, 1, 0, 1],
           moe_layer_freq=[0, 1, 1, 1, 1], num_attention_heads=8,
           num_key_value_heads=2, head_dim=24, v_head_dim=16,
           swa_num_attention_heads=8, swa_num_key_value_heads=4,
           swa_head_dim=24, swa_v_head_dim=16, sliding_window=16,
           partial_rotary_factor=0.334, rope_theta=5000000,
           swa_rope_theta=10000, attention_value_scale=0.707,
           add_swa_attention_sink_bias=True,
           add_full_attention_sink_bias=False, n_routed_experts=4,
           experts_first=4, num_experts_per_tok=4, norm_topk_prob=True,
           routed_scaling_factor=None, layernorm_epsilon=1e-5,
           max_position_embeddings=256, initializer_range=0.16,
           torch_dtype="bfloat16", reduced=["n_routed_experts"],
           published={"n_routed_experts": 16}, assumed={})
SERVE = dict(tiny.SERVE, prompt_lens=[8, 24, 40, 56],
             budgets=[16, 24, 32, 40], prefill_chunk=32)


def run(traffic, limits, seed=7, seconds=1.0, cfg=CFG):
    """The rest of a run after the look for a chip, on the CPU."""
    from chipbench import run as harness
    end_to_end = [{"name": "serve_tokens_per_s", "unit": "tokens/s"},
                  {"name": "setup_s", "unit": "s"}]
    return harness.run_cell(cfg, traffic, limits, end_to_end, [], seed,
                            seconds, 0, jax.devices()[:1], tiny.PEAK)


# The same mechanisms at the least widths the chip's kernels take (K rows
# of a lane and a half, V rows of one lane, blocks of 64 tokens, the
# published window), for a first run on the chip before the cell's own
# sizes: `python3 chipbench/tests/tiny_mimo_v2.py` prints the result line.
CHIP_CFG = dict(CFG, hidden_size=512, intermediate_size=1024,
                moe_intermediate_size=256, head_dim=192, v_head_dim=128,
                swa_head_dim=192, swa_v_head_dim=128, sliding_window=128,
                vocab_size=2048, max_position_embeddings=2048,
                initializer_range=0.057)
CHIP_SERVE = dict(tiny.SERVE, prompt_lens=[96, 200, 328, 520],
                  budgets=[48, 136, 72, 200], slots=8, block=64,
                  pool_blocks=129, max_len=1024, cycles=60,
                  prefill_chunk=256)


if __name__ == "__main__":
    import json
    import sys
    print(json.dumps(run(CHIP_SERVE, {"logit_gap": 0.1},
                         seed=int(sys.argv[1]) if len(sys.argv) > 1
                         else 2**31 + 5, seconds=5.0, cfg=CHIP_CFG)))
