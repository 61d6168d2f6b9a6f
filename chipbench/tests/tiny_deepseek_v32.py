"""A `deepseek_v32` size that a test run can hold: the same code paths as
the cell (a query and a KV latent, a rotary key shared by the heads, an
indexer whose top-k binds far below the contexts served, YaRN positions
whose ramp lies inside the rotary dims, a leading dense layer, a router
four groups wide of which two are kept and twice as wide as the experts
held, a shared expert, a prompt prefilled in several chunks, decode
chunks of 8) at toy widths. The weights' spread is 0.16 = 1.28 /
sqrt(64), what normal(0, 0.02) is to 7168-wide rows, so that a fault in
a mechanism shows among a hundred served tokens. The traffic's kind
serves every seed one arrangement and compares the mean gap beside the
widest, as the cell's does."""
import jax

from chipbench.tests import tiny

CFG = dict(reference="deepseek_v32", adapter="deepseek_v32", vocab_size=256,
           hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
           num_hidden_layers=3, first_k_dense_replace=1,
           num_attention_heads=4, q_lora_rank=32, kv_lora_rank=32,
           qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
           index_n_heads=4, index_head_dim=16, index_topk=16,
           n_routed_experts=8, experts_first=8, num_experts_per_tok=4,
           n_group=4, topk_group=2, n_shared_experts=1,
           routed_scaling_factor=2.5, norm_topk_prob=True,
           rms_norm_eps=1e-6, rope_theta=10000,
           rope_scaling=dict(type="yarn", factor=40,
                             original_max_position_embeddings=64,
                             beta_fast=32, beta_slow=1, mscale=1,
                             mscale_all_dim=1),
           max_position_embeddings=256, initializer_range=0.16,
           torch_dtype="bfloat16", reduced=["n_routed_experts"],
           published={"n_routed_experts": 16}, assumed={})
SERVE = dict(tiny.SERVE, kind="serve_long",
             prompt_lens=[40, 72, 104, 136],
             budgets=[16, 24, 32, 40], max_len=256, pool_blocks=129,
             prefill_chunk=32)


def run(traffic, limits, seed=7, seconds=1.0, cfg=CFG):
    """The rest of a run after the look for a chip, on the CPU."""
    from chipbench import run as harness
    end_to_end = [{"name": "serve_tokens_per_s", "unit": "tokens/s"},
                  {"name": "setup_s", "unit": "s"}]
    return harness.run_cell(cfg, traffic, limits, end_to_end, [], seed,
                            seconds, 0, jax.devices()[:1], tiny.PEAK)


# The same mechanisms at the least widths the chip's kernels take (blocks
# of 64 tokens, the indexer's 128-wide keys, a 1,024-key top-k below
# 2,048-token contexts), for a first run on the chip before the cell's
# own sizes: `python3 chipbench/tests/tiny_deepseek_v32.py` prints the
# result line.
CHIP_CFG = dict(CFG, hidden_size=512, intermediate_size=1024,
                moe_intermediate_size=256, num_attention_heads=16,
                q_lora_rank=256, kv_lora_rank=256, qk_nope_head_dim=64,
                qk_rope_head_dim=64, v_head_dim=64, index_n_heads=16,
                index_head_dim=128, index_topk=512, vocab_size=2048,
                max_position_embeddings=2048, initializer_range=0.057,
                rope_scaling=dict(CFG["rope_scaling"],
                                  original_max_position_embeddings=512))
CHIP_SERVE = dict(SERVE, prompt_lens=[600, 900, 1200, 1500],
                  budgets=[48, 136, 72, 200], slots=8, block=64,
                  pool_blocks=257, max_len=2048, cycles=60,
                  prefill_chunk=512)


if __name__ == "__main__":
    import json
    import sys
    print(json.dumps(run(CHIP_SERVE, {"logit_gap": 0.1,
                                      "logit_gap_mean": 0.02},
                         seed=int(sys.argv[1]) if len(sys.argv) > 1
                         else 2**31 + 5, seconds=5.0, cfg=CHIP_CFG)))
