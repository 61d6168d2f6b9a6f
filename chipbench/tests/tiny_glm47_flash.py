"""A `glm4_moe_lite` size that a test run can hold: the same code paths as
the cell (a query and a KV latent, a rotary key shared by the heads,
every causal latent row attended, a leading dense layer, experts top-4
with a shared expert, the MTP layer drafting one token a verify pass, a
prompt prefilled in several chunks, decode chunks of 8 passes) at toy
widths. The weights' spread is 0.16 = 1.28 / sqrt(64), what normal(0,
0.02) is to 4096-wide rows, so that a fault in a mechanism shows among a
hundred served tokens. The traffic's kind drafts on the device and
compares the drafts beside the served tokens, as the cell's does."""
import jax

from chipbench.tests import tiny

CFG = dict(reference="glm47_flash", adapter="glm47_flash",
           model_type="glm4_moe_lite", vocab_size=256, hidden_size=64,
           intermediate_size=128, moe_intermediate_size=32,
           num_hidden_layers=3, first_k_dense_replace=1,
           num_attention_heads=4, q_lora_rank=32, kv_lora_rank=32,
           qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=24,
           n_routed_experts=8, num_experts_per_tok=4, n_group=1,
           topk_group=1, n_shared_experts=1, routed_scaling_factor=1.8,
           norm_topk_prob=True, rms_norm_eps=1e-5, rope_theta=10000,
           rope_scaling=None, max_position_embeddings=256,
           num_nextn_predict_layers=1, partial_rotary_factor=1,
           initializer_range=0.16, torch_dtype="bfloat16", reduced=[],
           assumed={})
SERVE = dict(tiny.SERVE, kind="serve_mtp", prompt_lens=[40, 72, 104, 136],
             budgets=[16, 24, 32, 40], max_len=256, pool_blocks=129,
             prefill_chunk=32, draft_tokens=1)


def run(traffic, limits, seed=7, seconds=1.0, cfg=CFG):
    """The rest of a run after the look for a chip, on the CPU."""
    from chipbench import run as harness
    end_to_end = [{"name": "serve_tokens_per_s", "unit": "tokens/s"},
                  {"name": "setup_s", "unit": "s"}]
    return harness.run_cell(cfg, traffic, limits, end_to_end, [], seed,
                            seconds, 0, jax.devices()[:1], tiny.PEAK)


# The same mechanisms at the least widths the chip's kernels take (blocks
# of 64 tokens, latent rows of whole lanes), for a first run on the chip
# before the cell's own sizes: `PYTHONPATH=. python3
# chipbench/tests/tiny_glm47_flash.py` prints the result line. Its limits
# on the widest gaps are 0.5: on the chip (bf16 storage, seed 2**31 + 5)
# the widest served token's gap read 0.196, one token of 408 (request 3's
# third), while the mean over the 408 read 0.0014. The widest gap is one
# near-tied row that the bf16 rounding of the program's weights and rows
# turned over, as at the cell's own size, where sound runs read 0.36-0.47
# widest against means of 0.0057-0.0071 (limits 1.0 and 0.02); the means
# keep the cell's limit of 0.02 here.
CHIP_CFG = dict(CFG, hidden_size=512, intermediate_size=1024,
                moe_intermediate_size=256, num_attention_heads=8,
                q_lora_rank=256, kv_lora_rank=256, qk_nope_head_dim=64,
                qk_rope_head_dim=64, v_head_dim=128, vocab_size=2048,
                max_position_embeddings=2048, initializer_range=0.057)
CHIP_SERVE = dict(SERVE, prompt_lens=[600, 900, 1200, 1500],
                  budgets=[48, 136, 72, 200], slots=8, block=64,
                  pool_blocks=257, max_len=2048, cycles=60,
                  prefill_chunk=512)


if __name__ == "__main__":
    import json
    import sys
    print(json.dumps(run(CHIP_SERVE, {"logit_gap": 0.5,
                                      "logit_gap_mean": 0.02,
                                      "draft_gap": 0.5,
                                      "draft_gap_mean": 0.02,
                                      "verify_gap": 0.5,
                                      "verify_gap_mean": 0.02},
                         seed=int(sys.argv[1]) if len(sys.argv) > 1
                         else 2**31 + 5, seconds=5.0, cfg=CHIP_CFG)))
