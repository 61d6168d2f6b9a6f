"""A size that a test run can hold: the same code paths as the cells
(GQA, SwiGLU, paged cache, decode chunks of 8) at toy widths."""
import jax

CFG = dict(reference="llama_dense", adapter="llama_dense", hidden_size=64,
           intermediate_size=128, num_attention_heads=4,
           num_key_value_heads=2, num_hidden_layers=2, vocab_size=256,
           max_position_embeddings=256, rope_theta=10000.0,
           rms_norm_eps=1e-5, tie_word_embeddings=False,
           torch_dtype="bfloat16", initializer_range=0.02, reduced=[],
           assumed={})
TRAIN = dict(kind="train", batch=2, seq=64, ring=4, check_steps=3,
             optimizer=dict(name="adamw", learning_rate=1e-4, beta1=0.9,
                            beta2=0.999, epsilon=1e-8, weight_decay=0.01,
                            moment_dtype="bfloat16"))
SERVE = dict(kind="serve", arrivals={"kind": "backlog"},
             prompt_lens=[8, 12, 16, 24], budgets=[16, 24, 32, 40],
             cycles=40, slots=4, block=8, pool_blocks=65, max_len=128,
             chunk=8, check_requests=3)
PEAK = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}


def run(traffic, limits, seed=7, seconds=1.0):
    """The rest of a run after the look for a chip, on the CPU."""
    from chipbench import run as harness
    rate = {"train": "train_tokens_per_s",
            "serve": "serve_tokens_per_s"}[traffic["kind"]]
    end_to_end = [{"name": rate, "unit": "tokens/s"},
                  {"name": "setup_s", "unit": "s"}]
    return harness.run_cell(CFG, traffic, limits, end_to_end, [], seed,
                            seconds, 0, jax.devices()[:1], PEAK)
