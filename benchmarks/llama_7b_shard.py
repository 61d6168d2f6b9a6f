"""Realistic-scale validation (VERDICT r1 item 7): the PER-CHIP shard of
Llama-2-7B under mp=8 — full depth (32 layers), 7B hidden width (4096),
1/8 of the heads and ffn — trained with remat at seq 4096 on one chip.
This exercises the memory/remat behavior a real 7B mp-sharded run has per
chip (the single-chip flagship bench is wide but shallow). Records
tokens/s, MFU, and peak HBM.

On a multi-device (or bench-smoke virtual CPU) mesh the first config
also emits `llama_7b_grad_sync_bytes_ratio` — the bucketed int8 grad
sync vs exact tail sync A/B (benchmarks/gradsync_ab.py) — and
`llama_7b_mp_overlap_step_ratio` — the collective-matmul decomposition
vs the monolithic GSPMD lowering on a forced mp mesh
(benchmarks/mp_overlap_ab.py), plus the paddle_tpu_mp_overlap_*
counters bench_smoke gates on.
"""
from __future__ import annotations

import _bootstrap  # noqa: F401  (repo root on sys.path)

import json
import os
import time

import numpy as np

from bench import peak_flops, model_flops_per_token


def main(config="mp8", first=True):
    if os.environ.get("PT_BENCH_SMOKE"):
        _bootstrap.force_virtual_cpu_mesh(4)  # the A/B needs a dp mesh
    import jax
    import paddle_tpu as pt
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   LlamaPretrainingCriterion)

    on_tpu = jax.default_backend() == "tpu"
    accum, moment_dtype = 1, None
    if on_tpu and config == "mp8":
        # Llama-2-7B / mp=8 per-chip shard: 32 layers, hidden 4096,
        # heads 32/8=4 (head_dim 128), ffn 11008/8=1376, vocab 32000/8.
        # r3 recipe (VERDICT r2 item 4): bfloat16 AdamW moments (fp32
        # math, bf16 storage — halves optimizer state to ~3.4G) + fused
        # gradient accumulation at microbatch 2 lets rematerialization
        # be dropped ENTIRELY where r2's fp32 moments forced full remat
        # at 40.3% MFU. Sweep: no-remat mb1 52.2% / mb2 53.7% / mb4
        # 48.9% (memory pressure); dots-remat mb2 was 46.6%.
        cfg = LlamaConfig(vocab_size=4000, hidden_size=4096,
                          intermediate_size=1376, num_hidden_layers=32,
                          num_attention_heads=4, num_key_value_heads=4,
                          head_dim=128, max_position_embeddings=4096,
                          dtype="bfloat16", recompute=False)
        batch, seq, iters = 16, 4096, 6
        accum, moment_dtype = 8, "bfloat16"
    elif on_tpu:
        # north-star per-chip workload (BASELINE.json: 7B over mp x pp x
        # dp on v5e-256 => mp=8, pp=4): one pipeline stage = 8 layers of
        # the mp8 shard. r3: bf16 moments + the small per-stage state
        # let remat be dropped entirely (no-remat bs8 52.4% vs r2's
        # dots-remat 46.3%)
        cfg = LlamaConfig(vocab_size=4000, hidden_size=4096,
                          intermediate_size=1376, num_hidden_layers=8,
                          num_attention_heads=4, num_key_value_heads=4,
                          head_dim=128, max_position_embeddings=4096,
                          dtype="bfloat16", recompute=False)
        batch, seq, iters = 8, 4096, 10
        moment_dtype = "bfloat16"
    else:
        cfg = LlamaConfig(vocab_size=256, hidden_size=256,
                          intermediate_size=128, num_hidden_layers=4,
                          num_attention_heads=2, num_key_value_heads=2,
                          head_dim=64, max_position_embeddings=256,
                          dtype="float32", recompute=True)
        batch, seq, iters = 2, 128, 2

    pt.seed(0)
    model = LlamaForCausalLM(cfg)
    crit = LlamaPretrainingCriterion(cfg)
    opt = pt.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters(),
                             moment_dtype=moment_dtype)
    step = pt.jit.TrainStep(model,
                            lambda logits, labels: crit(logits, labels),
                            opt, accum_steps=accum)
    n_params = sum(p.size for p in model.parameters())

    rng = np.random.default_rng(0)
    ids = pt.to_tensor(rng.integers(0, cfg.vocab_size, (batch, seq)),
                       dtype="int64")
    labels = pt.to_tensor(rng.integers(0, cfg.vocab_size, (batch, seq)),
                          dtype="int64")

    loss = step((ids,), (labels,))
    loss = step((ids,), (labels,))
    _ = float(loss)

    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step((ids,), (labels,))
    _ = float(loss)
    dt = time.perf_counter() - t0

    tokens_per_sec = batch * seq * iters / dt
    flops = model_flops_per_token(cfg, seq, n_params) * tokens_per_sec
    # no MFU for a device whose peak is not known (the CPU smoke walk)
    peak = peak_flops(jax.devices()[0])
    mfu = None if peak is None else flops / peak * 100.0
    assert np.isfinite(float(loss))

    hbm_gb = None
    try:
        stats = jax.devices()[0].memory_stats()
        hbm_gb = round(stats.get("peak_bytes_in_use", 0) / 2 ** 30, 2)
    except Exception:
        pass

    print(json.dumps({
        "metric": f"llama_7b_{config}_shard_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": f"tokens/s ({n_params / 1e6:.0f}M params/chip, "
                f"bs={batch}, seq={seq}, MFU="
                + ("not measured" if mfu is None else f"{mfu:.1f}%")
                + f", peak HBM={hbm_gb} GiB)",
        "vs_baseline": None if mfu is None else round(mfu / 45.0, 3),
    }))

    # -- grad-sync A/B: once per invocation, dp mesh permitting (the
    # mp-only TPU shard configs have no dp axis to ride — skip there)
    if first and not on_tpu and jax.device_count() >= 2:
        from gradsync_ab import run_grad_sync_ab

        def make_model_opt():
            pt.seed(2)
            m = LlamaForCausalLM(cfg)
            o = pt.optimizer.AdamW(learning_rate=1e-4,
                                   parameters=m.parameters())
            return m, o

        ab_batch = max(2, jax.device_count())
        arng = np.random.default_rng(1)
        run_grad_sync_ab(
            make_model_opt,
            lambda logits, labels: crit(logits, labels),
            arng.integers(0, cfg.vocab_size,
                          (ab_batch, seq)).astype(np.int32),
            arng.integers(0, cfg.vocab_size,
                          (ab_batch, seq)).astype(np.int32),
            prefix="llama_7b_", iters=2, compress="int8")

        # -- collective-matmul A/B on the same forced mesh, as mp
        from mp_overlap_ab import run_mp_overlap_ab
        run_mp_overlap_ab(prefix="llama_7b_", iters=2, compress="int8")


if __name__ == "__main__":
    import sys
    for i, config in enumerate(sys.argv[1:] or ["mp8", "mp8pp4"]):
        main(config, first=i == 0)
