"""Long-context training throughput: Pallas flash attention at 8k/16k
sequence (the capability SURVEY §5 calls out — the reference has no ring
attention in-tree and its flash path is a dynloaded GPU library).

Single chip measures the flash kernel + remat pipeline at long seq; the
`sep`-axis ring/Ulysses runners extend the same model across chips."""
import _bootstrap  # noqa: F401  (repo root on sys.path)
import json
import os
import time

import numpy as np


def main():
    import jax
    import paddle_tpu as pt
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   LlamaPretrainingCriterion)

    on_tpu = jax.default_backend() == "tpu"
    smoke = bool(os.environ.get("PT_BENCH_SMOKE"))
    results = []
    for seq in ((8192, 16384, 32768) if on_tpu else (256,)):
        # r3: bf16 Adam moment storage leaves enough HBM to skip
        # rematerialization even at 32k (+~20% tok/s at every length)
        # bench-smoke CI lane: same driver, smallest model that still
        # exercises the remat + long-seq attention paths
        cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                          intermediate_size=128, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=4,
                          max_position_embeddings=seq,
                          dtype="float32", recompute=True) if smoke \
            else LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5504, num_hidden_layers=4,
                          num_attention_heads=16, num_key_value_heads=16,
                          max_position_embeddings=seq,
                          dtype="bfloat16" if on_tpu else "float32",
                          recompute=not on_tpu)
        pt.seed(0)
        model = LlamaForCausalLM(cfg)
        crit = LlamaPretrainingCriterion(cfg)
        opt = pt.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 moment_dtype="bfloat16" if on_tpu
                                 else None)
        step = pt.jit.TrainStep(model, lambda l, y: crit(l, y), opt)
        n_params = sum(p.size for p in model.parameters())
        rng = np.random.default_rng(0)
        bs = 1
        v = cfg.vocab_size
        ids = pt.to_tensor(rng.integers(0, v, (bs, seq)), dtype="int64")
        labels = pt.to_tensor(rng.integers(0, v, (bs, seq)),
                              dtype="int64")
        loss = step((ids,), (labels,)); float(loss)
        loss = step((ids,), (labels,)); float(loss)
        iters = 8 if on_tpu else 2
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = step((ids,), (labels,))
        float(loss)
        dt = time.perf_counter() - t0
        tps = bs * seq * iters / dt
        fl = (6 * n_params + 12 * cfg.num_hidden_layers
              * cfg.hidden_size * seq) * tps
        results.append({"seq": seq, "tokens_per_sec": round(tps, 1),
                        "mfu_pct": round(fl / 197e12 * 100, 1)})
    print(json.dumps({"metric": "long_context_flash_train",
                      "value": results}))
    ring_block_ab(on_tpu)
    serving_sweep(on_tpu)


def serving_sweep(on_tpu):
    """Serving at long context (ISSUE 19 tentpole c): tok/s and
    warm/cold TTFT vs context length, with context-length-sharded
    decode attention and host KV offload engaged where the geometry
    demands them. One engine per context (so the paging counters read
    per-point): each point serves the same prompt COLD (miss) then
    WARM (radix prefix hit), gates greedy parity between the two, and
    reads the offload byte counters — which must be > 0 only above the
    planner's resident-block budget (the acceptance monotonicity gate).
    CPU smoke runs tiny shapes through the same driver; the 8k->128k
    points need `run_r21_tpu.sh`."""
    import statistics
    import jax
    import paddle_tpu as pt
    import paddle_tpu.observability as obs
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.paged_decode import PagedDecoder

    if on_tpu:
        contexts = (8192, 16384, 32768, 65536, 131072)
        mnt, bs, pchunk, shard_budget = 64, 256, 8192, 128
        resident_target = 160            # blocks the budget leaves hot
        mcfg = dict(vocab_size=32000, hidden_size=2048,
                    intermediate_size=5504, num_hidden_layers=4,
                    num_attention_heads=16, num_key_value_heads=16,
                    max_position_embeddings=contexts[-1] + mnt,
                    use_flash_attention=False, dtype="bfloat16")
    else:
        contexts = (48, 96, 160)
        mnt, bs, pchunk, shard_budget = 8, 8, 32, 8
        resident_target = 14
        mcfg = dict(vocab_size=256, hidden_size=64,
                    intermediate_size=128, num_hidden_layers=2,
                    num_attention_heads=4, num_key_value_heads=2,
                    max_position_embeddings=contexts[-1] + mnt,
                    use_flash_attention=False, dtype="float32")
    pt.seed(0)
    model = LlamaForCausalLM(LlamaConfig(**mcfg))
    model.eval()
    rng = np.random.default_rng(21)

    def engine(ctx, **kw):
        nb = 2 * (-(-ctx // bs)) + 8
        return PagedDecoder(model, num_blocks=nb, max_len=ctx,
                            block_size=bs, max_slots=2,
                            ragged_kernel=True, **kw)

    # one probe prices the FIXED machine budget: weights plus a
    # resident KV allowance — the planner derives resident_frac from
    # it per engine (never a hand knob on the cache itself)
    probe = engine(contexts[0])
    budget_gib = (probe._weights_gib()
                  + resident_target * probe.bytes_per_block() / 2 ** 30)

    obs.enable()
    reg = obs.registry()
    c_out = reg.counter("paddle_tpu_kv_offload_out_bytes_total",
                        "KV bytes paged out to host")
    c_in = reg.counter("paddle_tpu_kv_offload_in_bytes_total",
                       "KV bytes faulted back from host")
    rows, tok_s_pts, ttft_cold, ttft_warm = [], [], [], []
    try:
        for ctx in contexts:
            P = [int(t) for t in
                 rng.integers(0, mcfg["vocab_size"], ctx - mnt)]
            dec = engine(ctx, prefix_cache=True, kv_offload=True,
                         hbm_budget_gib=budget_gib,
                         prefill_chunk=pchunk,
                         shard_block_budget=shard_budget)
            out0, in0 = c_out.value(), c_in.value()
            t0 = time.perf_counter()
            cold = dec.serve([(f"c{ctx}", P, mnt)])[f"c{ctx}"]
            t1 = time.perf_counter()
            warm = dec.serve([(f"w{ctx}", P, mnt)])[f"w{ctx}"]
            t2 = time.perf_counter()
            assert warm == cold, \
                f"warm/cold greedy parity broke at ctx {ctx}"
            recs = {r.rid: r
                    for r in dec.request_ledger.completed_records()}
            tc = recs[f"c{ctx}"].ttft_s() or (t1 - t0)
            tw = recs[f"w{ctx}"].ttft_s() or (t2 - t1)
            d_out = c_out.value() - out0
            d_in = c_in.value() - in0
            blocks = -(-ctx // bs)
            resident = dec.prefix_cache.resident_blocks
            if blocks <= resident and (d_out or d_in):
                raise AssertionError(
                    f"paging fired below the resident budget at ctx "
                    f"{ctx} ({blocks} <= {resident} blocks)")
            tps = 2 * mnt / (t2 - t0)
            rows.append({
                "context": ctx, "tok_s": round(tps, 2),
                "ttft_cold_s": round(tc, 4),
                "ttft_warm_s": round(tw, 4),
                "context_blocks": blocks,
                "resident_blocks": resident,
                "attn_shards": dec.attn_shards,
                "sharded_attn_calls": dec.sharded_attn_calls,
                "offload_out_bytes": int(d_out),
                "offload_in_bytes": int(d_in),
            })
            tok_s_pts.append(tps)
            ttft_cold.append(tc)
            ttft_warm.append(tw)
    finally:
        obs.disable()
    print(json.dumps({"metric": "long_context_serving", "value": rows}))
    # summary fields ride TOP-LEVEL (the serving_load_telemetry shape)
    # so bench_history's flattener records long_context_serving_summary
    # .tok_s / .p50_ttft_*_s as gateable series
    print(json.dumps({
        "metric": "long_context_serving_summary", "value": 1,
        "tok_s": round(statistics.median(tok_s_pts), 2),
        "p50_ttft_cold_s": round(statistics.median(ttft_cold), 4),
        "p50_ttft_warm_s": round(statistics.median(ttft_warm), 4),
        "unit": f"median over context lengths "
                f"{contexts[0]}..{contexts[-1]} (cold miss + warm "
                f"prefix-hit serve per point, greedy parity gated)",
    }))


def ring_block_ab(on_tpu):
    """Flash-block vs dense-block ring core A/B (VERDICT r4 #6 gate:
    flash >= 2x at the 32k regime). One chip runs exactly the per-device
    ring compute — the scan over kv blocks with online-softmax merge —
    for both block implementations; comm (the ppermute ring) is
    identical in both and excluded, so the ratio isolates what the
    kernel swap buys."""
    import importlib
    ra = importlib.import_module(
        "paddle_tpu.distributed.fleet.meta_parallel.ring_attention")
    from paddle_tpu.kernels.pallas.flash_attention import _flash_bhsd_lse

    if on_tpu:
        S, P, B, D = 32768, 8, 1, 128
        heads = (8, 16)
    elif os.environ.get("PT_BENCH_SMOKE"):
        S, P, B, D = 512, 4, 1, 64
        heads = (2,)
    else:
        S, P, B, D = 1024, 4, 1, 64
        heads = (2,)
    for H in heads:
        _ring_ab_one(ra, _flash_bhsd_lse, on_tpu, S, P, B, H, D)


def _ring_ab_one(ra, _flash_bhsd_lse, on_tpu, S, P, B, H, D):
    import time as _t
    import jax
    import jax.numpy as jnp
    sq = S // P                     # per-device block length
    rng = np.random.default_rng(0)
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    q = jnp.asarray(rng.standard_normal((B, sq, H, D)), dt)
    ks = jnp.asarray(rng.standard_normal((P, B, sq, H, D)), dt)
    vs = jnp.asarray(rng.standard_normal((P, B, sq, H, D)), dt)
    scale = float(1.0 / np.sqrt(D))   # python float: no f64 promotion
    my_idx = P // 2                 # a middle stage: P/2 real blocks

    def to_bh(x):
        return jnp.swapaxes(x, 1, 2).reshape(B * H, sq, D)

    @jax.jit
    def dense_core(q, ks, vs):
        tri = jnp.tril(jnp.ones((sq, sq), bool))

        def step(carry, kv):
            m, l, acc, src = carry
            k_t, v_t = kv
            full = src < my_idx
            none = src > my_idx
            mask = jnp.where(none, jnp.zeros_like(tri),
                             jnp.where(full, jnp.ones_like(tri), tri))
            bm, bl, bacc = ra._block_attn(q, k_t, v_t, scale, mask)
            m_new = jnp.maximum(m, bm)
            alpha, beta = jnp.exp(m - m_new), jnp.exp(bm - m_new)
            # src wraps like the real ring: blocks above the diagonal
            # arrive (and are masked out) before the below-diagonal ones
            return (m_new, l * alpha + bl * beta,
                    acc * alpha + bacc * beta,
                    jnp.mod(src - 1, P)), None

        m0 = jnp.full((B, H, sq, 1), -1e30, jnp.float32)
        l0 = jnp.zeros((B, H, sq, 1), jnp.float32)
        a0 = jnp.zeros((B, H, sq, D), jnp.float32)
        (m, l, acc, _), _ = jax.lax.scan(
            step, (m0, l0, a0, jnp.int32(my_idx)), (ks, vs))
        return acc / jnp.maximum(l, 1e-20)

    @jax.jit
    def flash_core(q, ks, vs):
        q_bh = to_bh(q)
        o0, lse0 = _flash_bhsd_lse(q_bh, to_bh(ks[0]), to_bh(vs[0]),
                                   True, float(scale))

        def step(carry, kv):
            m, l, acc, src = carry
            ob, lseb = _flash_bhsd_lse(q_bh, to_bh(kv[0]), to_bh(kv[1]),
                                       False, float(scale))
            lseb = jnp.where(src > my_idx, -1e30,
                             lseb.astype(jnp.float32))
            m_new = jnp.maximum(m, lseb)
            alpha, beta = jnp.exp(m - m_new), jnp.exp(lseb - m_new)
            return (m_new, l * alpha + beta,
                    acc * alpha[..., None]
                    + ob.astype(jnp.float32) * beta[..., None],
                    jnp.mod(src - 1, P)), None

        (m, l, acc, _), _ = jax.lax.scan(
            step, (lse0.astype(jnp.float32), jnp.ones_like(lse0, jnp.float32),
                   o0.astype(jnp.float32), jnp.int32(my_idx - 1)),
            (ks[1:], vs[1:]))
        return acc / jnp.maximum(l, 1e-20)[..., None]

    def timeit(fn):
        out = fn(q, ks, vs)
        jax.block_until_ready(out)
        reps = []
        for _ in range(3):                    # median beats HBM-layout
            t0 = _t.perf_counter()            # run-to-run variance
            for _ in range(2 if on_tpu else 1):
                out = fn(q, ks, vs)
            jax.block_until_ready(out)
            reps.append((_t.perf_counter() - t0) / (2 if on_tpu else 1))
        return sorted(reps)[1]

    t_dense = timeit(dense_core)
    t_flash = timeit(flash_core)
    print(json.dumps({
        "metric": f"ring_block_flash_vs_dense_speedup_h{H}",
        "value": round(t_dense / t_flash, 2),
        "unit": f"dense-block ring core time / flash-block ring core "
                f"time at {S} ctx (P={P} blocks of {sq}, H={H}, D={D}; "
                f"flash also never materializes the "
                f"{B * H * sq * sq * 4 / 2**20:.0f} MiB/block probs)",
        "dense_ms": round(t_dense * 1e3, 2),
        "flash_ms": round(t_flash * 1e3, 2),
    }))


if __name__ == "__main__":
    main()
