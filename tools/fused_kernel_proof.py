"""Pallas-or-proof for fused rope + upper-triangle masked softmax
(VERDICT r2 item 6).

Times the jnp compositions behind
`incubate.nn.functional.fused_rotary_position_embedding` and
`incubate.softmax_mask_fuse_upper_triangle` against hand-written Pallas
kernels (`kernels/pallas/fused_elementwise.py`) on the TPU. Decision
rule: a composition within ~5% of the hand kernel stays (XLA fusion has
already matched the kernel — record the row in BASELINE.md); a kernel
winning by more gets wired into the entry.

Run from the repo root: python tools/fused_kernel_proof.py
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np


def _timeit(fn, x, *args, iters=20):
    """Time `fn` chained `iters` times INSIDE one jitted fori_loop: a
    single dispatch + a scalar readback, so per-call dispatch overhead
    (which dwarfs sub-ms ops) cancels out of the per-iter number."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def many(n):
        @jax.jit
        def run(x):
            def body(i, acc):
                return fn(acc, *args)
            return jnp.sum(lax.fori_loop(0, n, body, x)
                           .astype(jnp.float32))
        return run

    run_n = many(iters)
    run_1 = many(1)
    float(run_n(x))  # compile + sync
    float(run_1(x))
    t0 = time.perf_counter()
    float(run_n(x))
    t_n = time.perf_counter() - t0
    t0 = time.perf_counter()
    float(run_1(x))
    t_1 = time.perf_counter() - t0
    return max(t_n - t_1, 1e-9) / (iters - 1) * 1e3  # ms per call


def main():
    import jax
    import jax.numpy as jnp
    sys.path.insert(0, ".")
    from paddle_tpu.kernels.pallas.fused_elementwise import (
        rope_pallas, masked_softmax_upper_tri_pallas)

    rows = []
    rng = np.random.default_rng(0)

    # -- rope: flagship shapes [B, S, H, D] -------------------------------
    b, s, h, d = 8, 2048, 32, 128
    x = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
    inv = 1.0 / (10000.0 ** (np.arange(0, d, 2) / d))
    freqs = np.outer(np.arange(s), inv)
    emb = np.concatenate([freqs, freqs], -1)
    cos = jnp.asarray(np.cos(emb), jnp.float32)
    sin = jnp.asarray(np.sin(emb), jnp.float32)

    def rope_jnp(x, cos, sin):
        c = cos[None, :, None, :].astype(x.dtype)
        sn = sin[None, :, None, :].astype(x.dtype)
        x1, x2 = jnp.split(x, 2, axis=-1)
        rot = jnp.concatenate([-x2, x1], axis=-1)
        return x * c + rot * sn

    t_jnp = _timeit(rope_jnp, x, cos, sin, iters=200)
    t_pl = _timeit(rope_pallas, x, cos, sin, iters=200)
    # correctness first
    np.testing.assert_allclose(
        np.asarray(jax.jit(rope_pallas)(x, cos, sin), np.float32),
        np.asarray(jax.jit(rope_jnp)(x, cos, sin), np.float32),
        rtol=2e-2, atol=2e-2)
    rows.append({"op": "fused_rope", "shape": [b, s, h, d],
                 "jnp_ms": round(t_jnp, 3), "pallas_ms": round(t_pl, 3),
                 "jnp_over_pallas": round(t_jnp / t_pl, 3)})

    # -- upper-tri masked softmax: [B, H, S, S] scores --------------------
    bh, sq = 16, 2048
    scores = jnp.asarray(rng.standard_normal((bh, sq, sq)), jnp.bfloat16)

    def smut_jnp(a):
        mask = jnp.tril(jnp.ones((a.shape[-1], a.shape[-1]), bool))
        masked = jnp.where(mask, a, jnp.asarray(-1e30, a.dtype))
        return jax.nn.softmax(masked.astype(jnp.float32),
                              -1).astype(a.dtype)

    t_jnp = _timeit(smut_jnp, scores, iters=100)
    t_pl = _timeit(masked_softmax_upper_tri_pallas, scores, iters=100)
    np.testing.assert_allclose(
        np.asarray(jax.jit(masked_softmax_upper_tri_pallas)(scores),
                   np.float32),
        np.asarray(jax.jit(smut_jnp)(scores), np.float32),
        rtol=2e-2, atol=2e-2)
    rows.append({"op": "softmax_mask_fuse_upper_triangle",
                 "shape": [bh, sq, sq],
                 "jnp_ms": round(t_jnp, 3), "pallas_ms": round(t_pl, 3),
                 "jnp_over_pallas": round(t_jnp / t_pl, 3)})

    print(json.dumps({"metric": "fused_kernel_proof",
                      "backend": jax.default_backend(), "rows": rows}))


if __name__ == "__main__":
    main()
