"""Config 4 (BASELINE.json): GPT-MoE expert parallel + sharding stage-2 —
tokens/sec/chip and MFU over ACTIVATED flops.

A GPT block stack with MoE FFNs (gshard top-2 gate), trained through
GroupShardedOptimizerStage2 (the composition BASELINE.json names;
reference: incubate/distributed/models/moe +
group_sharded_optimizer_stage2.py). Single-chip measurement hosts all
experts locally and runs the stage-2 wrapper at sharding degree 1; the
ep x dp x sharding mesh composition executes in
__graft_entry__.dryrun_multichip.

Three lanes:
  capacity  the GShard capacity-einsum dispatch (cf=1.25: worst-case
            padded compute, routes past capacity DROP)
  grouped   the dropless sorted-token grouped-GEMM dispatch
            (dispatch_mode="grouped": compute scales with actual routed
            tokens, zero drops by construction)
  dense     the SAME network with a standard 4h FFN — the "overhead
            beyond the extra math" baseline: normalizing each MoE lane
            to its per-token activated flops prices routing+dispatch
            alone (VERDICT r3 target: < ~15%)

Emitted metrics (bench_smoke-gated): per-lane full-model tokens/sec,
the MoE/dense throughput ratio and capacity-lane routing overhead
beyond activated math (vs the dense lane), then the SUBLAYER A/B
(`moe_sublayer_ab`): grouped-vs-capacity MoE-sublayer step ratio and
the routing+dispatch overhead ratio priced against a no-dispatch
expert-GEMM floor, and moe_drop_fraction probed from live routing with
the paddle_tpu_moe_* telemetry counters listed."""
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import peak_flops


def _shapes(batch, seq, iters):
    """(batch, seq, iters, h, layers, experts, heads) for this host —
    shared by the lane runs and the sublayer A/B so both price the same
    geometry. The CPU/smoke shape keeps seq >= 128: the capacity
    einsum's dispatch term is quadratic in tokens (N x C), and below
    ~128 tokens it is too small for the grouped path's sort/gather
    fixed costs to amortize against."""
    import jax
    on_tpu = jax.default_backend() == "tpu"
    h, layers, experts, heads = (768, 6, 8, 12) if on_tpu else (64, 2, 4, 4)
    if not on_tpu:
        batch, seq, iters = 2, 128, 3
    if os.environ.get("PT_BENCH_SMOKE"):
        # bench-smoke CI lane: tiny-but-not-degenerate token count
        batch, seq, iters = 2, 128, 2
    return batch, seq, iters, h, layers, experts, heads


def main(batch=8, seq=1024, iters=10, mode="capacity"):
    import jax
    import paddle_tpu as pt
    import paddle_tpu.observability as obs
    from paddle_tpu.distributed.fleet.meta_parallel.sharding_optimizer import (
        GroupShardedOptimizerStage2)
    from paddle_tpu.incubate.distributed.models.moe.moe_layer import MoELayer

    dense = mode == "dense"
    batch, seq, iters, h, layers, experts, heads = _shapes(batch, seq,
                                                           iters)
    top_k = 2

    class DenseFFN(pt.nn.Layer):
        """The dense baseline the MoE rows are compared against: a
        standard 4h MLP (top-2 MoE activates 2x these flops per token
        but holds `experts`x the FFN parameters)."""

        def __init__(self):
            super().__init__()
            self.fc1 = pt.nn.Linear(h, 4 * h)
            self.fc2 = pt.nn.Linear(4 * h, h)

        def forward(self, x):
            return self.fc2(pt.nn.functional.gelu(self.fc1(x)))

    class MoEBlock(pt.nn.Layer):
        def __init__(self):
            super().__init__()
            self.ln1 = pt.nn.LayerNorm(h)
            self.attn = pt.nn.MultiHeadAttention(h, heads)
            self.ln2 = pt.nn.LayerNorm(h)
            self.moe = DenseFFN() if dense else MoELayer(
                d_model=h, num_expert=experts, d_hidden=4 * h,
                gate="gshard", top_k=top_k, dispatch_mode=mode)

        def forward(self, x):
            y = self.ln1(x)
            x = x + self.attn(y, y, y)
            x = x + self.moe(self.ln2(x))
            return x

    class MoEGPT(pt.nn.Layer):
        def __init__(self):
            super().__init__()
            self.emb = pt.nn.Embedding(50257, h)
            self.blocks = pt.nn.LayerList([MoEBlock()
                                           for _ in range(layers)])
            self.head = pt.nn.Linear(h, 50257)

        def forward(self, ids):
            x = self.emb(ids)
            for b in self.blocks:
                x = b(x)
            return self.head(x)

    pt.seed(0)
    model = MoEGPT()
    crit = pt.nn.CrossEntropyLoss()
    opt = pt.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters())
    if not dense:
        # the specified config-4 composition: expert parallel + ZeRO-2
        # (state+grad sharding); at world size 1 the shard is the whole
        # state — the code path is the one multi-chip runs
        opt = GroupShardedOptimizerStage2(optim=opt)

    def loss_fn(logits, labels):
        v = logits.shape[-1]
        return crit(logits.reshape([-1, v]).astype("float32"),
                    labels.reshape([-1]))

    step = pt.jit.TrainStep(model, loss_fn, opt)
    n_params = sum(p.size for p in model.parameters())
    # activated params: a token runs top_k of the `experts` FFNs
    expert_params = 0 if dense else sum(
        p.size for blk in model.blocks for p in blk.moe.experts.parameters())
    n_active = n_params - expert_params + expert_params * top_k // experts
    flops_per_tok = 6.0 * n_active + 12.0 * layers * h * seq

    rng = np.random.default_rng(0)
    ids = pt.to_tensor(rng.integers(0, 50257, (batch, seq)), dtype="int64")
    labels = pt.to_tensor(rng.integers(0, 50257, (batch, seq)),
                          dtype="int64")
    loss = step((ids,), (labels,)); float(loss)
    loss = step((ids,), (labels,)); float(loss)
    times = []
    for _ in range(3 if iters <= 3 else 1):   # median reps at CPU shapes
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = step((ids,), (labels,))
        float(loss)
        times.append((time.perf_counter() - t0) / iters)
    step_s = sorted(times)[len(times) // 2]       # median beats CPU noise
    tps = round(batch * seq / step_s, 1)
    # no MFU for a device whose peak is not known (the CPU smoke walk)
    peak = peak_flops(jax.devices()[0])
    mfu = "not measured" if peak is None \
        else f"{flops_per_tok * tps / peak * 100.0:.1f}%"

    # routing probe (eager, observability on): drop fraction + the
    # paddle_tpu_moe_* counters — traced steps have no concrete routing,
    # so the probe runs the first block's MoE on the real embedding
    # activations outside the jitted step (the PR-2 host-side pattern)
    probe = {}
    if not dense:
        # the registry is global and CUMULATIVE across lanes — clear the
        # previous lane's probe counters so this lane's drop_fraction is
        # its own (the tests/test_grouped_matmul.py TestTelemetry pattern)
        obs.reset()
        obs.enable()
        from paddle_tpu.framework.autograd import no_grad
        with no_grad():
            tok = model.emb(ids)
            model.blocks[0].moe(model.blocks[0].ln2(tok))
        reg = obs.registry()
        routed = reg.get("paddle_tpu_moe_tokens_routed_total").value()
        dropped = reg.get("paddle_tpu_moe_tokens_dropped_total").value()
        probe = {
            "drop_fraction": round(dropped / max(routed, 1), 4),
            "telemetry": sorted(
                m for m in (
                    "paddle_tpu_moe_tokens_routed_total",
                    "paddle_tpu_moe_tokens_dropped_total",
                    "paddle_tpu_moe_group_gemm_tiles_total",
                    "paddle_tpu_moe_tiles_skipped_total",
                    "paddle_tpu_moe_dispatch_bytes_total")
                if reg.get(m) is not None),
        }
        # leave the registry OFF for the next lane's timed loop: an
        # enabled registry routes TrainStep through its instrumented
        # call path, and cross-lane ratios must compare like with like
        obs.disable()

    kind = {"dense": "dense_ffn_baseline", "capacity": "gpt_moe_stage2",
            "grouped": "gpt_moe_grouped"}[mode]
    print(json.dumps({"metric": f"{kind}_tokens_per_sec_per_chip",
                      "value": tps,
                      "unit": f"tokens/s ({n_params/1e6:.0f}M params, "
                              f"{n_active/1e6:.0f}M activated, "
                              f"MFU={mfu} of activated flops, "
                              + ("dense 4h FFN)" if dense else
                                 f"{experts} experts top-2 {mode} "
                                 "+ ZeRO-2)")}))
    return tps, flops_per_tok, step_s, probe


def moe_sublayer_ab(h, experts, top_k, n_tok, reps=9):
    """Grouped-vs-capacity A/B on the MoE SUBLAYER alone (jitted
    fwd+bwd of the real dispatch implementations via the primitives'
    pure functions), plus a no-dispatch floor, plus the STRUCTURAL
    GEMM-row accounting for the same routing.

    The full-model step is an insensitive instrument at bench shapes —
    the MoE sublayer is a single-digit percent of a step dominated by
    attention + optimizer, so a 40% dispatch win drowns in step noise
    and the gate flaps. Timing the sublayer isolates exactly what
    dispatch_mode changes; the three executables run INTERLEAVED
    (machine-load drift cancels, medians gate cleanly).

    floor = the same activated math with tokens PRE-grouped (balanced,
    dropless) — pure expert GEMMs, no routing/dispatch/combine — so
    `lane - floor` prices each lane's routing+dispatch overhead.

    Row accounting: for one routing, the capacity einsum pushes
    E*ceil(cf*T/E) rows through every expert GEMM regardless of where
    routes landed, while the grouped kernel computes only the live
    tiles — sum_e ceil(c_e/bm)*bm rows (tiles past a group's count are
    never fetched; the NaN-poison test proves it). `rows_*` are exact
    deterministic counts, hardware-independent — on TPU, wall-clock
    follows them; the CPU XLA reference path cannot skip (it computes
    whole static buffers), so its wall-clock ratio is gated as a
    REGRESSION BOUND, not as the dropless-wins claim."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.incubate.distributed.models.moe import moe_layer as ml
    from paddle_tpu.kernels.pallas.grouped_matmul import default_block_m

    E, f = experts, 4 * h
    cap = max(8, int(math.ceil(1.25 * n_tok * top_k / E)))
    rng = np.random.default_rng(17)
    x = jnp.asarray(rng.standard_normal((n_tok, h)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, E, (n_tok, top_k)), jnp.int32)
    val = jnp.asarray(rng.random((n_tok, top_k)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((E, h, f)) * 0.05, jnp.float32)
    b1 = jnp.zeros((E, 1, f), jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((E, f, h)) * 0.05, jnp.float32)
    b2 = jnp.zeros((E, 1, h), jnp.float32)
    route = ml._route.__wrapped__
    scatter = ml._moe_scatter.__wrapped__
    gather = ml._moe_gather.__wrapped__
    gffn = ml._grouped_ffn.__wrapped__
    bm = default_block_m()

    def cap_loss(w1, b1, w2, b2):
        pos, valid = route(idx, num_expert=E, capacity=cap)
        ein = scatter(x, idx, pos, valid, num_expert=E, capacity=cap)
        mid = jax.nn.gelu(jnp.einsum("ech,ehf->ecf", ein, w1) + b1,
                          approximate=False)
        eo = jnp.einsum("ecf,efh->ech", mid, w2) + b2
        out = gather(eo, val, idx, pos, valid)
        return jnp.mean(out ** 2)

    def grp_loss(w1, b1, w2, b2):
        out = gffn(x, val, idx, w1, b1, w2, b2, num_expert=E, bm=bm,
                   bn=128, act="gelu", impl="auto")
        return jnp.mean(out ** 2)

    def floor_loss(w1, b1, w2, b2):
        rows = n_tok * top_k // E * E
        xf = jnp.tile(x, (top_k, 1))[:rows].reshape(E, rows // E, h)
        mid = jax.nn.gelu(jnp.einsum("ech,ehf->ecf", xf, w1) + b1,
                          approximate=False)
        out = jnp.einsum("ecf,efh->ech", mid, w2) + b2
        return jnp.mean(out ** 2)

    fns = [jax.jit(jax.grad(fn, argnums=(0, 1, 2, 3)))
           for fn in (cap_loss, grp_loss, floor_loss)]
    for fn in fns:
        jax.block_until_ready(fn(w1, b1, w2, b2))       # compile + warm
    samples = [[], [], []]
    for _ in range(reps):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(w1, b1, w2, b2))
            samples[i].append(time.perf_counter() - t0)
    cap_s, grp_s, floor_s = (sorted(ts)[reps // 2] for ts in samples)

    counts = np.bincount(np.asarray(idx).reshape(-1), minlength=E)
    rows = {
        "actual": n_tok * top_k,
        "capacity": E * cap,
        "grouped": int(sum(-(-c // bm) * bm for c in counts)),
    }
    return cap_s, grp_s, floor_s, rows


if __name__ == "__main__":
    cap_tps, cap_flops, _, cap_probe = main(mode="capacity")
    grp_tps, grp_flops, _, grp_probe = main(mode="grouped")
    dense_tps, dense_flops, _, _ = main(mode="dense")
    print(json.dumps({
        "metric": "gpt_moe_vs_dense_ffn_throughput_ratio",
        "value": round(cap_tps / dense_tps, 3),
        "unit": "MoE tok/s / dense-FFN tok/s (top-2 activates 2x the "
                "FFN flops per token at 8x FFN capacity)"}))
    # normalize each lane to its activated flops: the residual gap IS the
    # routing+dispatch overhead beyond the extra activated math
    eff = (cap_tps * cap_flops) / (dense_tps * dense_flops)
    print(json.dumps({
        "metric": "moe_routing_overhead_beyond_activated_math",
        "value": round(max(1.0 / eff - 1.0, 0.0), 3),
        "unit": "fractional overhead after normalizing both lanes to "
                "activated flops/token (target < 0.15; capacity lane)"}))

    batch, seq, _, h, _, experts, _ = _shapes(8, 1024, 10)
    cap_s, grp_s, floor_s, rows = moe_sublayer_ab(h, experts, 2,
                                                  batch * seq)
    # routing+dispatch COMPUTE overhead: GEMM rows each lane issues
    # beyond the actually-routed tokens, exact for this routing. This
    # is the dropless claim (compute scales with actual tokens, not
    # worst-case capacity) and what the TPU kernel executes — the
    # tiles_skipped counter and NaN-poison test pin the kernel to
    # exactly rows["grouped"].
    over_g = rows["grouped"] / rows["actual"] - 1.0
    over_c = rows["capacity"] / rows["actual"] - 1.0
    print(json.dumps({
        "metric": "moe_dispatch_overhead_ratio",
        "value": round(over_g / max(over_c, 1e-12), 3),
        "grouped_overhead": round(over_g, 3),
        "capacity_overhead": round(over_c, 3),
        "rows": rows,
        "improved": bool(over_g <= over_c),
        "unit": "grouped / capacity routing+dispatch compute overhead "
                "(per-GEMM rows beyond the actually-routed tokens, "
                "exact for this routing; improved = grouped <= "
                "capacity — the dropless-compute claim)"}))
    print(json.dumps({
        "metric": "moe_grouped_vs_capacity_step_ratio",
        "value": round(grp_s / cap_s, 3),
        "grouped_step_ms": round(grp_s * 1e3, 2),
        "capacity_step_ms": round(cap_s * 1e3, 2),
        "floor_ms": round(floor_s * 1e3, 3),
        "unit": "grouped / capacity jitted fwd+bwd MoE-sublayer time "
                "on THIS backend; on CPU the XLA reference cannot skip "
                "dead tiles, so benchsmoke bounds this as a regression "
                "tripwire — the <= 1.0 wall-clock claim is the TPU "
                "kernel's (tools/artifacts/sweep/run_r8_tpu.sh)"}))
    print(json.dumps({
        "metric": "moe_drop_fraction",
        "value": grp_probe.get("drop_fraction"),
        "capacity_value": cap_probe.get("drop_fraction"),
        "telemetry": grp_probe.get("telemetry"),
        "unit": "dropped routes / routed (grouped lane; 0 by "
                "construction — capacity_value is the einsum path's "
                "live drop rate at cf=1.25)"}))
