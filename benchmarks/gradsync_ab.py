"""Shared grad-sync A/B probe for the training benchmarks.

Runs the SAME model + data twice through the fused TrainStep — once with
the exact tail gradient sync, once with the bucketed + compressed
scheduler (fleet/grad_buckets.py, compress="int8" by default) — on a dp
mesh over every local device, and emits one JSON metric line:

    {"metric": "<prefix>grad_sync_bytes_ratio",
     "value": <wire bytes / logical bytes from the telemetry counters>,
     "step_time_ratio": <compressed step time / baseline step time>,
     "loss_rel_err": <|loss_b - loss_a| / |loss_a| after `iters` steps>,
     "buckets": ..., "telemetry": [paddle_tpu_grad_sync_* counter names]}

The ratio comes from the observability registry (not the scheduler's
static fields) so the metric also proves the counter wiring end-to-end —
tools/bench_smoke.py gates on the counter names being present and on
value < 0.5 (int8 must beat bf16's halving). Needs >= 2 devices (the
bench-smoke lane forces a virtual CPU mesh); returns None and prints a
note on stderr otherwise.
"""
from __future__ import annotations

import json
import sys
import time


def run_grad_sync_ab(make_model_opt, loss_fn, ids_np, labels_np,
                     prefix="", iters=3, compress="int8", bucket_mb=None):
    """make_model_opt() -> (model, optimizer) — called twice under the
    same seed so A and B start from identical weights."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import paddle_tpu as pt
    from paddle_tpu import observability as obs
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.fleet.grad_buckets import (
        GradBucketScheduler)

    n = jax.device_count()
    if n < 2:
        print(f"grad-sync A/B skipped: {n} device(s), needs a dp mesh",
              file=sys.stderr)
        return None

    mesh = Mesh(np.array(jax.devices()), ("dp",))
    saved_mesh = mesh_mod._global_mesh[0]
    mesh_mod.set_mesh(mesh)
    # telemetry on for BOTH runs (the registry feeds the ratio; each
    # step is then synced, so the two walls are comparable)
    was_enabled = obs.enabled()
    obs.enable()
    try:
        dsh = NamedSharding(mesh, P("dp", None))
        rep = NamedSharding(mesh, P())
        ids = jax.device_put(jnp.asarray(ids_np), dsh)
        labels = jax.device_put(jnp.asarray(labels_np), dsh)

        def build(grad_sync):
            model, opt = make_model_opt()
            for _, p in model.named_parameters():
                p._data = jax.device_put(p._data, rep)
            step = pt.jit.TrainStep(model, loss_fn, opt,
                                    grad_sync=grad_sync)
            return model, step

        def timed(step):
            loss = step((pt.Tensor(ids),), (pt.Tensor(labels),))
            float(loss)                      # warm: trace + compile
            t0 = time.perf_counter()
            for _ in range(iters):
                loss = step((pt.Tensor(ids),), (pt.Tensor(labels),))
            last = float(loss)
            return time.perf_counter() - t0, last

        model_a, step_a = build(None)
        dt_a, loss_a = timed(step_a)

        model_b, opt_probe = make_model_opt()
        entries = [(k, tuple(p.shape),
                    jnp.dtype(p._data.dtype).name)
                   for k, p in model_b.named_parameters()]
        total_mb = sum(np.prod(s) * jnp.dtype(d).itemsize
                       for _, s, d in entries) / 2**20
        sched = GradBucketScheduler(
            entries,
            bucket_mb=bucket_mb or max(total_mb / 4, 0.25),
            compress=compress, axis="dp", mesh=mesh)

        for _, p in model_b.named_parameters():
            p._data = jax.device_put(p._data, rep)
        step_b = pt.jit.TrainStep(model_b, loss_fn, opt_probe,
                                  grad_sync=sched)
        dt_b, loss_b = timed(step_b)
        reg = obs.registry()
        sync_counters = sorted(
            name for name in list(reg._metrics)
            if name.startswith("paddle_tpu_grad_sync_"))
        logical = _counter_total(reg, "paddle_tpu_grad_sync_bytes_total")
        wire = _counter_total(
            reg, "paddle_tpu_grad_sync_compressed_bytes_total")

        ratio = wire / logical if logical else float("nan")
        row = {
            "metric": f"{prefix}grad_sync_bytes_ratio",
            "value": round(ratio, 4),
            "unit": f"wire/logical grad bytes (compress={compress}, "
                    f"dp={n}, {len(sched.buckets)} buckets)",
            "step_time_ratio": round(dt_b / dt_a, 3) if dt_a > 0 else None,
            "loss_rel_err": round(abs(loss_b - loss_a)
                                  / max(abs(loss_a), 1e-9), 5),
            "buckets": len(sched.buckets),
            "telemetry": sync_counters,
        }
        print(json.dumps(row))
        return row
    finally:
        if not was_enabled:
            obs.disable()
        mesh_mod._global_mesh[0] = saved_mesh


def _counter_total(reg, name):
    m = reg.get(name)
    if m is None:
        return 0.0
    return sum(m.labeled_values().values())
