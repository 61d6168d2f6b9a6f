"""Op-level cost model (reference: python/paddle/cost_model/cost_model.py:25
`CostModel` — static per-op benchmark table + profile-based measurement).

TPU-native: static cost = analytic roofline (flops / MXU peak vs bytes /
HBM bandwidth, whichever dominates); measured cost = time a jitted op on
the local device. The auto-parallel planner and the distributed
auto_tuner's dp_estimation mode consume these numbers."""
from __future__ import annotations

import time

__all__ = ["CostModel", "op_time_roofline"]

# per-chip numbers, override per device kind
_PEAKS = {"tpu": {"flops": 197e12, "hbm": 819e9},
          "cpu": {"flops": 1e12, "hbm": 50e9}}


def op_time_roofline(flops, bytes_moved, device="tpu"):
    """Lower-bound seconds for an op: max(compute, memory) leg."""
    peak = _PEAKS.get(device, _PEAKS["tpu"])
    return max(flops / peak["flops"], bytes_moved / peak["hbm"])


_STATIC_TABLE = {
    # op -> (flops per output elem, bytes per output elem fp32)
    "matmul": None,  # handled analytically from shapes
    "elementwise_add": (1, 12), "elementwise_mul": (1, 12),
    "relu": (1, 8), "gelu": (10, 8), "softmax": (5, 8),
    "layer_norm": (8, 8), "rms_norm": (6, 8), "reduce_sum": (1, 4),
    "transpose": (0, 8), "embedding": (0, 8),
}


class CostModel:
    def __init__(self):
        self._measured = {}

    # -- static (analytic) -------------------------------------------------
    def static_cost_data(self):
        return dict(_STATIC_TABLE)

    def get_static_op_time(self, op_name, forward=True, dtype="float32",
                           shape=(1024, 1024), device="tpu"):
        """Seconds for one op instance; backward modeled at 2x forward
        (reference returns table microseconds; here roofline)."""
        import numpy as np
        n = int(np.prod(shape))
        esize = 2 if dtype in ("float16", "bfloat16") else 4
        if op_name == "matmul":
            m, k = shape[0], shape[-1]
            flops = 2 * m * k * k
            bytes_moved = (m * k + k * k + m * k) * esize
        else:
            per = _STATIC_TABLE.get(op_name, (2, 12))
            flops = per[0] * n
            bytes_moved = per[1] * n * esize / 4
        t = op_time_roofline(flops, bytes_moved, device)
        return t if forward else 2 * t

    # -- measured ----------------------------------------------------------
    def profile_measure(self, fn, *args, iters=10, warmup=2):
        """Measure a jitted callable on the local device (the reference
        profiles a whole static program via Executor + profiler)."""
        import jax
        import numpy as np

        jitted = jax.jit(fn)
        out = jitted(*args)
        for _ in range(warmup - 1):
            out = jitted(*args)
        _sync(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = jitted(*args)
        _sync(out)
        return (time.perf_counter() - t0) / iters


def _sync(out):
    import jax
    leaves = jax.tree_util.tree_leaves(out)
    if leaves:
        jax.block_until_ready(leaves[0])
