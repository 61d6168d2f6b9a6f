"""Test harness: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's custom_cpu-plugin CI pattern (SURVEY.md §4: a CPU
masquerading as the accelerator so the full device/collective path is
exercised without special hardware).

The platform and the virtual device count are set before JAX makes its CPU
client.
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

import jax  # noqa: E402

assert jax.default_backend() == "cpu" and jax.device_count() == 8, (
    jax.default_backend(), jax.device_count())

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# module -> slow-tier marker; everything else is the fast default tier.
# Keep in sync with pyproject's addopts (default run excludes these).
_SLOW_TIERS = {
    "test_convergence": "convergence",
    "test_launch_cli": "e2e",
    "test_multiprocess_collective": "e2e",
    "test_trace_multiprocess": "e2e",
    "test_multiprocess_hybrid": "e2e",
    "test_rpc_elastic": "e2e",
    "test_hybrid_configs": "e2e",
    "test_pipeline_llama": "e2e",
    "test_pipeline_gpt": "e2e",
    "test_semi_auto_llama": "e2e",
    "test_vision": "e2e",        # model-zoo builds dominate suite time
    "test_models": "e2e",
    "test_context_parallel": "e2e",   # real-model parity runs (~1 min)
    # the broad golden sweep (584 tests, ~2 min serial) gets its own tier
    # so the default unit run stays fast; run_ci.sh lanes cover it (the
    # registry-enumeration gate stays in unit via test_op_golden_enum)
    "test_op_golden_sweep": "ops",
    # heavy distributed/system files: the default tier budget is hard
    # (the driver's tier-1 command runs under a fixed timeout), so the
    # expensive builds run in the e2e lanes; test_distributed (smoke core),
    # test_watchdog, and test_op_golden_enum stay in the default tier
    "test_auto_parallel": "e2e",
    "test_auto_tuner": "e2e",
    "test_flash_tp": "e2e",
    "test_gradient_merge": "e2e",
    "test_native_runtime": "e2e",
    "test_pipeline_schedules": "e2e",
    "test_ps": "e2e",
    "test_zero_memory": "e2e",
}

# tier-1 (`pytest -m 'not slow'`, fixed timeout) runs EVERYTHING not marked
# slow — its -m overrides the addopts tier filter, so the marker is the
# only way to keep the fixed-budget run fast. Two groups carry it:
# - distributed/system files whose multi-minute builds don't fit the
#   budget — test_distributed, test_watchdog and test_op_golden_enum are
#   cheap and stay tier-1;
# - heavyweight system/e2e files (two-process runs, model-zoo builds,
#   subprocess launch, convergence runs) that dominate wall time for a
#   handful of tests. All of them still run via tools/run_ci.sh lanes.
_TIER1_SLOW = {
    # multi-minute distributed/system builds
    "test_auto_parallel", "test_auto_tuner", "test_context_parallel",
    "test_elastic_e2e", "test_flash_tp", "test_gradient_merge",
    "test_hybrid_configs", "test_models", "test_native_runtime",
    "test_pipeline_gpt", "test_pipeline_llama", "test_pipeline_schedules",
    "test_ps", "test_rpc_elastic", "test_semi_auto_llama",
    "test_zero_memory",
    # heavyweight system files (~30-130 s each for 1-25 tests)
    "test_multiprocess_collective", "test_multiprocess_hybrid",
    "test_vision", "test_launch_cli", "test_convergence",
    "test_overlap_evidence", "test_trace_multiprocess",
}

# inner-loop tier (~100 s serial on 1 core): the load-bearing core files.
# `tools/run_ci.sh smoke` / `pytest -m smoke` (VERDICT r3 weak #8)
_SMOKE_FILES = {
    "test_tensor", "test_autograd", "test_nn", "test_optimizer",
    "test_distributed", "test_sot",
}


def pytest_collection_modifyitems(config, items):
    # tier markers by module
    for item in items:
        mod = item.module.__name__.rsplit(".", 1)[-1]
        tier = _SLOW_TIERS.get(mod)
        item.add_marker(pytest.mark.unit if tier is None
                        else getattr(pytest.mark, tier))
        if mod in _SMOKE_FILES:
            item.add_marker(pytest.mark.smoke)
        if mod in _TIER1_SLOW:
            item.add_marker(pytest.mark.slow)
    # order-independence lane: PADDLE_TPU_TEST_SHUFFLE=<seed> randomizes
    # test order so suite-order coupling (leaked global state, e.g. the
    # r2 AMP-hook leak) fails CI instead of shipping
    shuffle = os.environ.get("PADDLE_TPU_TEST_SHUFFLE")
    if shuffle:
        import random
        rng = random.Random(int(shuffle))
        rng.shuffle(items)
        print(f"[shuffle] test order randomized (seed {shuffle})")
    # optional sharding: PADDLE_TPU_TEST_SHARD=i/n keeps every test whose
    # stable nodeid hash lands on shard i (reference: tools/ CI sharding)
    shard = os.environ.get("PADDLE_TPU_TEST_SHARD")
    if shard:
        import zlib
        idx, n = (int(x) for x in shard.split("/"))
        kept, dropped = [], []
        for it in items:
            (kept if zlib.crc32(it.nodeid.encode()) % n == idx
             else dropped).append(it)
        items[:] = kept
        config.hook.pytest_deselected(items=dropped)
        print(f"[shard {idx}/{n}] running {len(kept)} tests "
              f"({len(dropped)} on other shards)")


@pytest.fixture(autouse=True)
def _seed_all():
    import paddle_tpu as pt
    pt.seed(2024)
    np.random.seed(2024)
    yield
