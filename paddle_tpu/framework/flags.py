"""Global flag registry: env + runtime dual-path configuration.

Mirrors the reference's exported-flag system (paddle/common/flags.h:38-94,
flags.cc — `PD_DEFINE_EXPORTED_*` settable via FLAGS_* env or
paddle.set_flags). Flags are declared here with defaults; environment
variables named FLAGS_<name> override at first read; `set_flags` overrides
at runtime.
"""
from __future__ import annotations

import os
from typing import Any, Dict

__all__ = ["define_flag", "get_flags", "set_flags", "flag"]

_FLAGS: Dict[str, dict] = {}


def _parse_env(raw: str, default: Any):
    if isinstance(default, bool):
        return raw.lower() in ("1", "true", "yes", "on")
    if isinstance(default, int):
        return int(raw)
    if isinstance(default, float):
        return float(raw)
    return raw


def _apply_flag_hooks(name: str, value: Any) -> None:
    """Side effects some flags carry beyond the registry (applied on BOTH
    the env path and the set_flags path)."""
    if name == "check_nan_inf":
        # eager ops get a host-side scan; ops traced into jitted
        # executables get a per-op debug callback that reports the PADDLE
        # op name (op_registry._check_nan_inf_traced — the reference's
        # nan_inf_utils_detail.cc attribution). jax_debug_nans is NOT
        # flipped: it would abort on the first jax primitive before the
        # attributed report fires. Executables compiled under the old
        # flag value have the callbacks baked in (or not): drop them so
        # the next call re-traces with the new behavior.
        import sys
        reg = sys.modules.get("paddle_tpu.framework.op_registry")
        if reg is not None:  # no caches exist during module bootstrap
            reg.clear_compiled_caches()
    elif name == "enable_telemetry":
        import sys
        obs = sys.modules.get("paddle_tpu.observability.registry")
        if obs is not None:  # else picked up at observability import
            obs._set_enabled(value)
    elif name == "compile_cache_dir":
        import sys
        cc = sys.modules.get(
            "paddle_tpu.distributed.resilience.compile_cache")
        if cc is not None:  # the module that defines the flag
            cc._sync_jax_cache(value)
    elif name == "allocator_strategy":
        from .memory import apply_allocator_policy
        apply_allocator_policy(strategy=value)
    elif name == "fraction_of_gpu_memory_to_use":
        from .memory import apply_allocator_policy
        apply_allocator_policy(fraction=value)


def define_flag(name: str, default: Any, doc: str = "") -> None:
    env = os.environ.get("FLAGS_" + name)
    value = _parse_env(env, default) if env is not None else default
    _FLAGS[name] = {"value": value, "default": default, "doc": doc}
    # an env var explicitly set to the default still expresses intent
    # (e.g. FLAGS_allocator_strategy=auto_growth must override the
    # backend's own default) — fire hooks whenever the env var exists
    if env is not None:
        _apply_flag_hooks(name, value)


def flag(name: str) -> Any:
    """Read one flag value."""
    return _FLAGS[name]["value"]


def get_flags(flags) -> Dict[str, Any]:
    """Reference: paddle.get_flags (pybind global_value_getter_setter.cc)."""
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for f in flags:
        key = f[6:] if f.startswith("FLAGS_") else f
        if key not in _FLAGS:
            raise ValueError(f"Flag {f} is not registered")
        out[f] = _FLAGS[key]["value"]
    return out


def set_flags(flags: Dict[str, Any]) -> None:
    """Reference: paddle.set_flags."""
    for f, v in flags.items():
        key = f[6:] if f.startswith("FLAGS_") else f
        if key not in _FLAGS:
            raise ValueError(f"Flag {f} is not registered")
        default = _FLAGS[key]["default"]
        if isinstance(default, bool) and not isinstance(v, bool):
            v = bool(v)
        elif isinstance(default, int) and not isinstance(v, (bool, int)):
            v = int(v)
        # hook first: a rejected side effect (e.g. allocator policy after
        # backend init) must not leave the registry claiming a value that
        # was never applied
        _apply_flag_hooks(key, v)
        _FLAGS[key]["value"] = v


# ---------------------------------------------------------------------------
# Flag groups reproduced from the reference (SURVEY.md appendix D;
# paddle/common/flags.cc). Only flags with a TPU-native meaning are wired;
# others are accepted for compatibility and read by the relevant subsystem.
# ---------------------------------------------------------------------------

# numerics / debugging (flags.cc:60-107)
define_flag("check_nan_inf", False, "Scan op outputs for NaN/Inf after each eager op.")
define_flag("check_nan_inf_level", 0,
            "0: error on nan/inf; 1: warn; 2: collect stats only; 3: log all.")
define_flag("benchmark", False, "Synchronize after each op and record timings.")
define_flag("low_precision_op_list", 0, "Collect per-op amp dtype statistics.")

# eager / executor
define_flag("eager_op_jit", True, "Dispatch eager ops through cached jax.jit executables.")
define_flag("retain_grads_for_all", False, "Retain .grad for non-leaf tensors.")

# memory (TPU: XLA owns HBM; these map to donation/remat policy)
define_flag("allocator_strategy", "auto_growth",
            "auto_growth (grow on demand) | naive_best_fit (preallocated "
            "pool) — configures the XLA client allocator at backend init.")
define_flag("fraction_of_gpu_memory_to_use", 0.92,
            "Device-memory share the allocator pool may use "
            "(XLA_PYTHON_CLIENT_MEM_FRACTION; init-time only).")

# collectives
define_flag("collective_timeout_s", 600, "Collective watchdog timeout (comm_task_manager equivalent).")
define_flag("collective_async_error_handling", True, "Propagate cross-rank failures.")

# compiler (CINN-equivalent = XLA; these gate our jit layer)
define_flag("use_compiled_step", True, "Fuse whole train steps into one XLA executable.")
define_flag("jit_cache_capacity", 4096, "Max cached compiled executables in the op cache.")

# observability (paddle_tpu/observability: metrics registry + sinks)
define_flag("enable_telemetry", False,
            "Turn on the runtime metrics registry (step/memory/collective "
            "telemetry; near-zero overhead when off).")

# kernels
define_flag("use_autotune", False, "Enable kernel autotune (pallas block-size search).")
define_flag("use_fast_math", False, "Allow XLA fast-math style relaxations.")
define_flag("flash_attn_version", 2, "Compat flag for flash-attention selection.")
