"""paddle_tpu: a TPU-native deep learning framework.

A brand-new framework with the capabilities of the PaddlePaddle reference
(see SURVEY.md), designed TPU-first: eager dygraph API over cached XLA
executables, whole-step jit, Pallas fused kernels, and a parallelism stack
(DP/TP/SP/PP/ZeRO/MoE/auto-parallel) built on jax.sharding meshes and XLA
collectives over ICI/DCN.
"""
from __future__ import annotations

import jax as _jax

# fp32 means fp32: float32 matmuls run at full precision (the reference's
# CUDA kernels are fp32-faithful). bf16 speed comes from bf16 dtypes (AMP),
# not silent downcasts inside fp32 ops.
_jax.config.update("jax_default_matmul_precision", "highest")

# int64 is the reference's default integer dtype (labels, indices); enable
# 64-bit types. Float creation paths still default to float32 (Tensor()
# downcasts f64 input), so no f64 compute sneaks onto the TPU.
_jax.config.update("jax_enable_x64", True)

# framework core -------------------------------------------------------------
from .framework.dtype import (  # noqa: F401
    DType, dtype as _dtype_fn, convert_dtype,
    bool_, uint8, int8, int16, int32, int64,
    float16, bfloat16, float32, float64, complex64, complex128,
)
from .framework.flags import get_flags, set_flags  # noqa: F401
from .framework.tensor import Tensor, Parameter, to_tensor  # noqa: F401
from .framework.autograd import no_grad, enable_grad, is_grad_enabled, grad  # noqa: F401
from .framework.random import seed, get_rng_state, set_rng_state  # noqa: F401
from .framework.io import save, load  # noqa: F401
from . import device  # noqa: F401  (the full paddle.device namespace)
from .framework.device import (  # noqa: F401
    CPUPlace, CUDAPlace, TPUPlace, set_device, get_device,
    is_compiled_with_cuda, is_compiled_with_rocm, is_compiled_with_xpu,
    is_compiled_with_distribute,
)

# ops surface ----------------------------------------------------------------
from .ops import *  # noqa: F401,F403
from .ops import creation, math, manipulation, logic, linalg as _linalg_ops  # noqa: F401

from . import autograd  # noqa: F401

# make `bool` etc available under canonical names without shadowing builtins
import builtins as _builtins

__version__ = "0.1.0"


def is_grad_enabled_():  # legacy alias
    return is_grad_enabled()


def in_dynamic_mode() -> bool:
    """True when executing eagerly (reference: paddle.in_dynamic_mode)."""
    from .jit.trace import in_tracing
    return not in_tracing() and not _static_mode


def in_dynamic_or_pir_mode() -> bool:
    return True


_static_mode = False


def disable_static(place=None):
    """Back to eager execution (reference: paddle.disable_static).
    Detaches the default main program from the op recorder."""
    global _static_mode
    if _static_mode:
        from .framework import op_registry
        op_registry.set_recorder(None)
        _static_mode = False
    return None


def enable_static():
    """Static-graph mode (reference: paddle.enable_static): ops record
    into ``static.default_main_program()`` until ``disable_static()``,
    and ``static.Executor.run`` replays the captured program — the same
    capture machinery ``static.program_guard`` scopes, installed
    globally. The legacy ProgramDesc world this toggled in the reference
    maps to the record/replay Program here (SURVEY §2.3)."""
    global _static_mode
    if _static_mode:
        return  # already static — re-asserting must not discard capture
    from . import static as static_mod
    from .framework import op_registry
    # fresh capture per enable: without this, records/placeholders from a
    # previous enable/disable cycle replay into (and break) the next one
    static_mod._main_program = static_mod.Program()
    static_mod._startup_program = static_mod.Program()
    op_registry.set_recorder(static_mod.default_main_program())
    _static_mode = True


def disable_signal_handler():
    return None


# subpackages (imported lazily via attribute access to keep import light) ----
_LAZY_SUBMODULES = (
    "nn", "optimizer", "io", "amp", "jit", "distributed", "vision", "metric",
    "hapi", "incubate", "linalg", "fft", "signal", "sparse", "static",
    "profiler", "observability", "utils", "models", "parallel",
    "distribution", "geometric",
    "text", "audio", "quantization", "onnx", "autograd", "inference",
    "cost_model", "version", "regularizer", "callbacks", "sysconfig", "reader", "hub",
)


from .ops.extras import _attach_all_tensor_methods as _aatm
_aatm()
del _aatm


def __getattr__(name):
    if name in _LAZY_SUBMODULES:
        import importlib
        mod = importlib.import_module("." + name, __name__)
        globals()[name] = mod
        return mod
    if name == "Model":  # paddle.Model lives in hapi
        from .hapi import Model
        globals()["Model"] = Model
        return Model
    if name == "summary":
        from .hapi import summary
        globals()["summary"] = summary
        return summary
    if name == "flops":
        from .hapi import flops
        globals()["flops"] = flops
        return flops
    if name == "ParamAttr":
        from .nn.initializer.attr import ParamAttr
        globals()["ParamAttr"] = ParamAttr
        return ParamAttr
    if name == "DataParallel":
        from .distributed import DataParallel
        globals()["DataParallel"] = DataParallel
        return DataParallel
    if name in ("get_cuda_rng_state", "set_cuda_rng_state"):
        from .framework.random import get_rng_state, set_rng_state
        globals()["get_cuda_rng_state"] = get_rng_state
        globals()["set_cuda_rng_state"] = set_rng_state
        return globals()[name]
    if name == "dtype":
        from .framework.dtype import DType
        globals()["dtype"] = DType
        return DType
    if name == "bool":
        from .framework.dtype import bool_
        globals()["bool"] = bool_
        return bool_
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")
