"""Put the repo root on sys.path so `import paddle_tpu` works when a
benchmark is run as a plain script from any directory. Imported for its
side effect: `import _bootstrap`."""
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def force_virtual_cpu_mesh(n):
    """Force an n-device virtual CPU mesh BEFORE jax instantiates a
    backend (as tests/conftest.py does). Call before the first real jax
    use; safe to call when jax is already imported but uninitialized."""
    flag = f"--xla_force_host_platform_device_count={n}"
    if flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
