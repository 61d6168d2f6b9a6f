"""Fault-tolerance subsystem (ROADMAP item 5): the three legs that make
training and serving survive what actually happens at scale — preempted
slices, killed ranks, cold restarts.

- **compile_cache**: `FLAGS_compile_cache_dir` turns on JAX's own
  persistent cache under that directory, so a restarted process
  retrieves the programs it runs instead of re-paying XLA, and beside
  it a store of serialized executables keyed by (HLO fingerprint,
  jax/backend version, topology) for the copies telemetry compiles to
  read (observability/programs.py), whose counters prove the restart.
- **checkpoint_manager**: step-numbered atomic checkpoints over the
  hardened distributed/checkpoint stack (manifest + checksums +
  rename-commit). `latest_committed()` is the restore contract: a torn
  or corrupted checkpoint is never loaded, the newest fully-committed
  one is.
- the preemption drill (tools/preempt_drill.py) is the CI proof: a
  4-process CPU-gloo job SIGKILLed mid-step, restarted, restored, with
  loss-trajectory parity against an uninterrupted run.
"""
from . import compile_cache  # noqa: F401
from .checkpoint_manager import CheckpointManager  # noqa: F401

__all__ = ["compile_cache", "CheckpointManager"]
