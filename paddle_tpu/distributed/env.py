"""Distributed environment (reference: python/paddle/distributed/parallel.py:687
ParallelEnv — env-var contract from the launcher, SURVEY.md appendix B).

TPU-native: one process per HOST (not per device); jax.distributed connects
hosts; ranks in the paddle API map to mesh positions (devices), with
`get_rank()` returning the process index for launcher parity.
"""
from __future__ import annotations

import os

import jax

__all__ = ["ParallelEnv", "get_rank", "get_world_size", "is_initialized",
           "init_distributed_runtime"]

_initialized = [False]


class ParallelEnv:
    """Reads the launcher's env contract (PADDLE_TRAINER_ID & co)."""

    def __init__(self):
        self._rank = int(os.getenv("PADDLE_TRAINER_ID", "0"))
        self._world_size = int(os.getenv("PADDLE_TRAINERS_NUM", "1"))
        self._device_id = int(os.getenv("FLAGS_selected_tpus",
                                        os.getenv("FLAGS_selected_gpus", "0")))
        self._current_endpoint = os.getenv("PADDLE_CURRENT_ENDPOINT", "")
        self._trainer_endpoints = os.getenv(
            "PADDLE_TRAINER_ENDPOINTS", "").split(",")
        self._coordinator = os.getenv("PADDLE_MASTER",
                                      os.getenv("MASTER_ADDR", ""))

    @property
    def rank(self):
        return self._rank

    @property
    def world_size(self):
        return self._world_size

    @property
    def device_id(self):
        return self._device_id

    @property
    def current_endpoint(self):
        return self._current_endpoint

    @property
    def trainer_endpoints(self):
        return self._trainer_endpoints

    nranks = world_size
    local_rank = rank


def init_distributed_runtime():
    """Connect this host into the jax.distributed runtime when launched
    multi-host (the TCPStore/NCCL-unique-id role, SURVEY §2.4).

    Rendezvous is retried with bounded backoff (ISSUE 11): on a
    preemption RESTART the workers race the coordinator back up, and a
    refused first connection is the expected transient, not a fatal —
    the kill-and-resume drill's run-2 is exactly this path."""
    env = ParallelEnv()
    if env.world_size > 1 and env._coordinator and not _initialized[0]:
        # CPU cross-process computations need the gloo collectives
        # client (the default CPU backend refuses them: "Multiprocess
        # computations aren't implemented"); must be set BEFORE
        # jax.distributed.initialize. Harmless for TPU pods — the knob
        # only shapes the host CPU client.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        from ..utils.retry import bounded_retry

        def _connect():
            try:
                jax.distributed.initialize(
                    coordinator_address=env._coordinator,
                    num_processes=env.world_size,
                    process_id=env.rank)
            except Exception:
                # a failed handshake can leave the client partially
                # initialized; reset so the retry is genuine and the
                # error that finally surfaces is the REAL rendezvous
                # failure, not a secondary "already initialized"
                try:
                    jax.distributed.shutdown()
                except Exception:
                    pass
                raise

        # broad retry_on: this jax wraps connect failures in plain
        # RuntimeError/XlaRuntimeError, so there is no narrow
        # transient class to match on
        bounded_retry(_connect, what="jax.distributed rendezvous",
                      attempts=3, base_delay=0.5, retry_on=(Exception,))
    _initialized[0] = True
    return env


def is_initialized() -> bool:
    return _initialized[0]


def get_rank(group=None) -> int:
    if group is not None:
        return group.rank
    return jax.process_index()


def get_world_size(group=None) -> int:
    """Host-process world size (launcher/data-loading parity). Device-level
    parallelism ("ranks" of a collective group) lives on Group objects."""
    if group is not None:
        return group.nranks
    return jax.process_count()
