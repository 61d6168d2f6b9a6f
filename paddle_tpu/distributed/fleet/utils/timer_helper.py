"""Interval timers for training loops (reference:
fleet/utils/timer_helper.py — the tokens/s-style timers the pipeline
driver prints via timer_printer, pipeline_parallel.py:428).

Device dispatch is async, so start()/stop() first wait for the device:
intervals measure real device time."""
from __future__ import annotations

import time

__all__ = ["Timer", "Timers", "get_timers", "set_timers"]


def _sync():
    """Wait for everything dispatched so far: a device runs its programs
    in order, so a fresh tiny one is ready only after them."""
    import jax
    jax.block_until_ready(jax.numpy.zeros((1,)))


class Timer:
    def __init__(self, name):
        self.name = name
        self._elapsed = 0.0
        self._started = False
        self._start_t = 0.0
        self._count = 0

    def start(self):
        assert not self._started, f"timer {self.name} already started"
        _sync()
        self._start_t = time.perf_counter()
        self._started = True

    def stop(self):
        assert self._started, f"timer {self.name} not started"
        _sync()
        self._elapsed += time.perf_counter() - self._start_t
        self._count += 1
        self._started = False

    def reset(self):
        self._elapsed = 0.0
        self._count = 0
        self._started = False

    def elapsed(self, reset=True):
        running = self._started
        if running:
            self.stop()
        out = self._elapsed
        if reset:
            self.reset()
        if running:
            self.start()
        return out

    @property
    def count(self):
        return self._count


class Timers:
    def __init__(self):
        self._timers = {}

    def __call__(self, name):
        if name not in self._timers:
            self._timers[name] = Timer(name)
        return self._timers[name]

    def log(self, names=None, normalizer=1.0, reset=True):
        names = names or list(self._timers)
        parts = []
        for n in names:
            if n in self._timers:
                t = self._timers[n].elapsed(reset=reset) * 1000.0
                parts.append(f"{n}: {t / normalizer:.2f}ms")
        msg = " | ".join(parts)
        print(f"[timers] {msg}")
        return msg


_GLOBAL_TIMERS = None


def get_timers():
    return _GLOBAL_TIMERS


def set_timers():
    global _GLOBAL_TIMERS
    if _GLOBAL_TIMERS is None:
        _GLOBAL_TIMERS = Timers()
    return _GLOBAL_TIMERS
