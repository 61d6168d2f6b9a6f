"""Persistent compile cache: cold restarts skip XLA compilation.

`FLAGS_compile_cache_dir=/path` (env or set_flags) is the one switch. It
turns on two stores under that directory:

- `<dir>/xla`: JAX's own persistent compilation cache, every entry kept
  (`_sync_jax_cache`). It serves the programs that RUN (`TrainStep`'s
  and the serve loop's jitted calls, telemetry on or off), so a
  restarted process retrieves them instead of compiling.
- `<dir>/*.ptcc`: serialized executables (`get_or_compile`). Its one
  caller in the package is the copy telemetry compiles to read
  (`observability/programs.py`), so its hit / miss counters say whether
  a restart found its programs on disk; dropping it for JAX's is D14.

`enable_jax_cache()` turns JAX's cache on without the flag, at
`cache_root()` (what the benchmark's `setup_s` measures).

Keying. An entry's key is a sha256 over:

- the LOWERED module text (the HLO fingerprint: shapes, dtypes,
  shardings, and donation are all in it — two programs that lower
  differently never collide);
- jax + jaxlib versions (an XLA upgrade silently invalidates every
  entry: serialized executables are not ABI-stable across releases);
- backend, device kind, local/global device counts, process count (a
  v5e executable must not load on CPU; a dp4 topology must not feed a
  dp8 restart);
- the global mesh's axis names + shape when one is set (same device
  count, different mesh ⇒ different partitioning);
- a caller tag separating executable families ("train_step", serve
  prefill buckets, decode chunks).

Durability contract (the same discipline as the flight recorder and the
checkpoint commit path):

- **atomic write**: entries are written to a per-pid tmp name, fsynced,
  and os.replace'd — a concurrent reader sees an old entry or a new
  entry, never a torn one; concurrent writers of the same key are
  idempotent (last replace wins, both blobs are identical).
- **corruption-tolerant load**: every entry carries its own payload
  checksum. A flipped byte, a truncated file, or an unpicklable blob
  means "cache miss, recompile, count it" — NEVER a crash. The bad
  entry is unlinked so the next store heals it.
- **fail-open everywhere**: serialization not supported on this
  backend, read-only cache dir, disk full — all degrade to the
  compile-every-time behavior the cache exists to avoid, with the
  error counted.

Telemetry: paddle_tpu_compile_cache_{hits,misses,stores,corrupt,
errors}_total and _bytes_{read,written}_total when the registry is
enabled; module-local stats() always (the preemption drill's cold-start
gate runs with telemetry off in the restarted process).

Empty disables: every lookup is a non-counted no-op, JAX's cache goes
back to where the process had it, and compilation proceeds as before.
"""
from __future__ import annotations

import hashlib
import logging
import os
import pathlib
import pickle
import threading

from ...framework.flags import define_flag, flag

__all__ = ["enabled", "cache_dir", "cache_key", "load", "store",
           "get_or_compile", "stats", "reset_stats", "cache_root",
           "enable_jax_cache"]

_JAX_PRIOR = {}       # JAX's cache settings while the flag holds them


def _sync_jax_cache(d):
    """FLAGS_compile_cache_dir's side effect (framework/flags.py fires
    it on the env path and on set_flags): the programs that run are
    jitted calls, which only JAX's own persistent cache can serve, so
    the flag points it at `<d>/xla` and keeps every entry, as the
    serialized store always did. Where JAX_COMPILATION_CACHE_DIR is set
    that directory stays. Emptied, the flag gives JAX's settings back."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as jcc
    if d:
        want = {"jax_persistent_cache_min_compile_time_secs": 0.0}
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            want["jax_compilation_cache_dir"] = os.path.join(d, "xla")
        for k in want:
            _JAX_PRIOR.setdefault(k, getattr(jax.config, k))
    elif _JAX_PRIOR:
        want = dict(_JAX_PRIOR)
        _JAX_PRIOR.clear()
    else:
        return
    for k, v in want.items():
        jax.config.update(k, v)
    # JAX decides at its first compile whether it has a cache
    jcc.reset_cache()


define_flag("compile_cache_dir", "",
            "directory of the persistent compile cache: JAX's own for "
            "the programs that run, serialized executables for "
            "telemetry's analysis copies (empty = disabled)")
define_flag("compile_cache_multiprocess", False,
            "serve persistent-cache hits for executables compiled under "
            "a multi-process runtime (TPU pods). UNSAFE on the gloo CPU "
            "backend: deserialized cross-process executables corrupt "
            "buffers and segfault (probed on jaxlib 0.4.37), so the "
            "default refuses and recompiles, counted as 'unsupported'")

logger = logging.getLogger("paddle_tpu.resilience")

_MAGIC = b"ptcc/2\n"

# process-local stats, maintained even with telemetry off: the drill's
# restarted (cold) process proves its hits through this surface
_LOCK = threading.Lock()
_STATS = {"hits": 0, "misses": 0, "stores": 0, "corrupt": 0, "errors": 0,
          "unsupported": 0, "bytes_read": 0, "bytes_written": 0}


def stats():
    with _LOCK:
        return dict(_STATS)


def reset_stats():
    with _LOCK:
        for k in _STATS:
            _STATS[k] = 0


def _count(what, n=1, nbytes=None):
    with _LOCK:
        _STATS[what] += n
        if nbytes:
            _STATS["bytes_read" if what == "hits"
                   else "bytes_written"] += nbytes
    try:
        from ... import observability as _obs
        if _obs.enabled():
            reg = _obs.registry()
            reg.counter(f"paddle_tpu_compile_cache_{what}_total",
                        "Persistent AOT compile cache events").inc(n)
            if nbytes:
                which = "read" if what == "hits" else "written"
                reg.counter(
                    f"paddle_tpu_compile_cache_bytes_{which}_total",
                    "Persistent AOT compile cache bytes moved").inc(
                        nbytes)
    except Exception:
        pass


def cache_root():
    """The one directory compiled programs are kept in: where
    JAX_COMPILATION_CACHE_DIR says, else `.jax_cache` beside the package
    (the checkout's root; git-ignored). The path is part of JAX's cache
    key, so it never comes from a temporary name, a pid or the time."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_jax_cache():
    """Turn JAX's persistent compilation cache on at `cache_root()`;
    call before the first compile. With JAX_COMPILATION_CACHE_DIR set
    JAX has already read it, and no directory is set in code."""
    root = cache_root()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        jax.config.update("jax_compilation_cache_dir", root)
    return root


def cache_dir():
    d = flag("compile_cache_dir") or ""
    return d or None


def enabled():
    return cache_dir() is not None


def _topology_tag():
    """Everything about THIS runtime that invalidates a serialized
    executable: toolchain versions, backend, device kind and counts,
    and the global mesh layout when one is set (read without creating
    one — key computation must be side-effect-free)."""
    import jax
    import jaxlib
    parts = [f"jax={jax.__version__}", f"jaxlib={jaxlib.__version__}",
             f"backend={jax.default_backend()}"]
    try:
        dev = jax.devices()[0]
        parts.append(f"kind={dev.device_kind}")
    except Exception:
        pass
    parts.append(f"devices={jax.device_count()}")
    parts.append(f"local={jax.local_device_count()}")
    parts.append(f"procs={jax.process_count()}")
    # a serialized SPMD executable embeds ITS process's local-device
    # binding — rank 0 deserializing rank 3's executable would address
    # the wrong devices (observed as garbage->NaN in the preemption
    # drill). Entries are therefore per-process-index.
    parts.append(f"proc_index={jax.process_index()}")
    from .. import mesh as mesh_mod
    m = mesh_mod._global_mesh[0]
    if m is not None:
        parts.append(f"mesh={tuple(m.axis_names)}x{tuple(m.devices.shape)}")
    return "|".join(parts)


def cache_key(lowered, tag=""):
    """sha256 hex key for a jax Lowered (or raw module text)."""
    text = lowered if isinstance(lowered, str) else lowered.as_text()
    h = hashlib.sha256()
    h.update(_topology_tag().encode())
    h.update(b"\0")
    h.update(str(tag).encode())
    h.update(b"\0")
    h.update(text.encode())
    return h.hexdigest()


def _entry_path(key):
    return os.path.join(cache_dir(), f"{key}.ptcc")


def load(key):
    """Deserialize the executable stored under `key`, or None on miss.
    A corrupt entry (bad magic, checksum mismatch, truncation, a blob
    the runtime refuses) counts, is unlinked, and reads as a miss —
    the one thing a cache must never do is take the job down."""
    if not enabled():
        return None
    path = _entry_path(key)
    try:
        with open(path, "rb") as f:
            raw = f.read()
    except OSError:
        _count("misses")
        return None
    try:
        # chaos site: a firing "compile_cache_read" injects a corrupt
        # read — the fail-open contract below (count, unlink, recompile)
        # is the machinery under test, never a crash
        from ...resilience import faults as _faults
        _faults.inject("compile_cache_read")
        if not raw.startswith(_MAGIC):
            raise ValueError("bad magic")
        body = raw[len(_MAGIC):]
        digest, blob = body[:64], body[64:]
        if hashlib.sha256(blob).hexdigest().encode() != digest:
            raise ValueError("payload checksum mismatch")
        payload, in_tree, out_tree, device_ids = pickle.loads(blob)
        import jax
        from jax.experimental import serialize_executable as _se
        # reload onto the devices it was compiled for, in their order:
        # left to itself the loader spreads it over every device
        by_id = {d.id: d for d in jax.devices()}
        compiled = _se.deserialize_and_load(
            payload, in_tree, out_tree,
            execution_devices=[by_id[i] for i in device_ids])
    except Exception as e:
        logger.warning("compile cache entry %s corrupt (%s): recompiling",
                       os.path.basename(path), e)
        _count("corrupt")
        try:
            os.unlink(path)
        except OSError:
            pass
        _count("misses")
        return None
    _count("hits", nbytes=len(raw))
    return compiled


def store(key, compiled):
    """Serialize `compiled` under `key` (atomic tmp+rename). Returns
    True on success; every failure (unserializable executable, full or
    read-only disk) degrades to "not cached" with the error counted."""
    if not enabled():
        return False
    try:
        from jax.experimental import serialize_executable as _se
        payload, in_tree, out_tree = _se.serialize(compiled)
        device_ids = [d.id for d in
                      compiled.runtime_executable().local_devices()]
        blob = pickle.dumps((payload, in_tree, out_tree, device_ids),
                            protocol=4)
        body = (_MAGIC + hashlib.sha256(blob).hexdigest().encode()
                + blob)
        d = cache_dir()
        os.makedirs(d, exist_ok=True)
        path = _entry_path(key)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(body)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except Exception as e:
        logger.warning("compile cache store failed for %s...: %s",
                       key[:12], e)
        _count("errors")
        return False
    _count("stores", nbytes=len(body))
    return True


def _topology_supported():
    """Whether serialized executables are safe to RELOAD here. Single
    process: always. Multi-process: opt-in only
    (FLAGS_compile_cache_multiprocess) — deserialized cross-process
    executables on the gloo CPU backend produce corrupt results and
    segfault (probed: same-process round-trip of a donated+collective
    training executable on 4 CPU processes, jaxlib 0.4.37), so the
    safe default is refuse-and-recompile."""
    import jax
    if jax.process_count() == 1:
        return True
    return bool(flag("compile_cache_multiprocess"))


def get_or_compile(lowered, tag=""):
    """Cache-or-compile a jax Lowered (telemetry's analysis compile
    is the caller). Returns (compiled, info) where info carries
    {"cache": "hit"|"miss"|"off"|"unsupported", "key": hex|None}. A hit
    deserializes a copy to read; it saves the program that runs nothing
    (that one is retrieved from `<dir>/xla`), it says the restart found
    this program on disk."""
    if not enabled():
        return lowered.compile(), {"cache": "off", "key": None}
    if not _topology_supported():
        _count("unsupported")
        return lowered.compile(), {"cache": "unsupported", "key": None}
    try:
        key = cache_key(lowered, tag=tag)
    except Exception as e:
        logger.warning("compile cache keying failed (%s): compiling", e)
        _count("errors")
        return lowered.compile(), {"cache": "off", "key": None}
    compiled = load(key)
    if compiled is not None:
        return compiled, {"cache": "hit", "key": key}
    compiled = lowered.compile()
    store(key, compiled)
    return compiled, {"cache": "miss", "key": key}
