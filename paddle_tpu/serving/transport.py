"""KV-block transport: prefill/decode disaggregation (ISSUE 18
tentpole c).

A long prompt on a shared engine stalls every other slot's TPOT for
the whole prefill. Disaggregation splits the roles: dedicated PREFILL
workers compute prompt KV into their own pools and stream the finished
blocks to a DECODE engine, which imports them straight into its pool
and joins the next decode chunk — the decode engine performs ZERO
prefill device work (``decode_engine.prefill_device_calls`` stays 0,
the drill's counter gate).

The wire format is :class:`KVBlockPayload`: host numpy copies of the
prompt's pool blocks (``PagedDecoder.export_blocks``) plus the first
generated token (the prefill argmax — so TTFT is paid on the prefill
side). In-process the "stream" is a thread-safe queue drained by the
batcher's ``feed`` hook; across processes the payload pickles through
the same multiprocessing pipes the replica router uses. Pool geometry
(block_size, kv_quant, dtype, layer count) must match between the two
sides — checked at construction.

When NOT to disaggregate (README operator guide): short prompts — the
export/import byte copy costs more than the prefill it saves — and
single-tenant batch jobs where there is no TPOT SLO to protect.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

__all__ = ["KVBlockPayload", "PrefillWorker", "DisaggregatedEngine"]


@dataclass
class KVBlockPayload:
    """One finished prefill, ready for streamed admission: the prompt,
    its first generated token, and host copies of the whole-block KV
    chain (k, v pytrees shaped [L, n_blocks, bs, ...])."""
    rid: object
    prompt: list
    first_token: int
    kv: tuple
    n_blocks: int
    prefill_s: float = 0.0       # prefill wall on the worker side
    cached_tokens: int = 0       # prefix-cache savings on the worker

    def nbytes(self):
        import jax
        return sum(x.nbytes for x in jax.tree_util.tree_leaves(self.kv))


class PrefillWorker:
    """Runs prompt prefill on its own engine and exports the finished
    KV blocks. The engine's own prefix cache (if enabled) serves warm
    prefills — shared system prompts are computed once on the prefill
    side and never again anywhere."""

    def __init__(self, engine):
        self.engine = engine
        self._lock = threading.Lock()
        self.prefills = 0

    def prefill(self, rid, prompt, max_new=0):
        """Prefill ``prompt`` and return a :class:`KVBlockPayload`.
        Thread-safe (one device pass at a time per worker)."""
        import jax.numpy as jnp
        from .cache import plan_prefix
        eng = self.engine
        prompt = list(map(int, prompt))
        s0 = len(prompt)
        if s0 > eng.max_len:
            raise ValueError(f"prompt of {s0} exceeds max_len "
                             f"{eng.max_len}")
        bs = eng.block_size
        nb = -(-s0 // bs)
        with self._lock:
            t0 = time.perf_counter()
            kpool, vpool = eng.ensure_pools()
            cache = eng.prefix_cache
            m, kb, cached, cow_src = plan_prefix(cache, prompt, s0)
            fresh = eng.allocator.alloc(nb - kb)
            shared = cache.acquire(m, kb) if kb else []
            if cache is not None and cache.pager is not None:
                # a fault-in (offload tier) rebinds the persistent
                # pools — pick up the rebound buffers before use
                kpool, vpool = eng.ensure_pools()
            blocks = shared + fresh
            row = np.zeros(eng.blocks_per_seq, np.int32)
            row[:nb] = blocks
            suffix = prompt[cached:]
            ns = len(suffix)
            bucket = bs
            while bucket < ns:
                bucket *= 2
            bucket = min(bucket, eng.max_len)
            ids = np.full(bucket, 0, np.int32)
            ids[:ns] = suffix
            args_w = (eng._params, jnp.asarray(ids), jnp.int32(cached),
                      jnp.int32(ns), jnp.asarray(row), kpool, vpool)
            fn = eng._warmfill_exec(bucket)
            if cow_src is not None:
                kpool, vpool = eng._cow_copy_jit(
                    kpool, vpool, jnp.int32(cow_src),
                    jnp.int32(fresh[0]))
                # rebuild args against the post-COW pools
                args_w = args_w[:5] + (kpool, vpool)
            # the executable's first output is the FUSED first token
            # (one int32 over the wire instead of a logits row); the
            # sign bit carries the non-finite flag, which transport
            # ignores exactly like the old host-side argmax did
            enc, kpool, vpool = fn(*args_w)
            first, _ = eng.decode_first_token([enc])
            eng.prefill_device_calls += 1
            eng.prefill_tokens_computed += ns
            if cache is not None:
                cache.record_admission(cached, kb,
                                       cow=cow_src is not None)
            payload_kv = eng.export_blocks(kpool, vpool, blocks)
            # rebind BEFORE the insert: an offload-tier insert may page
            # cold blocks out through the persistent binding, which the
            # warmfill donation above just invalidated
            eng._persistent_pools = (kpool, vpool)
            if cache is not None:
                # the prompt KV is fully resident here — adopt it so
                # the NEXT request with this prefix maps instead of
                # computing; the slot-side references drop right after
                cache.insert(prompt, blocks)
            eng.allocator.free(blocks)
            if cache is not None:
                # refs just dropped — the chain is now cold enough for
                # the offload tier's resident-budget enforcement
                cache.enforce_residency()
            self.prefills += 1
            return KVBlockPayload(
                rid=rid, prompt=prompt, first_token=first,
                kv=payload_kv, n_blocks=nb,
                prefill_s=time.perf_counter() - t0,
                cached_tokens=cached)


class DisaggregatedEngine:
    """One prefill worker streaming finished KV to one decode engine —
    the in-process composition the drill and tests gate; the replica
    router composes the same pieces across processes.

    Both engines must share pool geometry. The decode engine should be
    built WITHOUT a prefix cache (its prompts arrive as payloads and
    never re-prefill); the prefill engine usually WITH one.
    """

    def __init__(self, prefill_engine, decode_engine):
        pe, de = prefill_engine, decode_engine
        for attr in ("block_size", "kv_quant", "max_len"):
            if getattr(pe, attr) != getattr(de, attr):
                raise ValueError(
                    f"prefill/decode engines disagree on {attr}: "
                    f"{getattr(pe, attr)} vs {getattr(de, attr)}")
        if pe.cfg.num_hidden_layers != de.cfg.num_hidden_layers:
            raise ValueError("engines carry different models")
        self.worker = PrefillWorker(pe)
        self.decode_engine = de

    def serve(self, requests, max_new_tokens=32, **serve_kw):
        """Serve ``requests`` (the (rid, prompt[, max_new[, arrival]])
        records PagedDecoder.serve takes) with prefill on the worker
        and decode on the decode engine. Returns {rid: tokens} exactly
        like a monolithic serve — and greedy token-identical to one."""
        quads = []
        for r in requests:
            mnt = r[2] if len(r) > 2 else max_new_tokens
            arr = float(r[3]) if len(r) > 3 else 0.0
            quads.append((r[0], list(r[1]), mnt, arr))
        quads.sort(key=lambda q: q[3])
        ready = deque()
        ready_lock = threading.Lock()
        state = {"alive": True, "error": None}
        t0 = time.perf_counter()

        def run_prefills():
            try:
                for rid, prompt, mnt, arr in quads:
                    dt = (t0 + arr) - time.perf_counter()
                    if dt > 0:
                        time.sleep(dt)       # open-loop arrivals
                    payload = self.worker.prefill(rid, prompt, mnt)
                    with ready_lock:
                        ready.append((rid, payload, mnt))
            except BaseException as e:        # surfaced by feed_active
                state["error"] = e
                raise
            finally:
                state["alive"] = False

        def feed():
            out = []
            with ready_lock:
                while ready:
                    out.append(ready.popleft())
            return out

        def feed_active():
            if state["error"] is not None:
                raise RuntimeError(
                    "prefill worker died") from state["error"]
            return state["alive"] or bool(ready)

        th = threading.Thread(target=run_prefills, daemon=True,
                              name="prefill-worker")
        th.start()
        try:
            out = self.decode_engine.serve(
                [], max_new_tokens=max_new_tokens,
                feed=feed, feed_active=feed_active, **serve_kw)
        finally:
            th.join(timeout=30)
        return out
