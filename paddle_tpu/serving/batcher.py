"""Continuous-batching serve loop (ISSUE 18 refactor of
``PagedDecoder.serve`` — the ~700-line driver moved out of
models/paged_decode.py so the engine file holds device code and this
file holds serving policy).

``serve_loop(engine, requests, ...)`` is the loop
``PagedDecoder.serve()`` delegates to; behavior for cache-off engines
is the historical serve() byte for byte (same executables, same
ledger records, same fault-recovery paths — the chaos drill's parity
anchor). What's new rides on two opt-ins:

- **engine.prefix_cache** (ISSUE 18 tentpole a): admission matches the
  prompt against the radix tree, maps shared blocks copy-on-write into
  the new table (allocator refcounts), device-copies the boundary
  block for fully-cached prompts, and chunk-prefills ONLY the uncached
  suffix through the pool-mapped warm-prefill executable. Retirement
  adopts the retiree's full prefix blocks into the tree. Pool
  exhaustion and HeadroomGuard pressure evict cold LRU leaves first,
  live victims second. Cache-on engines serve from PERSISTENT pools
  (engine.ensure_pools) so cached KV survives across serve() calls.

- **feed / feed_active** (tentpole c): a callable drained every loop
  iteration yielding (rid, prompt_or_payload, max_new) records —
  streamed admission for prefill/decode disaggregation. A
  KVBlockPayload admits by IMPORTING its finished KV blocks into the
  pool: zero prefill device work on the decode engine.

Zero-sync pipelined decode (ISSUE 20): the fused decode path keeps
tokens/seqlens/live/budgets/poison DEVICE-RESIDENT — the state-carrying
chunk executable (`PagedDecoder._paged_chunk_state_impl`) advances them
on device, and the next chunk consumes its predecessor's donated output
buffers, so the steady-state loop performs ZERO host->device uploads
(`eng.h2d_uploads` / paddle_tpu_serve_h2d_uploads_total). Host writes
happen only at batch-composition changes — admission, eviction,
quarantine — as full-state delta updates (`mark_state_dirty`, the
delta-update protocol's sync point; `eng.pipeline_drains`). With
lookahead on (pipeline != False), chunk N+1 is dispatched off the
device-resident state BEFORE chunk N's tokens are consumed, so advance/
retire/cache/ledger bookkeeping overlaps device compute; greedy parity
with the serial loop holds by construction because the fed-back tokens
are the ones the device wrote, and token streams are invariant to chunk
partitioning (per-step gating depends only on per-slot budgets).

What the device has in flight: one chip runs what it is handed in
order, and the loop makes every device call and every blocking read
itself, so two integers say whether the device's queue is empty (the
calls `launched`, the number of the last one a read has `landed`). The
stretch from a read that leaves nothing queued to the next device call's
return is one `serve:starved` span (`after`: what ran last, `before`:
what ended it), the only account of device idle in this loop: the step
ledger's `host_gap` bucket is the starved seconds that ended in an
iteration, `paddle_tpu_serve_device_starved_seconds_total{before}` their
sum under telemetry. Nothing of it reads a clock or asks `is_ready()`
unless telemetry is on or the tracer records.

Telemetry observes this loop and never steers it: every program is
called by one expression whatever `telemetry` says. Around the calls it
adds counter mirrors, the step ledger's clock reads (`execute` = the
loop's waits, `compile` = what the compile listener heard) and, before a
program's first call, an analysis copy (`observability/programs.py`).

A model that drafts on the device (`spec_decode="mtp"`) keeps the fused
path: each step of its chunk is a verify pass
(`PagedDecoder._draft_scan`), the device state's token is [S, 2] (the
token and its draft), and the commit takes the one or two tokens each
pass emitted, so look-ahead and pipelined admission compose with it.

PT_PIPE_TEETH (CI mutation hooks, tools/serving_drill.py
--verify-teeth): "force_sync" re-uploads the full state every chunk
(the h2d/host_gap gates must trip); "mutate_feedback" corrupts one
fed-back token at upload (the parity gate must trip).
"""
from __future__ import annotations

import os
import time

import numpy as np
import jax.numpy as jnp

from .. import observability as _obs
from ..framework.flags import flag as _flag
from ..observability.programs import profile_program
from ..resilience import faults as _faults
from .cache import plan_prefix
from .scheduler import AdmissionQueue, ReplayTracker
from .transport import KVBlockPayload

__all__ = ["serve_loop"]


def serve_loop(eng, requests, *, max_new_tokens=32, eos_token_id=None,
               chunk=8, pad_token_id=0, admission_timeout_s=None,
               reject_oversized=False, spec_decode=None,
               max_restarts=3, evict_after_deferrals=2,
               max_deferrals=8, replay_backoff_s=0.05,
               max_chunk_retries=8, feed=None, feed_active=None,
               pipeline=None):
    """The continuous-batching driver. See ``PagedDecoder.serve`` for
    the full API contract; ``eng`` is the PagedDecoder."""
    from ..models.paged_decode import _Slot
    from ..models.spec_decode import resolve_spec
    spec_cfg, draft = resolve_spec(spec_decode, eng)
    # the model's own MTP layer drafts inside the chunk program: the
    # fused path, with no host-side provider between passes
    drafting = spec_cfg is not None and spec_cfg.draft == "mtp"
    if drafting:
        if spec_cfg.k != eng.draft_layers:
            raise NotImplementedError(
                f"spec_decode k={spec_cfg.k} with drafts on the device: "
                f"the engine's {eng.draft_layers} MTP layer(s) draft "
                f"{eng.draft_layers} token(s) a pass; more would chain "
                f"them on their own drafts")
        spec_cfg = None
    if pipeline is True and spec_cfg is not None:
        # explicit refusal, not a silent fallback: the verify pass is
        # host-interactive by construction (draft proposals come from
        # the host-side provider between device calls), so one-chunk
        # lookahead cannot compose with it
        raise ValueError(
            "pipeline=True does not compose with spec_decode: the "
            "draft-propose step needs the previous pass's tokens on "
            "host before the next verify can launch. Use "
            "pipeline=None/False with spec_decode (the verify path "
            "still reuses device-resident tables/budgets/poison).")
    pipe_teeth = os.environ.get("PT_PIPE_TEETH", "")
    # an eos can cut a look-ahead chunk short after the device has run
    # it whole (`serial_n`): K and V written past the cut are rewritten,
    # a recurrent state stepped past it could not be taken back
    lookahead_on = (pipeline is not False and spec_cfg is None
                    and pipe_teeth != "force_sync"
                    and (eos_token_id is None or eng._cache_rewinds))
    cache = eng.prefix_cache
    telemetry = _obs.enabled()
    if telemetry:
        if getattr(eng, "_serve_ledger", None) is None:
            from ..observability.attribution import StepLedger
            eng._serve_ledger = StepLedger("serve")
        # per-CALL classification: idle time between two serve()
        # invocations is the caller's, not this call's data_wait
        eng._serve_ledger._prev_end = None
    # the request ledger is the loop's own accounting, fed whatever
    # telemetry says (a few clock reads and one small call per slot and
    # chunk): queue wait, TTFT and TPOT are those of the program users
    # run. Only its export (registry quantiles, JSONL, per-request
    # tracks) is conditional, inside the ledger
    if eng.request_ledger is None:
        eng.request_ledger = _obs.requests.RequestLedger("serve")
    ledger = eng.request_ledger
    recovery = bool(_flag("serve_fault_recovery"))
    quarantine_on = bool(_flag("serve_logit_quarantine"))
    replays = ReplayTracker(max_restarts, replay_backoff_s)
    defer_counts = {}        # rid -> guard deferrals while queued
    chunk_failures = 0       # consecutive decode-pass faults
    phase = {"execute": 0.0, "host_gap": 0.0}
    compiled = _obs.tracing.compile_seconds
    t_start = time.perf_counter()
    queue = AdmissionQueue(t_start)
    quads = queue.load(requests, max_new_tokens)
    # register at the scheduled ABSOLUTE arrival: queue wait and
    # TTFT start on the user's clock, not at admission
    for rid, prompt, mnt, arr in quads:
        ledger.arrival(rid, _plen(prompt), mnt, ts=t_start + arr)
    # cache-on engines serve from persistent pools — cached KV written
    # by THIS call must outlive it. Cache-off engines keep the
    # historical fresh-pools-per-call behavior (and its zeroed-pool
    # determinism) untouched.
    # `pools` is the cache the loop carries: the engine's tuple of
    # device arrays ((kpool, vpool) for a PagedDecoder), donated to and
    # returned by every program in the same order
    if cache is not None:
        pools = tuple(eng.ensure_pools())
    else:
        pools = tuple(eng.new_pools())
    results = {}
    bs = eng.block_size
    MB = eng.blocks_per_seq
    tokens = np.zeros(eng.max_slots, np.int32)
    # each slot's draft, where the model drafts on the device
    drafts = np.zeros(eng.max_slots, np.int32)
    seqlens = np.zeros(eng.max_slots, np.int32)
    tables = np.zeros((eng.max_slots, MB), np.int32)
    live = np.zeros(eng.max_slots, bool)
    # --- device-resident decode state (ISSUE 20 tentpole a) ---------------
    # dev["state"] = (tok, lens, tables, live, budgets, poison) device
    # arrays, advanced chunk-to-chunk by the state-carrying executable;
    # None = dirty (a composition change happened — the next dispatch
    # re-uploads from the host mirrors above). poison_mirror tracks the
    # device poison column so a changed coin set swaps ONE component.
    # pending[0] holds the one-chunk-lookahead dispatch not yet
    # consumed; last_ready[0] is when the last chunk's tokens reached
    # the host (the request ledger bills chunks from it).
    eos_dev = -1 if eos_token_id is None else int(eos_token_id)
    dev = {"state": None}
    poison_mirror = np.zeros(eng.max_slots, bool)
    pending = [None]
    last_ready = [None]
    # device calls made so far, the number of the last one known to have
    # finished (a blocking read of call k's result says so of every call
    # up to k), the kind of the last call, admissions begun; `dry` is
    # the open `serve:starved` stretch while something records
    issued = done = begun = 0
    ran = "start"
    dry = None
    spec_mirror = {}
    # pipelined admission (`PagedDecoder(pipelined_admission=True)`): the
    # prefills an admission scan has dispatched and not yet read
    stage_admissions = bool(getattr(eng, "pipelined_admission", False))
    staged = []
    joined = [0.0]           # when the last first token was read
    # an engine whose prefill program takes a pack of prompts says how a
    # scan's staged prompts go into programs (`prefill_packs`); the scan
    # then reserves only, and `join_staged` dispatches the packs
    plan_packs = getattr(eng, "prefill_packs", None) \
        if stage_admissions else None

    def watching():
        return telemetry or _obs.recording()

    def unready(arr):
        """Asked just before a blocking read of `arr`, while something
        records: 1 if the read will have to wait, so that a stretch that
        begins behind it begins at the program's end to within a
        wake-up; 0 if the result is there already, so the device went
        dry earlier and the stretch is a lower reading."""
        return int(not arr.is_ready()) if watching() else 0

    def starve(after, blocked):
        """The loop knows the device's queue is empty from here on."""
        nonlocal dry
        dry = {"span": _obs.tracing.open_span(
                   "serve:starved", after=after, blocked=blocked),
               "t0": time.perf_counter() if telemetry else 0.0,
               "uploads": eng.h2d_uploads, "admitted": begun}

    def landed(number, blocked):
        """A blocking read of device call `number`'s result has
        returned: every call up to it has finished. With nothing queued
        behind it the device is starved from this instant."""
        nonlocal done
        done = max(done, number)
        if done == issued and dry is None and watching():
            starve("prefill" if ran == "warm_prefill" else ran, blocked)

    def launched(kind):
        """A device call of `kind` has returned to the loop: it takes
        the next number (returned) and ends a starved stretch."""
        nonlocal issued, ran, dry
        issued += 1
        ran = kind
        if dry is not None:
            dry["span"].set(before=kind,
                            uploads=eng.h2d_uploads - dry["uploads"],
                            admitted=begun - dry["admitted"]).close()
            if telemetry:
                secs = time.perf_counter() - dry["t0"]
                phase["host_gap"] += secs
                _obs.registry().counter(
                    "paddle_tpu_serve_device_starved_seconds_total",
                    "seconds the serve loop knew the device's queue "
                    "empty, by the kind of device call that ended the "
                    "stretch", ("before",)).inc(secs, before=kind)
            dry = None
        return issued

    def unstarve():
        """Drop the open stretch: no device call ends it (the loop
        returns, or sleeps until traffic comes: idle, not starved)."""
        nonlocal dry
        if dry is not None:
            dry["span"].close(keep=False)
            dry = None

    def idle(seconds):
        """Nothing live and nothing due: the device is idle for want of
        traffic (the step ledger's data_wait), not starved by this loop;
        a fresh stretch begins when the loop looks again."""
        unstarve()
        time.sleep(seconds)
        if watching():
            starve("start", 0)

    def note_uploads(k):
        eng.h2d_uploads += k
        if telemetry:
            _obs.registry().counter(
                "paddle_tpu_serve_h2d_uploads_total",
                "host->device uploads of decode batch state (zero "
                "per chunk in the pipelined steady state)").inc(k)

    def mark_state_dirty():
        """Invalidate the device-resident decode state after a batch-
        composition change the device cannot see (admission, eviction,
        quarantine): the next dispatch re-uploads the full state from
        the host mirrors — the delta-update protocol's sync point.
        Chunk-visible retirements (eos/budget) need NO drain: the
        executable retires the slot's device liveness itself."""
        if dev["state"] is not None:
            dev["state"] = None
            eng.pipeline_drains += 1
            if telemetry:
                _obs.registry().counter(
                    "paddle_tpu_serve_pipeline_drains_total",
                    "pipeline drains: batch-composition changes that "
                    "forced a device-state re-upload").inc()

    def spec_dev_arr(name, host):
        """Device copy of a spec-path batch array, re-uploaded only
        when the host value changed since the last verify pass (the
        verify executable donates only the pools, so cached device
        copies stay valid across passes)."""
        ent = spec_mirror.get(name)
        if ent is not None and np.array_equal(ent[0], host):
            return ent[1]
        arr = jnp.asarray(host)
        spec_mirror[name] = (np.array(host, copy=True), arr)
        note_uploads(1)
        return arr

    def analysed(key, program, args):
        """Telemetry's part before a program's call: its analysis record
        on the first one (`key`: the label's stem, its number, whatever
        else tells two programs apart). Returns the compile listener's
        reading: a first call compiles inside a window `execute` bills
        (it opens before the call: the request waited for that too), so
        what the listener hears from here to the call's end comes out of
        `execute` again."""
        profile_program(eng._analysed, key, "serve",
                        lambda: f"{key[0]}{key[1]}", program, args)
        return compiled()

    def blocks_needed(length):
        return -(-length // bs)

    def cache_sync(fn, *a, **kw):
        """Run a cache operation that may PAGE (offload tier, r21)
        against the live pools. The pager reads and writes
        ``eng._persistent_pools``, but every device call in this loop
        donates the pools and rebinds the LOCAL kpool/vpool — the
        persistent binding goes stale the moment the first chunk runs.
        Hand the pager the live pools for the duration of the call,
        then take back whatever a page-in rebound. No-op (and
        byte-identical history) when no pager is armed."""
        nonlocal pools
        if cache is None or cache.pager is None:
            return fn(*a, **kw)
        eng._persistent_pools = pools
        out = fn(*a, **kw)
        pools = tuple(eng.ensure_pools())
        return out

    def never_fits(prompt, mnt):
        total = _plen(prompt) + mnt
        return (total > eng.max_len
                or blocks_needed(total) > eng.num_blocks - 1)

    def abort_cleanup():
        """A serve() unwinding mid-flight (MemoryError, oversized
        ValueError, a failing executable) must not leave its
        registered-but-unfinished requests haunting the ledger's
        in-flight table — the flight recorder would name them
        'stuck' forever on a decoder that outlives the call."""
        for rid, _, _, _ in queue:       # never admitted
            ledger.discard(rid)
        for s in eng._slots:             # admitted, mid-flight
            if not s.done:
                ledger.discard(s.req_id)

    def reject(rid, cause, now):
        # a rejected REPLAY still delivers the tokens its earlier
        # incarnations generated (the max_restarts giveup path's
        # contract); a never-admitted request delivers []
        results[rid] = finalize_tokens(replays.prefix(rid))
        eng.rejected_requests[cause] = \
            eng.rejected_requests.get(cause, 0) + 1
        ledger.reject(rid, cause, ts=now)

    def finalize_tokens(toks):
        if eos_token_id is not None and eos_token_id in toks:
            cut = toks.index(eos_token_id)
            toks = toks[:cut + 1] + \
                [pad_token_id] * (len(toks) - cut - 1)
        return toks

    def retire(i, cause):
        s = eng._slots[i]
        results[s.req_id] = finalize_tokens(s.emitted)
        if cache is not None:
            # adopt the retiree's RESIDENT prefix into the radix tree
            # before the slot's references drop: tokens with KV in the
            # pool are the first seqlens[i] of prompt+emitted (the last
            # emitted token was never fed back, so its KV was never
            # written). Duplicate chains dedupe onto existing nodes.
            chain = (list(s.prompt) + list(s.emitted))[:int(seqlens[i])]
            cache_sync(cache.insert, chain, s.blocks)
        self_free = s.blocks
        eng.allocator.free(self_free)
        if cache is not None:
            # NOW the chain is cold (rc==1, cache-only) — page the
            # overflow past the planner's resident budget to host
            cache_sync(cache.enforce_residency)
        ledger.retire(s.req_id, cause)
        eng._slots[i] = _Slot(done=True)
        tables[i] = 0
        live[i] = False

    def requeue(rid, prompt, mnt, prefix, now, admitted):
        """Schedule a replay of an evicted/faulted incarnation
        (bounded restarts, exponential backoff), or deliver the
        partial stream past the max_restarts cap."""
        delay = replays.note(rid, prefix)
        if delay is None:
            eng.replay_giveups += 1
            results[rid] = finalize_tokens(list(prefix))
            if telemetry:
                _obs.registry().counter(
                    "paddle_tpu_request_replay_giveups_total",
                    "Requests abandoned (partial stream "
                    "delivered) after max_restarts replays").inc()
            if not admitted:
                # a never-admitted incarnation is still live in the
                # ledger — close it out as a deferral-storm loss
                ledger.reject(rid, "rejected_deferred", ts=now)
            return
        arr_rel = (now - t_start) + delay
        queue.push(rid, prompt, mnt, arr_rel)
        eng.replays += 1
        if telemetry:
            _obs.registry().counter(
                "paddle_tpu_request_replays_total",
                "Evicted/faulted requests re-admitted via "
                "chunked-prefill replay").inc()
        if admitted:
            # the replay is a NEW ledger incarnation of the same
            # rid; its clock starts at the scheduled replay arrival
            # (the prior incarnation retired evicted/quarantined)
            ledger.arrival(rid, len(prompt) + len(prefix),
                           mnt - len(prefix), ts=t_start + arr_rel)

    def evict(i, cause, now):
        """Free slot i's blocks, retire the incarnation under
        `cause` with its tokens retained, schedule the replay."""
        s = eng._slots[i]
        rid, prompt = s.req_id, list(s.prompt)
        prefix = list(s.emitted)
        mnt_orig = len(prefix) + s.budget
        eng.allocator.free(s.blocks)
        eng._slots[i] = _Slot(done=True)
        tables[i] = 0
        live[i] = False
        # an eviction is invisible to the device (the slot's device
        # liveness still says live) — drain the pipeline state
        mark_state_dirty()
        if cause == "evicted":
            eng.evictions += 1
        ledger.retire(rid, cause, ts=now)
        requeue(rid, prompt, mnt_orig, prefix, now, admitted=True)

    def pick_victim():
        """The live slot with the most remaining budget: evicting
        the longest-still-to-run slot frees its blocks for the
        longest time per token of completed work thrown away."""
        best, best_budget = None, -1
        for j in range(eng.max_slots):
            if live[j] and eng._slots[j].budget > best_budget:
                best, best_budget = j, eng._slots[j].budget
        return best

    def quarantine(i, t0c, t1c, now):
        """Slot i's logits went non-finite this pass: count it,
        flight-record it, recycle the slot, replay the request
        from its last good token."""
        s = eng._slots[i]
        eng.quarantines += 1
        if telemetry:
            _obs.registry().counter(
                "paddle_tpu_logits_quarantine_total",
                "Decode slots quarantined on non-finite "
                "logits").inc()
        try:
            from ..observability import flight_recorder as _fr
            if _fr.armed():
                _fr.trip_once(
                    f"logits_nonfinite:req{s.req_id}",
                    {"rid": str(s.req_id), "slot": i,
                     "tokens_generated": len(s.emitted)})
        except Exception:
            pass
        # the poisoned pass still occupied the slot: bill its
        # wall to the request (0 tokens kept)
        ledger.chunk(s.req_id, t0c, t1c, 0)
        evict(i, "quarantined", now)

    def advance(i, emit, t0c, t1c):
        """Commit `emit` tokens to slot i after a decode pass (fused
        chunk or spec verify) — ONE definition of the bookkeeping
        both serving modes share, so retirement/ledger semantics
        cannot silently diverge between them."""
        s = eng._slots[i]
        take = len(emit)
        s.emitted.extend(emit)
        s.length += take
        s.budget -= take
        seqlens[i] += take
        tokens[i] = emit[-1]
        # the whole pass wall is this request's decode cost —
        # its slot rode the batch for all of it
        ledger.chunk(s.req_id, t0c, t1c, take)
        hit_eos = (eos_token_id is not None
                   and eos_token_id in s.emitted)
        if s.budget <= 0 or hit_eos:
            retire(i, "eos" if hit_eos else "budget_exhausted")

    def predict_n(after_n=None):
        """Host-predicted length of the NEXT fused chunk from the
        mirrors alone, optionally as seen after an in-flight chunk of
        ``after_n`` steps consumes its takes. Greedy chunk streams are
        partition-invariant (the per-step act gate depends only on
        per-slot budgets), so a prediction that overshoots — a slot
        the in-flight chunk retires on EOS held the max budget — costs
        wasted device steps, never wrong tokens; serial_n() trims the
        overshoot before any token is committed."""
        best = 0
        for i in range(eng.max_slots):
            if not live[i]:
                continue
            b = eng._slots[i].budget
            if after_n is not None:
                b -= min(after_n, b)
            best = max(best, b)
        return min(chunk, best)

    def serial_n(rec):
        """The chunk length the serial loop would have run where `rec`
        sits: a LOOKAHEAD chunk was sized before the chunk ahead of it
        reached the host, so an EOS retirement there can leave rec's n
        larger than min(chunk, max live budget). Consuming only this
        serial-sized prefix keeps the emitted grouping — and with it
        the EOS-padded result length — identical to the serial loop;
        the over-advanced device state is resynced by the caller
        (mark_state_dirty)."""
        if not rec["lookahead"]:
            return rec["n"]
        alive = [eng._slots[i].budget for i, s_ref in rec["slots"]
                 if live[i] and eng._slots[i] is s_ref]
        if not alive:
            return rec["n"]
        return min(rec["n"], max(alive))

    def dispatch_chunk(n, after_n=None):
        """Launch one state-carrying decode chunk of ``n`` steps off
        the device-resident batch state and return the un-consumed
        record (device token/bad handles + the (index, slot) pairs the
        rows belong to). Steady state performs ZERO host->device
        uploads: the executable's donated outputs are the next
        dispatch's inputs. Only a composition change (dev["state"]
        is None) re-uploads the six mirrors; a changed poison-coin
        set swaps that single component. With ``after_n`` set this is
        the LOOKAHEAD dispatch — chunk N+1 launched off chunk N's
        device outputs before the host has seen N's tokens."""
        nonlocal pools
        budg = np.asarray(
            [eng._slots[i].budget if live[i] else 0
             for i in range(eng.max_slots)], np.int32)
        lens_now = seqlens
        if after_n is not None:
            took = np.where(live, np.minimum(after_n, budg),
                            0).astype(np.int32)
            budg = budg - took
            lens_now = seqlens + took
        coins = np.zeros(eng.max_slots, bool)
        if _faults.active():
            for i in range(eng.max_slots):
                if (live[i] and budg[i] > 0
                        and _faults.fire("logits_poison")):
                    coins[i] = True
        if pipe_teeth == "force_sync":
            mark_state_dirty()
        uploads = 0
        if dev["state"] is None:
            tok_up = np.stack([tokens, drafts], axis=1) if drafting \
                else tokens.copy()
            if pipe_teeth == "mutate_feedback" and live.any():
                # teeth: corrupt one feedback token AT UPLOAD — the
                # parity gate must catch the divergent stream
                tok_up[int(np.argmax(live))] += 1
            # the executable DONATES tok/seqlens/live/budgets — and
            # jnp.asarray on CPU may alias the numpy buffer it is
            # given, which would let XLA write chunk OUTPUTS into the
            # loop's persistent host mirrors (observed: live[] flipping
            # mid-dispatch under a deserialized compile-cache hit).
            # Upload throwaway copies; tok_up and budg are already
            # fresh temporaries
            dev["state"] = (jnp.asarray(tok_up),
                            jnp.asarray(seqlens.copy()),
                            jnp.asarray(tables.copy()),
                            jnp.asarray(live.copy()),
                            jnp.asarray(budg), jnp.asarray(coins))
            poison_mirror[:] = coins
            uploads = 6
        elif not np.array_equal(coins, poison_mirror):
            dev["state"] = dev["state"][:5] + (jnp.asarray(coins),)
            poison_mirror[:] = coins
            uploads = 1
        if uploads:
            note_uploads(uploads)
        st = dev["state"]
        args = (eng._params,) + st + pools + (n, eos_dev)
        if telemetry:
            analysed(("chunkst_n", int(n), eos_dev, drafting),
                     eng._paged_chunk_state_jit, args)
        t_disp = time.perf_counter()
        with _obs.span("serve:chunk", steps=int(n),
                       lookahead=int(after_n is not None),
                       uploads=uploads):
            out = eng._paged_chunk_state_jit(*args)
            number = launched("chunk")
        # the batch state, the pools in their order, then whatever
        # counters the engine's program sends home with the tokens
        toks, bad, tok_o, len_o, live_o, budg_o = out[:6]
        aux = tuple(out[6 + len(pools):])
        pools = tuple(out[6:6 + len(pools)])
        dev["state"] = (tok_o, len_o, st[2], live_o, budg_o, st[5])
        eng.chunk_dispatches += 1
        if after_n is not None:
            eng.lookahead_dispatches += 1
            if telemetry:
                _obs.registry().counter(
                    "paddle_tpu_serve_pipeline_depth_total",
                    "lookahead dispatches: chunk N+1 launched before "
                    "chunk N's tokens reached the host").inc()
        eng._record_traffic(lens_now, n, live, budg)
        return {"toks": toks, "bad": bad, "aux": aux, "n": int(n),
                "lookahead": after_n is not None, "t_disp": t_disp,
                "number": number,
                "slots": [(i, eng._slots[i])
                          for i in range(eng.max_slots) if live[i]]}

    def consume(rec, n_eff=None):
        """Block on a dispatched chunk's device outputs and commit its
        first ``n_eff`` steps to the host mirrors — quarantine,
        retirement, and ledger arithmetic identical to the serial
        loop's post-pass sweep. Slots are matched by _Slot OBJECT
        identity, not index: retire/evict always replace the slot
        object, so a recycled index (a new request admitted into a
        slot this chunk still references) is skipped instead of being
        advanced with another request's tokens."""
        if n_eff is None:
            n_eff = serial_n(rec)
        t_w0 = time.perf_counter()
        with _obs.span("serve:wait_chunk", steps=rec["n"]):
            # the loop's wait for the device: the tokens are read here
            waited = unready(rec["toks"])
            toks = np.asarray(rec["toks"])
            landed(rec["number"], waited)
            bad = np.asarray(rec["bad"])
            counters = eng.chunk_counters(rec["aux"])
        t_ready = time.perf_counter()
        if telemetry:
            # in the pipelined loop "execute" is the EXPOSED device
            # wait (results not ready when the host asked); overlapped
            # device time the host never waited on is the win
            phase["execute"] += t_ready - t_w0
        # pipelined chunks overlap the previous consume's host work:
        # clamp this chunk's billing interval to start where the last
        # one ended so per-request decode seconds never double-count
        ct0 = rec["t_disp"]
        if last_ready[0] is not None:
            ct0 = max(ct0, last_ready[0])
        ct0 = min(ct0, t_ready)
        last_ready[0] = t_ready
        with _obs.span("serve:commit") as sp:
            took, live_before = 0, int(live.sum())
            proposed = accepted = 0
            for i, s_ref in rec["slots"]:
                if not live[i] or eng._slots[i] is not s_ref:
                    continue
                if quarantine_on and bad[i]:
                    quarantine(i, ct0, t_ready, time.perf_counter())
                    continue
                if drafting:
                    # a pass's (g0, g1, emitted, draft after them)
                    passes = toks[i, :n_eff]
                    emit = [int(t) for g0, g1, k, _ in passes
                            for t in (g0, g1)[:k]][:eng._slots[i].budget]
                    made = passes[passes[:, 2] > 0]
                    if not len(made):
                        continue
                    drafts[i] = made[-1, 3]
                    proposed += len(made)
                    accepted += int(np.sum(made[:, 2] == 2))
                    # each pass's draft, by the index of the token it
                    # predicts
                    slot = eng._slots[i]
                    at = len(slot.prompt) + len(slot.emitted) \
                        + np.cumsum(made[:, 2])
                    slot.drafts.extend(zip(at.tolist(),
                                           made[:, 3].tolist()))
                    slot.verified.extend(zip((at - made[:, 2]).tolist(),
                                             made[:, 1].tolist()))
                else:
                    emit = [int(t) for t in
                            toks[i, :min(n_eff, eng._slots[i].budget)]]
                advance(i, emit, ct0, t_ready)
                took += len(emit)
            # `steps` the device ran for this chunk, `committed` of them
            # kept (fewer where a look-ahead chunk was trimmed), in rows:
            # a verify pass computes two a slot
            rows = 2 if drafting else 1
            extra = dict(drafted=proposed, accepted=accepted) \
                if drafting else {}
            sp.set(tokens=took, retired=live_before - int(live.sum()),
                   steps=rec["n"] * rows, committed=int(n_eff) * rows,
                   **extra, **counters)
        if drafting:
            note_drafts(int(n_eff), proposed, accepted, took)
        if n_eff < rec["n"]:
            # the device ran the full overshot chunk — its state is
            # ahead of the trimmed mirrors; resync at next dispatch
            # (the extra pool writes hold exactly the tokens the next
            # chunk re-derives, so rewriting them is value-identical)
            mark_state_dirty()

    def note_drafts(passes, proposed, accepted, emitted):
        """The speculative tallies of committed passes (one draft a live
        slot a pass), from the tokens the chunk sent home."""
        st = eng.spec_stats
        st["verify_calls"] += passes
        st["proposed"] += proposed
        st["accepted"] += accepted
        st["emitted"] += emitted
        if telemetry:
            reg = _obs.registry()
            reg.counter("paddle_tpu_spec_decode_verify_calls_total",
                        "speculative batched-verify passes").inc(passes)
            reg.counter("paddle_tpu_spec_decode_proposed_total",
                        "draft tokens proposed").inc(proposed)
            reg.counter("paddle_tpu_spec_decode_accepted_total",
                        "draft tokens accepted by greedy "
                        "verification").inc(accepted)

    def admit_payload(i, req_id, payload, max_new, t_admit, sp):
        """Streamed-KV admission (prefill/decode disaggregation): the
        prefill worker already computed the prompt's KV and first
        token — import the blocks, write the table, and join the next
        decode chunk. ZERO prefill device work here (the counter gate
        the disaggregation drill reads)."""
        nonlocal pools
        mark_state_dirty()
        prompt = list(map(int, payload.prompt))
        s0 = len(prompt)
        total = s0 + max_new
        if total > eng.max_len:
            raise ValueError(f"{total} tokens exceed max_len "
                             f"{eng.max_len}")
        blocks = eng.allocator.alloc(blocks_needed(total))
        slot = _Slot(req_id=req_id, length=s0, blocks=blocks,
                     prompt=prompt, budget=max_new)
        eng._slots[i] = slot
        row = np.zeros(MB, np.int32)
        row[:len(blocks)] = blocks
        tables[i] = row
        ledger.admit(req_id, slot=i, blocks=len(blocks), ts=t_admit)
        _faults.inject("prefill_chunk")
        t0p = time.perf_counter()
        used = blocks_needed(s0)
        with _obs.span("serve:kv_import", blocks=used):
            pools = tuple(eng.import_blocks(
                *pools, blocks[:used], payload.kv))
            launched("import")
        t1p = time.perf_counter()
        if telemetry:
            phase["execute"] += t1p - t0p
        # the import IS this request's prefill segment on this
        # engine; every prompt token arrived cached
        ledger.prefill(req_id, t0p, t1p, bucket=0, cached_tokens=s0)
        ledger.first_token(req_id, ts=t1p)
        sp.set(bucket=0, cached_tokens=s0, tokens=1)
        first = int(payload.first_token)
        slot.emitted.append(first)
        slot.budget -= 1
        tokens[i] = first
        seqlens[i] = s0
        hit_eos = (eos_token_id is not None and first == eos_token_id)
        live[i] = slot.budget > 0 and not hit_eos
        if not live[i]:
            retire(i, "eos" if hit_eos else "budget_exhausted")

    def admit(i, req_id, prompt, max_new, t_admit):
        """One admission as the loop pays for it: from the pop off the
        queue to the slot joining the batch, the wait for the prefill's
        first token included. With `eng.pipelined_admission` a prompt's
        prefill is only dispatched here (only reserved, where the engine
        packs prompts); `join_staged` reads the first tokens once the
        scan has dispatched them all, so the device runs the scan's
        prefills back to back and a host that is slow or held up between
        two of them leaves it no gap."""
        nonlocal begun
        begun += 1
        if stage_admissions and not isinstance(prompt, KVBlockPayload):
            rec = reserve_prompt(i, req_id, prompt, max_new, t_admit)
            if plan_packs is None:
                dispatch_prefill(rec)
            staged.append(rec)
            return
        with _obs.span("serve:admit", rid=req_id, slot=i,
                       prompt_tokens=_plen(prompt)) as sp:
            if isinstance(prompt, KVBlockPayload):
                admit_payload(i, req_id, prompt, max_new, t_admit, sp)
            else:
                rec = reserve_prompt(i, req_id, prompt, max_new, t_admit)
                dispatch_prefill(rec)
                join_prompt(rec, sp)

    def join_staged():
        """The second half of a pipelined scan's admissions, in the
        order of admission: `serve:admit` spans the loop's wait for each
        first token and the slot joining the batch. Where the engine
        packs prompts, the scan has only reserved: its packs are
        dispatched here, back to back, before the first read (one read a
        pack: the others of its admissions find their token on the
        host)."""
        if plan_packs is not None and staged:
            packs = None        # planned under the first pack's span
            while packs is None or packs:
                with _obs.span("serve:prefill_inputs") as sp:
                    if packs is None:
                        packs = plan_packs([r["s0"] for r in staged])
                    bucket, members = packs.pop(0)
                    members = [(staged[j], start) for j, start in members]
                    calls = prefill_calls(bucket, members, sp)
                dispatch_cold(bucket, members, calls)
        for rec in staged:
            slot = rec["slot"]
            with _obs.span("serve:admit", rid=slot.req_id, slot=rec["i"],
                           prompt_tokens=len(slot.prompt)) as sp:
                join_prompt(rec, sp)
        staged.clear()

    def reserve_prompt(i, req_id, prompt, max_new, t_admit):
        """Everything an admission does before its first device call:
        the slot, its blocks and table row, the ledger. Returns the
        record that `dispatch_prefill` fills in and `join_prompt`
        reads."""
        with _obs.span("serve:reserve", rid=req_id) as sp:
            mark_state_dirty()
            prompt = list(map(int, prompt))
            # chunked-prefill replay: a previously evicted incarnation
            # re-enters with its retained tokens appended to the
            # prompt — ONE prefill recomputes the whole KV prefix into
            # fresh pages and its argmax IS the next token of the
            # stream (greedy replay is token-identical to the
            # uninterrupted serve; the chaos drill's parity anchor)
            prefix = replays.prefix(req_id)
            ids_full = prompt + prefix
            s0 = len(ids_full)
            total = len(prompt) + max_new
            if total > eng.max_len:
                raise ValueError(f"{total} tokens exceed max_len "
                                 f"{eng.max_len}")
            # prefix-cache admission plan: which cached blocks to map
            # copy-on-write, and whether the boundary block needs a device
            # fork (fully-cached prompt). Planned BEFORE the alloc so the
            # fresh-block bill excludes the shared span.
            m, kb, cached, cow_src = cache_sync(plan_prefix, cache,
                                                ids_full, s0)
            # allocate pages for the whole run up front (admission is
            # the backpressure point; a growth-on-demand variant would
            # allocate per chunk). Fresh blocks first — alloc can fault
            # (chaos) — then the infallible shared-block acquire.
            fresh = eng.allocator.alloc(blocks_needed(total) - kb)
            shared = cache_sync(cache.acquire, m, kb) if kb else []
            blocks = shared + fresh
            slot = _Slot(req_id=req_id, length=s0, blocks=blocks,
                         prompt=prompt, budget=max_new - len(prefix))
            slot.emitted = list(prefix)
            eng._slots[i] = slot
            row = np.zeros(MB, np.int32)
            row[:len(blocks)] = blocks
            tables[i] = row
            ledger.admit(req_id, slot=i, blocks=len(blocks), ts=t_admit)
            sp.set(blocks=len(blocks))
            # chaos site: prefill execution failure — fires BEFORE the
            # device call (pools untouched, donation not yet consumed),
            # the window where recovery is clean unwind + replay
            _faults.inject("prefill_chunk")
            return {"i": i, "slot": slot, "s0": s0, "ids": ids_full,
                    "cached": cached, "kb": kb, "cow_src": cow_src,
                    "fresh": fresh, "seg": 0}

    def prefill_calls(bucket, members, sp):
        """The inputs of the bucket's program calls for `members`
        [(record, start row)], made and uploaded under the open
        `serve:prefill_inputs` span `sp`."""
        calls = eng._prefill_calls(
            bucket, [(r["i"], r["ids"], start) for r, start in members],
            tables, pad_token_id)
        sp.set(bucket=bucket, prompts=len(members), calls=len(calls))
        return calls

    def dispatch_cold(bucket, members, calls):
        """One bucketed in-prompt prefill program over `members`
        [(record, start row)]: one prompt from row 0, or a pack on an
        engine whose program takes one."""
        nonlocal pools
        fn = eng._prefill_exec(bucket)
        args_of = lambda call: (eng._params,) + call[0] + pools + call[1]
        c0 = analysed(("prefill_b", bucket), fn, args_of(calls[0])) \
            if telemetry else 0.0
        rows = sum(r["s0"] for r, _ in members)
        encs = []
        t0p = time.perf_counter()
        with _obs.span("serve:prefill", bucket=bucket,
                       prompts=len(members), rows=rows):
            # one call, or one a chunk of the prompt: each takes the
            # pools the one before it returned
            for call in calls:
                enc, *out = fn(*args_of(call))
                number = launched("prefill")
                pools = tuple(out)
                encs.append(enc)
        if telemetry:
            phase["execute"] -= compiled() - c0
        eng.prefill_device_calls += len(calls)
        eng.prefill_tokens_computed += rows
        for seg, (r, _) in enumerate(members):
            r.update(enc=encs, seg=seg, t0p=t0p, bucket=bucket,
                     number=number)

    def dispatch_prefill(rec):
        """Dispatch one reserved prompt's prefill by itself."""
        nonlocal pools
        i, s0, cached = rec["i"], rec["s0"], rec["cached"]
        if cache is None:
            # historical cold path: bucketed in-prompt prefill —
            # cache-off engines keep their executables byte-identical
            bucket = eng.prefill_bucket(s0)
            with _obs.span("serve:prefill_inputs") as sp:
                calls = prefill_calls(bucket, [(rec, 0)], sp)
            dispatch_cold(bucket, [(rec, 0)], calls)
        else:
            # warm path: every cache-on prefill — hit or miss — runs
            # the pool-mapped suffix executable (cold is just
            # start=0), so cold and warm streams share numerics and
            # the greedy parity gate holds by construction
            suffix = rec["ids"][cached:]
            ns = len(suffix)
            cow_src, fresh = rec["cow_src"], rec["fresh"]
            # chunked prefill (r21 long-context): when the engine was
            # built with prefill_chunk, a long suffix runs through
            # FIXED chunk-sized warmfill executables over successive
            # windows instead of one prompt-sized bucket — a 128k
            # admission must not compile (and hold) a 128k-wide
            # prefill program per bucket. Numerics are unchanged: each
            # window writes its KV at its true positions and the LAST
            # window's logits row is the same next-token row the
            # single-shot call returns.
            pchunk = eng.prefill_chunk
            if pchunk and ns > pchunk:
                pieces = [(off, suffix[off:off + pchunk])
                          for off in range(0, ns, pchunk)]
            else:
                pieces = [(0, suffix)]
            t0p = 0.0
            enc = None
            for off, piece in pieces:
                npiece = len(piece)
                bucket = eng.prefill_bucket(npiece)
                with _obs.span("serve:prefill_inputs", bucket=bucket,
                               prompts=1, calls=1):
                    ids = np.full(bucket, pad_token_id, np.int32)
                    ids[:npiece] = piece
                    args_w = (eng._params, jnp.asarray(ids),
                              jnp.int32(cached + off), jnp.int32(npiece),
                              jnp.asarray(tables[i])) + pools
                fn = eng._warmfill_exec(bucket)
                c0 = analysed(("warmfill_b", bucket), fn, args_w) \
                    if telemetry else 0.0
                if off == 0:
                    t0p = time.perf_counter()
                with _obs.span("serve:warm_prefill", bucket=bucket,
                               cached=cached + off):
                    if off == 0 and cow_src is not None:
                        # fully-cached prompt: fork the boundary block
                        # before the one-token suffix recompute writes
                        # into it (timed inside the prefill window —
                        # COW is prefill cost)
                        pools = tuple(eng._cow_copy_jit(
                            *pools, jnp.int32(cow_src),
                            jnp.int32(fresh[0])))
                        launched("warm_prefill")
                        # rebuild args against the post-COW pools (the
                        # copy donated the ones args_w captured)
                        args_w = args_w[:5] + pools
                    enc, *out = fn(*args_w)
                    number = launched("warm_prefill")
                if telemetry:
                    phase["execute"] -= compiled() - c0
                pools = tuple(out)
                eng.prefill_device_calls += 1
            # only the LAST window's fused first-token matters (the
            # earlier windows exist for their KV writes)
            eng.prefill_tokens_computed += ns
            cache.record_admission(cached, rec["kb"],
                                   cow=cow_src is not None)
            rec.update(enc=[enc], t0p=t0p, bucket=bucket, number=number)

    def join_prompt(rec, sp):
        """Read the first token of a dispatched prefill and let the
        slot join the batch."""
        i, slot, s0, cached = rec["i"], rec["slot"], rec["s0"], rec["cached"]
        t0p, bucket = rec["t0p"], rec["bucket"]
        req_id = slot.req_id
        # ONE int32 on the wire (ISSUE 20 tentpole c): the argmax AND
        # the finiteness probe are fused on device — a 128k-vocab f32
        # row used to cross per admission. Reading it is where the loop
        # waits for the prefill (a pack's tokens come in one array, read
        # once)
        with _obs.span("serve:wait_first_token", rid=req_id):
            waited = unready(rec["enc"][-1])
            first, nonfinite = eng.decode_first_token(rec["enc"],
                                                      rec["seg"])
            landed(rec["number"], waited)
        bad_prefill = quarantine_on and nonfinite
        t1p = time.perf_counter()
        if telemetry:
            # staged prefills overlap on the host's clock: each is
            # billed from where the one before it was read
            since = max(t0p, joined[0])
            phase["execute"] += t1p - since
        joined[0] = t1p
        ledger.prefill(req_id, t0p, t1p, bucket=bucket,
                       cached_tokens=cached)
        sp.set(bucket=bucket, cached_tokens=cached,
               tokens=0 if bad_prefill else 1, **eng.admit_metadata())
        if bad_prefill:
            # non-finite prefill logits: same quarantine contract
            # as a poisoned decode pass (host-side detection — the
            # prefill logits are already here). No first-token, no
            # chunk bill: the prefill segment is already recorded,
            # and the discarded argmax never counts as generated
            quarantine(i, t1p, t1p, t1p)
            return
        ledger.first_token(req_id, ts=t1p)
        slot.emitted.append(first)
        slot.budget -= 1
        tokens[i] = first
        if drafting:
            drafts[i] = eng.first_draft()
            slot.drafts.append((s0 + 1, int(drafts[i])))
        seqlens[i] = s0
        hit_eos = (eos_token_id is not None
                   and first == eos_token_id)
        live[i] = slot.budget > 0 and not hit_eos
        if not live[i]:
            retire(i, "eos" if hit_eos else "budget_exhausted")

    def shed_heads(now):
        queue.shed(now, never_fits=never_fits,
                   admission_timeout_s=admission_timeout_s,
                   reject_oversized=reject_oversized, reject=reject)

    def drain_feed():
        """Pull streamed admissions (disaggregation: finished-prefill
        payloads) into the queue at their delivery time."""
        if feed is None:
            return
        with _obs.span("serve:feed") as sp:
            pushed = 0
            for rid, body, mnt in feed():
                now_abs = time.perf_counter()
                ledger.arrival(rid, _plen(body), mnt, ts=now_abs)
                queue.push(rid, body, mnt, now_abs - t_start)
                pushed += 1
            sp.set(pushed=pushed)

    feeding = (lambda: False) if feed_active is None else feed_active

    try:
        if watching():
            starve("start", 0)
        if plan_packs is not None:
            # a pack's bucket follows from what a scan happens to stage:
            # every bucket's program exists before the first admission
            with _obs.span("serve:warm_packs"):
                warmed = eng.warm_prefill(pools, pad_token_id)
                if warmed[0] is not pools[0]:    # a bucket's program ran
                    launched("warm_prefill")
                pools = warmed
        while queue or live.any() or feeding():
            with _obs.span("serve:iteration", live=int(live.sum()),
                           queued=len(queue)) as it_sp:
                it0 = time.perf_counter() if telemetry else 0.0
                compiled0 = compiled() if telemetry else 0.0
                phase["execute"] = phase["host_gap"] = 0.0
                drain_feed()
                now = time.perf_counter()
                # drain on peer death (ISSUE 14): once the watchdog
                # declares a peer dead, the pod is degraded — reject
                # everything still queued so the in-flight slots can
                # retire cleanly, and admit nothing new
                if queue:
                    drain = eng._drain_reason()
                    if drain is not None:
                        drained = queue.drain()
                        for rid_d, _, _, arr_d in drained:
                            reject(rid_d, "rejected_draining",
                                   max(now, t_start + arr_d))
                        eng.drained_rejections += len(drained)
                        if telemetry:
                            _obs.registry().counter(
                                "paddle_tpu_serving_drain_rejections"
                                "_total",
                                "Queued requests rejected because the "
                                "watchdog declared a peer dead",
                            ).inc(len(drained))
                        try:
                            from ..observability import (
                                flight_recorder as _fr)
                            _fr.trip_once(
                                f"serving_drain:{drain}",
                                {"reason": drain,
                                 "rejected": len(drained),
                                 "in_flight": int(live.sum())})
                        except Exception:
                            pass
                # admission: fill free slots while blocks allow
                # (`admit_stop`: why the scan ended)
                deferred_scan = False
                admit_stop = "full"
                for i in range(eng.max_slots):
                    shed_heads(now)
                    if not queue:
                        admit_stop = "queue_empty"
                        break
                    rid, prompt, mnt, arr = queue.head()
                    if t_start + arr > now:
                        admit_stop = "not_due"
                        break                # next arrival is in the future
                    if not eng._slots[i].done:
                        continue
                    need = blocks_needed(_plen(prompt) + mnt)
                    if need > eng.allocator.free_count:
                        # pool pressure: cold cache entries go first —
                        # LRU leaves whose blocks only the tree holds;
                        # live tables are untouchable by construction
                        if cache is not None:
                            cache_sync(cache.evict,
                                       need - eng.allocator.free_count)
                        if need > eng.allocator.free_count:
                            admit_stop = "no_blocks"
                            break            # backpressure: decode first
                    # the pool itself is preallocated — admitting consumes no
                    # pool HBM. What admission DOES allocate is transient: the
                    # bucketed prefill executable + its workspace, priced here
                    # by the prompt's KV footprint as a proxy. Worst case under
                    # sustained pressure is drain-to-empty serialization (live
                    # slots always keep decoding, and an empty batch bypasses
                    # the guard), never a mid-serve RESOURCE_EXHAUSTED.
                    prefill_est = blocks_needed(_plen(prompt)) * \
                        eng.bytes_per_block()
                    if (eng.headroom_guard is not None
                            and (live.any() or staged)
                            and not eng.headroom_guard.check(prefill_est)):
                        eng.admission_deferrals += 1
                        deferred_scan = True
                        admit_stop = "deferred"
                        defer_counts[rid] = defer_counts.get(rid, 0) + 1
                        ledger.defer(rid)
                        if _obs.enabled():
                            _obs.registry().counter(
                                "paddle_tpu_paged_admission_deferrals_total",
                                "Admissions deferred by the headroom guard"
                            ).inc()
                        if recovery and defer_counts[rid] >= max_deferrals:
                            # deferral storm: degrade to rejection —
                            # the queue must not wedge behind a head
                            # the guard will never let in
                            queue.pop()
                            reject(rid, "rejected_deferred",
                                   time.perf_counter())
                            continue
                        if (recovery and defer_counts[rid]
                                == evict_after_deferrals):
                            # sustained pressure: free a victim's
                            # blocks so the head (or the next loop's
                            # empty-batch bypass) can make progress.
                            # Cold cache subtrees are the cheapest
                            # victims (no work thrown away); a live
                            # slot pays only when the cache has nothing
                            # cold. Exactly ONCE per head's deferral
                            # streak: organic HBM pressure is not
                            # relieved by freeing preallocated pool
                            # blocks, so a persisting violation must
                            # escalate to the max_deferrals rejection
                            # above, not serially evict the whole live
                            # batch
                            freed = cache_sync(cache.evict, need) \
                                if cache is not None else 0
                            if not freed:
                                v = pick_victim()
                                if v is not None:
                                    evict(v, "evicted", time.perf_counter())
                        break
                    queue.pop()
                    try:
                        admit(i, rid, prompt, mnt, time.perf_counter())
                        defer_counts.pop(rid, None)
                    except (_faults.InjectedFault, MemoryError):
                        if not recovery:
                            raise
                        # transient admission failure (injected pool /
                        # prefill fault): unwind the incarnation and
                        # schedule its replay
                        t_fail = time.perf_counter()
                        s = eng._slots[i]
                        plain = (list(prompt.prompt)
                                 if isinstance(prompt, KVBlockPayload)
                                 else list(map(int, prompt)))
                        if not s.done and s.req_id == rid:
                            evict(i, "evicted", t_fail)
                        else:
                            requeue(rid, plain, mnt, replays.prefix(rid),
                                    t_fail, admitted=False)
                join_staged()
                it_sp.set(free=eng.max_slots - int(live.sum()),
                          admit_stop=admit_stop)
                if not live.any():
                    if not queue:
                        if feeding():
                            # disaggregation: prefill workers still
                            # running — idle until a payload lands
                            idle(0.002)
                            continue
                        break
                    if deferred_scan:
                        # the guard deferred the head but the eviction
                        # (or retirements) just emptied the batch — an
                        # empty batch bypasses the guard, so re-scan
                        # with a fresh clock instead of misreading the
                        # deferral as pool-too-small
                        continue
                    next_arrival = t_start + queue.head()[3]
                    fresh = time.perf_counter()
                    if next_arrival > fresh:
                        # open-loop idle: nothing live, next arrival in the
                        # future — sleep to it (the serve ledger bills the
                        # gap as data_wait, which it is)
                        idle(next_arrival - fresh)
                        continue
                    if next_arrival > now:
                        # the head arrived BETWEEN the admission scan's
                        # clock and this check — the scan never saw it;
                        # retry with a fresh clock instead of
                        # misdiagnosing an admittable head as
                        # pool-too-small
                        continue
                    if cache is not None and cache.held_blocks:
                        # last resort before declaring the pool too small:
                        # drop the whole cache (it holds blocks the head
                        # needs) and re-scan
                        cache_sync(cache.evict, cache.held_blocks)
                        continue
                    raise MemoryError(
                        "pool too small for even one pending request")
                budgets = np.asarray(
                    [eng._slots[i].budget if live[i] else 0
                     for i in range(eng.max_slots)], np.int32)
                # chaos site: a failed/stuck decode pass. Fires BEFORE
                # the device call (pools intact): recovery is bounded
                # retry with backoff — the batch re-runs the same pass
                if _faults.active():
                    try:
                        _faults.inject("decode_chunk")
                    except _faults.InjectedFault:
                        if not recovery:
                            raise
                        chunk_failures += 1
                        if chunk_failures > max_chunk_retries:
                            raise
                        time.sleep(min(
                            replay_backoff_s
                            * (2 ** (chunk_failures - 1)), 0.5))
                        continue
                    chunk_failures = 0
                if spec_cfg is not None:
                    # the chaos harness's logits-poison lane: one coin per
                    # live slot per decode pass, applied ON DEVICE so the
                    # non-finite detection path is exercised end to end
                    # (the fused path fires its coins inside
                    # dispatch_chunk — one set per dispatched chunk,
                    # lookahead chunks included)
                    poison = np.zeros(eng.max_slots, bool)
                    if _faults.active():
                        for i in range(eng.max_slots):
                            if live[i] and _faults.fire("logits_poison"):
                                poison[i] = True
                    # draft-propose -> batched-verify instead of a fused
                    # chunk: one target forward prices k+1 candidate
                    # tokens per slot against ONE pass over the KV pool
                    K = spec_cfg.k
                    toks_in = np.zeros((eng.max_slots, K + 1), np.int32)
                    toks_in[:, 0] = tokens
                    for i in range(eng.max_slots):
                        if live[i]:
                            s = eng._slots[i]
                            toks_in[i, 1:] = np.asarray(draft.propose(
                                s.prompt + s.emitted, K), np.int32)
                    # device-resident reuse (ISSUE 20 satellite): only the
                    # per-pass candidate tokens and positions upload every
                    # verify; tables/live/budgets/poison ride cached device
                    # copies refreshed on host-value change (the verify
                    # executable donates only the pools, so they survive)
                    args_s = (eng._params, jnp.asarray(toks_in),
                              jnp.asarray(seqlens),
                              spec_dev_arr("tables", tables),
                              spec_dev_arr("live", live),
                              spec_dev_arr("budgets", budgets),
                              spec_dev_arr("poison", poison)) + pools
                    note_uploads(2)
                    c0 = analysed(("spec_k", int(K)), eng._spec_verify_jit,
                                  args_s) if telemetry else 0.0
                    t0c = time.perf_counter()
                    with _obs.span("serve:spec_verify", k=int(K)):
                        g, bad, *out = eng._spec_verify_jit(*args_s)
                        number = launched("verify")
                        pools = tuple(out)
                    if telemetry:
                        phase["execute"] -= compiled() - c0
                    with _obs.span("serve:wait_chunk", steps=int(K + 1)):
                        # the pass's results reach the host here
                        waited = unready(g)
                        g = np.asarray(g)
                        landed(number, waited)
                        bad = np.asarray(bad)
                    t1c = time.perf_counter()
                    if telemetry:
                        phase["execute"] += t1c - t0c
                        last_ready[0] = t1c
                    eng.chunk_dispatches += 1
                    eng._record_traffic(seqlens, K + 1, live, budgets,
                                        launches=1)
                    st = eng.spec_stats
                    st["verify_calls"] += 1
                    call_prop = call_acc = 0
                    with _obs.span("serve:commit") as sp:
                        took, live_before = 0, int(live.sum())
                        for i in range(eng.max_slots):
                            if not live[i]:
                                continue
                            if quarantine_on and bad[i]:
                                quarantine(i, t0c, t1c,
                                           time.perf_counter())
                                continue
                            s = eng._slots[i]
                            # accept the longest draft prefix the target's
                            # own argmax reproduces, then the bonus token —
                            # exactly the plain-greedy stream
                            emit = [int(g[i, 0])]
                            j = 0
                            while (j < K and len(emit) < s.budget
                                   and int(toks_in[i, j + 1])
                                   == int(g[i, j])):
                                j += 1
                                emit.append(int(g[i, j]))
                            call_prop += K
                            call_acc += j
                            st["emitted"] += len(emit)
                            took += len(emit)
                            advance(i, emit, t0c, t1c)
                        sp.set(tokens=took,
                               retired=live_before - int(live.sum()),
                               steps=int(K + 1), committed=int(K + 1))
                    st["proposed"] += call_prop
                    st["accepted"] += call_acc
                    if telemetry:
                        reg = _obs.registry()
                        reg.counter(
                            "paddle_tpu_spec_decode_verify_calls_total",
                            "speculative batched-verify passes").inc()
                        reg.counter(
                            "paddle_tpu_spec_decode_proposed_total",
                            "draft tokens proposed").inc(call_prop)
                        reg.counter(
                            "paddle_tpu_spec_decode_accepted_total",
                            "draft tokens accepted by greedy "
                            "verification").inc(call_acc)
                else:
                    # pipelined fused-chunk path (ISSUE 20 tentpole b):
                    # take the in-flight chunk if one exists, dispatch the
                    # NEXT chunk off device-resident state before the
                    # in-flight results reach the host, then consume. A
                    # composition change (mark_state_dirty) forces
                    # consume-before-reupload so the mirrors include the
                    # in-flight chunk's takes before they are snapshot.
                    fused_steps = 0
                    rec = pending[0]
                    pending[0] = None
                    if rec is not None and dev["state"] is None:
                        consume(rec)
                        rec = None
                    if rec is None and live.any():
                        rec = dispatch_chunk(max(predict_n(), 1))
                    if rec is not None:
                        n_eff = serial_n(rec)
                        fused_steps = n_eff
                        if (lookahead_on and dev["state"] is not None
                                and n_eff == rec["n"]):
                            # no trim pending -> the device state ahead of
                            # this chunk is exactly what the serial loop
                            # would feed chunk N+1: launch it now
                            n2 = predict_n(after_n=rec["n"])
                            if n2 >= 1:
                                pending[0] = dispatch_chunk(
                                    n2, after_n=rec["n"])
                        consume(rec, n_eff)
                if telemetry:
                    eng._serve_ledger.step(
                        it0, time.perf_counter(),
                        compile_s=compiled() - compiled0,
                        execute_s=max(phase["execute"], 0.0),
                        host_gap_s=phase["host_gap"],
                        extra={"live_slots": int(live.sum()),
                               "chunk_steps": (int(spec_cfg.k + 1)
                                               if spec_cfg is not None
                                               else int(fused_steps)
                                               * (2 if drafting else 1))})
    except BaseException:
        # the engine may be unusable, but the OBSERVABILITY
        # must stay truthful: drop this call's unfinished
        # ledger records before propagating
        unstarve()
        abort_cleanup()
        if cache is not None:
            # donation may have consumed the persistent pools
            # mid-call — the cached KV is gone with them
            eng.release_pools()
        raise
    unstarve()
    if cache is not None:
        # the loop's final pool bindings ARE the persistent pools now
        # (every device call rebound them through donation)
        eng._persistent_pools = pools
    return results


def _plen(prompt):
    """Prompt length of a queue entry body (a token list or a
    streamed KVBlockPayload)."""
    if isinstance(prompt, KVBlockPayload):
        return len(prompt.prompt)
    return len(prompt)
