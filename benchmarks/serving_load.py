"""Arrival-driven sustained-load serving benchmark (ISSUE 12, ROADMAP 1).

The serving benchmark the step-ratio rows can't be: an OPEN-LOOP
arrival process (Poisson arrivals at a configurable QPS, mixed
prompt/output-length distributions) over `PagedDecoder.serve()`, scored
the way the Ragged Paged Attention paper and the Gemma-on-TPU serving
comparison score serving — request-level percentiles under load, not
isolated step times:

- **p50/p99 TTFT** (time to first token, queue wait included),
- **p50/p99 TPOT** (time per output token past the first),
- **goodput**: tokens/s from requests meeting BOTH SLOs over the run's
  makespan — the gate metric the continuous-batching scheduler
  (ROADMAP 1) will be built against,
- **rejected/evicted counts** (overload shedding: admission timeout +
  oversized rejection; one oversized request is planted so the
  rejection path is exercised, not just declared).

Open loop means arrivals do NOT wait for completions: under overload
the queue grows and the percentiles degrade — which is the measurement.
A closed loop (next request sent on completion) self-throttles and
hides saturation.

Everything comes from the per-request lifecycle ledger
(observability/requests.py): the artifact line carries the ledger's
percentiles, the sums-to-wall reconcile residual (<= 2% gate, CI tier
`servingload`), and a cross-check that the sliding-window Quantile
series are LIVE in the registry scrape. A chrome/Perfetto trace with
one named track per request (queue -> prefill bucket -> decode chunks)
is written to --trace-out.

Session traffic (ISSUE 18): ``--sessions N --turns T`` switches the
generator to multi-turn chat traffic — every session opens with the
SAME block-aligned system prompt, and each turn's prompt is the full
conversation so far (prior prompts + synthetic replies + new user
text). With ``--prefix-cache`` the engine's radix cache turns that
growing shared prefix into mapped blocks instead of recomputed
prefill; the artifact line then carries ``cache_hit_ratio`` (cached
prompt tokens / total prompt tokens over completed requests) and the
warm/cold TTFT split (warm = requests whose ledger record shows
``prefill_cached_tokens > 0``).

Usage:
    python benchmarks/serving_load.py --qps 8 [--requests 64]
        [--slo-ttft-s 2.0] [--slo-tpot-s 0.2] [--trace-out t.json]
    python benchmarks/serving_load.py --sessions 4 --turns 3 \
        --prefix-cache            (multi-turn shared-prefix traffic)
    PT_BENCH_SMOKE=1 ... (tiny CPU config, the CI tier's invocation)
"""
from __future__ import annotations

import _bootstrap  # noqa: F401  (repo root on sys.path)

import argparse
import json
import os
import tempfile
import time

import numpy as np


def build_requests(rng, n, qps, max_len, chunk):
    """Poisson arrivals + mixed length distributions. Returns
    (rid, prompt, max_new, arrival_s) quads, arrival-sorted, with ONE
    planted oversized request (prompt+budget past max_len) so the
    rejection path is live in every run."""
    t = 0.0
    reqs = []
    short_hi = max(max_len // 6, 5)
    long_lo, long_hi = max_len // 4, max_len // 2
    for i in range(n):
        t += float(rng.exponential(1.0 / qps))
        if rng.random() < 0.7:           # short interactive prompts
            plen = int(rng.integers(4, short_hi))
        else:                            # long-context stragglers
            plen = int(rng.integers(long_lo, long_hi))
        # outputs in whole chunks mostly, so the decode-chunk executable
        # set stays small; +1 tail exercises sub-chunk budgets
        max_new = int(chunk * rng.integers(1, 4)) + int(rng.integers(0, 2))
        prompt = [int(v) for v in rng.integers(0, 90, plen)]
        reqs.append((f"r{i}", prompt, max_new, round(t, 6)))
    # the planted shed: can never fit — must come back as
    # rejected_oversized, not crash the run
    mid = reqs[len(reqs) // 2][3]
    reqs.append(("oversized", [1] * max_len, max_len, mid))
    reqs.sort(key=lambda r: r[3])
    return reqs


def build_session_requests(rng, sessions, turns, qps, max_len, chunk,
                           block_size):
    """Multi-turn chat traffic with a shared system prompt: rids
    ``s{k}:t{j}``, turn j's prompt = system + session history (prior
    prompts + SYNTHETIC replies — the generator can't know the real
    completions up front; real histories diverge at the reply, which
    is exactly what the radix match tolerates: the shared-prefix
    blocks still map, only the boundary block recomputes) + fresh user
    text. Turns are emitted in waves (all sessions' turn j before any
    turn j+1) so a session's earlier turn has usually retired — and
    its chain entered the cache — before the next one lands."""
    system = [int(v) for v in rng.integers(0, 90, 4 * block_size)]
    history = {k: list(system) for k in range(sessions)}
    reqs, t = [], 0.0
    for j in range(turns):
        for k in range(sessions):
            t += float(rng.exponential(1.0 / qps))
            user = [int(v)
                    for v in rng.integers(0, 90,
                                          int(rng.integers(
                                              block_size // 2,
                                              2 * block_size)))]
            max_new = int(chunk * rng.integers(1, 3))
            prompt = history[k] + user
            if len(prompt) + max_new > max_len:
                continue                 # session hit the context limit
            reqs.append((f"s{k}:t{j}", prompt, max_new, round(t, 6)))
            reply = [int(v) for v in rng.integers(0, 90, max_new)]
            history[k] = prompt + reply
    reqs.sort(key=lambda r: r[3])
    return reqs


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--qps", type=float, default=8.0,
                    help="Poisson arrival rate (requests/s)")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slo-ttft-s", type=float, default=None)
    ap.add_argument("--slo-tpot-s", type=float, default=None)
    ap.add_argument("--max-slots", type=int, default=None)
    ap.add_argument("--block-size", type=int, default=None)
    ap.add_argument("--chunk", type=int, default=None)
    ap.add_argument("--admission-timeout-s", type=float, default=None,
                    help="shed requests queued past this wait")
    ap.add_argument("--sessions", type=int, default=0,
                    help="multi-turn session traffic: this many chat "
                         "sessions sharing one system prompt (0 = the "
                         "classic independent-request generator)")
    ap.add_argument("--turns", type=int, default=3,
                    help="turns per session in --sessions mode")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="enable the engine's radix prefix cache "
                         "(ISSUE 18) — shared/previous-turn prefixes "
                         "map blocks instead of recomputing prefill")
    ap.add_argument("--spec-k", type=int, default=None,
                    help="speculative decoding: n-gram draft length per "
                         "batched verify pass (0 = off; the smoke "
                         "config defaults it ON so the CI tier "
                         "exercises spec serving under open-loop load)")
    ap.add_argument("--trace-out", default=None,
                    help="chrome/Perfetto trace with per-request tracks")
    ap.add_argument("--jsonl-out", default=None,
                    help="JSONL sink (request_lifecycle + "
                         "step_attribution records)")
    args = ap.parse_args()

    import jax
    import paddle_tpu as pt
    import paddle_tpu.observability as obs
    from paddle_tpu.observability import roofline, tracing
    from paddle_tpu.observability.requests import RequestLedger
    from paddle_tpu.framework.memory import HeadroomGuard
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.paged_decode import PagedDecoder

    on_tpu = jax.default_backend() == "tpu"
    smoke = bool(os.environ.get("PT_BENCH_SMOKE"))
    if smoke:
        # CI tier config: the smallest shape that still walks every
        # path — Poisson admission, prefill buckets, chunk tails,
        # rejection, percentiles — in a couple of minutes on CPU
        cfg = LlamaConfig(vocab_size=128, hidden_size=64,
                          intermediate_size=128, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=128, dtype="float32",
                          use_flash_attention=False)
        defaults = dict(requests=10, max_slots=4, block_size=8,
                        chunk=4, max_len=96, spec_k=2,
                        # CPU walls are not the SLO story; generous
                        # bounds keep goodput > 0 (the gate) while the
                        # percentile/reconcile plumbing is what's tested
                        slo_ttft_s=120.0, slo_tpot_s=30.0)
    elif on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=4096,
                          intermediate_size=11008, num_hidden_layers=4,
                          num_attention_heads=32, num_key_value_heads=32,
                          max_position_embeddings=4096, dtype="bfloat16",
                          use_flash_attention=False)
        defaults = dict(requests=64, max_slots=16, block_size=256,
                        max_len=4096, chunk=16, spec_k=0,
                        slo_ttft_s=2.0, slo_tpot_s=0.2)
    else:
        cfg = LlamaConfig(vocab_size=256, hidden_size=128,
                          intermediate_size=256, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=4,
                          max_position_embeddings=512, dtype="float32",
                          use_flash_attention=False)
        defaults = dict(requests=16, max_slots=4, block_size=16,
                        max_len=192, slo_ttft_s=60.0, slo_tpot_s=10.0,
                        chunk=8, spec_k=0)

    def opt(value, key):
        # NOT `value or default`: an explicit 0 (e.g. --slo-ttft-s 0,
        # the nothing-meets-SLO probe) must stick
        return defaults[key] if value is None else value

    n_requests = opt(args.requests, "requests")
    max_slots = opt(args.max_slots, "max_slots")
    block_size = opt(args.block_size, "block_size")
    chunk = opt(args.chunk, "chunk")
    spec_k = int(opt(args.spec_k, "spec_k")) or None
    max_len = defaults["max_len"]
    slo_ttft = opt(args.slo_ttft_s, "slo_ttft_s")
    slo_tpot = opt(args.slo_tpot_s, "slo_tpot_s")
    trace_out = args.trace_out or os.path.join(
        tempfile.gettempdir(), f"serving_load_trace.{os.getpid()}.json")

    pt.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()

    # chaos harness (ISSUE 14): keep the warm-up pass clean — the
    # FLAGS_fault_plan plan (if any) arms AFTER warm-up so its
    # invocation windows anchor to the timed run
    from paddle_tpu.resilience import faults
    faults.clear()

    obs.enable()
    tracing.enable_tracing()
    if args.jsonl_out:
        obs.set_jsonl_path(args.jsonl_out)

    guard = HeadroomGuard(fraction=0.92)
    # pool sized like the serving bench: ~60% of the worst-case bill —
    # the continuous-batching bet that mean length < max
    blocks_full = max_slots * (-(-max_len // block_size))
    dec = PagedDecoder(model, max_len=max_len, block_size=block_size,
                       max_slots=max_slots,
                       num_blocks=int(blocks_full * 0.6) + 1,
                       headroom_guard=guard,
                       prefix_cache=args.prefix_cache or None)

    rng = np.random.default_rng(args.seed)
    if args.sessions:
        reqs = build_session_requests(rng, args.sessions, args.turns,
                                      args.qps, dec.max_len, chunk,
                                      block_size)
    else:
        reqs = build_requests(rng, n_requests, args.qps, dec.max_len,
                              chunk)

    # warm every executable class the timed run hits: cold compiles
    # would otherwise bill multi-second walls into the FIRST requests'
    # TTFT and the artifact would measure XLA, not serving. That means
    # every prefill bucket present in reqs AND both decode-chunk
    # lengths the budget arithmetic can produce — n=chunk while any
    # live budget >= chunk, and the n=chunk-1 tail (a tail=0 request's
    # budget is chunk*k-1 after its prefill token): max_new=2*chunk
    # walks 2c-1 -> n=c -> c-1 -> n=c-1 -> 0, covering both
    buckets = {}
    for _, prompt, mnt, _ in reqs:
        if len(prompt) + mnt > dec.max_len:
            continue
        b = block_size
        while b < len(prompt):
            b *= 2
        buckets.setdefault(min(b, dec.max_len), prompt)
    dec.serve([(f"warm{b}", p, 2 * chunk) for b, p in buckets.items()],
              chunk=chunk, spec_decode=spec_k)
    if dec.prefix_cache is not None:
        # warm the warm-prefill executable class too (a fully-cached
        # re-serve compiles the small-suffix bucket + the COW copy),
        # then drop the warm-up chains: the timed run's hit ratio must
        # measure SESSION sharing, not warm-up leftovers
        p0 = next(iter(buckets.values()))
        dec.serve([("warmdup", p0, 2 * chunk)], chunk=chunk,
                  spec_decode=spec_k)
        dec.prefix_cache.clear()
        for key in dec.prefix_cache.stats:
            dec.prefix_cache.stats[key] = 0
    # fresh books for the timed window: the warm requests must not sit
    # in the percentile windows or the reconcile gate
    obs.registry().reset()
    tracing.clear()
    dec.request_ledger = RequestLedger("serve")
    dec.rejected_requests = {}
    dec.admission_deferrals = 0
    dec.evictions = dec.replays = dec.quarantines = 0
    dec.replay_giveups = dec.drained_rejections = 0
    dec.spec_stats = {"verify_calls": 0, "proposed": 0, "accepted": 0,
                      "emitted": 0}
    # pipelined-decode books (ISSUE 20): the timed window's host_gap
    # fraction and upload-per-chunk rate must not include warm-up
    dec._serve_ledger = None
    dec.h2d_uploads = dec.chunk_dispatches = 0
    dec.lookahead_dispatches = dec.pipeline_drains = 0
    # chaos harness: arm the FLAGS_fault_plan plan (no-op when unset)
    # now that warm-up is done — the timed run owns the schedule
    faults.install_from_flags()

    t0 = time.perf_counter()
    out = dec.serve(reqs, chunk=chunk,
                    admission_timeout_s=args.admission_timeout_s,
                    reject_oversized=True, spec_decode=spec_k)
    makespan = time.perf_counter() - t0

    led = dec.request_ledger
    summ = led.summary(slo_ttft_s=slo_ttft, slo_tpot_s=slo_tpot)
    completed = led.completed_records()
    rejected = sum(n for c, n in led.by_cause.items()
                   if c.startswith("rejected"))
    evicted = led.by_cause.get("evicted", 0)
    # terminal completions only: evicted/quarantined incarnations are
    # interruptions of a request that retires AGAIN under a terminal
    # cause (or gives up) — counting them would double-book the rid
    from paddle_tpu.observability.requests import NON_COMPLETION_CAUSES
    served = [r for r in completed
              if r.finish_reason not in NON_COMPLETION_CAUSES]
    goodput = summ["goodput_tokens"] / makespan if makespan > 0 else 0.0
    slo_ok = sum(1 for r in served
                 if r.ttft_s() is not None and r.ttft_s() <= slo_ttft
                 and (r.tpot_s() is None or r.tpot_s() <= slo_tpot))

    # prefix-cache scoring (ISSUE 18): hit ratio over prompt tokens,
    # and the TTFT ledger split into warm (some prompt tokens served
    # from cache) vs cold — the serving-lane history row's directions
    # (hit ratio up, warm TTFT down)
    prompt_toks = sum(r.prompt_tokens for r in served)
    cached_toks = sum(r.prefill_cached_tokens for r in served)
    hit_ratio = cached_toks / prompt_toks if prompt_toks else 0.0
    warm_ttfts = [r.ttft_s() for r in served
                  if r.prefill_cached_tokens > 0
                  and r.ttft_s() is not None]
    cold_ttfts = [r.ttft_s() for r in served
                  if r.prefill_cached_tokens == 0
                  and r.ttft_s() is not None]
    p50_warm = (float(np.percentile(warm_ttfts, 50))
                if warm_ttfts else None)
    p50_cold = (float(np.percentile(cold_ttfts, 50))
                if cold_ttfts else None)

    # the sliding-window quantiles must be LIVE operational metrics —
    # scrape()-visible — not just this process's post-hoc arithmetic
    scrape_txt = obs.scrape()
    scrape_live = ("paddle_tpu_request_ttft_seconds" in scrape_txt
                   and 'quantile="0.99"' in scrape_txt)

    # the share of the serve wall in which the loop knew the device's
    # queue empty (the ledger's host_gap: `serve:starved` stretches,
    # admissions included; 0 between chunks under look-ahead), and the
    # steady-state upload rate (0/chunk when composition is stable)
    sl = dec._serve_ledger
    starved_frac = (sl.totals.get("host_gap", 0.0) / sl.wall_total
                     if sl is not None and sl.wall_total > 0 else 0.0)
    h2d_per_chunk = dec.h2d_uploads / max(dec.chunk_dispatches, 1)

    # per-request Perfetto tracks: queue -> prefill -> decode chunks on
    # one named lane per request
    tracing.export_chrome(trace_out)
    with open(trace_out) as f:
        trace_doc = json.load(f)
    req_events = [e for e in trace_doc.get("traceEvents", [])
                  if str(e.get("name", "")).startswith("req:")]
    req_tracks = {e["args"]["name"]
                  for e in trace_doc.get("traceEvents", [])
                  if e.get("ph") == "M"
                  and e.get("name") == "thread_name"
                  and str(e.get("args", {}).get("name", ""))
                  .startswith("req ")}

    print(json.dumps({
        "metric": "serving_load_telemetry",
        "value": round(goodput, 2),
        "unit": f"goodput tokens/s (tokens from requests meeting "
                f"TTFT<={slo_ttft}s AND TPOT<={slo_tpot}s, over the "
                f"{round(makespan, 2)}s makespan; Poisson open loop "
                f"at {args.qps} QPS, {len(reqs)} requests incl. one "
                f"planted oversized, {max_slots} slots)",
        "qps": args.qps,
        "requests": len(reqs),
        "completed": len(served),
        "rejected": rejected,
        "evicted": evicted,
        "retired_by_cause": dict(led.by_cause),
        "p50_ttft_s": round(summ["p50_ttft_s"], 6),
        "p99_ttft_s": round(summ["p99_ttft_s"], 6),
        "p50_tpot_s": round(summ["p50_tpot_s"], 6),
        "p99_tpot_s": round(summ["p99_tpot_s"], 6),
        "p50_queue_wait_s": round(summ["p50_queue_wait_s"], 6),
        "p99_queue_wait_s": round(summ["p99_queue_wait_s"], 6),
        "goodput_tokens_per_sec": round(goodput, 2),
        "slo": {"ttft_s": slo_ttft, "tpot_s": slo_tpot},
        "slo_attainment": round(slo_ok / max(len(served), 1), 4),
        "tokens_generated": summ["tokens_generated"],
        "tokens_per_sec": round(
            summ["tokens_generated"] / makespan, 2) if makespan else 0,
        "makespan_s": round(makespan, 4),
        "reconcile_max_residual_frac":
            summ["reconcile_max_residual_frac"],
        "deferred_admissions": dec.admission_deferrals,
        # the loop's hand-overs: both lower-is-better,
        # regression-gated by tools/bench_history.py
        "starved_frac": round(starved_frac, 4),
        "h2d_uploads_per_chunk": round(h2d_per_chunk, 4),
        "chunk_dispatches": dec.chunk_dispatches,
        "lookahead_dispatches": dec.lookahead_dispatches,
        "pipeline_drains": dec.pipeline_drains,
        # prefix-cache telemetry (ISSUE 18): ratio of prompt tokens
        # served from mapped cache blocks, warm/cold TTFT split, and
        # the engine cache's own tallies (None when --prefix-cache off
        # — a cache-off run scoring a hit ratio would be teeth-less)
        "sessions": args.sessions or None,
        "turns": args.turns if args.sessions else None,
        "cache_hit_ratio": round(hit_ratio, 4),
        "prompt_tokens_total": prompt_toks,
        "prompt_tokens_cached": cached_toks,
        "p50_ttft_warm_s": (round(p50_warm, 6)
                            if p50_warm is not None else None),
        "p50_ttft_cold_s": (round(p50_cold, 6)
                            if p50_cold is not None else None),
        "warm_requests": len(warm_ttfts),
        "cold_requests": len(cold_ttfts),
        "prefix_cache": (dict(dec.prefix_cache.stats)
                         if dec.prefix_cache is not None else None),
        # fault-recovery accounting (ISSUE 14): goodput above already
        # excludes evicted/quarantined incarnations (the replay
        # incarnation of the same rid is the one that counts)
        "evictions": dec.evictions,
        "replays": dec.replays,
        "quarantined": dec.quarantines,
        "replay_giveups": dec.replay_giveups,
        "fault_injections": faults.counts() if faults.active() else None,
        "pool_blocks": dec.num_blocks,
        # speculative-decode accept telemetry under open-loop load (the
        # end-to-end tokens/s above IS the spec throughput when on)
        "spec_decode": ({
            "k": spec_k,
            "accept_rate": round(
                dec.spec_stats["accepted"] / dec.spec_stats["proposed"],
                4) if dec.spec_stats["proposed"] else 0.0,
            "proposed": dec.spec_stats["proposed"],
            "accepted": dec.spec_stats["accepted"],
            "verify_calls": dec.spec_stats["verify_calls"],
        } if spec_k else None),
        "scrape_percentiles_live": scrape_live,
        "trace_path": trace_out,
        "request_track_events": len(req_events),
        "request_tracks": len(req_tracks),
        # per-op attribution for the serving bandwidth bill (ISSUE 16):
        # which ops in this run's serve executables were HBM-bound
        "top_hbm_bound_ops": [
            {"executable": o["executable"], "op": o["op"],
             "scope": o["scope"], "seconds": round(o["seconds"], 9),
             "bytes": o["bytes"]}
            for o in roofline.top_hbm_bound_ops(3, source="serve")],
    }))

    # sanity: every request came back (generated or rejected-empty)
    missing = [r[0] for r in reqs if r[0] not in out]
    if missing:
        raise SystemExit(f"requests lost by serve(): {missing}")
    tracing.disable_tracing()
    if args.jsonl_out:
        obs.set_jsonl_path(None)
    obs.disable()


if __name__ == "__main__":
    main()
