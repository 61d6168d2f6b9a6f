"""The one span stream (ISSUE 29): spans follow a JAX profiler session as
well as the armed ring, carry id/parent, lie in the `.xplane.pb` host
plane under their names, cover the serve loop's and the train step's
phases on the plain (telemetry-off) path, and leave nothing behind when
nothing records. The request ledger is fed on that plain path too.
"""
import glob
import os
import threading
import time

import numpy as np
import pytest

import jax

import paddle_tpu as pt
import paddle_tpu.nn as nn
import paddle_tpu.observability as obs
from paddle_tpu.observability import tracing

SERVE_SPANS = ("serve:iteration", "serve:feed", "serve:admit",
               "serve:prefill", "serve:wait_first_token", "serve:chunk",
               "serve:wait_chunk", "serve:commit", "serve:starved",
               "serve:reserve", "serve:prefill_inputs")


@pytest.fixture
def quiet():
    """Nothing records, and the ring starts empty."""
    assert not obs.enabled()
    assert not tracing.recording()
    tracing.clear()
    yield tracing
    tracing.disable_tracing()
    tracing.clear()


@pytest.fixture
def armed(quiet):
    tracing.enable_tracing()
    yield tracing


def _host_events(trace_dir):
    """{name: [stats dict]} of the written `.xplane.pb`'s host plane."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                found.setdefault(ev.name, []).append(dict(ev.stats))
    return found


def _tiny_decoder(**kw):
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.paged_decode import PagedDecoder
    pt.seed(5)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=97, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=64, use_flash_attention=False))
    model.eval()
    args = dict(max_len=32, block_size=16, max_slots=2, num_blocks=9)
    args.update(kw)
    return PagedDecoder(model, **args)


def _requests(n=5, seed=3):
    rng = np.random.default_rng(seed)
    return [(i, [int(t) for t in rng.integers(0, 97, 4 + i)], 3 + i % 3)
            for i in range(n)]


def _serve(dec, **kw):
    """Serve `_requests()`, the last two through the `feed` hook."""
    reqs = _requests()
    late = [reqs[3:]]

    def feed():
        return late.pop() if late else ()
    return dec.serve(reqs[:3], max_new_tokens=8, chunk=2, feed=feed,
                     feed_active=lambda: bool(late), **kw)


def test_pipelined_admission_on_the_dense_engine(quiet):
    """The option the hybrid cell's adapter turns on, on a
    `PagedDecoder` of Llama blocks: the same tokens; a scan's prefills
    are dispatched under the iteration, ahead of the first read."""
    reference = _serve(_tiny_decoder())
    tracing.enable_tracing()
    out = _serve(_tiny_decoder(pipelined_admission=True))
    tracing.disable_tracing()
    assert out == reference
    spans = tracing.tail()
    by = _by_name(spans)
    ids = {s["id"]: s for s in spans}
    assert {ids[s["parent"]]["name"] for s in by["serve:prefill"]} \
        == {"serve:iteration"}
    assert {ids[s["parent"]]["name"]
            for s in by["serve:wait_first_token"]} == {"serve:admit"}
    assert len(by["serve:admit"]) == len(by["serve:prefill"]) == 5
    first_read = min(s["t0_ns"] for s in by["serve:wait_first_token"])
    assert sum(s["t0_ns"] < first_read for s in by["serve:prefill"]) == 2
    tokens = sum(s["meta"]["tokens"]
                 for s in by["serve:commit"] + by["serve:admit"])
    assert tokens == sum(len(t) for t in out.values())


def test_pipelined_admission_refuses_the_prefix_cache():
    with pytest.raises(NotImplementedError, match="pipelined_admission"):
        _tiny_decoder(pipelined_admission=True, prefix_cache=True)


def _tiny_step():
    pt.seed(0)
    net = nn.Linear(4, 3)
    opt = pt.optimizer.SGD(learning_rate=0.05, parameters=net.parameters())
    return pt.jit.TrainStep(net, lambda o, l: ((o - l) ** 2).mean(), opt)


def _batch(bs=4):
    rng = np.random.default_rng(0)
    return (pt.to_tensor(rng.standard_normal((bs, 4), np.float32)),
            pt.to_tensor(rng.standard_normal((bs, 3), np.float32)))


def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s["name"], []).append(s)
    return out


# -- (a) the tracer follows the profiler ------------------------------------
def test_span_follows_a_profiler_session(quiet, tmp_path):
    assert tracing.span("x") is tracing._NULL
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert tracing.recording() and not tracing.tracing_enabled()
        with tracing.span("outer_x", k=1, who="me") as sp:
            with tracing.span("inner_x"):
                pass
            sp.set(done=2)
    finally:
        jax.profiler.stop_trace()
    # the null object again once the session has ended
    assert not tracing.recording()
    assert tracing.span("x") is tracing._NULL
    with tracing.span("after"):
        pass
    ring = _by_name(tracing.tail())
    assert set(ring) == {"outer_x", "inner_x"}
    outer, inner = ring["outer_x"][0], ring["inner_x"][0]
    assert outer["parent"] is None and inner["parent"] == outer["id"]
    assert outer["meta"] == {"k": 1, "who": "me", "done": 2}
    # ...and in the written trace's host plane, metadata as event stats
    host = _host_events(str(tmp_path))
    assert "inner_x" in host
    assert host["outer_x"] == [{"k": 1, "who": "me", "done": 2}]


# -- (b) nesting, per thread -------------------------------------------------
def test_parent_is_the_enclosing_span_per_thread(armed):
    seen = {}

    def other():
        with tracing.span("t_outer") as o:
            with tracing.span("t_inner") as i:
                seen["t"] = (o.id, i.id, i.parent, o.parent)

    with tracing.span("m_outer") as o:
        worker = threading.Thread(target=other)
        worker.start()
        worker.join()
        with tracing.span("m_inner") as i:
            # an already-timed span takes the open span as its parent
            tracing.record_span("m_timed", 1, 2)
            seen["m"] = (o.id, i.id, i.parent, o.parent)
    t_outer, t_inner, t_parent, t_top = seen["t"]
    m_outer, m_inner, m_parent, m_top = seen["m"]
    # the other thread's spans do not nest under this thread's open span
    assert t_top is None and t_parent == t_outer
    assert m_top is None and m_parent == m_outer
    assert len({t_outer, t_inner, m_outer, m_inner}) == 4
    spans = _by_name(tracing.tail())
    assert spans["m_timed"][0]["parent"] == m_inner
    assert spans["m_inner"][0]["id"] == m_inner
    # a sibling after a closed span hangs off the top again
    with tracing.span("later") as later:
        assert later.parent is None
    # chrome events and drain carry both
    ev = {e["name"]: e for e in tracing.chrome_events() if e["ph"] == "X"}
    assert ev["m_inner"]["args"]["parent"] == m_outer
    assert ev["m_inner"]["args"]["id"] == m_inner
    drained = _by_name(tracing.drain())
    assert drained["t_inner"][0]["parent"] == t_outer


# -- (c) the serve loop on the plain path ------------------------------------
def test_serve_spans_on_the_plain_path(quiet):
    reference = _serve(_tiny_decoder())
    assert tracing.tail() == []          # nothing recorded, nothing kept

    tracing.enable_tracing()
    dec = _tiny_decoder()
    out = _serve(dec)
    tracing.disable_tracing()
    spans = tracing.tail()
    by = _by_name(spans)
    for name in SERVE_SPANS + ("req:queue", "req:prefill", "req:decode"):
        assert by.get(name), f"no {name} span"

    # observation did not change the observed: same tokens, and no
    # analysis record without telemetry
    assert out == reference
    assert dec._analysed == {}

    # the tree: phases under their iteration, waits under their admit
    ids = {s["id"]: s for s in spans}
    parent_name = lambda s: ids[s["parent"]]["name"]
    for name in ("serve:feed", "serve:admit", "serve:chunk",
                 "serve:wait_chunk", "serve:commit"):
        assert {parent_name(s) for s in by[name]} == {"serve:iteration"}
    for name in ("serve:prefill", "serve:wait_first_token"):
        assert {parent_name(s) for s in by[name]} == {"serve:admit"}
    assert all(s["parent"] is None for s in by["serve:iteration"])
    children = {}
    for s in spans:
        if s["parent"] is not None:
            children.setdefault(s["parent"], []).append(s)
    for pid, kids in children.items():
        assert sum(k["dur_ns"] for k in kids) <= ids[pid]["dur_ns"]
        for k in kids:
            assert k["t0_ns"] >= ids[pid]["t0_ns"]

    # the counts the metrics lean on
    assert len(by["serve:chunk"]) == dec.chunk_dispatches
    assert len(by["serve:wait_chunk"]) == len(by["serve:commit"])
    assert len(by["serve:admit"]) == len(out) == 5
    assert sum(s["meta"]["pushed"] for s in by["serve:feed"]) == 2
    tokens = sum(s["meta"]["tokens"]
                 for s in by["serve:commit"] + by["serve:admit"])
    assert tokens == sum(len(t) for t in out.values())
    for s in by["serve:admit"]:
        assert {"rid", "slot", "prompt_tokens", "bucket", "cached_tokens",
                "tokens"} <= set(s["meta"])
    for s in by["serve:chunk"]:
        assert {"steps", "lookahead", "uploads"} <= set(s["meta"])
    assert {s["meta"]["rid"] for s in by["serve:wait_first_token"]} \
        == set(out)
    # every budget here is over 1, so each request retires in a sweep
    assert sum(s["meta"]["retired"] for s in by["serve:commit"]) == 5


@pytest.mark.parametrize("record", [False, True],
                         ids=["nothing_recording", "ring_armed"])
def test_request_ledger_runs_on_the_plain_path(quiet, record):
    if record:
        tracing.enable_tracing()
    dec = _tiny_decoder()
    out = _serve(dec)
    led = dec.request_ledger
    recs = {r.rid: r for r in led.completed_records()}
    assert set(recs) == set(out) and led.in_flight() == []
    for rid, r in recs.items():
        assert r.ttft_s() is not None and r.ttft_s() > 0
        assert r.tokens_generated == len(out[rid])
        assert r.reconcile_residual_frac() <= 0.02
    summary = led.summary()
    assert summary["completed"] == 5 and summary["p50_ttft_s"] > 0
    assert summary["p50_tpot_s"] > 0
    if not record:
        assert tracing.tail() == []


def test_spec_verify_commits_through_the_same_spans(armed):
    dec = _tiny_decoder()
    reqs = _requests(3)
    out = dec.serve(reqs, max_new_tokens=8, chunk=2,
                    spec_decode={"k": 2, "draft": "ngram"})
    by = _by_name(tracing.tail())
    assert len(by["serve:spec_verify"]) == dec.chunk_dispatches
    assert len(by["serve:wait_chunk"]) == len(by["serve:commit"]) \
        == len(by["serve:spec_verify"])
    tokens = sum(s["meta"]["tokens"]
                 for s in by["serve:commit"] + by["serve:admit"])
    assert tokens == sum(len(t) for t in out.values())
    assert dec._analysed == {}          # spans are not telemetry
    assert all(r.tpot_s() is None or r.tpot_s() > 0
               for r in dec.request_ledger.completed_records())


# -- (d) the train step -------------------------------------------------------
def test_train_step_emits_one_call_span_a_step(quiet):
    step = _tiny_step()
    step(*_batch())
    assert tracing.tail() == []
    tracing.enable_tracing()
    for _ in range(3):
        step(*_batch())
    tracing.disable_tracing()
    calls = _by_name(tracing.tail())["train_step:call"]
    assert [s["meta"]["step"] for s in calls] == [1, 2, 3]
    assert all(s["parent"] is None for s in calls)
    # spans are not telemetry: nothing analysed
    assert step._analysed == {} and step.analysed_executables() == {}


def test_train_step_phases_nest_under_the_call_with_telemetry(armed):
    obs.registry().reset()
    obs.enable()
    try:
        step = _tiny_step()
        step(*_batch())
    finally:
        obs.disable()
    by = _by_name(tracing.tail())
    call, = by["train_step:call"]
    # telemetry's analysis, the timed call and every backend compile of
    # the step's program lie under the one call span
    analyse, = by["train_step:analyse"]
    assert analyse["parent"] == call["id"]
    assert by["train_step:execute"][0]["parent"] == call["id"]
    own = [s for s in by["xla:compile"]
           if "_traced" in s["meta"]["fun_name"]]
    assert own and all(s["parent"] in (call["id"], analyse["id"])
                       for s in own)


# -- (e) compiles --------------------------------------------------------------
def test_xla_compile_span_for_a_first_call_only(armed):
    @jax.jit
    def fresh_program_for_this_test(x):
        return x * 3 + 1

    x = jax.numpy.arange(7.0)
    with tracing.span("caller") as caller:
        fresh_program_for_this_test(x).block_until_ready()
    mine = [s for s in tracing.tail() if s["name"] == "xla:compile"
            and "fresh_program_for_this_test" in s["meta"]["fun_name"]]
    assert len(mine) == 1
    assert mine[0]["parent"] == caller.id
    assert mine[0]["dur_ns"] == pytest.approx(
        mine[0]["meta"]["seconds"] * 1e9, abs=2)
    tracing.clear()
    fresh_program_for_this_test(x).block_until_ready()
    assert [s for s in tracing.tail() if s["name"] == "xla:compile"] == []


def test_no_compile_span_when_nothing_records(quiet):
    @jax.jit
    def another_fresh_program(x):
        return x - 2

    another_fresh_program(jax.numpy.arange(5.0)).block_until_ready()
    assert tracing.tail() == []


# -- one store: the legacy Profiler on the ring -------------------------------
def test_profiler_arms_and_disarms_the_ring(quiet, tmp_path):
    import paddle_tpu.profiler as profiler
    assert not hasattr(tracing, "_PROF_BUFFER")
    assert not hasattr(profiler.profiler, "_HostEventBuffer")
    with tracing.span("before"):
        pass
    prof = profiler.Profiler(
        scheduler=(1, 3),
        on_trace_ready=profiler.export_chrome_tracing(str(tmp_path)))
    prof._start_device_trace = lambda: None
    prof.start()
    assert not tracing.tracing_enabled()          # step 0 is CLOSED
    with tracing.span("closed"):
        pass
    prof.step()
    assert tracing.tracing_enabled()              # RECORD arms the ring
    with tracing.span("kept", n=1):
        with profiler.RecordEvent("legacy"):
            pass
    prof.stop()
    assert not tracing.tracing_enabled()
    names = [e["name"] for e in
             profiler.load_profiler_result(prof._last_export)["traceEvents"]]
    assert sorted(names) == ["kept", "legacy"]
    ring = _by_name(tracing.tail())
    assert "closed" not in ring and "before" not in ring
    if "legacy" in ring:       # else the native tracer recorded it
        assert ring["legacy"][0]["parent"] == ring["kept"][0]["id"]


def test_profiler_leaves_a_ring_someone_else_armed(armed):
    import paddle_tpu.profiler as profiler
    prof = profiler.Profiler(timer_only=False)
    prof._start_device_trace = lambda: None
    prof.start()
    prof.stop()
    assert tracing.tracing_enabled()


# -- the device's queue, as the loop knows it (ISSUE 39) ----------------------
def _end(s):
    return s["t0_ns"] + s["dur_ns"]


def _uniform(n, budget):
    rng = np.random.default_rng(11)
    return [(i, [int(t) for t in rng.integers(0, 97, 6)], budget)
            for i in range(n)]


@pytest.mark.parametrize("staged", [False, True],
                         ids=["one_at_a_time", "pipelined_admission"])
def test_starved_lies_across_the_loops_spans(armed, staged):
    dec = _tiny_decoder(pipelined_admission=staged)
    out = _serve(dec)
    spans = tracing.tail()
    by = _by_name(spans)
    ids = {s["id"]: s for s in spans}
    starved = sorted(by["serve:starved"], key=lambda s: s["t0_ns"])
    # no parent, nobody's parent, one at a time
    assert all(s["parent"] is None for s in starved)
    assert not {s["id"] for s in starved} & {s["parent"] for s in spans}
    assert all(_end(a) <= b["t0_ns"] for a, b in zip(starved, starved[1:]))
    # each ends inside the dispatch that ended it, and names it
    enders = [d for name in ("serve:chunk", "serve:prefill",
                             "serve:warm_prefill") for d in by.get(name, [])]
    for s in starved:
        inside, = [d for d in enders
                   if d["t0_ns"] <= _end(s) <= _end(d)]
        assert "serve:" + s["meta"]["before"] == inside["name"]
        assert s["meta"]["after"] in ("start", "chunk", "prefill")
        assert s["meta"]["blocked"] in (0, 1)
        assert {"uploads", "admitted"} <= set(s["meta"])
    assert starved[0]["meta"]["after"] == "start"
    assert {s["meta"]["before"] for s in starved} == {"chunk", "prefill"}
    # a stretch a chunk ended saw that chunk's state go up, whole
    assert {s["meta"]["uploads"] for s in starved
            if s["meta"]["before"] == "chunk"} == {6}
    # an admission's host work, once an admission, where the existing
    # tree allows it
    where = "serve:iteration" if staged else "serve:admit"
    for name in ("serve:reserve", "serve:prefill_inputs"):
        assert len(by[name]) == len(by["serve:admit"]) == len(out)
        assert {ids[s["parent"]]["name"] for s in by[name]} == {where}
    assert {s["meta"]["rid"] for s in by["serve:reserve"]} == set(out)
    assert all(s["meta"]["blocks"] >= 1 for s in by["serve:reserve"])
    assert all(s["meta"]["calls"] == s["meta"]["prompts"] == 1
               for s in by["serve:prefill_inputs"])
    # the scan's end, on every iteration
    assert all(s["meta"]["admit_stop"] in ("full", "queue_empty")
               and 0 <= s["meta"]["free"] <= 2
               for s in by["serve:iteration"])


def test_no_starved_stretch_between_chunks_under_lookahead(armed):
    """A full batch and nothing to admit: chunk N + 1 is queued before
    chunk N is read, so the loop never knows the device's queue empty
    between two chunks."""
    dec = _tiny_decoder()
    dec.serve(_uniform(2, 9), max_new_tokens=9, chunk=2)
    by = _by_name(tracing.tail())
    assert dec.lookahead_dispatches >= 3
    first_chunk = min(s["t0_ns"] for s in by["serve:chunk"])
    assert [s["meta"]["before"] for s in by["serve:starved"]] \
        == ["prefill", "prefill", "chunk"]
    assert all(s["t0_ns"] < first_chunk for s in by["serve:starved"])
    # without look-ahead every chunk but the first is waited for dry
    tracing.clear()
    dec = _tiny_decoder()
    dec.serve(_uniform(2, 9), max_new_tokens=9, chunk=2, pipeline=False)
    between = [s for s in _by_name(tracing.tail())["serve:starved"]
               if s["meta"]["after"] == "chunk"]
    assert len(between) == dec.chunk_dispatches - 1
    assert all(s["meta"]["before"] == "chunk" and s["meta"]["uploads"] == 0
               for s in between)


@pytest.mark.parametrize("requests,share", [(2, 1.0), (1, 0.5)],
                         ids=["full_batch", "one_of_two_slots"])
def test_commit_counts_the_rows_the_device_ran(armed, requests, share):
    """`tokens` over `steps` x slots: 1.0 for a batch that is full
    throughout, k / slots with k live (`serve_loop.live_slot_share`)."""
    dec = _tiny_decoder()
    dec.serve(_uniform(requests, 9), max_new_tokens=9, chunk=2)
    commits = _by_name(tracing.tail())["serve:commit"]
    assert all(s["meta"]["committed"] == s["meta"]["steps"]
               for s in commits)
    tokens = sum(s["meta"]["tokens"] for s in commits)
    steps = sum(s["meta"]["steps"] for s in commits)
    assert tokens == requests * 8 and steps == 8
    assert tokens / (steps * dec.max_slots) == share


def test_a_trimmed_lookahead_chunk_commits_fewer_steps(armed):
    """An eos retires the slot with the largest budget while the chunk
    sized by it is in flight: the device ran `steps`, `committed` of
    them count."""
    reqs = [(0, [5, 6, 7, 8], 7), (1, [9, 10, 11], 12)]
    plain = _tiny_decoder().serve(reqs, max_new_tokens=12, chunk=4)
    # a token of the first chunk that request 1 alone emits, once
    eos = next(t for k, t in enumerate(plain[1][1:5], 1)
               if t not in plain[1][:k] and t not in plain[0])
    tracing.clear()
    dec = _tiny_decoder()
    dec.serve(reqs, max_new_tokens=12, chunk=4, eos_token_id=eos)
    assert dec.lookahead_dispatches >= 1
    commits = _by_name(tracing.tail())["serve:commit"]
    assert all(s["meta"]["committed"] <= s["meta"]["steps"]
               for s in commits)
    assert any(s["meta"]["committed"] < s["meta"]["steps"]
               for s in commits)


def test_the_queue_account_reads_nothing_when_nothing_records(
        quiet, monkeypatch):
    """`test_ledger_cost_per_iteration_is_small`'s way of counting, on
    the account of the device's queue: with nothing recording it asks no
    array whether it is ready and reads no clock; with the ring armed it
    asks before every blocking read and still reads no clock of its own
    (the spans do)."""
    import sys
    import types
    from paddle_tpu.serving import batcher
    account = {"watching", "unready", "starve", "landed", "launched",
               "unstarve", "idle"}
    asked, clock = [0], [0]
    array = type(jax.numpy.zeros(1))
    is_ready = array.is_ready

    def counted_is_ready(self):
        asked[0] += 1
        return is_ready(self)

    def perf_counter():
        clock[0] += sys._getframe(1).f_code.co_name in account
        return time.perf_counter()

    monkeypatch.setattr(array, "is_ready", counted_is_ready)
    monkeypatch.setattr(batcher, "time", types.SimpleNamespace(
        perf_counter=perf_counter, sleep=time.sleep))
    dec = _tiny_decoder()
    out = _serve(dec)
    assert (asked[0], clock[0]) == (0, 0)
    assert tracing.tail() == []
    tracing.enable_tracing()
    dec = _tiny_decoder()
    assert _serve(dec) == out
    tracing.disable_tracing()
    by = _by_name(tracing.tail())
    reads = len(by["serve:wait_chunk"]) + len(by["serve:wait_first_token"])
    assert asked[0] == reads and clock[0] == 0
    assert by["serve:starved"]


def test_open_span_takes_no_parent_and_is_none(armed):
    with tracing.span("outer") as outer:
        across = tracing.open_span("across", k=1)
        with tracing.span("inner") as inner:
            assert inner.parent == outer.id
    with tracing.span("later") as later:
        across.set(done=2).close()
        assert later.parent is None
    dropped = tracing.open_span("never")
    dropped.close(keep=False)
    by = _by_name(tracing.tail())
    assert "never" not in by
    got, = by["across"]
    assert got["parent"] is None and got["meta"] == {"k": 1, "done": 2}
    assert got["t0_ns"] > by["outer"][0]["t0_ns"]
    assert _end(got) > _end(by["inner"][0])
    tracing.disable_tracing()
    assert tracing.open_span("off") is tracing._NULL
    tracing._NULL.set(a=1).close()


def test_starved_lies_in_a_profiler_sessions_trace(quiet, tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(_tiny_decoder())
    finally:
        jax.profiler.stop_trace()
    ring = _by_name(tracing.tail())["serve:starved"]
    host = _host_events(str(tmp_path))["serve:starved"]
    kept = [e for e in host if "dropped" not in e]
    assert len(kept) == len(ring)
    assert sorted(e["before"] for e in kept) \
        == sorted(s["meta"]["before"] for s in ring)
    assert {"after", "blocked", "uploads", "admitted"} <= set(kept[0])


# -- what the always-on ledger costs ------------------------------------------
def test_ledger_cost_per_iteration_is_small(quiet):
    """The time spent inside the serve loop's ledger calls, per loop
    iteration, stays far under a millisecond (PERF.md reports the figure
    at 32 slots, beside the `process_time` of a serve with the calls
    stubbed out and not)."""
    from paddle_tpu.observability.requests import RequestLedger
    spent = [0.0, 0]

    def timed(name):
        inner = getattr(RequestLedger, name)

        def call(self, *a, **k):
            t0 = time.perf_counter()
            try:
                return inner(self, *a, **k)
            finally:
                spent[0] += time.perf_counter() - t0
                spent[1] += 1
        return call

    Timed = type("Timed", (RequestLedger,), {
        name: timed(name) for name in ("arrival", "admit", "prefill",
                                       "first_token", "chunk", "retire")})
    dec = _tiny_decoder()
    dec.request_ledger = Timed("serve")
    tracing.enable_tracing()
    out = _serve(dec)
    tracing.disable_tracing()
    iterations = len(_by_name(tracing.tail())["serve:iteration"])
    # five calls a request and one per slot and chunk it rode
    assert spent[1] >= 5 * len(out) + dec.chunk_dispatches
    assert spent[0] / iterations < 1e-3, \
        f"{spent[0] / iterations * 1e6:.0f} us an iteration"
