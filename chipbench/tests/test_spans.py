"""The span arithmetic the `program_span` metrics rest on: clipping to
the window, self time, None where the program recorded nothing, and the
four readers on a hand-made window."""
import importlib.util
import os
import types

import pytest

from chipbench import spans

HERE = os.path.dirname(os.path.abspath(__file__))


def span(sid, parent, name, start, end, **meta):
    return {"id": sid, "parent": parent, "name": name, "start": start,
            "end": end, "meta": meta}


# one window [10, 20]: an iteration that began before it, one whole
# iteration with an admission, one that ends after it
LOOP = [
    span(1, None, "serve:iteration", 8.0, 11.0),
    span(2, 1, "serve:wait_chunk", 9.0, 10.5),
    span(3, None, "serve:iteration", 11.0, 17.0),
    span(4, 3, "serve:feed", 11.0, 11.1),
    span(5, 3, "serve:admit", 11.5, 13.5),
    span(6, 5, "serve:prefill", 11.6, 11.7),
    span(7, 5, "serve:wait_first_token", 11.7, 13.4),
    span(8, 3, "serve:chunk", 13.6, 13.7),
    span(9, 3, "serve:wait_chunk", 13.7, 16.7),
    span(10, 3, "serve:commit", 16.7, 16.9),
    span(11, None, "serve:iteration", 17.0, 23.0),
    span(12, 11, "serve:chunk", 17.5, 17.6),
    span(13, 11, "serve:wait_chunk", 18.0, 22.0),
    span(14, None, "serve:chunk", 30.0, 31.0),
]


def test_clip_cuts_to_the_window_and_drops_what_lies_outside():
    cut = spans.clip(LOOP, 10.0, 20.0)
    by_id = {s["id"]: s for s in cut}
    assert 14 not in by_id and len(cut) == 13
    assert (by_id[1]["start"], by_id[1]["end"]) == (10.0, 11.0)
    assert (by_id[13]["start"], by_id[13]["end"]) == (18.0, 20.0)
    assert by_id[7] == LOOP[6] and by_id[7] is not LOOP[6]
    assert spans.clip(LOOP, 40.0, 50.0) == []


def test_seconds_and_durations():
    cut = spans.clip(LOOP, 10.0, 20.0)
    assert spans.durations(cut, "serve:wait_chunk") == pytest.approx(
        [0.5, 3.0, 2.0])
    assert spans.seconds(cut, "serve:wait_chunk") == pytest.approx(5.5)
    assert spans.seconds(cut, "serve:nothing") == 0
    assert spans.durations(cut, "serve:nothing") == []


def test_self_time_is_duration_less_what_children_cover():
    # iteration 3: 6.0 s less feed 0.1, admit 2.0, chunk 0.1, wait 3.0,
    # commit 0.2; admit: 2.0 less prefill 0.1 and wait 1.7
    only = [s for s in LOOP if s["id"] in range(3, 11)]
    assert spans.self_seconds(only, "serve:iteration") == pytest.approx(0.6)
    assert spans.self_seconds(only, "serve:admit") == pytest.approx(0.2)
    assert spans.self_seconds(only, "serve:commit") == pytest.approx(0.2)
    # children that overlap are counted once, and only inside the parent
    lap = [span(1, None, "p", 0.0, 10.0), span(2, 1, "c", 1.0, 5.0),
           span(3, 1, "c", 4.0, 6.0), span(4, 1, "c", 9.0, 12.0)]
    assert spans.self_seconds(lap, "p") == pytest.approx(10 - 5 - 1)


def test_ring_records_become_spans():
    got = spans.from_ring([
        {"id": 7, "parent": 3, "name": "serve:admit", "t0_ns": 1_500_000_000,
         "dur_ns": 250_000_000, "tid": 1, "rank": 0, "meta": {"rid": 4}},
        # a record of a tracer that knows no ids, and one with no meta
        {"name": "old", "t0_ns": 0, "dur_ns": 10, "tid": 1, "rank": 0}])
    assert got[0] == span(7, 3, "serve:admit", 1.5, 1.75, rid=4)
    assert got[1]["id"] is None and got[1]["parent"] is None
    assert got[1]["meta"] == {}


def reader(name):
    path = os.path.join(os.path.dirname(HERE), "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"),
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


READERS = ("serve_loop.host_work_ms_per_chunk", "serve_loop.admit_stall_ms_p50",
           "serve_loop.first_token_wait_share", "train_step.host_ms_per_step")


@pytest.mark.parametrize("name", READERS)
def test_a_reader_that_finds_no_span_returns_none(name, monkeypatch):
    view = types.SimpleNamespace(window=(10.0, 20.0))
    monkeypatch.setattr(spans, "in_window", lambda view: None)
    assert reader(name)(view) is None
    # spans, but none of the kind it reads
    monkeypatch.setattr(spans, "in_window",
                        lambda view: [span(1, None, "other", 11.0, 12.0)])
    assert reader(name)(view) is None


def test_the_readers_on_the_hand_made_window(monkeypatch):
    view = types.SimpleNamespace(window=(10.0, 20.0))
    train = [span(20 + k, None, "train_step:call", 10.0 + k, 10.004 + k)
             for k in range(5)]
    monkeypatch.setattr(spans, "in_window",
                        lambda view: spans.clip(LOOP + train, *view.window))
    # 10 s less wait_chunk 5.5, wait_first_token 1.7, feed 0.1, 2 chunks
    assert reader(READERS[0])(view) == pytest.approx(1e3 * 2.7 / 2)
    assert reader(READERS[1])(view) == pytest.approx(2000.0)
    assert reader(READERS[2])(view) == pytest.approx(17.0)
    assert reader(READERS[3])(view) == pytest.approx(4.0)


def test_in_window_reads_the_programs_ring():
    from paddle_tpu.observability import tracing
    import time
    tracing.clear()
    view = types.SimpleNamespace(window=(time.perf_counter(), None))
    assert spans.in_window(types.SimpleNamespace(
        window=(view.window[0], view.window[0] + 1.0))) is None
    tracing.enable_tracing()
    try:
        with tracing.span("serve:admit", rid=1):
            time.sleep(0.002)
    finally:
        tracing.disable_tracing()
    view.window = (view.window[0], time.perf_counter())
    found = spans.in_window(view)
    tracing.clear()
    assert [s["name"] for s in found] == ["serve:admit"]
    assert found[0]["meta"] == {"rid": 1}
    assert 0.002 <= found[0]["end"] - found[0]["start"] < 0.5
