"""The six readers of PR 39 on hand-made span lists: `serve:starved`
clipped at the window's edges and split by `before`, the idle the loop
cannot account for, the share of the decode rows that became tokens, an
admission's host work; None where the program recorded no such span."""
import types

import pytest

from chipbench import spans
from chipbench.tests.test_spans import reader, span

STARVED = "serve_loop.starved_share"
PREFILL = "serve_loop.starved_share.before_prefill"
CHUNK = "serve_loop.starved_share.before_chunk"
UNACCOUNTED = "device.idle_unaccounted_share.serve"
LIVE = "serve_loop.live_slot_share"
ADMIT = "serve_loop.admit_host_ms_per_prompt"
ALL = (STARVED, PREFILL, CHUNK, UNACCOUNTED, LIVE, ADMIT)


def starved(sid, start, end, before, after="prefill", blocked=1):
    return span(sid, None, "serve:starved", start, end, after=after,
                before=before, blocked=blocked, uploads=0, admitted=0)


# one window [10, 20], 4 slots. A stretch that began before it (1.0 s
# inside), one a prefill ended (0.2), one a pool-mapped prefill ended
# (0.1), one a chunk ended that runs past the window (0.5 inside), one a
# verify ended (0.05), one after the window
LOOP = [
    starved(1, 9.5, 11.0, "chunk"),
    span(2, None, "serve:iteration", 11.0, 15.0),
    span(3, 2, "serve:admit", 11.0, 12.0),
    span(4, 3, "serve:reserve", 11.0, 11.002, rid=7, blocks=3),
    span(5, 3, "serve:prefill_inputs", 11.002, 11.005, bucket=256,
         prompts=1, calls=1),
    starved(6, 11.0, 11.2, "prefill", after="chunk"),
    span(7, 3, "serve:prefill", 11.005, 11.009, bucket=256, prompts=1,
         rows=200),
    span(8, 2, "serve:commit", 13.0, 13.1, tokens=24, retired=0, steps=8,
         committed=8),
    starved(9, 14.0, 14.1, "warm_prefill"),
    span(10, None, "serve:iteration", 15.0, 21.0),
    span(11, 10, "serve:admit", 15.0, 16.0),
    span(12, 10, "serve:reserve", 15.0, 15.001, rid=8, blocks=2),
    span(13, 10, "serve:prefill_inputs", 15.001, 15.003, bucket=128,
         prompts=1, calls=1),
    span(14, 10, "serve:prefill", 15.003, 15.005, bucket=128, prompts=1,
         rows=100),
    span(15, 10, "serve:commit", 16.0, 16.1, tokens=10, retired=2, steps=4,
         committed=2),
    starved(16, 17.0, 17.05, "verify", after="verify"),
    starved(17, 19.5, 20.5, "chunk", blocked=0),
    starved(18, 22.0, 23.0, "chunk"),
]


def read(monkeypatch, name, found, idle_share=0.05):
    view = types.SimpleNamespace(
        window=(10.0, 20.0), observed={"slots": 4},
        summary=types.SimpleNamespace(idle_share=idle_share))
    monkeypatch.setattr(
        spans, "in_window",
        lambda view: found and (spans.clip(found, *view.window) or None))
    return reader(name)(view)


def test_starved_is_clipped_to_the_window(monkeypatch):
    # 1.0 + 0.2 + 0.1 + 0.05 + 0.5 of 10 s
    assert read(monkeypatch, STARVED, LOOP) == pytest.approx(18.5)
    # one stretch that covers the whole window and more
    assert read(monkeypatch, STARVED,
                [starved(1, 5.0, 25.0, "chunk")]) == pytest.approx(100.0)


def test_the_split_by_before_sums_to_the_whole(monkeypatch):
    whole = read(monkeypatch, STARVED, LOOP)
    by_prefill = read(monkeypatch, PREFILL, LOOP)
    by_chunk = read(monkeypatch, CHUNK, LOOP)
    assert by_prefill == pytest.approx(3.0)      # prefill and warm_prefill
    assert by_chunk == pytest.approx(15.0)
    # what is left is the stretch that another kind of call ended
    assert whole - by_prefill - by_chunk == pytest.approx(0.5)
    only = [s for s in LOOP if s["name"] != "serve:starved"
            or s["meta"]["before"] in ("chunk", "prefill", "warm_prefill")]
    assert read(monkeypatch, PREFILL, only) + read(monkeypatch, CHUNK, only) \
        == pytest.approx(read(monkeypatch, STARVED, only))
    # stretches, but none of the kind: 0, the program does record them
    assert read(monkeypatch, PREFILL,
                [starved(1, 11.0, 12.0, "chunk")]) == 0.0


def test_idle_the_loop_cannot_account_for(monkeypatch):
    assert read(monkeypatch, UNACCOUNTED, LOOP, idle_share=0.25) \
        == pytest.approx(25.0 - 18.5)
    # the stretches cover more than the trace's idle: below 0, as it is
    assert read(monkeypatch, UNACCOUNTED, LOOP, idle_share=0.05) \
        == pytest.approx(5.0 - 18.5)


def test_live_slot_share_counts_every_row_the_device_ran(monkeypatch):
    # 24 + 10 tokens of 4 slots x (8 + 4) steps
    assert read(monkeypatch, LIVE, LOOP) == pytest.approx(100 * 34 / 48)
    full = [span(1, None, "serve:commit", 11.0, 11.1, tokens=32, retired=0,
                 steps=8, committed=8)]
    assert read(monkeypatch, LIVE, full) == pytest.approx(100.0)
    # a commit outside the window is not counted
    assert read(monkeypatch, LIVE, full + [span(
        2, None, "serve:commit", 21.0, 21.1, tokens=0, retired=0, steps=8,
        committed=8)]) == pytest.approx(100.0)


def test_admit_host_ms_per_prompt(monkeypatch):
    # (2 + 3 + 4) + (1 + 2 + 2) ms over two admissions
    assert read(monkeypatch, ADMIT, LOOP) == pytest.approx(7.0)
    # a pack: one preparation and one program for three admissions
    packed = [span(1, None, "serve:iteration", 11.0, 14.0)] + [
        span(2 + k, 1, "serve:reserve", 11.0 + 0.001 * k,
             11.001 + 0.001 * k, rid=k, blocks=1) for k in range(3)] + [
        span(5, 1, "serve:prefill_inputs", 11.003, 11.009, bucket=2048,
             prompts=3, calls=1),
        span(6, 1, "serve:prefill", 11.009, 11.012, bucket=2048, prompts=3,
             rows=1900)] + [
        span(7 + k, 1, "serve:admit", 11.02 + 0.1 * k, 11.1 + 0.1 * k)
        for k in range(3)]
    assert read(monkeypatch, ADMIT, packed) == pytest.approx(4.0)


# what an older commit's tree records: the loop's other spans, a commit
# without `steps`, no stretch and no `serve:reserve`
PARENT = [
    span(1, None, "serve:iteration", 11.0, 15.0),
    span(2, 1, "serve:admit", 11.0, 12.0),
    span(3, 2, "serve:prefill", 11.0, 11.1, bucket=256, prompts=1, rows=200),
    span(4, 1, "serve:commit", 13.0, 13.1, tokens=24, retired=0),
]


@pytest.mark.parametrize("name", ALL)
@pytest.mark.parametrize("found", [None, PARENT],
                         ids=["no_spans", "the_parents_spans"])
def test_a_reader_returns_none_where_nothing_was_recorded(monkeypatch, name,
                                                          found):
    assert read(monkeypatch, name, found) is None
