"""The trace reduction: arithmetic on made-up events, then the small
trace recorded on the chip (tests/data/tiny.xplane.pb: three runs of a
jitted matmul + tanh under `chipbench.step` spans, a 10 ms sleep under
`chipbench.sleep` after each; TPU v5 lite, PR 28)."""
import os

import pytest

from chipbench import trace
from chipbench.trace import Event

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny.xplane.pb")


def test_union_busy_and_gaps():
    evs = [Event("a", 0.0, 1.0), Event("b", 0.5, 2.0), Event("c", 3.0, 4.0),
           Event("d", 3.2, 3.4)]
    assert trace.union(evs) == [(0.0, 2.0), (3.0, 4.0)]
    assert trace.busy_seconds(evs) == 3.0
    assert trace.gaps(evs, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]
    assert trace.busy_seconds(trace.clip(evs, 0.5, 3.5)) == 2.0
    assert trace.op_seconds(evs) == {"a": 1.0, "b": 1.5, "c": 1.0,
                                     "d": pytest.approx(0.2)}


def test_gap_goes_to_the_innermost_span_that_covers_half_of_it():
    host = [Event("chipbench.window", 0.0, 10.0),
            Event("$batcher.py:607 admit", 2.0, 3.1),
            Event("$numpy asarray", 2.0, 3.0),           # not a label
            Event("$other.py:1 helper", 2.1, 2.9)]       # not a listed file
    sums = trace.attribute_gaps([(2.0, 3.0), (5.0, 6.0)], host,
                                files=("batcher.py",))
    assert sums == {"batcher.py:admit": 1.0, "chipbench.window": 1.0}
    assert trace.attribute_gaps([(20.0, 21.0)], host) == {"(no span)": 1.0}


def test_instruction_names():
    text = ("%decode.attend.4 = bf16[32,32,128]{2,1,0:T(8,128)(2,1)S(1)} "
            "custom-call(s32[32,32]{1,0:T(8,128)} %x), custom_call_target=\"tpu\"")
    assert trace.op_instruction_name(text) == "decode.attend.4"
    assert trace.op_opcode(text) == "custom-call"
    assert trace.short_op_name(text) == \
        "decode.attend.4 custom-call bf16[32,32,128]"
    loop = "%while.3 = (s32[], bf16[8]{0}) while((s32[], bf16[8]{0}) %t), body=%b"
    s = trace.Summary(window_s=1.0, busy_s=0.5,
                      ops={text: 0.2, loop: 0.5,
                           text.replace("attend.4", "attend.5"): 0.1,
                           text.replace("decode.attend.4", "attn.12"): 0.05},
                      idle_gaps={})
    assert trace.scope_seconds(s, "decode.attend") == pytest.approx(0.3)
    assert trace.scope_seconds(s, "attn") == pytest.approx(0.05)
    assert trace.op_opcode(loop) == "while"
    assert all("while" not in name for name, _ in
               trace.breakdown(s)["device_ops"])


def test_recorded_trace():
    t = trace.load(DATA)
    assert list(t.device_ops) == [0]
    ops, modules = t.device_ops[0], t.device_modules[0]
    assert len(modules) == 3 and len(ops) == 9
    assert {trace.op_opcode(e.name) for e in ops} == \
        {"fusion", "copy-start", "copy-done"}
    # each program run is one busy stretch of about 15 us
    assert trace.busy_seconds(ops) == pytest.approx(45.3e-6, rel=0.02)
    # the whole trace as the window: from the first step's span to the
    # last sleep's end
    steps = sorted((e for e in t.host if e.name == "chipbench.step"),
                   key=lambda e: e.start)
    sleeps = sorted((e for e in t.host if e.name == "chipbench.sleep"),
                    key=lambda e: e.start)
    assert len(steps) == len(sleeps) == 3
    t0, t1 = steps[0].start - 2e-3, sleeps[-1].end
    t.host.append(Event("chipbench.window", t0, t1))
    s = trace.summarize(t, "chipbench.window")
    assert s.window_s == pytest.approx(t1 - t0)
    assert s.busy_s == pytest.approx(45.3e-6, rel=0.02)
    assert 0.99 < s.idle_share < 1.0
    fusion = [k for k in s.ops if trace.op_opcode(k) == "fusion"]
    assert len(fusion) == 1 and s.ops[fusion[0]] == pytest.approx(45.2e-6,
                                                                  rel=0.02)
    # nearly all idle time lies under the three sleeps
    assert s.idle_gaps["chipbench.sleep"] > 0.8 * (s.window_s - s.busy_s)
    top = trace.breakdown(s)
    assert top["device_ops"][0][0].startswith("fusion fusion bf16[]")
    assert top["idle_gaps"][0][0] == "chipbench.sleep"
