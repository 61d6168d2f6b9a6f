"""`moe.buffer_rows_per_pair_here` on hand-made spans: the expert
layers' buffered rows over the pairs computed here, decode chunks and
prompts each brought to the rows the harness counted; None where the
program counts no buffer rows."""
import types

import pytest

from chipbench import spans
from chipbench.tests.test_spans import reader, span

NAME = "moe.buffer_rows_per_pair_here"
CFG = {"mamba_num_heads": 128, "mamba_head_dim": 64, "ssm_state_size": 128,
       "n_groups": 8, "hybrid_override_pattern": "MEMEMEMEM*E",
       "n_routed_experts": 128}


def commit(sid, start, pairs, buffered=None, steps=8, slots=128):
    """A chunk's span; without `buffered` as the parent records it."""
    meta = {} if buffered is None else {"moe_rows_buffered": buffered}
    return span(sid, None, "serve:commit", start, start + 0.1,
                tokens=steps * slots, steps=steps,
                ssm_rows=5 * steps * slots, moe_pairs_here=pairs,
                moe_pairs_all=22 * 5 * steps * slots, moe_experts_touched=640,
                moe_max_load=40, **meta)


def admit(sid, start, prompt, pairs, buffered):
    return span(sid, None, "serve:admit", start, start + 0.1,
                prompt_tokens=prompt, moe_pairs_here=pairs,
                moe_pairs_all=22 * 5 * prompt if pairs else 0,
                moe_experts_touched=600 if pairs else 0,
                moe_max_load=300 if pairs else 0, moe_rows_buffered=buffered)


# one window [10, 20]: two chunks of 8 steps (5 expert blocks a step at
# 1,152 rows), one pack of two prompts (its counts on the first
# admission), and a chunk after the window
WINDOW = [
    commit(1, 11.0, 28000, 46080),
    admit(2, 12.0, 1500, 55000, 84480),
    admit(3, 12.1, 500, 0, 0),
    commit(4, 13.0, 29000, 46080),
    commit(5, 21.0, 99999, 1),
]
WANT = (2 * 46080 + 84480) / (28000 + 29000 + 55000)


def read(monkeypatch, found, decode_rows=2048, prefill_tokens=2000,
         cfg=CFG):
    view = types.SimpleNamespace(
        window=(10.0, 20.0), cfg=cfg,
        observed={"decode_rows": decode_rows,
                  "prefill_tokens": prefill_tokens})
    monkeypatch.setattr(
        spans, "in_window",
        lambda view: found and (spans.clip(found, *view.window) or None))
    return reader(NAME)(view)


def test_buffered_rows_over_pairs_computed_here(monkeypatch):
    assert read(monkeypatch, WINDOW) == pytest.approx(WANT)
    # decode alone
    assert read(monkeypatch, [WINDOW[0], WINDOW[3]], prefill_tokens=0) \
        == pytest.approx(2 * 46080 / 57000)


def test_each_kind_is_brought_to_the_rows_the_harness_counted(monkeypatch):
    # the spans cover half the decode rows the window ran
    assert read(monkeypatch, WINDOW, decode_rows=4096) == pytest.approx(
        (4 * 46080 + 84480) / (2 * 57000 + 55000))


# what the parent records: the same spans without `moe_rows_buffered`
PARENT = [commit(1, 11.0, 28000), span(
    2, None, "serve:admit", 12.0, 12.1, prompt_tokens=1500,
    moe_pairs_here=55000, moe_pairs_all=165000, moe_experts_touched=600,
    moe_max_load=300)]


@pytest.mark.parametrize("found,kw", [
    (None, {}), (PARENT, {}), ([WINDOW[1]], {}),
    # prompts prefilled in the window but no admission counted them
    ([WINDOW[0]], {}),
    (WINDOW, {"cfg": {"num_hidden_layers": 4}}),
], ids=["no_spans", "the_parents_spans", "no_chunk", "no_admission",
        "another_family"])
def test_none_where_nothing_was_counted(monkeypatch, found, kw):
    assert read(monkeypatch, found, **kw) is None
