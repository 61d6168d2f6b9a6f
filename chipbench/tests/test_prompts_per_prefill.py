"""`serve_loop.prompts_per_prefill` on hand-made spans: admissions over
prefill programs inside the window, None where there is nothing to
read."""
import types

import pytest

from chipbench import spans
from chipbench.tests.test_spans import reader, span

NAME = "serve_loop.prompts_per_prefill"
# one window [10, 20]. A packed scan: two programs for five admissions;
# an admission of its own program; a prefill before the window and an
# admission after it are not counted
PACKED = [
    span(1, None, "serve:iteration", 9.0, 10.0),
    span(2, 1, "serve:prefill", 9.1, 9.2, bucket=128, prompts=1, rows=100),
    span(3, None, "serve:iteration", 11.0, 14.0),
    span(4, 3, "serve:prefill", 11.1, 11.2, bucket=2048, prompts=3,
         rows=1700),
    span(5, 3, "serve:prefill", 11.2, 11.3, bucket=512, prompts=2, rows=400),
    *[span(6 + k, 3, "serve:admit", 11.3 + 0.1 * k, 11.4 + 0.1 * k)
      for k in range(5)],
    span(11, None, "serve:iteration", 14.0, 16.0),
    span(12, 11, "serve:admit", 14.1, 15.0),
    span(13, 12, "serve:prefill", 14.2, 14.3, bucket=256, prompts=1,
         rows=200),
    span(14, None, "serve:admit", 21.0, 22.0),
]


@pytest.mark.parametrize("found,want", [
    (PACKED, 6 / 3),
    # every prompt a program of its own, as the dense engine dispatches
    ([s for s in PACKED if s["id"] in (11, 12, 13)], 1.0),
    ([span(1, None, "serve:admit", 11.0, 12.0)], None),
    ([span(1, None, "serve:prefill", 11.0, 12.0)], None),
    ([span(1, None, "other", 11.0, 12.0)], None),
    (None, None),
])
def test_admissions_over_prefill_programs_in_the_window(monkeypatch, found,
                                                        want):
    view = types.SimpleNamespace(window=(10.0, 20.0))
    monkeypatch.setattr(
        spans, "in_window",
        lambda view: found and (spans.clip(found, *view.window) or None))
    got = reader(NAME)(view)
    assert got == want if want is None else got == pytest.approx(want)
