"""Driver loop for traffic of kind `serve_long`: the loop and the
comparison of kind `serve`, for prompts whose prefill takes seconds, with
two differences.

One arrangement for every seed. A window of 40 s admits a few dozen
prompts of 8k-16k tokens, no whole number of `generate`'s seeded groups,
and closes at the first loop iteration past its end, which may hold a
scan's prefills of several seconds. Under a seeded order of prompt
lengths, the prompt rows a window prefilled moved its tokens/s by 4 %
from seed to seed. Here the requests come in one arrangement, the cyclic
Latin square of `generate.serve_cycle_shapes` with no permutation: every
seed does the same work in the same order, and draws only the token ids
and the weights.

Beside the widest gap over the sample, its MEAN over the sample's served
tokens (`logit_gap_mean`), compared under a limit of its own. The widest
gap is the gap of one token in a thousand or more. Where the attention
reads a learned top-k of thousands of near-equal scores, the
configuration's own rounding flips a few keys at the choice's edge, and
through the seeded weights that moves a few served tokens far: the widest
gap of a sound run then lies within 2 x of the float8 control's. Most
tokens do not move, so the mean stays an order of magnitude below the
control's mean, which every token's rounding raises. A token altered or
a request stopped short still shows in the widest gap; a lower precision
or a wrong choice of keys shows in both.
"""
from __future__ import annotations

from unittest import mock

import numpy as np

from chipbench import generate
from chipbench.kinds import serve
from chipbench.kinds.serve import gap_below_best


def cycle_shapes(traffic):
    """One cycle of (prompt_len, budget) pairs, the same for every seed:
    request i of group g asks `prompt_lens[(g + i) % n]` with
    `budgets[i]`, so that each group of n holds every length and every
    budget once, and every pair comes once a cycle."""
    p, b = traffic["prompt_lens"], traffic["budgets"]
    n = len(p)
    if len(b) != n:
        raise ValueError("the Latin arrangement needs as many budgets as "
                         "prompt lengths")
    return [(p[(g + i) % n], b[i]) for g in range(n) for i in range(n)]


def serve_requests(traffic, vocab, seed):
    """`generate.serve_requests` with every cycle in `cycle_shapes`'
    arrangement: token ids and arrival times from the seed as there."""
    shapes = cycle_shapes(traffic) * traffic["cycles"]
    rng = generate.rng_for(seed, 3)
    due = generate.arrival_times(traffic, seed, len(shapes))
    return [(rid, rng.integers(0, vocab, plen).tolist(), int(budget),
             float(due[rid]))
            for rid, (plen, budget) in enumerate(shapes)]


class Session(serve.Session):
    def run(self):
        """`serve`'s run on `serve_requests`' list."""
        with mock.patch.object(generate, "serve_requests", serve_requests):
            return super().run()

    def reference_rows(self, rid, precision):
        """`serve`'s, with each row's gap kept for the mean: of the served
        token under the reference, and of the token a control puts
        first (the control's rows come after the same request's
        reference rows)."""
        rows = super().reference_rows(rid, precision)
        if precision == "f32":
            self._f32 = rows
            gaps = gap_below_best(rows, self.served[rid])
        else:
            gaps = gap_below_best(self._f32, rows.argmax(axis=-1))
        self._gaps.setdefault(precision, []).append(gaps)
        return rows

    def check(self, control=None):
        """`serve`'s rows, then the mean gap of the served tokens and,
        with `control`, the mean gap of the control's tokens."""
        self._gaps, self._f32 = {}, None
        rows_out = super().check(control)
        means = {p: float(np.concatenate(g).mean()) if g else float("inf")
                 for p, g in self._gaps.items()}
        if rows_out[0][1] == float("inf"):      # no sample, or not finite
            means["f32"] = float("inf")
        n = sum(len(g) for g in self._gaps.get("f32", []))
        rows_out.append(("logit_gap_mean", means.get("f32", float("inf")),
                         "logit_gap_mean", f"{n} tokens"))
        if control:
            rows_out.append((f"logit_gap_mean_{control}",
                             means.get(control, float("inf")),
                             "logit_gap_mean", "control"))
        self._gaps = self._f32 = None
        return rows_out
