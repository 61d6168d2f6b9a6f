"""The loop for traffic of kind `serve_mtp`: kind `serve_long`'s loop
and comparison, with the model drafting its own next token on the device
(`PagedDecoder.serve(spec_decode="mtp")`: every step of a decode chunk is
one verify pass, which yields one or two tokens a slot), and both rows
of its passes compared as well as the served tokens.

Greedy verification is exact, so the served tokens do not notice a
cheaper or wrong draft layer: only the speed would. And where the drafts
are rejected, as nearly all are under seeded weights, every served token
comes from a pass's first row, so the served tokens do not see its
second row either. So beside the served tokens' `logit_gap` and
`logit_gap_mean`, two more pairs of numbers, of the sampled requests:

- the drafts the program made (each pass's, and the first from the
  prefill) against the reference's MTP layer at the same rows, which
  takes the same inputs as the program's did (the main model's normed
  last hidden state and the embedding of the token served after it):
  `draft_gap`, the widest over the sample of the reference MTP's best
  logit less its logit at the drafted token, as a share of the row's
  (best - mean), and `draft_gap_mean`, the mean of the same gaps;
- each pass's second row, the target's token after the draft it
  verified, accepted or not, against the reference's logits at the
  draft's position with the draft in that place
  (`reference.replaced_logits_at`): `verify_gap` and `verify_gap_mean`,
  the widest and the mean gap of those tokens.

With a control, the gaps of the tokens the control's rows put first come
beside them (`<name>_<control>`).

The window's verify passes, drafts proposed and drafts accepted (from
`dec.spec_stats` at the window's two ends) ride in `observed`.
"""
from __future__ import annotations

import functools
from unittest import mock

import numpy as np

from chipbench.kinds import serve, serve_long
from chipbench.kinds.serve import gap_below_best

SPEC = ("verify_calls", "proposed", "accepted")
# the compared pairs beside the served tokens': (widest, mean)
PAIRS = (("draft_gap", "draft_gap_mean"), ("verify_gap", "verify_gap_mean"))


class Probe(serve.Probe):
    """`serve.Probe` whose snapshots hold the speculative tallies."""

    def _snapshot(self, now):
        out = super()._snapshot(now)
        out["spec"] = {k: self.dec.spec_stats[k] for k in SPEC}
        return out


class Session(serve_long.Session):
    def __init__(self, ctx):
        super().__init__(ctx)
        if self.traffic["draft_tokens"] != 1:
            raise ValueError(
                f"draft_tokens {self.traffic['draft_tokens']}: the engine's "
                f"one MTP layer drafts one token a pass")

    def run(self):
        """`serve_long`'s run with `spec_decode="mtp"` passed to
        `serve()`."""
        build = self.ctx.adapter.build_decoder

        def drafting(cfg, traffic, weights):
            dec = build(cfg, traffic, weights)
            dec.serve = functools.partial(dec.serve, spec_decode="mtp")
            return dec
        with mock.patch.object(self.ctx.adapter, "build_decoder", drafting), \
                mock.patch.object(serve, "Probe", Probe):
            out = super().run()
        a, b = self.probe.start["spec"], self.probe.end["spec"]
        out["observed"].update(
            verify_passes=b["verify_calls"] - a["verify_calls"],
            drafted=b["proposed"] - a["proposed"],
            accepted=b["accepted"] - a["accepted"])
        return out

    def release(self):
        """`serve`'s, then a wait for the device. The window closes while
        a look-ahead chunk still runs, and the pool and weights it holds
        (13.5 GB in the cell) are freed only when it ends: the
        reference's weights (9.1 GB) must not be made beside them. The
        device runs programs in the order they were launched, so one
        more, waited for, ends after it."""
        import jax
        seen = self.probe.seen.items()
        self.drafted = {rid: list(s.drafts) for rid, s in seen}
        self.verified = {rid: list(s.verified) for rid, s in seen}
        super().release()
        jax.jit(lambda x: x + 1)(np.int32(0)).block_until_ready()

    def reference_rows(self, rid, precision):
        """`serve_long`'s, and beside them the reference's rows where the
        program drafted and verified for request `rid`: the MTP's rows of
        its drafts (a draft of the token at index q of prompt + served
        comes from MTP row q - 2) and the main model's rows of its
        passes' second rows (row q with the draft of index q in its
        place). The gaps of the program's tokens under the float32 rows,
        and of the tokens the control's rows put first."""
        rows = super().reference_rows(rid, precision)
        prompt, out = self.prompts[rid], self.served[rid]
        seq = len(prompt) + len(out)
        ids = np.zeros(self.traffic["max_len"], np.int32)
        ids[:seq] = prompt + out
        ref = self.ctx.reference
        made = [(q, tok) for q, tok in self.drafted[rid] if q <= seq]
        draft_of = dict(self.drafted[rid])
        passes = [(q, tok) for q, tok in self.verified[rid] if q < seq]
        self._compare("draft_gap", precision, [tok for _, tok in made],
                      lambda: ref.draft_logits_at(
                          self.cfg, self.weights, ids,
                          np.asarray([q - 2 for q, _ in made], np.int32),
                          precision))
        self._compare("verify_gap", precision, [tok for _, tok in passes],
                      lambda: ref.replaced_logits_at(
                          self.cfg, self.weights, ids,
                          [q for q, _ in passes],
                          [draft_of[q] for q, _ in passes], precision))
        return rows

    def _compare(self, name, precision, tokens, logits):
        """The gaps of `name`'s tokens under the float32 rows that
        `logits()` gives (kept for the control), or of the tokens the
        control's rows put first."""
        if not tokens:
            return
        got = np.asarray(logits(), np.float32)
        if precision == "f32":
            self._exact[name] = got
            gaps = gap_below_best(got, tokens)
        else:
            gaps = gap_below_best(self._exact[name], got.argmax(axis=-1))
        self._found.setdefault(name, {}).setdefault(precision, []) \
            .append(gaps)

    def check(self, control=None):
        """`serve_long`'s rows, then the widest and the mean gap of the
        drafts and of the passes' second rows and, with `control`, of
        the control's tokens."""
        self._found, self._exact = {}, {}
        rows_out = super().check(control)
        sound = rows_out[0][1] != float("inf")
        for widest, mean in PAIRS:
            found = {p: np.concatenate(g)
                     for p, g in self._found.get(widest, {}).items()}
            ok = sound and "f32" in found and found["f32"].size > 0
            for name, reduce in ((widest, np.max), (mean, np.mean)):
                own = float(reduce(found["f32"])) if ok else float("inf")
                rows_out.append((name, own, name,
                                 f"{len(found.get('f32', ()))} rows"))
                if control:
                    low = found.get(control)
                    rows_out.append((f"{name}_{control}",
                                     float(reduce(low)) if low is not None
                                     else float("inf"), name, "control"))
        self._found = self._exact = None
        return rows_out
