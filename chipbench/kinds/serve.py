"""Driver loop for traffic of kind `serve`: one `PagedDecoder.serve()` call
over the seeded request list, watched from the `feed` hook.

`serve()` calls `feed` at the top of every loop iteration, after the
tokens of the chunk before have reached the host. The hook (a `Probe`)
is the benchmark's clock and counter there: it keeps every slot object
it has seen (their `emitted` lists are the tokens on the host), opens the
window once every slot has been occupied and the first admissions have
begun to retire and be replaced (so that slots are at staggered phases;
the ramp before it is set-up and warms every prefill bucket and the
decode chunk), closes it
at the first iteration `seconds` later, and ends the call there by
raising: the drain is not counted. Both ends of the window lie on
iteration boundaries, so every token between them is counted and the
rate is those tokens over exactly that time.

Each request carries its scheduled arrival (`arrival_s`); a backlog is
the case where all are 0.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import generate as traffic_mod


class WindowClosed(Exception):
    """Raised from the feed hook to end `serve()` at the window's end."""


class Probe:
    def __init__(self, ctx, dec, budgets):
        self.ctx, self.dec, self.budgets = ctx, dec, budgets
        self.seen = {}            # rid -> slot object (live or retired)
        self.state = "ramp"
        self.start = self.end = None

    def _snapshot(self, now):
        dec = self.dec
        return {"t": now,
                "emitted": {rid: len(s.emitted)
                            for rid, s in self.seen.items()},
                "live": {s.req_id for s in dec._slots if not s.done},
                "h2d_uploads": dec.h2d_uploads,
                "chunks": dec.chunk_dispatches,
                "drains": dec.pipeline_drains}

    def __call__(self):
        now = time.perf_counter()
        slots = self.dec._slots
        for s in slots:
            if not s.done:
                self.seen[s.req_id] = s
        if self.state == "ramp":
            # more requests seen than there are slots: every slot has been
            # occupied and the first to retire has been replaced
            if len(self.seen) > len(slots):
                self.state = "window"
                now = self.ctx.window_open()
                self.start = self._snapshot(now)
        elif self.state == "window":
            if now - self.start["t"] >= self.ctx.seconds:
                now = self.ctx.window_close()
                self.end = self._snapshot(now)
                self.state = "closed"
                raise WindowClosed
        return ()


def gap_below_best(rows, tokens):
    """For logits `rows` [n, V] (float32) and the tokens picked at those
    rows: how far each picked token's logit lies below the row's best,
    as a share of the row's (best - mean)."""
    rows = np.asarray(rows, np.float32)
    best = rows.max(axis=-1)
    got = rows[np.arange(len(tokens)), np.asarray(tokens)]
    return (best - got) / (best - rows.mean(axis=-1))


class Session:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.traffic = ctx.cfg, ctx.traffic

    def run(self):
        ctx, ref = self.ctx, self.ctx.reference
        weights = ref.make_weights(self.cfg, ctx.seed)
        ctx.mark("weights_made")
        self.dec = ctx.adapter.build_decoder(self.cfg, self.traffic, weights)
        del weights
        requests = traffic_mod.serve_requests(
            self.traffic, self.cfg["vocab_size"], ctx.seed)
        self.prompts = {rid: prompt for rid, prompt, _, _ in requests}
        self.budgets = {rid: budget for rid, _, budget, _ in requests}
        probe = self.probe = Probe(ctx, self.dec, self.budgets)
        ctx.mark("decoder_built")
        try:
            self.dec.serve(requests, max_new_tokens=max(self.budgets.values()),
                           eos_token_id=None, chunk=self.traffic["chunk"],
                           feed=probe)
        except WindowClosed:
            pass
        else:
            raise RuntimeError(
                f"the request list ({len(requests)} requests) ran out in "
                f"state {probe.state!r} before the window closed: raise "
                f"`cycles` in the traffic file")
        a, b = probe.start, probe.end
        # finished inside the window: live or not yet seen at its start,
        # retired by its end
        self.finished = [rid for rid in probe.seen
                         if rid not in b["live"]
                         and (rid in a["live"] or rid not in a["emitted"])]
        failed = [rid for rid in self.finished
                  if b["emitted"][rid] != self.budgets[rid]]
        tokens = sum(b["emitted"].values()) - sum(a["emitted"].values())
        window_s = b["t"] - a["t"]
        work = self._work(a, b)
        return {
            "attempted": len(self.finished),
            "failed": len(failed) if self.finished else 1,
            "end_to_end": {"serve_tokens_per_s": tokens / window_s},
            "observed": dict(
                work, tokens=tokens, window_s=window_s,
                finished=len(self.finished),
                h2d_uploads=b["h2d_uploads"] - a["h2d_uploads"],
                chunks=b["chunks"] - a["chunks"],
                drains=b["drains"] - a["drains"],
                pool_blocks=self.traffic["pool_blocks"],
                slots=self.traffic["slots"]),
        }

    def _work(self, a, b):
        """What the window processed, for the FLOP and byte counts:
        prompts prefilled (tokens, causal pairs), decode rows (one per
        token that a decode step emitted) and the cached positions those
        rows attended to. A prompt of P tokens yields token 1 from its
        prefill; token j >= 2 comes from a decode step that attends to
        P + j - 1 positions."""
        prefill_tokens = prefill_pairs = prefills = 0
        decode_rows = decode_context = 0
        for rid, end in b["emitted"].items():
            plen = len(self.prompts[rid])
            begin = a["emitted"].get(rid)
            if begin is None:                 # admitted inside the window
                prefills += 1
                prefill_tokens += plen
                prefill_pairs += plen * (plen + 1) // 2
                begin = 1
            n = end - begin
            decode_rows += n
            # sum over j = begin + 1 .. end of (plen + j - 1)
            decode_context += n * plen + (begin + end - 1) * n // 2
        return {"prefills": prefills, "prefill_tokens": prefill_tokens,
                "prefill_pairs": prefill_pairs, "decode_rows": decode_rows,
                "decode_context": decode_context}

    def release(self):
        self.served = {rid: list(s.emitted)
                       for rid, s in self.probe.seen.items()}
        self.dec = self.probe.dec = None
        self.probe.seen = {}
        gc.collect()

    def sample(self):
        """The requests whose tokens are compared: the longest that
        finished in the window and `check_requests - 1` more drawn from
        the seed."""
        if not self.finished:
            return []
        total = lambda rid: len(self.prompts[rid]) + self.budgets[rid]
        ordered = sorted(self.finished)
        longest = max(ordered, key=total)
        rest = [r for r in ordered if r != longest]
        rng = traffic_mod.rng_for(self.ctx.seed, 4)
        extra = min(self.traffic["check_requests"] - 1, len(rest))
        picked = [rest[i] for i in rng.choice(len(rest), extra, replace=False)]
        return [longest] + picked

    def reference_rows(self, rid, precision):
        """Reference logits at the positions that produced request
        `rid`'s served tokens: one full causal forward over its prompt
        and served tokens, padded behind to `max_len`."""
        import jax.numpy as jnp
        prompt, out = self.prompts[rid], self.served[rid]
        ids = np.zeros(self.traffic["max_len"], np.int32)
        ids[:len(prompt) + len(out)] = prompt + out
        rows = len(prompt) - 1 + np.arange(max(self.budgets.values()))
        logits = self.ctx.reference.logits_at(
            self.cfg, self.weights, jnp.asarray(ids),
            jnp.asarray(np.minimum(rows, len(ids) - 1)), precision)
        return np.asarray(logits[:len(out)], np.float32)

    def check(self, control=None):
        """The widest gap by which a served token's logit lies below the
        reference's best, over the sample. With `control` (a precision),
        also the gap of the token that precision puts first at each of
        the same positions: the control's reading."""
        self.weights = self.ctx.reference.make_weights(self.cfg,
                                                       self.ctx.seed)
        widest, where, ctl_widest, n_tokens = -1.0, None, 0.0, 0
        for rid in self.sample():
            out = self.served[rid]
            rows = self.reference_rows(rid, "f32")
            if not np.isfinite(rows).all():
                widest, where = float("inf"), f"request {rid}: not finite"
                break
            gaps = gap_below_best(rows, out)
            n_tokens += len(out)
            if gaps.max() > widest:
                widest = float(gaps.max())
                where = (f"request {rid} (prompt {len(self.prompts[rid])}, "
                         f"budget {self.budgets[rid]}) token "
                         f"{int(gaps.argmax())}")
            if control:
                low = self.reference_rows(rid, control)
                ctl_widest = max(ctl_widest, float(gap_below_best(
                    rows, low.argmax(axis=-1)).max()))
        self.weights = None
        rows_out = [("logit_gap", widest if where else float("inf"),   # no sample
                     "logit_gap", f"{where}; {n_tokens} tokens compared")]
        if control:
            rows_out.append((f"logit_gap_{control}", ctl_widest, "logit_gap",
                             "control"))
        return rows_out
