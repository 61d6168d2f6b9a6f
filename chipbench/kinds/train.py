"""Driver loop for traffic of kind `train`: one compiled train step with
its state, driven from the seed through its first steps and then handed
to the measured window.

Set-up makes the seeded weights, builds the program's trainer, and runs
`check_steps` steps through the window's own call and feed (`_feed`),
reading after step 1 each leaf's first-moment norm (the gradient as the
optimizer got it: m1 = (1 - beta1) g) and after the last the norm of each
leaf's change. The window then keeps one step in flight: it dispatches
step k + 1 before it waits for step k, and ends by waiting for the last.
The rate is all tokens of all steps finished over all the window's time.
"""
from __future__ import annotations

import statistics
import time

import jax

from chipbench import generate as traffic_mod


def norm_gap(got, ref):
    """Worst leaf's gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger. Returns (gap, leaf)."""
    median = statistics.median(ref.values())
    worst = max(ref, key=lambda k: abs(got[k] - ref[k]) / max(ref[k], median))
    return abs(got[worst] - ref[worst]) / max(ref[worst], median), worst


class Session:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.traffic = ctx.cfg, ctx.traffic
        self.tokens_per_step = self.traffic["batch"] * self.traffic["seq"]

    def _feed(self, k):
        ids, labels = self.ring[k % len(self.ring)]
        return self.trainer(ids, labels)

    def run(self):
        ctx, ref = self.ctx, self.ctx.reference
        weights = ref.make_weights(self.cfg, ctx.seed)
        jax.block_until_ready(weights)
        ctx.mark("weights_made")
        self.trainer = ctx.adapter.Trainer(self.cfg, self.traffic, weights)
        leaves = list(weights)
        del weights
        self.ring = traffic_mod.train_ring(self.traffic,
                                           self.cfg["vocab_size"], ctx.seed)
        beta1 = self.traffic["optimizer"]["beta1"]
        steps = self.traffic["check_steps"]
        self.losses = []
        ctx.mark("trainer_built")
        for k in range(steps):
            self.losses.append(float(self._feed(k)))
            ctx.mark(f"step{k + 1}")
            if k == 0:
                first = ref.norms({leaf: self.trainer.moment1(leaf)
                                   for leaf in leaves})
                self.grad_norm = {leaf: float(v) / (1 - beta1)
                                  for leaf, v in first.items()}
        self.change_norm = ref.change_norms(
            self.cfg, ctx.seed,
            {leaf: self.trainer.param(leaf) for leaf in leaves})
        ctx.mark("norms_read")
        k = steps
        float(self._feed(k))        # one more, so that nothing is new below
        k += 1
        ctx.mark("warmed")

        t0 = ctx.window_open()
        done, pending = 0, None
        while True:
            with ctx.annotate("chipbench.train.dispatch"):
                loss = self._feed(k)
            k += 1
            if pending is not None:
                with ctx.annotate("chipbench.train.wait"):
                    pending.block_until_ready()
                done += 1
            pending = loss
            if time.perf_counter() - t0 >= ctx.seconds:
                break
        with ctx.annotate("chipbench.train.wait"):
            pending.block_until_ready()
        done += 1
        t1 = ctx.window_close()
        last = float(pending)
        self.steps_done = done
        failed = 0 if last == last and abs(last) != float("inf") else done
        return {
            "attempted": done, "failed": failed,
            "end_to_end": {"train_tokens_per_s":
                           done * self.tokens_per_step / (t1 - t0)},
            "observed": {"steps": done, "tokens": done * self.tokens_per_step,
                         "window_s": t1 - t0, "batch": self.traffic["batch"],
                         "seq": self.traffic["seq"]},
        }

    def release(self):
        self.trainer.close()
        self.trainer = None

    def check(self, precision="f32", rows=None):
        """Compare what set-up's steps produced with the reference's
        following of the same steps. `precision` and `rows` put the
        control or the half-batch fault in the reference's place."""
        out = self.ctx.reference.train_steps(
            self.cfg, self.ctx.seed, self.ring, self.traffic["optimizer"],
            self.traffic["check_steps"], precision=precision, rows=rows)
        return compare(
            {"loss": self.losses, "grad_norm": self.grad_norm,
             "change_norm": self.change_norm}, out)


def compare(got, ref):
    """[(name, value, limit key or None, detail)] for one training cell."""
    rows = []
    for t, (a, b) in enumerate(zip(got["loss"], ref["loss"]), 1):
        # limit key None: read and printed, not compared (PERF.md section 2:
        # neither the control nor a fault gives the loss an upper reading)
        rows.append((f"loss_gap_step{t}", abs(a - b) / abs(b), None,
                     f"{a:.6f} vs {b:.6f}"))
    gap, leaf = norm_gap(got["grad_norm"], ref["grad_norm"])
    rows.append(("grad_norm_gap", gap, "grad_norm_gap", leaf))
    # a leaf whose reference gradient is nought to rounding moves under
    # Adam by round-off alone: left out by a rule on the gradient
    median = statistics.median(ref["grad_norm"].values())
    moved = [k for k, g in ref["grad_norm"].items() if g >= 1e-3 * median]
    gap, leaf = norm_gap({k: got["change_norm"][k] for k in moved},
                         {k: ref["change_norm"][k] for k in moved})
    rows.append(("change_norm_gap", gap, "change_norm_gap", leaf))
    return rows
