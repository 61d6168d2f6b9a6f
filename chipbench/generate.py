"""Traffic from a data file and a seed: the one general generator.

A traffic file (`chipbench/traffic/<traffic>.json`) holds parameters only.
Its `kind` names the driver loop (`chipbench/kinds/<kind>.py`); this module
turns the parameters and `--seed` into token ids, orders and arrival times.
The seed never changes the amount of work: every seed gives the same
multiset of sizes in another order.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed, stream):
    """One generator per (seed, stream): seeds run past 2**31, so the
    pair goes in as a sequence rather than a sum."""
    return np.random.default_rng([int(seed), int(stream)])


# -- training ---------------------------------------------------------------

def train_ring(traffic, vocab, seed):
    """`ring` host batches of (ids, labels), each [batch, seq] int64.
    Every row differs; labels are the ids shifted by one."""
    rng = rng_for(seed, 1)
    b, s = traffic["batch"], traffic["seq"]
    ring = []
    for _ in range(traffic["ring"]):
        toks = rng.integers(0, vocab, (b, s + 1), dtype=np.int64)
        ring.append((toks[:, :-1].copy(), toks[:, 1:].copy()))
    return ring


# -- serving ----------------------------------------------------------------

def serve_cycle_shapes(traffic, seed, cycle):
    """One cycle of (prompt_len, budget) pairs: the full grid of
    `prompt_lens` x `budgets`, arranged as a seeded Latin square so that
    each consecutive group of len(budgets) requests holds every prompt
    length once and every budget once.

    The budgets come in the file's order in every group, for every seed:
    which slot retires when, and with it the tokens each loop iteration
    yields, follows from the budgets' order alone, so a seeded order of
    budgets made the window's tokens/s move by 1 % with the seed (PERF.md,
    PR 28). The seed orders the prompt lengths: row, column and symbol
    permutations of the cyclic Latin square, so that every (prompt,
    budget) pair still comes once a cycle, in another order."""
    p, b = traffic["prompt_lens"], traffic["budgets"]
    n = len(p)
    if len(b) != n:
        raise ValueError("the Latin arrangement needs as many budgets as "
                         "prompt lengths")
    rng = rng_for(seed, 1000 + cycle)
    sigma, rho, tau = (rng.permutation(n), rng.permutation(n),
                       rng.permutation(n))
    return [(p[sigma[(rho[g] + tau[i]) % n]], b[i])
            for g in range(n) for i in range(n)]


def arrival_times(traffic, seed, count):
    """Seconds after the serve call's start at which each request is due.
    `backlog`: all at 0. `poisson`: exponential gaps at `rate_rps` (the
    arrival arithmetic of benchmarks/serving_load.py)."""
    arr = traffic.get("arrivals", {"kind": "backlog"})
    if arr["kind"] == "backlog":
        return np.zeros(count)
    if arr["kind"] == "poisson":
        gaps = rng_for(seed, 2).exponential(1.0 / arr["rate_rps"], count)
        return np.cumsum(gaps)
    raise ValueError(f"unknown arrivals kind {arr['kind']!r}")


def serve_requests(traffic, vocab, seed):
    """(rid, prompt token list, budget, arrival_s) quads for `cycles`
    cycles. Token ids are uniform over the vocabulary."""
    shapes = []
    for c in range(traffic["cycles"]):
        shapes.extend(serve_cycle_shapes(traffic, seed, c))
    rng = rng_for(seed, 3)
    due = arrival_times(traffic, seed, len(shapes))
    return [(rid, rng.integers(0, vocab, plen).tolist(), int(budget),
             float(due[rid]))
            for rid, (plen, budget) in enumerate(shapes)]
