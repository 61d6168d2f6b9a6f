"""The serving traffic: the seed changes token ids and order only."""
from collections import Counter

from chipbench import generate
from chipbench.run import load_json

TRAFFIC = load_json("traffic", "serve_backlog.json")
SEEDS = (0, 1, 2**31 + 11, 3000000019)


def test_every_seed_offers_the_same_multiset():
    grid = Counter((p, b) for p in TRAFFIC["prompt_lens"]
                   for b in TRAFFIC["budgets"])
    for seed in SEEDS:
        for cycle in (0, 5):
            shapes = generate.serve_cycle_shapes(TRAFFIC, seed, cycle)
            assert Counter(shapes) == grid


def test_every_group_of_8_holds_each_length_and_each_budget_once():
    n = len(TRAFFIC["budgets"])
    for seed in SEEDS:
        shapes = generate.serve_cycle_shapes(TRAFFIC, seed, 0)
        for g in range(0, len(shapes), n):
            group = shapes[g:g + n]
            assert sorted(p for p, _ in group) == sorted(TRAFFIC["prompt_lens"])
            assert sorted(b for _, b in group) == sorted(TRAFFIC["budgets"])


def test_seeds_differ_in_order_and_ids_only():
    orders = {tuple(generate.serve_cycle_shapes(TRAFFIC, s, 0)) for s in SEEDS}
    assert len(orders) == len(SEEDS)
    small = dict(TRAFFIC, cycles=2)
    a = generate.serve_requests(small, 102400, 5)
    b = generate.serve_requests(small, 102400, 5)
    c = generate.serve_requests(small, 102400, 6)
    assert a == b and a != c
    for reqs in (a, c):
        assert len(reqs) == 128
        assert sum(len(p) for _, p, _, _ in reqs) == 2 * 8 * sum(TRAFFIC["prompt_lens"])
        assert sum(b for _, _, b, _ in reqs) == 2 * 8 * sum(TRAFFIC["budgets"])
        assert all(due == 0.0 for _, _, _, due in reqs)
        assert all(0 <= t < 102400 for _, p, _, _ in reqs for t in p)


def test_longest_context_fits_and_budgets_outlast_a_chunk():
    longest = max(TRAFFIC["prompt_lens"]) + max(TRAFFIC["budgets"])
    assert longest <= TRAFFIC["max_len"]
    assert min(TRAFFIC["budgets"]) > TRAFFIC["chunk"]


def test_train_ring_rows_all_differ():
    t = load_json("traffic", "train_seq4k.json")
    ring = generate.train_ring(dict(t, seq=64), 32000, 2**31 + 5)
    rows = [tuple(r) for ids, _ in ring for r in ids]
    assert len(set(rows)) == len(rows) == t["ring"] * t["batch"]
    ids, labels = ring[0]
    assert (ids[:, 1:] == labels[:, :-1]).all()
