"""The control has to come out as not correct: the reference computed in
the nearest precision below the configuration's (float8_e4m3 operands
for bfloat16), put in the program's place, at a size a test run can hold.
The readings at the cells' own sizes, on the chip, are in PERF.md."""
import numpy as np

from chipbench.kinds import serve as serve_kind
from chipbench.kinds import train as train_kind
from chipbench.reference import llama_dense as ref
from chipbench.tests import tiny

SEED = 2**31 + 17


def test_training_control_reads_far_above_the_reference_itself():
    from chipbench import generate
    ring = generate.train_ring(tiny.TRAIN, tiny.CFG["vocab_size"], SEED)
    opt = tiny.TRAIN["optimizer"]
    exact = ref.train_steps(tiny.CFG, SEED, ring, opt, 3)
    again = ref.train_steps(tiny.CFG, SEED, ring, opt, 3)
    low = ref.train_steps(tiny.CFG, SEED, ring, opt, 3, precision="fp8")
    same = {n: v for n, v, _, _ in train_kind.compare(again, exact)}
    ctl = {n: v for n, v, _, _ in train_kind.compare(low, exact)}
    assert max(same.values()) == 0.0
    # float8 operands move the first gradient's norm by parts in a
    # hundred; bfloat16 (the program, tests/test_faults.py) by parts in
    # a thousand
    assert ctl["grad_norm_gap"] > 0.004


def test_half_batch_fault_moves_the_gradient_norm():
    from chipbench import generate
    ring = generate.train_ring(tiny.TRAIN, tiny.CFG["vocab_size"], SEED)
    opt = tiny.TRAIN["optimizer"]
    exact = ref.train_steps(tiny.CFG, SEED, ring, opt, 3)
    half = ref.train_steps(tiny.CFG, SEED, ring, opt, 3, rows=slice(0, 1))
    gaps = {n: v for n, v, _, _ in train_kind.compare(half, exact)}
    assert gaps["grad_norm_gap"] > 0.1


def test_serving_control_token_lies_below_the_reference_best():
    weights = ref.make_weights(tiny.CFG, SEED)
    rng = np.random.default_rng(SEED)
    ids = rng.integers(0, tiny.CFG["vocab_size"], 128).astype(np.int32)
    rows = np.arange(16, 112)
    exact = np.asarray(ref.logits_at(tiny.CFG, weights, ids, rows, "f32"))
    low = np.asarray(ref.logits_at(tiny.CFG, weights, ids, rows, "fp8"))
    own = serve_kind.gap_below_best(exact, exact.argmax(-1))
    ctl = serve_kind.gap_below_best(exact, low.argmax(-1))
    assert own.max() == 0.0
    assert ctl.max() > 0.01
