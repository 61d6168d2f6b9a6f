"""The `lfm2_moe` family (gated short convolutions + GQA with QK-norm +
sparse SwiGLU experts) against its plain reference, on the CPU at toy
widths with seeded weights, float32: logits whole and for each of the
four shares, the four shares of one expert layer, loss and every leaf's
gradient, two `TrainStep` steps with AdamW, what each mechanism is, the
backward of the sorted grouped product, the step's counters, and the
serving programs of the family whose routing it shares.

The reference (`chipbench/reference/lfm2.py`) is float32 `highest`, one
sequence and one block at a time, each expert a dense masked product, and
imports nothing of the program.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from chipbench.adapters import lfm2 as adapter
from chipbench.reference import lfm2 as ref
from paddle_tpu.kernels.pallas.grouped_matmul import (_sorted_reference,
                                                      grouped_matmul_sorted)
from paddle_tpu.models import lfm2

F32 = jnp.float32
WHOLE = dict(vocab_size=96, hidden_size=32, intermediate_size=48,
             num_hidden_layers=4,
             layer_types=["conv", "full_attention", "conv",
                          "full_attention"],
             num_dense_layers=1, num_attention_heads=4,
             num_key_value_heads=2, conv_L_cache=3, conv_bias=False,
             num_experts=8, experts_first=0, num_experts_per_tok=2,
             moe_intermediate_size=24, norm_topk_prob=True,
             routed_scaling_factor=1.0, use_expert_bias=True, norm_eps=1e-5,
             rope_theta=1000000.0, max_position_embeddings=64,
             initializer_range=0.2, torch_dtype="float32")
SEED = 2**31 + 35
TOL = 2e-5          # float32 against float32 `highest`, sums reordered
SPARSE = 2          # a sparse layer with a convolution operator
STACKS = ("w1", "w3", "w2")
OPT = dict(name="adamw", learning_rate=1e-3, beta1=0.9, beta2=0.999,
           epsilon=1e-8, weight_decay=0.01, moment_dtype=None)


def share_cfg(first, count=2):
    return dict(WHOLE, num_experts=count, experts_first=first,
                published={"num_experts": 8})


def share_weights(weights, first, count=2):
    return {k: (v[first:first + count] if k.rsplit(".", 1)[-1] in STACKS
                else v) for k, v in weights.items()}


@pytest.fixture(scope="module")
def weights():
    """The reference's seeded leaves, bfloat16 values held in float32 so
    that program and reference compute on the same numbers."""
    return {k: v.astype(F32) for k, v in ref.make_weights(WHOLE, SEED).items()}


@pytest.fixture(scope="module")
def model(weights):
    return lfm2.Lfm2ForCausalLM(adapter.program_config(WHOLE),
                                arrays=weights)


def _copies(arrays):
    """A `TrainStep` donates its parameters: it gets its own."""
    return {k: jnp.array(v, copy=True) for k, v in arrays.items()}


def _batch(rows=2, seq=16, seed=0):
    toks = np.random.default_rng(seed).integers(
        0, WHOLE["vocab_size"], (rows, seq + 1))
    return toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32)


def _hidden(n, seed=1):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=(n, WHOLE["hidden_size"])), F32)


def _close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max())


# -- logits, whole and by share ------------------------------------------------------

@pytest.mark.parametrize("first", [None, 0, 2, 4, 6])
def test_logits_match_the_reference(weights, first):
    cfg, w = (WHOLE, weights) if first is None else \
        (share_cfg(first), share_weights(weights, first))
    model = lfm2.Lfm2ForCausalLM(adapter.program_config(cfg), arrays=w)
    ids, _ = _batch()
    got = model(pt.to_tensor(ids))._data
    assert got.dtype == F32
    for r in range(ids.shape[0]):
        _close(got[r], ref.logits(cfg, w, ids[r]), 1e-4)


def test_the_four_shares_add_up_to_the_uncut_layer(model, weights):
    """No shared expert to count once: the shares' expert outputs sum to
    the uncut layer, and their router gradients to the uncut gradient."""
    v = _hidden(24)
    rp = ref.layer_params(WHOLE, weights, SPARSE)
    cot = _hidden(24, seed=2)

    def whole(router):
        return ref.moe(WHOLE, dict(rp, router=router), v, "f32")
    want, pull = jax.vjp(whole, rp["router"])
    want_grad, = pull(cot)
    got, got_grad = 0.0, 0.0
    for first in (0, 2, 4, 6):
        cfg = adapter.program_config(share_cfg(first))
        p = dict(model.param_tree()["layers"][SPARSE])
        p.update({k: p[k][first:first + 2] for k in STACKS})

        def part(router):
            out, counts = lfm2.sparse_moe(cfg, dict(p, router=router),
                                          v[None])
            return out[0], counts
        out, pull, counts = jax.vjp(part, p["router"], has_aux=True)
        _close(out, ref.moe(share_cfg(first), {
            **rp, **{k: rp[k][first:first + 2] for k in STACKS}}, v, "f32"))
        got, got_grad = got + out, got_grad + pull(cot)[0]
        assert int(counts[1]) == 48
    _close(got, want)
    _close(got_grad, want_grad)


# -- loss, gradients, two optimizer steps --------------------------------------------

def _program_loss(cfg, tree, ids, labels):
    lg, _ = lfm2.forward(cfg, tree, jnp.asarray(ids))
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, jnp.asarray(labels)[..., None], -1)
    return jnp.mean(lse - picked[..., 0])


def test_loss_and_every_gradient_match_the_reference(model, weights):
    ids, labels = _batch()
    want_loss, want = ref.loss_and_grads(WHOLE, weights, ids, labels)
    cfg, tree = model.config, model.param_tree()
    got_loss, got = jax.value_and_grad(
        lambda t: _program_loss(cfg, t, ids, labels))(tree)
    assert abs(float(got_loss) - want_loss) <= 1e-5 * want_loss
    for name, g in want.items():
        if name.startswith("layers."):
            _, i, leaf = name.split(".")
            mine = got["layers"][int(i)][leaf]
        else:
            mine = got[name]
        if ref.frozen(name):
            assert not np.asarray(mine).any()     # the choice has no slope
        else:
            _close(mine, g, 1e-4)


def test_two_train_steps_match_the_reference(weights):
    model = lfm2.Lfm2ForCausalLM(adapter.program_config(WHOLE),
                                 arrays=_copies(weights))
    crit = lfm2.Lfm2PretrainingCriterion()
    opt = pt.optimizer.AdamW(
        learning_rate=OPT["learning_rate"], beta1=OPT["beta1"],
        beta2=OPT["beta2"], epsilon=OPT["epsilon"],
        weight_decay=OPT["weight_decay"], parameters=model.parameters())
    step = pt.jit.TrainStep(model, lambda lg, lb: crit(lg, lb), opt)
    batches = [_batch(seed=s) for s in (3, 4)]
    losses = [float(step((pt.to_tensor(i, dtype="int64"),),
                         (pt.to_tensor(l, dtype="int64"),)))
              for i, l in batches]

    # the reference's two steps on the same float32 values
    params = dict(weights)
    m = {k: jnp.zeros_like(a) for k, a in params.items()}
    v = {k: jnp.zeros_like(a) for k, a in params.items()}
    hyper = [OPT[k] for k in ("learning_rate", "beta1", "beta2", "epsilon",
                              "weight_decay")]
    for t, (ids, labels) in enumerate(batches, 1):
        loss, grads = ref.loss_and_grads(WHOLE, params, ids, labels)
        assert abs(losses[t - 1] - loss) <= 2e-5 * loss
        for name, g in grads.items():
            if not ref.frozen(name):
                params[name], m[name], v[name] = ref._adamw(
                    params[name], g, m[name], v[name], float(t), *hyper)
    trained = {id(p) for p in model.parameters()}
    assert {pid for (_, pid) in opt._accumulators} == trained
    for name in params:
        got = model.array(name)
        if ref.frozen(name):
            assert id(getattr(model, model._names[name])) not in trained
            np.testing.assert_array_equal(got, weights[name])
        else:
            # two steps move a weight by about 2e-3: the tolerance is a
            # hundredth of that
            _close(got, params[name], 2e-5)
            assert np.abs(np.asarray(got - weights[name])).max() > 1e-4


# -- what each mechanism is ----------------------------------------------------------

def _conv_out(model, u):
    cfg, p = model.config, model.param_tree()["layers"][0]
    return lfm2.short_conv(cfg, p, u[None])[0]


def test_the_convolution_is_causal_and_sees_three_taps(model, weights):
    u = _hidden(20)
    base = _conv_out(model, u)
    _close(base, ref.short_conv(WHOLE, ref.layer_params(WHOLE, weights, 0),
                                u, "f32"))
    moved = np.asarray(_conv_out(model, u.at[9].add(1.0)) - base)
    changed = np.flatnonzero(np.abs(moved).max(axis=1) > 0)
    assert changed.tolist() == [9, 10, 11]       # nothing before, 3 taps


def test_q_and_k_are_normalised_before_the_rotary_term(model, weights):
    cfg, p = model.config, model.param_tree()["layers"][1]
    rp = ref.layer_params(WHOLE, weights, 1)
    # weights off 1, or a norm that is left out would go unseen
    for k in ("q_norm", "k_norm"):
        p = dict(p, **{k: p[k] * jnp.linspace(0.5, 1.5, p[k].shape[0])})
        rp = dict(rp, **{k: p[k]})
    u = _hidden(20)
    got = lfm2.attention(cfg, p, u[None])[0]
    _close(got, ref.attention(WHOLE, rp, u, "f32"))
    without = ref.attention(WHOLE, rp, u, "f32", qk_norm=False)
    assert np.abs(np.asarray(got - without)).max() > 1e-2

    # after the rotary term the per-head weight would meet other pairs
    def norm_after(x, w):
        t, nh = x.shape[0], x.shape[1]
        return ref.rms_norm(ref.rope(x, jnp.arange(t), 1e6), w, 1e-5)
    t, nh, nkv, hd = 20, 4, 2, 8
    q = (u @ rp["wq"]).reshape(t, nh, hd)
    k = (u @ rp["wk"]).reshape(t, nkv, hd)
    v = (u @ rp["wv"]).reshape(t, nkv, hd)
    after = ref.causal_attention(norm_after(q, rp["q_norm"]),
                                 norm_after(k, rp["k_norm"]), v) @ rp["wo"]
    assert np.abs(np.asarray(got - after)).max() > 1e-3


def test_leading_dense_layers_are_dense(model, weights):
    cfg = model.config
    shapes = cfg.param_shapes()
    assert "layers.0.wg" in shapes and "layers.0.router" not in shapes
    assert "layers.1.router" in shapes and "layers.1.wg" not in shapes
    assert [cfg.is_sparse(i) for i in range(4)] == [False, True, True, True]
    v = _hidden(12)
    p = model.param_tree()["layers"][0]
    _close(lfm2.swiglu(p, v), ref.swiglu(ref.layer_params(WHOLE, weights, 0),
                                         v, "f32"))


def test_the_bias_moves_the_choice_and_not_the_weights(model):
    cfg, p = model.config, model.param_tree()["layers"][SPARSE]
    v = _hidden(16)
    idx, w = lfm2.moe_route(cfg, p, v)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-5)
    pushed = dict(p, b_corr=p["b_corr"].at[5].add(10.0))
    idx2, w2 = lfm2.moe_route(cfg, pushed, v)
    assert (np.asarray(idx2) == 5).any(axis=1).all()
    s = jax.nn.sigmoid(v @ p["router"])
    picked = jnp.take_along_axis(s, idx2, axis=1)
    _close(w2, picked / (picked.sum(-1, keepdims=True) + 1e-6))


# -- the step's counters -------------------------------------------------------------

def test_train_step_call_carries_the_counters(weights):
    from paddle_tpu.observability import tracing
    cfg = share_cfg(2, 4)
    model = lfm2.Lfm2ForCausalLM(adapter.program_config(cfg),
                                 arrays=_copies(share_weights(weights, 2, 4)))
    crit = lfm2.Lfm2PretrainingCriterion()
    opt = pt.optimizer.SGD(learning_rate=0.0, parameters=model.parameters())
    step = pt.jit.TrainStep(model, lambda lg, lb: crit(lg, lb), opt)
    ids, labels = _batch()
    _, want = lfm2.forward(model.config, model.param_tree(),
                           jnp.asarray(ids))
    tracing.enable_tracing()
    try:
        for _ in range(3):
            float(step((pt.to_tensor(ids, dtype="int64"),),
                       (pt.to_tensor(labels, dtype="int64"),)))
        calls = [r["meta"] for r in tracing.tail()
                 if r["name"] == "train_step:call"][-3:]
    finally:
        tracing.disable_tracing()
    assert "moe_pairs_here" not in calls[0]       # nothing finished yet
    for k, meta in enumerate(calls[1:]):
        assert meta["counters_step"] == meta["step"] - 1
        got = [meta[f] for f in lfm2.COUNTERS]
        assert got == np.asarray(want).tolist()
    pairs_all = 2 * 16 * 2 * 3                    # tokens x top-2 x layers
    assert got[1] == pairs_all and 0 < got[0] < pairs_all
    assert 1 <= got[2] <= 12 and got[3] * got[2] >= got[0] / 3


# -- the sorted grouped product's backward -------------------------------------------

@pytest.mark.parametrize("impl", ["kernel", "reference"])
@pytest.mark.parametrize("sizes", [
    [100, 0, 50, 130],      # sums to less than the rows; an empty group
    [0, 0, 384, 0],         # one group holds everything
    [1, 2, 3, 4],
])
def test_grouped_matmul_sorted_backward(impl, sizes):
    rng = np.random.default_rng(0)
    m, k, n, e = 384, 256, 128, 4
    x = jnp.asarray(rng.normal(size=(m, k)), F32)
    w = jnp.asarray(rng.normal(size=(e, k, n)), F32)
    cot = jnp.asarray(rng.normal(size=(m, n)), F32)
    s = jnp.asarray(sizes, jnp.int32)
    inside = (jnp.arange(m) < sum(sizes))[:, None]

    def loss(fn):
        return lambda x, w: jnp.sum(jnp.where(inside, fn(x, w, s), 0.0) * cot)
    want = jax.grad(loss(_sorted_reference), argnums=(0, 1))(x, w)
    got = jax.grad(loss(lambda x, w, s: grouped_matmul_sorted(
        x, w, s, impl=impl)), argnums=(0, 1))(x, w)
    for a, b in zip(got, want):
        _close(a, b, 1e-5)
    dx, dw = got
    assert not np.asarray(dx[sum(sizes):]).any()  # rows past the groups
    for g, size in enumerate(sizes):
        if size == 0:
            assert not np.asarray(dw[g]).any()


# -- the family whose routing it shares serves what it served ------------------------

def test_nemotron_h_serve_programs_are_unchanged():
    """`moe_route` and the sort are shared with `nemotron_h`; its serve
    programs have to lower to the text they lowered to before this family
    came (sha256 of the CPU lowering at the parent commit; a PR that
    means to change those programs replaces the two digests: PR 36 made
    the prefill the packed program and replaced its digest; PR 38 put
    the state-update kernel into the chunk and replaced the chunk's; PR
    41 sized the expert layer's sorted buffer by the pairs held here and
    replaced both)."""
    from paddle_tpu.models.nemotron_h import (NemotronHForCausalLM,
                                              nemotron_h_tiny)
    from paddle_tpu.models.paged_decode import PagedDecoder
    dec = PagedDecoder(NemotronHForCausalLM(nemotron_h_tiny()), max_len=64,
                       block_size=8, num_blocks=33, max_slots=4)
    pools = dec.new_pools()
    s, mb = dec.max_slots, dec.blocks_per_seq
    i32, flag = jnp.int32, jnp.bool_
    chunk = dec._paged_chunk_state_jit.lower(
        dec._params, jnp.zeros((s,), i32), jnp.zeros((s,), i32),
        jnp.zeros((s, mb), i32), jnp.zeros((s,), flag),
        jnp.zeros((s,), i32), jnp.zeros((s,), flag), *pools, 2, -1).as_text()
    head, tail = dec._prefill_inputs(16, [], (), 0)
    prefill = dec._prefill_exec(16).lower(
        dec._params, *head, *pools, *tail).as_text()
    digest = {name: hashlib.sha256(text.encode()).hexdigest()
              for name, text in (("chunk", chunk), ("prefill", prefill))}
    assert digest == {
        "chunk": "4e632dec47f75144f038bbcf0103e55c0992aa2ace0ccd7dbae785ad515ee976",
        "prefill": "033483e6dc0cc406a84f6295debe9163209a6db0cd4c2b73f2156ccfd542c2a4"}


def test_rows_the_kernel_never_wrote_do_not_reach_a_gradient(
        model, monkeypatch):
    """The grouped kernel leaves the rows past the held pairs unwritten
    (NaN when interpreted, whatever the memory held on the chip): the
    layer has to select them away before anything multiplies them, in
    the backward pass too, where 0 x NaN would reach the router."""
    from paddle_tpu.kernels.pallas import grouped_matmul as gm
    cfg = adapter.program_config(share_cfg(2))
    p = dict(model.param_tree()["layers"][SPARSE])
    p.update({k: p[k][2:4] for k in STACKS})
    v = _hidden(24)[None]
    names = ("router", "w1", "w3", "w2")

    def grads():
        def loss(v, *leaves):
            out, _ = lfm2.sparse_moe(cfg, dict(p, **dict(zip(names, leaves))),
                                     v)
            return jnp.sum(out * out)
        return jax.grad(loss, argnums=range(5))(v, *(p[n] for n in names))
    want = grads()
    monkeypatch.setattr(gm, "grouped_matmul_sorted", lambda *a, **kw:
                        grouped_matmul_sorted(*a, **dict(kw, impl="kernel")))
    for got, ref_grad in zip(grads(), want):
        assert np.isfinite(np.asarray(got)).all()
        _close(got, ref_grad, 1e-4)
