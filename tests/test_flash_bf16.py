"""The flash kernels' operand types (interpret mode on the CPU): bf16 inputs
go to every product as bf16 with float32 accumulation, float32 inputs as
float32, and either way the result is the plain softmax attention's."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import paddle_tpu  # noqa: F401  (the process-wide x64 and matmul precision)
from paddle_tpu.kernels.pallas import flash_attention as fa

F32, BF16 = jnp.float32, jnp.bfloat16

# Against a float32 reference on the same values, a float32 kernel differs by
# accumulation order alone. A bf16 one rounds to 8 mantissa bits (at most
# 2**-9 of the value) in three places on the way to any result: `p` or `ds`
# before its product, `o` inside `delta = sum(do * o)`, and the result as it
# is stored. Each is bounded by 2**-9 of a sum of magnitudes that cancellation
# can leave up to about twice the largest result, so 3 x 2 x 2**-9 of the
# reference's largest element.
TOL = {jnp.dtype(F32): 1e-5, jnp.dtype(BF16): 6 * 2.0 ** -9}


def _ref(q, k, v, causal, scale):
    """Plain attention on [bh, s, d] in float32, whatever the storage."""
    q, k, v = (x.astype(F32) for x in (q, k, v))
    st = jnp.einsum("bsd,btd->bst", q, k) * scale
    if causal:
        st = jnp.where(jnp.tril(jnp.ones(st.shape[-2:], bool)), st, -jnp.inf)
    return jnp.einsum("bst,btd->bsd", jax.nn.softmax(st, axis=-1), v)


def _inputs(dtype, bh=2, s=256, d=64, n=4, seed=0):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal((bh, s, d)), dtype)
            for _ in range(n)]


def _close(got, ref, dtype):
    got, ref = np.asarray(got.astype(F32)), np.asarray(ref.astype(F32))
    tol = TOL[jnp.dtype(dtype)]
    assert np.abs(got - ref).max() <= tol * np.abs(ref).max()


def _grads_close(q, k, v, w, causal, scale):
    """dq, dk, dv of sum(o * w) against the reference's; w, the cotangent,
    is the stored values in float32 on both sides."""
    w = w.astype(F32)
    got = jax.grad(lambda *a: (fa._flash_bhsd(*a, causal, scale)
                               .astype(F32) * w).sum(), (0, 1, 2))(q, k, v)
    ref = jax.grad(lambda *a: (_ref(*a, causal, scale) * w).sum(),
                   (0, 1, 2))(q, k, v)
    for g, r in zip(got, ref):
        assert g.dtype == q.dtype
        _close(g, r, q.dtype)


@pytest.fixture()
def streaming(monkeypatch):
    """256 tokens take the streaming kernels, four blocks of 128 x 128."""
    monkeypatch.setattr(fa, "_RESIDENT_LIMIT", 128 * 64)
    monkeypatch.setitem(fa._BLOCK_OVERRIDE, "flash", (128, 128))


@pytest.fixture()
def resident(monkeypatch):
    """Blocks of unlike sizes, so that the block pairs the diagonal crosses
    are not simply those with i == j."""
    monkeypatch.setitem(fa._BLOCK_OVERRIDE, "flash", (64, 128))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("variant", ["resident", "streaming"])
def test_forward_and_grad_match_reference(variant, dtype, causal, request):
    request.getfixturevalue(variant)
    q, k, v, w = _inputs(dtype)
    assert fa._use_streaming(*q.shape[1:]) == (variant == "streaming")
    scale = q.shape[-1] ** -0.5
    o = fa._flash_bhsd(q, k, v, causal, scale)
    assert o.dtype == dtype
    _close(o, _ref(q, k, v, causal, scale), dtype)
    _grads_close(q, k, v, w, causal, scale)


def test_default_blocks_match_reference_bf16():
    """Nothing overridden: 512 tokens as one 512 x 512 block pair."""
    _grads_close(*_inputs(BF16, s=512, seed=1), True, 0.125)


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
def test_ring_backward_blocks_match_reference(dtype):
    """The ring's backward: `_mha_bwd` once a kv block, each with the merged
    lse of the whole row; dq summed over blocks, dk and dv block by block.
    A rank's q block is as long as each kv block that visits it."""
    q, k, v, do = _inputs(dtype, s=256)
    q, do = q[:, :128], do[:, :128]
    scale = q.shape[-1] ** -0.5
    st = jnp.einsum("bsd,btd->bst", q.astype(F32), k.astype(F32)) * scale
    lse = jax.nn.logsumexp(st, axis=-1)
    o, vjp = jax.vjp(lambda *a: _ref(*a, False, scale), q, k, v)
    ref_dq, ref_dk, ref_dv = vjp(do.astype(F32))

    dq = jnp.zeros(q.shape, F32)
    dks, dvs = [], []
    for blk in (slice(0, 128), slice(128, 256)):
        dq_b, dk_b, dv_b = fa._mha_bwd(q, k[:, blk], v[:, blk],
                                       o.astype(dtype), lse, do, False, scale)
        assert dq_b.dtype == dk_b.dtype == dv_b.dtype == dtype
        dq = dq + dq_b.astype(F32)
        dks.append(dk_b)
        dvs.append(dv_b)
    _close(dq, ref_dq, dtype)
    _close(jnp.concatenate(dks, axis=1), ref_dk, dtype)
    _close(jnp.concatenate(dvs, axis=1), ref_dv, dtype)


# -- what the kernels hand to the MXU ------------------------------------------

def _sub_jaxprs(params):
    for val in params.values():
        for item in val if isinstance(val, (list, tuple)) else (val,):
            if hasattr(item, "eqns"):
                yield item
            elif hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                yield item.jaxpr


def _eqns(jaxpr, name):
    """Every equation of primitive `name`, however deeply nested."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            yield eqn
        else:
            for sub in _sub_jaxprs(eqn.params):
                yield from _eqns(sub, name)


def _kernel_products(dtype, causal):
    """[[(lhs dtype, rhs dtype, out dtype, precision)] a kernel] for the
    forward, dQ and dK/dV kernels of one forward and backward, as the
    kernels' jaxprs have them."""
    q, k, v, do = _inputs(dtype)

    def fwd_bwd(q, k, v, do):
        o, lse = fa._mha_fwd(q, k, v, causal, 0.125)
        return fa._mha_bwd(q, k, v, o, lse, do, causal, 0.125)

    kernels = list(_eqns(jax.make_jaxpr(fwd_bwd)(q, k, v, do).jaxpr,
                         "pallas_call"))
    assert len(kernels) == 3
    return [[(*(x.aval.dtype for x in dot.invars), dot.outvars[0].aval.dtype,
              dot.params["precision"])
             for dot in _eqns(kernel.params["jaxpr"], "dot_general")]
            for kernel in kernels]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype,precision", [(BF16, lax.Precision.DEFAULT),
                                             (F32, lax.Precision.HIGHEST)],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("variant", ["resident", "streaming"])
def test_products_take_operands_as_stored(variant, dtype, precision, causal,
                                          request):
    """A float32 operand is six MXU passes where one would do, and only a
    chip run shows the time, so the CPU run reads the types: bf16 inputs
    reach every product as bf16, naming the one-pass precision themselves
    (Mosaic refuses the process-wide `highest` on them); float32 inputs
    keep float32 products under `highest`, as before."""
    request.getfixturevalue(variant)
    for products, least in zip(_kernel_products(dtype, causal), (2, 3, 4)):
        assert len(products) >= least
        for product in products:
            assert product == (dtype, dtype, F32, (precision, precision))
