"""Plain reference for dense Llama-style decoders (Mistral-7B, DeepSeek-LLM-7B).

Straightforward `jax.numpy` in float32 at `highest` matmul precision: RMSNorm,
rotary embedding (rotate-half), grouped causal attention by plain softmax,
SwiGLU, a shifted-label mean cross-entropy, and AdamW with decoupled decay.
No kernels, no cache, no batching. It imports nothing of the program and
takes nothing the program made: weights come from `make_weights(cfg, seed)`
here, which the harness also hands to the program.

Departures from the published models, each stated: weights are seeded
normal(0, initializer_range) in bfloat16 (storage type the configuration
states); parameters, gradients and Adam moments are rounded to bfloat16
where the configuration stores them so (`moment_dtype`), all arithmetic
between is float32; Mistral's sliding window is not applied (sequences do
not exceed it, see the configuration's `assumed`).

`precision` selects the arithmetic of the weight matmuls: "f32" is the
reference; "fp8" (operands rounded to float8_e4m3 with a per-tensor scale)
is the control that a `correct` comparison has to refuse.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
BF16 = jnp.bfloat16
LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "wg", "wu", "wd")


def head_dim(cfg):
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def leaf_shapes(cfg):
    """Ordered {leaf name: shape}. Matrices are [in, out]."""
    h, hd = cfg["hidden_size"], head_dim(cfg)
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, v = cfg["intermediate_size"], cfg["vocab_size"]
    shapes = {"embed": (v, h)}
    for i in range(cfg["num_hidden_layers"]):
        shapes.update({
            f"layers.{i}.ln1": (h,), f"layers.{i}.wq": (h, nh * hd),
            f"layers.{i}.wk": (h, nkv * hd), f"layers.{i}.wv": (h, nkv * hd),
            f"layers.{i}.wo": (nh * hd, h), f"layers.{i}.ln2": (h,),
            f"layers.{i}.wg": (h, f), f"layers.{i}.wu": (h, f),
            f"layers.{i}.wd": (f, h)})
    shapes["norm"] = (h,)
    if not cfg.get("tie_word_embeddings"):
        shapes["head"] = (h, v)
    return shapes


def seed_key(seed):
    """A PRNG key for any whole-number seed (they run past 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def make_leaf(cfg, key, index, shape):
    if len(shape) == 1:
        return jnp.ones(shape, BF16)
    std = cfg.get("initializer_range", 0.02)
    return (jax.random.normal(jax.random.fold_in(key, index), shape, F32)
            * std).astype(BF16)


def make_weights(cfg, seed):
    """Every leaf, on the device, in one jitted call, in bfloat16."""
    shapes = leaf_shapes(cfg)

    @jax.jit
    def gen(key):
        return {name: make_leaf(cfg, key, i, shape)
                for i, (name, shape) in enumerate(shapes.items())}
    return gen(seed_key(seed))


def change_norms(cfg, seed, params):
    """{leaf: norm of (params[leaf] - the seeded leaf)} in one jitted
    call; the seeded values are made again inside it, a leaf at a time."""
    shapes = leaf_shapes(cfg)

    @jax.jit
    def run(key, params):
        return {name: jnp.sqrt(jnp.sum(jnp.square(
            params[name].astype(F32)
            - make_leaf(cfg, key, i, shape).astype(F32))))
            for i, (name, shape) in enumerate(shapes.items())}
    return {k: float(v) for k, v in run(seed_key(seed), params).items()}


@jax.jit
def norms(arrays):
    """{leaf: norm} of a dict of arrays, in one jitted call."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(a.astype(F32))))
            for k, a in arrays.items()}


# -- arithmetic ---------------------------------------------------------------

def _fp8(x, dtype):
    """x rounded to an 8-bit float type under a per-tensor scale."""
    scale = jnp.max(jnp.abs(x)) / float(jnp.finfo(dtype).max) + 1e-30
    return (x / scale).astype(dtype).astype(F32) * scale


def _dot(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


@jax.custom_vjp
def _linear_fp8(x, w):
    return _dot(_fp8(x, jnp.float8_e4m3fn), _fp8(w, jnp.float8_e4m3fn))


def _linear_fp8_fwd(x, w):
    xq, wq = _fp8(x, jnp.float8_e4m3fn), _fp8(w, jnp.float8_e4m3fn)
    return _dot(xq, wq), (xq, wq)


def _linear_fp8_bwd(saved, dy):
    xq, wq = saved
    dyq = _fp8(dy, jnp.float8_e5m2)
    return _dot(dyq, wq.T), _dot(xq.T, dyq)


_linear_fp8.defvjp(_linear_fp8_fwd, _linear_fp8_bwd)


def linear(x, w, precision):
    """x @ w. "f32": float32 at `highest`. "fp8": the usual 8-bit
    training recipe, operands rounded to e4m3 going forward and the
    incoming gradient to e5m2 going back, each under a per-tensor scale
    (the products themselves are exact)."""
    x, w = x.astype(F32), w.astype(F32)
    if precision == "fp8":
        return _linear_fp8(x, w)
    if precision != "f32":
        raise ValueError(f"unknown precision {precision!r}")
    return _dot(x, w)


def rms_norm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def rope(x, positions, theta):
    """x [S, heads, hd]; rotate-half (GPT-NeoX) convention."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def causal_attention(q, k, v):
    """q [S, nh, hd], k and v [S, nkv, hd]: softmax(q k^T / sqrt(hd)) v
    under a causal mask, one KV head's group of query heads at a time."""
    s, nh, hd = q.shape
    nkv = k.shape[1]
    qg = q.reshape(s, nkv, nh // nkv, hd).transpose(1, 2, 0, 3)
    mask = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint       # a backward pass recomputes a group's scores
    def group(args):
        qh, kh, vh = args                     # [rep, S, hd], [S, hd] x 2
        att = jnp.einsum("rqd,kd->rqk", qh, kh,
                         precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
        att = jax.nn.softmax(jnp.where(mask, att, -1e30), axis=-1)
        return jnp.einsum("rqk,kd->rqd", att, vh,
                          precision=jax.lax.Precision.HIGHEST)
    out = jax.lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(2, 0, 1, 3).reshape(s, nh * hd)


def layer_forward(cfg, p, x, precision):
    """One decoder block on one sequence x [S, H] (float32)."""
    s = x.shape[0]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   head_dim(cfg))
    pos = jnp.arange(s)
    h1 = rms_norm(x, p["ln1"], cfg["rms_norm_eps"])
    q = rope(linear(h1, p["wq"], precision).reshape(s, nh, hd), pos,
             cfg["rope_theta"])
    k = rope(linear(h1, p["wk"], precision).reshape(s, nkv, hd), pos,
             cfg["rope_theta"])
    v = linear(h1, p["wv"], precision).reshape(s, nkv, hd)
    x = x + linear(causal_attention(q, k, v), p["wo"], precision)
    h2 = rms_norm(x, p["ln2"], cfg["rms_norm_eps"])
    gate = jax.nn.silu(linear(h2, p["wg"], precision))
    return x + linear(gate * linear(h2, p["wu"], precision), p["wd"],
                      precision)


def layer_params(weights, i):
    return {k: weights[f"layers.{i}.{k}"] for k in LAYER_LEAVES}


def head_weight(weights):
    return weights["head"] if "head" in weights else weights["embed"].T


# -- serving: logits of chosen rows ---------------------------------------------

def logits_at(cfg, weights, ids, rows, precision="f32"):
    """Full causal forward over `ids` [S] (one sequence, padded behind as
    the caller likes) and the logits [len(rows), V] of positions `rows`."""
    fwd = _jit_layer_forward(_freeze(cfg), precision)
    x = jnp.take(weights["embed"], ids, axis=0).astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        x = fwd(layer_params(weights, i), x)
    return _jit_head(_freeze(cfg), precision)(
        weights["norm"], head_weight(weights), jnp.take(x, rows, axis=0))


def _freeze(cfg):
    keys = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "num_hidden_layers", "vocab_size",
            "rope_theta", "rms_norm_eps", "head_dim", "tie_word_embeddings")
    return tuple((k, cfg[k]) for k in keys if k in cfg)


@functools.lru_cache(maxsize=None)
def _jit_layer_forward(frozen, precision):
    cfg = dict(frozen)
    return jax.jit(lambda p, x: layer_forward(cfg, p, x, precision))


@functools.lru_cache(maxsize=None)
def _jit_head(frozen, precision):
    cfg = dict(frozen)
    return jax.jit(lambda norm_w, head, x: linear(
        rms_norm(x, norm_w, cfg["rms_norm_eps"]), head, precision))


# -- training: loss, gradients, AdamW, layer by layer ---------------------------

@functools.lru_cache(maxsize=None)
def _jit_layer_vjp(frozen, precision):
    cfg = dict(frozen)

    def run(p, x, dy):
        p32 = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
        _, pull = jax.vjp(lambda p_, x_: layer_forward(cfg, p_, x_, precision),
                          p32, x)
        return pull(dy)
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _jit_head_loss(frozen, precision):
    cfg = dict(frozen)

    def loss_sum(norm_w, head, x, labels):
        norm_w, head = norm_w.astype(F32), head.astype(F32)
        logits = linear(rms_norm(x, norm_w, cfg["rms_norm_eps"]), head,
                        precision)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - picked)
    return jax.jit(jax.value_and_grad(loss_sum, argnums=(0, 1, 2)))


@jax.jit
def _adamw(p, g, m, v, t, lr, b1, b2, eps, decay):
    """AdamW with decoupled decay; float32 arithmetic, storage types kept.
    The gradient passes through the parameter's type first, as a
    gradient of a bfloat16 parameter does."""
    g = g.astype(p.dtype).astype(m.dtype).astype(F32)
    p32 = p.astype(F32) * (1 - lr * decay)
    m_new = b1 * m.astype(F32) + (1 - b1) * g
    v_new = b2 * v.astype(F32) + (1 - b2) * g * g
    mhat = m_new / (1 - b1 ** t)
    vhat = v_new / (1 - b2 ** t)
    p_new = p32 - lr * mhat / (jnp.sqrt(vhat) + eps)
    return p_new.astype(p.dtype), m_new.astype(m.dtype), v_new.astype(v.dtype)


@jax.jit
def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))


def train_steps(cfg, seed, batches, opt, steps, precision="f32", rows=None):
    """Follow `steps` optimizer steps from the seeded weights on
    `batches` [(ids, labels)], each [B, S]. Returns
    {"loss": [per step], "grad_norm": {leaf: norm of the first step's
    gradient}, "change_norm": {leaf: norm of (parameters after the last
    step - seeded parameters)}}. `rows` (a slice) keeps only those rows
    of every batch, the mean taken over them: the half-batch fault.
    One sequence and one layer at a time, so that it fits beside nothing."""
    frozen = _freeze(cfg)
    fwd = _jit_layer_forward(frozen, precision)
    vjp = _jit_layer_vjp(frozen, precision)
    head_loss = _jit_head_loss(frozen, precision)
    mdt = jnp.dtype(opt.get("moment_dtype") or "float32")
    params = make_weights(cfg, seed)
    if "head" not in params:
        raise NotImplementedError("tied output head")
    # Adam's moments wait on the host between a leaf's updates (None: still
    # all zero). On the chip, loading the layer's backward program asks for
    # 8 GB of scratch beside whatever is resident (its own analysis says
    # 1.4 GB; cause not found, PERF.md), and with the moments resident
    # only 7.6 GB are free
    m = dict.fromkeys(params)
    v = dict.fromkeys(params)
    hyper = (opt["learning_rate"], opt["beta1"], opt["beta2"],
             opt["epsilon"], opt["weight_decay"])
    n_layers = cfg["num_hidden_layers"]
    losses, grad_norm = [], {}

    def update(name, grad, t):
        if t == 1:
            grad_norm[name] = float(_norm(grad.astype(params[name].dtype)))
        shape = params[name].shape
        m_in = jnp.zeros(shape, mdt) if m[name] is None else jnp.asarray(m[name])
        v_in = jnp.zeros(shape, mdt) if v[name] is None else jnp.asarray(v[name])
        params[name], m_out, v_out = _adamw(
            params[name], grad, m_in, v_in, float(t), *hyper)
        if t < steps:
            m[name], v[name] = np.asarray(m_out), np.asarray(v_out)

    for t in range(1, steps + 1):
        ids, labels = batches[t - 1]
        if rows is not None:
            ids, labels = ids[rows], labels[rows]
        n_rows, seq = ids.shape
        inv = 1.0 / (n_rows * seq)
        acts = []                               # acts[l][r]: input of layer l
        for r in range(n_rows):
            x = jnp.take(params["embed"], jnp.asarray(ids[r]), axis=0
                         ).astype(F32)
            row_acts = []
            for i in range(n_layers):
                row_acts.append(x)
                x = fwd(layer_params(params, i), x)
            row_acts.append(x)
            acts.append(row_acts)
        loss, g_norm, g_head, dxs = 0.0, 0.0, 0.0, []
        for r in range(n_rows):
            val, (gn, gh, dx) = head_loss(params["norm"], params["head"],
                                          acts[r][n_layers],
                                          jnp.asarray(labels[r]))
            loss += float(val) * inv
            g_norm, g_head = g_norm + gn * inv, g_head + gh * inv
            dxs.append(dx * inv)
        losses.append(loss)
        update("norm", g_norm, t)
        update("head", g_head, t)
        del g_norm, g_head
        for i in reversed(range(n_layers)):
            p_i = layer_params(params, i)
            total = None
            for r in range(n_rows):
                gp, dxs[r] = vjp(p_i, acts[r][i], dxs[r])
                total = gp if total is None else jax.tree_util.tree_map(
                    jnp.add, total, gp)
            for k in LAYER_LEAVES:
                update(f"layers.{i}.{k}", total[k], t)
            del total, gp, p_i
        g_embed = jnp.zeros(params["embed"].shape, F32)
        for r in range(n_rows):
            g_embed = g_embed.at[jnp.asarray(ids[r])].add(dxs[r])
        update("embed", g_embed, t)
        del g_embed, acts, dxs
    return {"loss": losses, "grad_norm": grad_norm,
            "change_norm": change_norms(cfg, seed, params)}
