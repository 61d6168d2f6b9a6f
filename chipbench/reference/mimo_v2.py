"""Plain reference for the `mimo_v2_flash` family (sliding-window and
full attention mixed, sparse SwiGLU experts: XiaomiMiMo MiMo-V2-Flash).

Straightforward `jax.numpy` in float32 at `highest` matmul precision. It
imports nothing of the program and takes nothing the program made:
weights come from `make_weights(cfg, seed)` here, which the harness also
hands to the program. No kernels, no cache, no batching: one sequence,
one layer at a time, attention a block of queries after another (so that
10,240 tokens fit: the scores of a block are [heads, block, keys], never
[T, T]; a window layer's block meets only the keys its windows reach),
the routed experts one expert at a time.

Layer `l` on x [T, hidden], no bias anywhere:

    kind(l) = full if hybrid_layer_pattern[l] == 0 else window
    h = rmsnorm(x, ln1)
    q = h Wq [T, nh, dk];  k = h Wk [T, nkv(kind), dk]
    v = (h Wv) * attention_value_scale  [T, nkv(kind), dv]
    q, k: rotary on dims 0 .. int(partial_rotary_factor * dk) - 1
          (rotate-half pairs (i, i + rot/2)), base rope_theta (full) |
          swa_rope_theta (window); the other dims pass
    s_ij = q_i . k_j / sqrt(dk)  for j <= i, window: and i - j < window
    full:    p_ij = softmax_j(s_ij)
    window:  p_ij = exp(s_ij) / (sum_j' exp(s_ij') + exp(sink_head))
    x = x + (sum_j p_ij v_j) Wo
    h2 = rmsnorm(x, ln2)
    moe_layer_freq[l] == 0:  x = x + (silu(h2 Wg) * (h2 Wu)) Wd
    else: s = sigmoid(h2 Wr) float32 over all published experts;
          choice = top_k(s + b_corr) (the bias in the choice only);
          w = s_chosen / sum(s_chosen); x = x + sum over the chosen
          experts HELD HERE of w_e (silu(h2 W1_e) * (h2 W3_e)) W2_e
    logits = rmsnorm(x, norm) Whead

What the experts held elsewhere would add is left out (the configuration
holds `n_routed_experts` of `published.n_routed_experts`, from
`experts_first`). Departures and choices, each listed in the
configuration's `assumed`: seeded weights (normal(0, 0.02) matrices in
bfloat16, norm weights 1, the sink bias normal(0, 1) and the router with
its choice bias normal(0, 0.01) in float32), the rotate-half pairing,
the sink as a value-free column, a window that counts the query's own
position, v scaled before the product, `attention_chunk_size` unused,
the multi-token-prediction layers left out.

`precision` selects the arithmetic of the weight matmuls: "f32" is the
reference; "fp8" (operands rounded to float8_e4m3 under a per-tensor
scale) is the control that a `correct` comparison has to refuse.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
BF16 = jnp.bfloat16
HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 128


# -- sizes ----------------------------------------------------------------------

def is_window(cfg, l):
    return cfg["hybrid_layer_pattern"][l] != 0


def is_sparse(cfg, l):
    return cfg["moe_layer_freq"][l] != 0


def sizes(cfg):
    published = cfg.get("published", {})
    return {"router_width": published.get("n_routed_experts",
                                          cfg["n_routed_experts"]),
            "held": cfg["n_routed_experts"],
            "first": cfg.get("experts_first", 0),
            "top_k": cfg["num_experts_per_tok"],
            "rot": int(cfg["partial_rotary_factor"] * cfg["head_dim"])}


def kv_heads(cfg, l):
    return cfg["swa_num_key_value_heads"] if is_window(cfg, l) \
        else cfg["num_key_value_heads"]


def has_sink(cfg, l):
    return bool(cfg["add_swa_attention_sink_bias"] if is_window(cfg, l)
                else cfg["add_full_attention_sink_bias"])


def leaf_shapes(cfg):
    """Ordered {leaf name: shape}. Matrices are [in, out]; an expert
    stack is [experts held, in, out]."""
    z = sizes(cfg)
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    nh, dk, dv = cfg["num_attention_heads"], cfg["head_dim"], cfg["v_head_dim"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    shapes = {"embed": (v, h)}
    for i in range(cfg["num_hidden_layers"]):
        pre, nkv = f"layers.{i}.", kv_heads(cfg, i)
        shapes.update({pre + "ln1": (h,), pre + "wq": (h, nh * dk),
                       pre + "wk": (h, nkv * dk), pre + "wv": (h, nkv * dv),
                       pre + "wo": (nh * dv, h)})
        if has_sink(cfg, i):
            shapes[pre + "sink"] = (nh,)
        shapes[pre + "ln2"] = (h,)
        if is_sparse(cfg, i):
            shapes.update({pre + "router": (h, z["router_width"]),
                           pre + "b_corr": (z["router_width"],),
                           pre + "w1": (z["held"], h, fe),
                           pre + "w3": (z["held"], h, fe),
                           pre + "w2": (z["held"], fe, h)})
        else:
            shapes.update({pre + "wg": (h, f), pre + "wu": (h, f),
                           pre + "wd": (f, h)})
    shapes["norm"] = (h,)
    shapes["head"] = (h, v)
    return shapes


def seed_key(seed):
    """A PRNG key for any whole-number seed (they run past 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def make_leaf(cfg, key, index, name, shape):
    """One seeded leaf as the configuration stores it (`torch_dtype`:
    bfloat16 in every cell; float32 where a test wants the program's
    rounding out of the comparison)."""
    store = {"bfloat16": BF16, "float32": F32}[cfg["torch_dtype"]]
    kind = name.rsplit(".", 1)[-1]
    key = jax.random.fold_in(key, index)
    if kind in ("ln1", "ln2", "norm"):
        return jnp.ones(shape, store)
    if kind == "sink":
        return jax.random.normal(key, shape, F32)
    if kind == "b_corr":
        return jax.random.normal(key, shape, F32) * 0.01
    w = jax.random.normal(key, shape, F32) * cfg.get("initializer_range", 0.02)
    return w if kind == "router" else w.astype(store)


def make_weights(cfg, seed):
    """Every leaf, on the device, in one jitted call."""
    shapes = leaf_shapes(cfg)

    @jax.jit
    def gen(key):
        return {name: make_leaf(cfg, key, i, name, shape)
                for i, (name, shape) in enumerate(shapes.items())}
    return gen(seed_key(seed))


# -- arithmetic -------------------------------------------------------------------

def _fp8(x):
    """x rounded to float8_e4m3 under a per-tensor scale."""
    dtype = jnp.float8_e4m3fn
    scale = jnp.max(jnp.abs(x)) / float(jnp.finfo(dtype).max) + 1e-30
    return (x / scale).astype(dtype).astype(F32) * scale


def linear(x, w, precision):
    """x @ w in float32 at `highest`; "fp8" rounds both operands to
    e4m3 first (the products themselves stay exact)."""
    x, w = x.astype(F32), w.astype(F32)
    if precision == "fp8":
        x, w = _fp8(x), _fp8(w)
    elif precision != "f32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.dot(x, w, precision=HIGHEST)


def rms_norm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


# -- attention --------------------------------------------------------------------

def rotary(x, rot, theta):
    """x [T, heads, D] at positions 0 .. T-1: dims (i, i + rot/2), i <
    rot/2, turn by position * theta^(-2i/rot); dims from `rot` on
    pass."""
    t, half = x.shape[0], rot // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / rot)
    ang = jnp.asarray(np.arange(t, dtype=np.float64)[:, None] * inv[None, :],
                      F32)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang),
                            x[..., rot:]], axis=-1)


def attend(q, k, v, window, sink):
    """q [T, nh, dk], k [T, nkv, dk], v [T, nkv, dv]: causal attention,
    within `window` keys where it is not None, `sink` [nh] (or None)
    joining each head's denominator. A block of queries at a time, one
    after another, against all the keys (a full layer) or against the
    keys its rows' windows reach (a window layer)."""
    t, nh, dk = q.shape
    nkv = k.shape[1]
    block = min(QUERY_BLOCK, t)
    qg = jnp.pad(q, ((0, -t % block), (0, 0), (0, 0))) \
        .reshape(-1, block, nkv, nh // nkv, dk)
    bias = None if sink is None else \
        sink.astype(F32).reshape(nkv, nh // nkv, 1, 1)
    if window is None:
        reach, span = 0, t
    else:
        # zeros before the sequence, so that every block's slice is whole
        reach, span = window - 1, block + window - 1
        k, v = (jnp.pad(a, ((reach, -t % block), (0, 0), (0, 0)))
                for a in (k, v))

    def rows(args):
        q_b, first = args
        row = first + jnp.arange(block)[:, None]
        if window is None:
            k_b, v_b, col = k, v, jnp.arange(t)[None, :]
            sees = col <= row
        else:
            k_b, v_b = (jax.lax.dynamic_slice_in_dim(a, first, span)
                        for a in (k, v))
            col = first - reach + jnp.arange(span)[None, :]
            sees = (col <= row) & (col > row - window) & (col >= 0)
        s = jnp.einsum("qgnd,kgd->gnqk", q_b, k_b,
                       precision=HIGHEST) / np.sqrt(dk)
        s = jnp.where(sees, s, -jnp.inf)
        top = s.max(axis=-1, keepdims=True)
        if bias is not None:
            top = jnp.maximum(top, bias)
        e = jnp.exp(s - top)
        den = e.sum(axis=-1, keepdims=True)
        if bias is not None:
            den = den + jnp.exp(bias - top)
        return jnp.einsum("gnqk,kgd->qgnd", e / den, v_b, precision=HIGHEST)
    out = jax.lax.map(rows, (qg, jnp.arange(qg.shape[0]) * block))
    return out.reshape(-1, nh * v.shape[-1])[:t]


def attention(cfg, l, p, h, precision):
    t = h.shape[0]
    nh, nkv = cfg["num_attention_heads"], kv_heads(cfg, l)
    dk, dv = cfg["head_dim"], cfg["v_head_dim"]
    window = is_window(cfg, l)
    theta = cfg["swa_rope_theta"] if window else cfg["rope_theta"]
    rot = sizes(cfg)["rot"]
    q = rotary(linear(h, p["wq"], precision).reshape(t, nh, dk), rot, theta)
    k = rotary(linear(h, p["wk"], precision).reshape(t, nkv, dk), rot, theta)
    v = linear(h, p["wv"], precision).reshape(t, nkv, dv) \
        * cfg["attention_value_scale"]
    o = attend(q, k, v, cfg["sliding_window"] if window else None,
               p.get("sink"))
    return linear(o, p["wo"], precision)


# -- the MLPs -----------------------------------------------------------------------

def route(cfg, p, u, precision):
    """(chosen expert ids [T, k] over the published width, their weights
    [T, k]): choice by `s + b_corr`, weights from `s` alone, normalised
    over all chosen, held here or not."""
    s = jax.nn.sigmoid(linear(u, p["router"], precision))
    _, idx = jax.lax.top_k(s + p["b_corr"].astype(F32)[None, :],
                           sizes(cfg)["top_k"])
    weights = jnp.take_along_axis(s, idx, axis=1)
    if cfg.get("norm_topk_prob", True):
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return idx, weights * (cfg.get("routed_scaling_factor") or 1.0)


def moe(cfg, p, u, precision, held=None):
    """u [T, H] -> [T, H]: the part of the routed sum that the experts
    whose weights `p` holds give. `held` = (first, count) of them
    (default: the configuration's)."""
    z = sizes(cfg)
    first, count = held if held is not None else (z["first"], z["held"])
    idx, weights = route(cfg, p, u, precision)
    # [T, count]: the weight each held expert gets from each token
    local = idx - first
    dense = jnp.zeros((u.shape[0], count + 1), F32).at[
        jnp.arange(u.shape[0])[:, None],
        jnp.where((local >= 0) & (local < count), local, count)].add(weights)

    def one_expert(acc, xs):
        w1, w3, w2, col = xs
        y = linear(jax.nn.silu(linear(u, w1, precision))
                   * linear(u, w3, precision), w2, precision)
        return acc + col[:, None] * y, None
    routed, _ = jax.lax.scan(one_expert, jnp.zeros(u.shape, F32),
                             (p["w1"], p["w3"], p["w2"], dense[:, :count].T))
    return routed


def dense_mlp(p, u, precision):
    return linear(jax.nn.silu(linear(u, p["wg"], precision))
                  * linear(u, p["wu"], precision), p["wd"], precision)


# -- the whole model ------------------------------------------------------------------------

def layer_params(weights, i):
    pre = f"layers.{i}."
    return {k[len(pre):]: v for k, v in weights.items() if k.startswith(pre)}


def block(cfg, l, p, x, precision):
    eps = cfg["layernorm_epsilon"]
    x = x + attention(cfg, l, p, rms_norm(x, p["ln1"], eps), precision)
    h2 = rms_norm(x, p["ln2"], eps)
    if is_sparse(cfg, l):
        return x + moe(cfg, p, h2, precision)
    return x + dense_mlp(p, h2, precision)


def _key(cfg):
    """A configuration as a hashable key of the jit caches below."""
    return json.dumps(cfg, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _jit_block(key, l, precision):
    cfg = json.loads(key)
    return jax.jit(lambda p, x: block(cfg, l, p, x, precision))


def _like(cfg, l):
    """The first layer of l's kind (window or full, sparse or dense):
    layers of one kind run the same program on their own weights."""
    kind = (is_window(cfg, l), is_sparse(cfg, l))
    return next(i for i in range(cfg["num_hidden_layers"])
                if (is_window(cfg, i), is_sparse(cfg, i)) == kind)


@functools.lru_cache(maxsize=None)
def _jit_head(key, precision):
    cfg = json.loads(key)
    return jax.jit(lambda norm_w, head, x: linear(
        rms_norm(x, norm_w, cfg["layernorm_epsilon"]), head, precision))


def hidden_states(cfg, weights, ids, precision="f32"):
    """The residual stream after the last layer, [T, H] float32."""
    key = _key(cfg)
    x = jnp.take(weights["embed"], ids, axis=0).astype(F32)
    for l in range(cfg["num_hidden_layers"]):
        x = _jit_block(key, _like(cfg, l), precision)(
            layer_params(weights, l), x)
    return x


def logits_at(cfg, weights, ids, rows, precision="f32"):
    """Full causal forward over `ids` [T] (one sequence, padded behind as
    the caller likes) and the logits [len(rows), V] of positions `rows`."""
    x = hidden_states(cfg, weights, ids, precision)
    return _jit_head(_key(cfg), precision)(
        weights["norm"], weights["head"], jnp.take(x, rows, axis=0))
