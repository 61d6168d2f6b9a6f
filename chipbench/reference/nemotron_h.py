"""Plain reference for the `nemotron_h` family (Mamba-2 + attention +
LatentMoE hybrids: NVIDIA-Nemotron-3-Super-120B-A12B).

Straightforward `jax.numpy` in float32 at `highest` matmul precision. It
imports nothing of the program and takes nothing the program made:
weights come from `make_weights(cfg, seed)` here, which the harness also
hands to the program. No kernels, no cache, no batching: one sequence,
one block at a time, the Mamba-2 recurrence step by step (the
*sequential* form, never the chunked one) and the routed experts one
expert at a time.

Block `l` of kind `p[l]` in {M, *, E} (`hybrid_override_pattern`):
`x <- x + Mixer_l(RMSNorm(x; w_l))`; after the last block
`logits = RMSNorm(x; w_f) @ W_head`. No bias anywhere but the conv.

- M (Mamba-2): `[z | xBC | dt] = u W_in`; `xBC` through a causal
  depthwise conv of 4 taps (zeros before the start) and silu, split into
  `x` [heads, head_dim], `B` and `C` [groups, state]; `dt = softplus(dt +
  dt_bias)`, `A = -exp(A_log)`; per head h of group g = h // (heads /
  groups): `S_t = exp(dt_t A_h) S_{t-1} + dt_t x_{t,h} (x) B_{t,g}`,
  `y_{t,h} = S_t C_{t,g} + D_h x_{t,h}`; then `y <- RMSNorm_group(y *
  silu(z); w)` over each group's channels and `out = y W_out`.
- * (attention): GQA, causal, scale head_dim^-1/2, no positional term.
- E (LatentMoE): `s = sigmoid(u W_r)` over all published experts;
  `choice = top_k(s + b_corr)`; `w_k = scale * s_k / (sum of the chosen
  s + 1e-20)`; `v = u W_down`; `r = sum over chosen k held here of w_k
  relu(v W1_k)^2 W2_k`; `out = r W_up + relu(u Ws1)^2 Ws2`. What the
  experts held elsewhere would add is left out (the configuration holds
  `n_routed_experts` of `published.n_routed_experts`, from
  `experts_first`).

Departures and choices, each listed in the configuration's `assumed`:
seeded weights (normal(0, 0.02) matrices in bfloat16, norm weights 1,
conv taps and bias uniform(-1/2, 1/2), `A_log = log U(1, 16)`, `dt_bias`
the inverse softplus of a log-uniform step in [time_step_min,
time_step_max] floored at time_step_floor, `D = 1`, `b_corr` a small
normal), router weights and the four per-head vectors kept in float32,
`dt` not clamped after softplus, the gate applied before the grouped
norm, no rotary term in attention, the multi-token-prediction head left
out.

`precision` selects the arithmetic of the weight matmuls: "f32" is the
reference; "fp8" (operands rounded to float8_e4m3 under a per-tensor
scale) is the control that a `correct` comparison has to refuse.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
BF16 = jnp.bfloat16
HIGHEST = jax.lax.Precision.HIGHEST


# -- sizes ----------------------------------------------------------------------

def sizes(cfg):
    """The family's derived sizes from the published keys."""
    heads, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, state = cfg["n_groups"], cfg["ssm_state_size"]
    d_in = heads * hd
    published = cfg.get("published", {})
    return {
        "heads": heads, "hd": hd, "groups": groups, "state": state,
        "d_in": d_in, "conv_dim": d_in + 2 * groups * state,
        "kernel": cfg["conv_kernel"],
        "router_width": published.get("n_routed_experts",
                                      cfg["n_routed_experts"]),
        "held": cfg["n_routed_experts"],
        "first": cfg.get("experts_first", 0),
        "top_k": cfg["num_experts_per_tok"],
    }


def leaf_shapes(cfg):
    """Ordered {leaf name: shape}. Matrices are [in, out]; an expert
    stack is [experts held, in, out]."""
    z = sizes(cfg)
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    nh, nkv, ad = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    lat, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    fs = cfg["moe_shared_expert_intermediate_size"]
    shapes = {"embed": (v, h)}
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        pre = f"layers.{i}."
        shapes[pre + "norm"] = (h,)
        if kind == "M":
            shapes.update({
                pre + "in_proj": (h, 2 * z["d_in"] + 2 * z["groups"]
                                  * z["state"] + z["heads"]),
                pre + "conv_w": (z["conv_dim"], z["kernel"]),
                pre + "conv_b": (z["conv_dim"],),
                pre + "A_log": (z["heads"],), pre + "D": (z["heads"],),
                pre + "dt_bias": (z["heads"],),
                pre + "gnorm": (z["d_in"],),
                pre + "out_proj": (z["d_in"], h)})
        elif kind == "*":
            shapes.update({
                pre + "wq": (h, nh * ad), pre + "wk": (h, nkv * ad),
                pre + "wv": (h, nkv * ad), pre + "wo": (nh * ad, h)})
        elif kind == "E":
            shapes.update({
                pre + "router": (h, z["router_width"]),
                pre + "b_corr": (z["router_width"],),
                pre + "w_down": (h, lat), pre + "w_up": (lat, h),
                pre + "w1": (z["held"], lat, f),
                pre + "w2": (z["held"], f, lat),
                pre + "ws1": (h, fs), pre + "ws2": (fs, h)})
        else:
            raise ValueError(f"unknown block kind {kind!r} in the pattern")
    shapes["norm"] = (h,)
    shapes["head"] = (h, v)
    return shapes


def seed_key(seed):
    """A PRNG key for any whole-number seed (they run past 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def make_leaf(cfg, key, index, name, shape):
    kind = name.rsplit(".", 1)[-1]
    key = jax.random.fold_in(key, index)
    if kind in ("norm", "gnorm"):
        return jnp.ones(shape, BF16)
    if kind == "D":
        return jnp.ones(shape, F32)
    if kind == "A_log":
        return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    if kind == "dt_bias":
        lo, hi = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])
        dt = jnp.exp(jax.random.uniform(key, shape, F32) * (hi - lo) + lo)
        dt = jnp.maximum(dt, cfg["time_step_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))        # inverse softplus
    if kind in ("conv_w", "conv_b"):
        return jax.random.uniform(key, shape, F32, -0.5, 0.5).astype(BF16)
    if kind == "b_corr":
        return jax.random.normal(key, shape, F32) * 0.01
    w = jax.random.normal(key, shape, F32) * cfg.get("initializer_range", 0.02)
    return w if kind == "router" else w.astype(BF16)


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


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


# -- M: Mamba-2, step by step -------------------------------------------------------

def causal_conv(xbc, w, b):
    """Depthwise causal conv over time: xbc [S, C], w [C, K], b [C];
    zeros before the start."""
    k = w.shape[1]
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), F32), xbc])
    out = b.astype(F32)[None, :]
    for j in range(k):
        out = out + padded[j:j + xbc.shape[0]] * w.astype(F32)[:, j][None, :]
    return out


def ssm_sequential(x, b, c, dt, a, d, state0=None):
    """The recurrence one position at a time. x [S, heads, hd]; b, c
    [S, groups, state]; dt [S, heads] (after softplus); a, d [heads].
    Returns (y [S, heads, hd], last state [heads, hd, state])."""
    heads, hd = x.shape[1], x.shape[2]
    rep = heads // b.shape[1]
    if state0 is None:
        state0 = jnp.zeros((heads, hd, b.shape[2]), F32)

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        bh = jnp.repeat(b_t, rep, axis=0)                    # [heads, state]
        ch = jnp.repeat(c_t, rep, axis=0)
        state = state * jnp.exp(dt_t * a)[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * bh[:, None, :]
        y_t = jnp.einsum("hpn,hn->hp", state, ch, precision=HIGHEST) \
            + d[:, None] * x_t
        return state, y_t
    last, y = jax.lax.scan(step, state0, (x, b, c, dt))
    return y, last


def group_rms_norm(y, w, groups, eps):
    s, width = y.shape
    g = y.reshape(s, groups, width // groups)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    return g.reshape(s, width) * w.astype(F32)


def mamba_mixer(cfg, p, u, precision, state0=None):
    """u [S, H] (normalised input) -> ([S, H], last SSM state)."""
    z_ = sizes(cfg)
    d_in, gn = z_["d_in"], z_["groups"] * z_["state"]
    s = u.shape[0]
    zxbcdt = linear(u, p["in_proj"], precision)
    z, xbc, dt = (zxbcdt[:, :d_in], zxbcdt[:, d_in:d_in + z_["conv_dim"]],
                  zxbcdt[:, d_in + z_["conv_dim"]:])
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
    x = xbc[:, :d_in].reshape(s, z_["heads"], z_["hd"])
    b = xbc[:, d_in:d_in + gn].reshape(s, z_["groups"], z_["state"])
    c = xbc[:, d_in + gn:].reshape(s, z_["groups"], z_["state"])
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))
    a = -jnp.exp(p["A_log"].astype(F32))
    y, last = ssm_sequential(x, b, c, dt, a, p["D"].astype(F32), state0)
    y = y.reshape(s, d_in) * jax.nn.silu(z)
    y = group_rms_norm(y, p["gnorm"], z_["groups"], cfg["layer_norm_epsilon"])
    return linear(y, p["out_proj"], precision), last


# -- *: attention without a positional term -------------------------------------------

def causal_attention(q, k, v):
    """q [S, nh, hd], k and v [S, nkv, hd]: softmax(q k^T / sqrt(hd)) v
    under a causal mask, one KV head's group of query heads at a time."""
    s, nh, hd = q.shape
    nkv = k.shape[1]
    qg = q.reshape(s, nkv, nh // nkv, hd).transpose(1, 2, 0, 3)
    mask = jnp.tril(jnp.ones((s, s), bool))

    def group(args):
        qh, kh, vh = args
        out = []
        for r in range(qh.shape[0]):          # one query head at a time
            att = jnp.dot(qh[r], kh.T, precision=HIGHEST) / np.sqrt(hd)
            att = jax.nn.softmax(jnp.where(mask, att, -1e30), axis=-1)
            out.append(jnp.dot(att, vh, precision=HIGHEST))
        return jnp.stack(out)
    out = jax.lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(2, 0, 1, 3).reshape(s, nh * hd)


def attention_mixer(cfg, p, u, precision):
    s = u.shape[0]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    q = linear(u, p["wq"], precision).reshape(s, nh, hd)
    k = linear(u, p["wk"], precision).reshape(s, nkv, hd)
    v = linear(u, p["wv"], precision).reshape(s, nkv, hd)
    return linear(causal_attention(q, k, v), p["wo"], precision)


# -- E: LatentMoE, one held expert at a time ---------------------------------------------

def route(cfg, p, u, precision):
    """(chosen expert ids [S, k] over the published width, their weights
    [S, k]): choice by `s + b_corr`, weights from `s` alone, normalised
    over all chosen, held here or not."""
    z = sizes(cfg)
    s = jax.nn.sigmoid(linear(u, p["router"], precision))
    _, idx = jax.lax.top_k(s + p["b_corr"].astype(F32)[None, :], z["top_k"])
    weights = jnp.take_along_axis(s, idx, axis=1)
    if cfg.get("norm_topk_prob", True):
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return idx, weights * cfg["routed_scaling_factor"]


def moe_mixer(cfg, p, u, precision, held=None):
    """u [S, H] -> [S, H]. `held` = (first, count) of the experts whose
    weights `p` holds (default: the configuration's)."""
    z = sizes(cfg)
    first, count = held if held is not None else (z["first"], z["held"])
    idx, weights = route(cfg, p, u, precision)
    # [S, count]: the weight each held expert gets from each token
    local = idx - first
    dense = jnp.zeros((u.shape[0], count + 1), F32).at[
        jnp.arange(u.shape[0])[:, None],
        jnp.where((local >= 0) & (local < count), local, count)].add(weights)
    v = linear(u, p["w_down"], precision)

    def one_expert(acc, xs):
        w1, w2, col = xs
        y = linear(relu2(linear(v, w1, precision)), w2, precision)
        return acc + col[:, None] * y, None
    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(v),
                             (p["w1"], p["w2"], dense[:, :count].T))
    shared = linear(relu2(linear(u, p["ws1"], precision)), p["ws2"],
                    precision)
    return linear(routed, p["w_up"], precision) + shared


# -- the whole model ------------------------------------------------------------------------

def layer_params(weights, i):
    pre = f"layers.{i}."
    return {k[len(pre):]: v for k, v in weights.items() if k.startswith(pre)}


def block(cfg, kind, p, x, precision):
    u = rms_norm(x, p["norm"], cfg["layer_norm_epsilon"])
    if kind == "M":
        return x + mamba_mixer(cfg, p, u, precision)[0]
    if kind == "*":
        return x + attention_mixer(cfg, p, u, precision)
    return x + moe_mixer(cfg, p, u, precision)


def _key(cfg):
    """A configuration as a hashable key of the jit caches below."""
    return json.dumps(cfg, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _jit_block(key, kind, precision):
    cfg = json.loads(key)
    return jax.jit(lambda p, x: block(cfg, kind, p, x, precision))


@functools.lru_cache(maxsize=None)
def _jit_head(key, precision):
    cfg = json.loads(key)
    return jax.jit(lambda norm_w, head, x: linear(
        rms_norm(x, norm_w, cfg["layer_norm_epsilon"]), head, precision))


def hidden_states(cfg, weights, ids, precision="f32"):
    """The residual stream after the last block, [S, H] float32."""
    key = _key(cfg)
    x = jnp.take(weights["embed"], ids, axis=0).astype(F32)
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        x = _jit_block(key, kind, precision)(layer_params(weights, i), x)
    return x


def logits_at(cfg, weights, ids, rows, precision="f32"):
    """Full causal forward over `ids` [S] (one sequence, padded behind as
    the caller likes) and the logits [len(rows), V] of positions `rows`."""
    x = hidden_states(cfg, weights, ids, precision)
    return _jit_head(_key(cfg), precision)(
        weights["norm"], weights["head"], jnp.take(x, rows, axis=0))
