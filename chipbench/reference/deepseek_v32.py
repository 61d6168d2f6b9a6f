"""Plain reference for the `deepseek_v32` family (latent attention with a
learned key selection, sparse experts with a group-limited router and a
shared expert: deepseek-ai DeepSeek-V3.2).

Straightforward `jax.numpy` in float32 at `highest` matmul precision. It
imports nothing of the program and takes nothing the program made:
weights come from `make_weights(cfg, seed)` here, which the harness also
hands to the program. No kernels, no cache, no batching, no absorbed
form: one sequence, one layer at a time; every head's keys and values
expanded from the latent rows; the indexer's scores and the attention's
a block of queries after another against every key, masked to the keys
`lax.top_k` selects (so that 16,384 tokens fit: a block's scores are
[heads, block, keys], never [T, T] for all heads); the routed experts one
expert at a time over every token.

Layer `l` on x [T, hidden], RMS = rmsnorm(eps `rms_norm_eps`):

    h = RMS(x, ln1)
    cq = RMS(h Wqa, q_norm);  q = cq Wqb -> [T, nh, dn + dr] = [q_nope | q_pe]
    [c | k_pe] = h Wkva;  c = RMS(c, kv_norm)
    q_pe, k_pe: rotary, pairs (2i, 2i + 1), YaRN frequencies
    [k_nope | v] = c Wkvb -> [T, nh, dn + dv]
    qI = cq WqI [T, ih, id];  kI = LayerNorm(h WkI, k_norm, k_norm_b)
    qI, kI: rotary on dims 0 .. dr - 1, pairs (i, i + dr / 2)
    wI = (h WwI) / sqrt(ih * id)
    I(t, j) = sum_h wI_h(t) relu(qI_h(t) . kI(j))  for j <= t, else -inf
    sel(t) = lax.top_k(I(t, .), min(index_topk, T)) and j <= t
    s_h(t, j) = (q_nope_h . k_nope_h(j) + q_pe_h . k_pe(j)) * tau  on sel(t)
    tau = (dn + dr)^-1/2 * (0.1 ln(factor) + 1)^2
    x = x + (softmax_j s_h  v_h) Wo
    h2 = RMS(x, ln2)
    l < first_k_dense_replace:  x = x + (silu(h2 Wg) * (h2 Wu)) Wd
    else: s = sigmoid(h2 Wr) float32 over all published experts;
          choice = s + b_corr; groups of n_routed / n_group experts, a
          group's score the sum of its two best choices, the top
          topk_group groups kept; top_k(choice) among their experts;
          w = s_chosen / sum(s_chosen) * routed_scaling_factor;
          x = x + sum over the chosen experts HELD HERE of
              w_e (silu(h2 W1_e) * (h2 W3_e)) W2_e
              + (silu(h2 Wsg) * (h2 Wsu)) Wsd
    logits = RMS(x, norm) Whead

What the experts held elsewhere would add is left out (the configuration
holds `n_routed_experts` of `published.n_routed_experts`, from
`experts_first`). Departures and choices, each listed in the
configuration's `assumed`: seeded weights (normal(0, `initializer_range`)
matrices in bfloat16, norm weights 1, the indexer's LayerNorm bias 0,
the router with its choice bias normal(0, 0.01) in float32), the indexer in the
configuration's precision without its Hadamard rotation (orthonormal on
q and k alike: every q . k is unchanged), the rotary pairings, the
LayerNorm's epsilon, the multi-token-prediction module left out.

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
QUERY_BLOCK = 64


# -- sizes ----------------------------------------------------------------------

def is_sparse(cfg, l):
    return l >= cfg["first_k_dense_replace"]


def sizes(cfg):
    published = cfg.get("published", {})
    return {"router_width": published.get("n_routed_experts",
                                          cfg["n_routed_experts"]),
            "held": cfg["n_routed_experts"],
            "first": cfg.get("experts_first", 0),
            "top_k": cfg["num_experts_per_tok"],
            "qk": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]}


def leaf_shapes(cfg):
    """Ordered {leaf name: shape}. Matrices are [in, out]; an expert
    stack is [experts held, in, out]."""
    z = sizes(cfg)
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    nh, qr, kvr = cfg["num_attention_heads"], cfg["q_lora_rank"], \
        cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = fe * cfg["n_shared_experts"]
    shapes = {"embed": (v, h)}
    for i in range(cfg["num_hidden_layers"]):
        pre = f"layers.{i}."
        shapes.update({
            pre + "ln1": (h,), pre + "wq_a": (h, qr), pre + "q_norm": (qr,),
            pre + "wq_b": (qr, nh * (dn + dr)), pre + "wkv_a": (h, kvr + dr),
            pre + "kv_norm": (kvr,), pre + "wkv_b": (kvr, nh * (dn + dv)),
            pre + "wo": (nh * dv, h), pre + "wq_idx": (qr, ih * idim),
            pre + "wk_idx": (h, idim), pre + "k_norm": (idim,),
            pre + "k_norm_b": (idim,), pre + "w_idx": (h, ih),
            pre + "ln2": (h,)})
        if is_sparse(cfg, i):
            shapes.update({pre + "router": (h, z["router_width"]),
                           pre + "b_corr": (z["router_width"],),
                           pre + "w1": (z["held"], h, fe),
                           pre + "w3": (z["held"], h, fe),
                           pre + "w2": (z["held"], fe, h),
                           pre + "ws_g": (h, fs), pre + "ws_u": (h, fs),
                           pre + "ws_d": (fs, h)})
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
    bfloat16 in the cell; float32 where a test wants the program's
    rounding out of the comparison)."""
    store = {"bfloat16": BF16, "float32": F32}[cfg["torch_dtype"]]
    kind = name.rsplit(".", 1)[-1]
    key = jax.random.fold_in(key, index)
    if kind in ("ln1", "ln2", "norm", "q_norm", "kv_norm", "k_norm"):
        return jnp.ones(shape, store)
    if kind == "k_norm_b":
        return jnp.zeros(shape, store)
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


def layer_norm(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(F32) \
        + b.astype(F32)


# -- positions --------------------------------------------------------------------

def inv_freq(cfg):
    """YaRN: base^(-2i/dr), divided by `factor` where i lies below the
    correction range, unchanged above it, a linear ramp in between; the
    range from beta_fast and beta_slow rotations over the original
    context. Computed in float64."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    rs = cfg["rope_scaling"]
    factor = float(rs["factor"])
    original = rs["original_max_position_embeddings"]
    freqs = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def corr(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return freqs / factor * ramp + freqs * (1.0 - ramp)


def softmax_scale(cfg):
    m = 0.1 * cfg["rope_scaling"].get("mscale_all_dim", 1.0) \
        * math.log(cfg["rope_scaling"]["factor"]) + 1.0
    return sizes(cfg)["qk"] ** -0.5 * m * m


def _cos_sin(cfg, t):
    ang = np.arange(t, dtype=np.float64)[:, None] * inv_freq(cfg)[None, :]
    return jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)


def rotary_pairs(x, cos, sin):
    """x [T, .., dr]: pairs (2i, 2i + 1) turned by position * freq_i."""
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (-1,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def rotary_halves(x, cos, sin, dr):
    """x [T, .., D]: pairs (i, i + dr / 2) of its first dr dims turned,
    the rest pass."""
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (-1,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    a, b = x[..., :dr // 2], x[..., dr // 2:dr]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., dr:]], axis=-1)


# -- attention --------------------------------------------------------------------

def attention(cfg, p, h, precision):
    """The attention half of a block on h [T, H]. Every head's keys and
    values are made once for all T positions; queries, the indexer's
    scores and the attention's a block of rows at a time."""
    t = h.shape[0]
    eps = cfg["rms_norm_eps"]
    nh, kvr = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
    cos, sin = _cos_sin(cfg, t)
    cq = rms_norm(linear(h, p["wq_a"], precision), p["q_norm"], eps)
    kv = linear(h, p["wkv_a"], precision)
    c = rms_norm(kv[:, :kvr], p["kv_norm"], eps)
    k_pe = rotary_pairs(kv[:, kvr:], cos, sin)
    wkv = p["wkv_b"].reshape(kvr, nh, dn + dv)
    k_nope = linear(c, wkv[..., :dn].reshape(kvr, -1), precision) \
        .reshape(t, nh, dn)
    v = linear(c, wkv[..., dn:].reshape(kvr, -1), precision).reshape(t, nh, dv)
    ki = rotary_halves(layer_norm(linear(h, p["wk_idx"], precision),
                                  p["k_norm"], p["k_norm_b"], 1e-6),
                       cos, sin, dr)
    wi = linear(h, p["w_idx"], precision) / math.sqrt(ih * idim)
    keep = min(cfg["index_topk"], t)
    scale = softmax_scale(cfg)
    block = min(QUERY_BLOCK, t)
    pad = -t % block

    def blocked(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((-1, block) + a.shape[1:])

    def rows(args):
        cq_b, wi_b, cos_b, sin_b, first = args
        q = linear(cq_b, p["wq_b"], precision).reshape(block, nh, dn + dr)
        q_nope, q_pe = q[..., :dn], rotary_pairs(q[..., dn:], cos_b, sin_b)
        qi = rotary_halves(linear(cq_b, p["wq_idx"], precision)
                           .reshape(block, ih, idim), cos_b, sin_b, dr)
        row = first + jnp.arange(block)[:, None]
        causal = jnp.arange(t)[None, :] <= row
        index = jnp.einsum("qhd,kd->qhk", qi, ki, precision=HIGHEST)
        index = jnp.einsum("qhk,qh->qk", jnp.maximum(index, 0.0), wi_b,
                           precision=HIGHEST)
        index = jnp.where(causal, index, -jnp.inf)
        _, top = jax.lax.top_k(index, keep)
        chosen = jnp.zeros((block, t), bool).at[
            jnp.arange(block)[:, None], top].set(True) & causal
        s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope, precision=HIGHEST)
             + jnp.einsum("qhd,kd->hqk", q_pe, k_pe,
                          precision=HIGHEST)) * scale
        s = jnp.where(chosen[None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", pr, v, precision=HIGHEST)
    out = jax.lax.map(rows, (blocked(cq), blocked(wi), blocked(cos),
                             blocked(sin), jnp.arange(-(-t // block)) * block))
    return linear(out.reshape(-1, nh * dv)[:t], p["wo"], precision)


# -- the MLPs -----------------------------------------------------------------------

def route(cfg, p, u, precision):
    """(chosen expert ids [T, k] over the published width, their weights
    [T, k]): choice by `s + b_corr` among the kept groups' experts,
    weights from `s` alone, normalised over all chosen, held here or
    not, times the routed scaling factor."""
    z = sizes(cfg)
    s = jax.nn.sigmoid(linear(u, p["router"], precision))
    choice = s + p["b_corr"].astype(F32)[None, :]
    t, e, groups = u.shape[0], z["router_width"], cfg["n_group"]
    group_score = jnp.sort(choice.reshape(t, groups, e // groups),
                           axis=-1)[..., -2:].sum(-1)
    _, kept = jax.lax.top_k(group_score, cfg["topk_group"])
    in_kept = (jnp.arange(e)[None, :, None] // (e // groups)
               == kept[:, None, :]).any(-1)
    _, idx = jax.lax.top_k(jnp.where(in_kept, choice, -jnp.inf), z["top_k"])
    weights = jnp.take_along_axis(s, idx, axis=1)
    if cfg.get("norm_topk_prob", True):
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return idx, weights * cfg["routed_scaling_factor"]


def swiglu(u, wg, wu, wd, precision):
    return linear(jax.nn.silu(linear(u, wg, precision))
                  * linear(u, wu, precision), wd, precision)


def moe(cfg, p, u, precision, held=None, shared=True):
    """u [T, H] -> [T, H]: the part of the routed sum that the experts
    whose weights `p` holds give (`held` = (first, count) of them;
    default: the configuration's), plus the shared expert."""
    z = sizes(cfg)
    first, count = held if held is not None else (z["first"], z["held"])
    idx, weights = route(cfg, p, u, precision)
    local = idx - first
    dense = jnp.zeros((u.shape[0], count + 1), F32).at[
        jnp.arange(u.shape[0])[:, None],
        jnp.where((local >= 0) & (local < count), local, count)].add(weights)

    def one_expert(acc, xs):
        w1, w3, w2, col = xs
        return acc + col[:, None] * swiglu(u, w1, w3, w2, precision), None
    routed, _ = jax.lax.scan(one_expert, jnp.zeros(u.shape, F32),
                             (p["w1"], p["w3"], p["w2"], dense[:, :count].T))
    if shared:
        routed = routed + swiglu(u, p["ws_g"], p["ws_u"], p["ws_d"],
                                 precision)
    return routed


# -- the whole model ------------------------------------------------------------------------

def layer_params(weights, i):
    pre = f"layers.{i}."
    return {k[len(pre):]: v for k, v in weights.items() if k.startswith(pre)}


def block(cfg, l, p, x, precision):
    eps = cfg["rms_norm_eps"]
    x = x + attention(cfg, p, rms_norm(x, p["ln1"], eps), precision)
    h2 = rms_norm(x, p["ln2"], eps)
    if is_sparse(cfg, l):
        return x + moe(cfg, p, h2, precision)
    return x + swiglu(h2, p["wg"], p["wu"], p["wd"], precision)


def _key(cfg):
    """A configuration as a hashable key of the jit caches below."""
    return json.dumps(cfg, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _jit_block(key, sparse, precision):
    cfg = json.loads(key)
    layer = cfg["first_k_dense_replace"] if sparse else 0
    return jax.jit(lambda p, x: block(cfg, layer, p, x, precision))


@functools.lru_cache(maxsize=None)
def _jit_head(key, precision):
    cfg = json.loads(key)
    return jax.jit(lambda norm_w, head, x: linear(
        rms_norm(x, norm_w, cfg["rms_norm_eps"]), head, precision))


def hidden_states(cfg, weights, ids, precision="f32"):
    """The residual stream after the last layer, [T, H] float32."""
    key = _key(cfg)
    x = jnp.take(weights["embed"], ids, axis=0).astype(F32)
    for l in range(cfg["num_hidden_layers"]):
        x = _jit_block(key, is_sparse(cfg, l), precision)(
            layer_params(weights, l), x)
    return x


def logits_at(cfg, weights, ids, rows, precision="f32"):
    """Full causal forward over `ids` [T] (one sequence, padded behind as
    the caller likes) and the logits [len(rows), V] of positions `rows`."""
    x = hidden_states(cfg, weights, ids, precision)
    return _jit_head(_key(cfg), precision)(
        weights["norm"], weights["head"], jnp.take(x, rows, axis=0))
