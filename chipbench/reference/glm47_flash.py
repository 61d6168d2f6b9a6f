"""Plain reference for the `glm4_moe_lite` family (zai-org GLM-4.7-Flash:
latent attention over every causal key, sparse experts with a sigmoid
router and a shared expert, and a multi-token-prediction layer).

Straightforward `jax.numpy` in float32 at `highest` matmul precision. It
imports nothing of the program and takes nothing the program made:
weights come from `make_weights(cfg, seed)` here, which the harness also
hands to the program. No kernels, no cache, no batching, no absorbed
form: one sequence, one layer at a time; every head's keys and values
expanded from the latent rows; the attention a block of queries after
another against every key, causally masked (so that 13,312 tokens fit:
a block's scores are [heads, block, keys], never [T, T] for all heads);
the routed experts one expert at a time over every token. A verify
pass's second row (its draft in the place of the token at the draft's
position) goes through every layer against the keys and values of the
sequence's rows before it, made again from the layer's inputs
(`replaced_logits_at`).

Layer `l` on x [T, hidden], RMS = rmsnorm(eps `rms_norm_eps`):

    h = RMS(x, ln1)
    cq = RMS(h Wqa, q_norm);  q = cq Wqb -> [T, nh, dn + dr] = [q_nope | q_pe]
    [c | k_pe] = h Wkva;  c = RMS(c, kv_norm)
    q_pe, k_pe: rotary, pairs (2i, 2i + 1), angle t * theta^(-2i / dr)
    [k_nope | v] = c Wkvb -> [T, nh, dn + dv]
    s_h(t, j) = (q_nope_h . k_nope_h(j) + q_pe_h . k_pe(j)) (dn + dr)^-1/2,
                j <= t
    x = x + (softmax_j s_h  v_h) Wo
    h2 = RMS(x, ln2)
    l < first_k_dense_replace:  x = x + (silu(h2 Wg) * (h2 Wu)) Wd
    else: s = sigmoid(h2 Wr) float32 over all published experts;
          top_k(s + b_corr);  w = s_chosen / sum(s_chosen) * scaling;
          x = x + sum over the chosen experts HELD HERE of
              w_e (silu(h2 W1_e) * (h2 W3_e)) W2_e
              + (silu(h2 Wsg) * (h2 Wsu)) Wsd
    hn = RMS(x, norm);  logits = hn Whead

The MTP layer, row i from the main model's normed last hidden state and
the embedding of the token after it:

    u = Weh [RMS(emb(t_{i+1}), enorm) ; RMS(hn_i, hnorm)]
    u = layer L (an expert layer, its own weights `mtp.*`) on u
    draft logits_i = RMS(u_i, mtp norm) Whead            predicts t_{i+2}

What the experts held elsewhere would add is left out (the configuration
holds `n_routed_experts` of `published.n_routed_experts`, from
`experts_first`; here all of them). Departures and choices, each listed
in the configuration's `assumed`: seeded weights (normal(0,
`initializer_range`) matrices in bfloat16, norm weights 1, the router
with its choice bias normal(0, 0.01) in float32), the rotary pairing,
the MTP layer's inputs.

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
QUERY_BLOCK = 64
NORMS = ("ln1", "ln2", "norm", "q_norm", "kv_norm", "enorm", "hnorm")


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


def block_shapes(cfg, pre, l):
    """{leaf name: shape} of one layer (`pre` its prefix)."""
    z = sizes(cfg)
    h = cfg["hidden_size"]
    nh, qr, kvr = cfg["num_attention_heads"], cfg["q_lora_rank"], \
        cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = fe * cfg["n_shared_experts"]
    shapes = {pre + "ln1": (h,), pre + "wq_a": (h, qr), pre + "q_norm": (qr,),
              pre + "wq_b": (qr, nh * (dn + dr)),
              pre + "wkv_a": (h, kvr + dr), pre + "kv_norm": (kvr,),
              pre + "wkv_b": (kvr, nh * (dn + dv)), pre + "wo": (nh * dv, h),
              pre + "ln2": (h,)}
    if is_sparse(cfg, l):
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
    return shapes


def leaf_shapes(cfg):
    """Ordered {leaf name: shape}: the main layers, the final norm and the
    head, then the MTP layer under `mtp.`. Matrices are [in, out]; an
    expert stack is [experts held, in, out]."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    layers = cfg["num_hidden_layers"]
    shapes = {"embed": (v, h)}
    for i in range(layers):
        shapes.update(block_shapes(cfg, f"layers.{i}.", i))
    shapes["norm"] = (h,)
    shapes["head"] = (h, v)
    if cfg.get("num_nextn_predict_layers", 0):
        shapes.update({"mtp.enorm": (h,), "mtp.hnorm": (h,),
                       "mtp.eh_proj": (2 * h, h)})
        shapes.update(block_shapes(cfg, "mtp.", layers))
        shapes["mtp.norm"] = (h,)
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
    if kind in NORMS:
        return jnp.ones(shape, store)
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

def _cos_sin(cfg, t):
    """Rotary angles t * theta^(-2i / dr), computed in float64."""
    dim = cfg["qk_rope_head_dim"]
    freqs = float(cfg["rope_theta"]) ** (-np.arange(0, dim, 2,
                                                    dtype=np.float64) / dim)
    ang = np.arange(t, dtype=np.float64)[:, None] * freqs[None, :]
    return jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)


def rotary_pairs(x, cos, sin):
    """x [T, .., dr]: pairs (2i, 2i + 1) turned by position * freq_i."""
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (-1,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def keys_values(cfg, p, h, cos, sin, precision):
    """Every head's keys and values of rows h [T, H] at the positions
    whose rotary angles are cos, sin [T, dr / 2]: (k_nope [T, nh, dn],
    k_pe [T, dr], v [T, nh, dv])."""
    t = h.shape[0]
    eps = cfg["rms_norm_eps"]
    nh, kvr = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    kv = linear(h, p["wkv_a"], precision)
    c = rms_norm(kv[:, :kvr], p["kv_norm"], eps)
    k_pe = rotary_pairs(kv[:, kvr:], cos, sin)
    wkv = p["wkv_b"].reshape(kvr, nh, dn + dv)
    k_nope = linear(c, wkv[..., :dn].reshape(kvr, -1), precision) \
        .reshape(t, nh, dn)
    v = linear(c, wkv[..., dn:].reshape(kvr, -1), precision).reshape(t, nh, dv)
    return k_nope, k_pe, v


def attention(cfg, p, h, precision):
    """The attention half of a block on h [T, H]. Every head's keys and
    values are made once for all T positions; queries and the attention
    a block of rows at a time, against every key up to each row."""
    t = h.shape[0]
    eps = cfg["rms_norm_eps"]
    nh = cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    cos, sin = _cos_sin(cfg, t)
    cq = rms_norm(linear(h, p["wq_a"], precision), p["q_norm"], eps)
    k_nope, k_pe, v = keys_values(cfg, p, h, cos, sin, precision)
    scale = sizes(cfg)["qk"] ** -0.5
    block = min(QUERY_BLOCK, t)
    pad = -t % block

    def blocked(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((-1, block) + a.shape[1:])

    def rows(args):
        cq_b, cos_b, sin_b, first = args
        q = linear(cq_b, p["wq_b"], precision).reshape(block, nh, dn + dr)
        q_nope, q_pe = q[..., :dn], rotary_pairs(q[..., dn:], cos_b, sin_b)
        causal = jnp.arange(t)[None, :] <= first + jnp.arange(block)[:, None]
        s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope, precision=HIGHEST)
             + jnp.einsum("qhd,kd->hqk", q_pe, k_pe,
                          precision=HIGHEST)) * scale
        s = jnp.where(causal[None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", pr, v, precision=HIGHEST)
    out = jax.lax.map(rows, (blocked(cq), blocked(cos), blocked(sin),
                             jnp.arange(-(-t // block)) * block))
    return linear(out.reshape(-1, nh * dv)[:t], p["wo"], precision)


# -- the MLPs -----------------------------------------------------------------------

def route(cfg, p, u, precision):
    """(chosen expert ids [T, k] over the published width, their weights
    [T, k]): choice by `s + b_corr`, weights from `s` alone, normalised
    over all chosen, held here or not, times the routed scaling factor.
    One group (`n_group` 1): no group limit."""
    z = sizes(cfg)
    s = jax.nn.sigmoid(linear(u, p["router"], precision))
    _, idx = jax.lax.top_k(s + p["b_corr"].astype(F32)[None, :], z["top_k"])
    weights = jnp.take_along_axis(s, idx, axis=1)
    if cfg.get("norm_topk_prob", True):
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return idx, weights * cfg["routed_scaling_factor"]


def swiglu(u, wg, wu, wd, precision):
    return linear(jax.nn.silu(linear(u, wg, precision))
                  * linear(u, wu, precision), wd, precision)


def moe(cfg, p, u, precision):
    """u [T, H] -> [T, H]: the part of the routed sum that the experts
    held here give, plus the shared expert."""
    z = sizes(cfg)
    first, count = z["first"], z["held"]
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
    return routed + swiglu(u, p["ws_g"], p["ws_u"], p["ws_d"], precision)


# -- the whole model ------------------------------------------------------------------------

def layer_params(weights, pre):
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
def _jit_norm(key):
    cfg = json.loads(key)
    return jax.jit(lambda w, x: rms_norm(x, w, cfg["rms_norm_eps"]))


@functools.lru_cache(maxsize=None)
def _jit_head(key, precision):
    cfg = json.loads(key)
    return jax.jit(lambda norm_w, head, x: linear(
        rms_norm(x, norm_w, cfg["rms_norm_eps"]), head, precision))


@functools.lru_cache(maxsize=None)
def _jit_mtp_input(key, precision):
    cfg = json.loads(key)
    eps = cfg["rms_norm_eps"]
    return jax.jit(lambda m, emb, hn: linear(jnp.concatenate(
        [rms_norm(emb, m["enorm"], eps), rms_norm(hn, m["hnorm"], eps)],
        axis=-1), m["eh_proj"], precision))


# the normed last hidden states of the last sequence asked for, and each
# layer's input, so that the draft logits and the replaced rows of a
# sequence after its logits cost only their own rows
_LAST = {}


def normed_states(cfg, weights, ids, precision="f32"):
    """The main model's last hidden states after its final norm, [T, H]
    float32, for one sequence ids [T]."""
    key = (_key(cfg), np.asarray(ids).tobytes(), precision)
    if _LAST.get("key") != key:
        _LAST.clear()
        x = jnp.take(weights["embed"], ids, axis=0).astype(F32)
        inputs = []
        for l in range(cfg["num_hidden_layers"]):
            inputs.append(x)
            x = _jit_block(key[0], is_sparse(cfg, l), precision)(
                layer_params(weights, f"layers.{l}."), x)
        _LAST.update(key=key, inputs=inputs,
                     hn=_jit_norm(key[0])(weights["norm"], x))
    return _LAST["hn"]


def logits_at(cfg, weights, ids, rows, precision="f32"):
    """Full causal forward over `ids` [T] (one sequence, padded behind as
    the caller likes) and the logits [len(rows), V] of positions `rows`."""
    hn = normed_states(cfg, weights, ids, precision)
    return linear(jnp.take(hn, jnp.asarray(rows), axis=0), weights["head"],
                  precision)


def draft_logits_at(cfg, weights, ids, rows, precision="f32"):
    """The MTP layer's draft logits [len(rows), V] at positions `rows`
    of `ids` [T]: row i from the normed last hidden state at i and the
    embedding of ids[i + 1] (every row asked for lies before the last),
    predicting the token at i + 2."""
    key = _key(cfg)
    hn = normed_states(cfg, weights, ids, precision)
    after = jnp.concatenate([jnp.asarray(ids)[1:], jnp.zeros(1, jnp.int32)])
    m = layer_params(weights, "mtp.")
    u = _jit_mtp_input(key, precision)(
        m, jnp.take(weights["embed"], after, axis=0).astype(F32), hn)
    u = _jit_block(key, True, precision)(m, u)
    return _jit_head(key, precision)(m["norm"], weights["head"],
                                     jnp.take(u, jnp.asarray(rows), axis=0))


def replaced_block(cfg, l, p, x, y, at, precision):
    """Layer l on rows y [B, H] that stand in for the rows at positions
    `at` [B] of a sequence whose inputs to the layer are x [T, H]: row b
    attends to the sequence's keys before at[b] and to its own, as if
    its token had taken that position's place."""
    eps = cfg["rms_norm_eps"]
    t = x.shape[0]
    nh = cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    cos, sin = _cos_sin(cfg, t)
    k_nope, k_pe, v = keys_values(cfg, p, rms_norm(x, p["ln1"], eps), cos,
                                  sin, precision)
    h = rms_norm(y, p["ln1"], eps)
    cos_b, sin_b = jnp.take(cos, at, axis=0), jnp.take(sin, at, axis=0)
    own_nope, own_pe, own_v = keys_values(cfg, p, h, cos_b, sin_b, precision)
    cq = rms_norm(linear(h, p["wq_a"], precision), p["q_norm"], eps)
    q = linear(cq, p["wq_b"], precision).reshape(-1, nh, dn + dr)
    q_nope, q_pe = q[..., :dn], rotary_pairs(q[..., dn:], cos_b, sin_b)
    scale = sizes(cfg)["qk"] ** -0.5
    s = (jnp.einsum("bhd,khd->bhk", q_nope, k_nope, precision=HIGHEST)
         + jnp.einsum("bhd,kd->bhk", q_pe, k_pe, precision=HIGHEST)) * scale
    s = jnp.where(jnp.arange(t)[None, None, :] < at[:, None, None], s,
                  -jnp.inf)
    own = (jnp.einsum("bhd,bhd->bh", q_nope, own_nope, precision=HIGHEST)
           + jnp.einsum("bhd,bd->bh", q_pe, own_pe,
                        precision=HIGHEST)) * scale
    pr = jax.nn.softmax(jnp.concatenate([s, own[..., None]], axis=-1),
                        axis=-1)
    out = jnp.einsum("bhk,khd->bhd", pr[..., :t], v, precision=HIGHEST) \
        + pr[..., t:] * own_v
    y = y + linear(out.reshape(-1, nh * dv), p["wo"], precision)
    h2 = rms_norm(y, p["ln2"], eps)
    if is_sparse(cfg, l):
        return y + moe(cfg, p, h2, precision)
    return y + swiglu(h2, p["wg"], p["wu"], p["wd"], precision)


@functools.lru_cache(maxsize=None)
def _jit_replaced(key, sparse, precision):
    cfg = json.loads(key)
    layer = cfg["first_k_dense_replace"] if sparse else 0
    return jax.jit(lambda p, x, y, at: replaced_block(cfg, layer, p, x, y,
                                                      at, precision))


def replaced_logits_at(cfg, weights, ids, rows, tokens, precision="f32"):
    """The main model's logits [len(rows), V] at positions `rows` of ids
    [T], each with its token replaced by the one in `tokens`, one row at
    a time: row r over ids[:r] and tokens[i] at r. A verify pass's
    second row is such a row: its draft at the draft's position, whether
    the draft was accepted or not. The rows go through in blocks of
    QUERY_BLOCK (the last padded with copies of the first)."""
    key = _key(cfg)
    normed_states(cfg, weights, ids, precision)
    inputs = _LAST["inputs"]
    rows, tokens = np.asarray(rows, np.int32), np.asarray(tokens, np.int32)
    n = len(rows)
    pad = -n % QUERY_BLOCK
    rows = np.concatenate([rows, np.repeat(rows[:1], pad)])
    tokens = np.concatenate([tokens, np.repeat(tokens[:1], pad)])
    out = []
    for b in range(0, len(rows), QUERY_BLOCK):
        at = jnp.asarray(rows[b:b + QUERY_BLOCK])
        y = jnp.take(weights["embed"], jnp.asarray(tokens[b:b + QUERY_BLOCK]),
                     axis=0).astype(F32)
        for l in range(cfg["num_hidden_layers"]):
            y = _jit_replaced(key, is_sparse(cfg, l), precision)(
                layer_params(weights, f"layers.{l}."), inputs[l], y, at)
        out.append(_jit_head(key, precision)(weights["norm"],
                                             weights["head"], y))
    return jnp.concatenate(out)[:n]
