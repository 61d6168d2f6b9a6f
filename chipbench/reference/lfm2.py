"""Plain reference for the `lfm2_moe` family (gated short convolutions +
GQA with QK-norm + sparse SwiGLU experts: LiquidAI LFM2-8B-A1B), with
training: loss, gradients, AdamW.

Straightforward `jax.numpy` in float32 at `highest` matmul precision. It
imports nothing of the program and takes nothing the program made:
weights come from `make_weights(cfg, seed)` here, which the harness also
hands to the program. No kernels, no sort, no grouped product: one
sequence and one block at a time, each held expert as a dense product
over every token, masked by the token's weight for it; attention by plain
softmax in blocks of one KV head's query heads and 1024 query rows, so
that no [heads, T, T] array exists.

Block `l` (d = hidden_size, no bias anywhere):

    u = RMSNorm(x; operator_norm)
    x = x + ShortConv(u)  if layer_types[l] == "conv"  else  x + Attention(u)
    v = RMSNorm(x; ffn_norm)
    x = x + W_d(silu(W_g v) * (W_u v))     if l < num_dense_layers
    x = x + MoE(v)                         otherwise

- ShortConv: `[B, C, z] = split(u W_in, 3)` in this order; `s = B * z`;
  `c_t = sum_{j<L} k[:, j] s_{t-(L-1)+j}` (depthwise, causal, L =
  `conv_L_cache` taps, zeros before the sequence); out = `(C * c) W_out`.
- Attention: q [T, heads, hd], k and v [T, kv heads, hd]; `q =
  RMSNorm_hd(q; q_norm)`, `k = RMSNorm_hd(k; k_norm)`; rotate-half RoPE
  on q and k; causal softmax(q k^T / sqrt(hd)) v; `W_o`.
- MoE: `s = sigmoid(v W_r)` over all published experts; `choice =
  top_k(s + b)`; `w = s[choice] / (sum + 1e-6) * routed_scaling_factor`;
  out = `sum over chosen k held here of w_k W2_k(silu(W1_k v) * (W3_k
  v))`. What the experts held elsewhere would add is left out (the
  configuration holds `num_experts` of `published.num_experts`, from
  `experts_first`).
- Ends: `x = E[ids]`; `RMSNorm(x; norm)`; logits = `x E^T` (tied); loss:
  mean next-token cross entropy over the vocabulary held.

Departures and choices, each in the configuration's `assumed`: seeded
weights (normal(0, 0.02) matrices in bfloat16, norm weights 1, conv taps
uniform(-1/2, 1/2), router weights float32, `b` normal(0, 0.01)
float32); `b` is a leaf like the others but is held fixed: its gradient
reads 0, it has no Adam state and it never changes. Parameters,
gradients and Adam's moments are rounded to their storage types where
the configuration stores them so; all arithmetic between is float32.

`precision` selects the arithmetic of the weight matmuls (the router's
too): "f32" is the reference; "fp8" is the control that a `correct`
comparison has to refuse (e4m3 operands, e5m2 gradients).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.llama_dense import (_adamw, _norm, linear, norms,
                                             rms_norm, rope, seed_key)

__all__ = ["make_weights", "norms", "change_norms", "train_steps",
           "logits", "loss_and_grads"]

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
ROWS = 1024          # query rows of one attention block


def head_dim(cfg):
    return cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]


def router_width(cfg):
    return cfg.get("router_width") or \
        cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def held(cfg):
    return cfg.get("experts_first", 0), cfg["num_experts"]


def is_sparse(cfg, i):
    return i >= cfg["num_dense_layers"]


def storage(cfg):
    """The type the configuration stores weights in."""
    return {"bfloat16": jnp.bfloat16,
            "float32": F32}[cfg.get("torch_dtype", "bfloat16")]


def layer_leaves(cfg, i):
    """Ordered {leaf of block i: (shape, dtype)}. Matrices are [in, out];
    an expert stack is [experts held, in, out]."""
    st = storage(cfg)
    d, hd = cfg["hidden_size"], head_dim(cfg)
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    out = {"operator_norm": ((d,), st)}
    if cfg["layer_types"][i] == "conv":
        out.update({"in_proj": ((d, 3 * d), st),
                    "conv_w": ((d, cfg["conv_L_cache"]), st),
                    "out_proj": ((d, d), st)})
    else:
        out.update({"wq": ((d, nh * hd), st), "wk": ((d, nkv * hd), st),
                    "wv": ((d, nkv * hd), st), "q_norm": ((hd,), st),
                    "k_norm": ((hd,), st), "wo": ((nh * hd, d), st)})
    out["ffn_norm"] = ((d,), st)
    if is_sparse(cfg, i):
        n, fe = cfg["num_experts"], cfg["moe_intermediate_size"]
        out.update({"router": ((d, router_width(cfg)), F32),
                    "w1": ((n, d, fe), st), "w3": ((n, d, fe), st),
                    "w2": ((n, fe, d), st),
                    "b_corr": ((router_width(cfg),), F32)})
    else:
        f = cfg["intermediate_size"]
        out.update({"wg": ((d, f), st), "wu": ((d, f), st),
                    "wd": ((f, d), st)})
    return out


def leaf_shapes(cfg):
    shapes = {"embed": ((cfg["vocab_size"], cfg["hidden_size"]),
                        storage(cfg))}
    for i in range(cfg["num_hidden_layers"]):
        shapes.update({f"layers.{i}.{k}": v
                       for k, v in layer_leaves(cfg, i).items()})
    shapes["norm"] = ((cfg["hidden_size"],), storage(cfg))
    return shapes


def frozen(name):
    """A leaf the optimizer never sees."""
    return name.endswith(".b_corr")


def make_leaf(cfg, key, index, name, shape, dtype):
    kind = name.rsplit(".", 1)[-1]
    key = jax.random.fold_in(key, index)
    if kind.endswith("norm"):
        return jnp.ones(shape, dtype)
    if kind == "conv_w":
        return jax.random.uniform(key, shape, F32, -0.5, 0.5).astype(dtype)
    std = 0.01 if kind == "b_corr" else cfg.get("initializer_range", 0.02)
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


def make_weights(cfg, seed):
    """Every leaf, on the device, in one jitted call."""
    shapes = leaf_shapes(cfg)

    @jax.jit
    def gen(key):
        return {name: make_leaf(cfg, key, i, name, shape, dtype)
                for i, (name, (shape, dtype)) in enumerate(shapes.items())}
    return gen(seed_key(seed))


def change_norms(cfg, seed, params):
    """{leaf: norm of (params[leaf] - the seeded leaf)} in one jitted
    call; the seeded values are made again inside it, a leaf at a time."""
    shapes = leaf_shapes(cfg)

    @jax.jit
    def run(key, params):
        return {name: jnp.sqrt(jnp.sum(jnp.square(
            params[name].astype(F32)
            - make_leaf(cfg, key, i, name, shape, dtype).astype(F32))))
            for i, (name, (shape, dtype)) in enumerate(shapes.items())}
    return {k: float(v) for k, v in run(seed_key(seed), params).items()}


# -- the operators on one sequence x [T, d] ------------------------------------------------

def short_conv(cfg, p, u, precision):
    b, c, z = jnp.split(linear(u, p["in_proj"], precision), 3, axis=-1)
    s = b * z
    taps, t = cfg["conv_L_cache"], u.shape[0]
    padded = jnp.pad(s, ((taps - 1, 0), (0, 0)))
    w = p["conv_w"].astype(F32)
    conv = sum(padded[j:j + t] * w[:, j] for j in range(taps))
    return linear(c * conv, p["out_proj"], precision)


def causal_attention(q, k, v):
    """q [T, nh, hd], k and v [T, nkv, hd]: softmax(q k^T / sqrt(hd)) v
    under a causal mask, one KV head's query heads and `ROWS` query rows
    at a time (a backward pass computes a block's scores again)."""
    t, nh, hd = q.shape
    nkv = k.shape[1]
    rows = min(ROWS, t)
    if t % rows:
        raise ValueError(f"{t} tokens do not divide into blocks of {rows}")
    qg = q.reshape(t // rows, rows, nkv, nh // nkv, hd).transpose(2, 0, 3, 1, 4)
    cols = jnp.arange(t)

    def group(args):
        qs, kh, vh = args          # [blocks, rep, rows, hd], [T, hd] x 2

        @jax.checkpoint
        def block(args):
            qb, first = args       # [rep, rows, hd]
            att = jnp.einsum("rqd,kd->rqk", qb, kh,
                             precision=HIGHEST) / np.sqrt(hd)
            mask = cols[None, :] <= (first + jnp.arange(rows))[:, None]
            att = jax.nn.softmax(jnp.where(mask, att, -1e30), axis=-1)
            return jnp.einsum("rqk,kd->rqd", att, vh, precision=HIGHEST)
        return jax.lax.map(block, (qs, jnp.arange(t // rows) * rows))
    out = jax.lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    # [nkv, blocks, rep, rows, hd] -> [T, nh * hd]
    return out.transpose(1, 3, 0, 2, 4).reshape(t, nh * hd)


def attention(cfg, p, u, precision, qk_norm=True):
    t = u.shape[0]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   head_dim(cfg))
    q = linear(u, p["wq"], precision).reshape(t, nh, hd)
    k = linear(u, p["wk"], precision).reshape(t, nkv, hd)
    v = linear(u, p["wv"], precision).reshape(t, nkv, hd)
    if qk_norm:
        q = rms_norm(q, p["q_norm"], cfg["norm_eps"])
        k = rms_norm(k, p["k_norm"], cfg["norm_eps"])
    pos = jnp.arange(t)
    q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos, cfg["rope_theta"])
    return linear(causal_attention(q, k, v), p["wo"], precision)


def route(cfg, p, v, precision):
    """(weights [T, router width] float32: a chosen expert's normalised
    score, 0 elsewhere)."""
    s = jax.nn.sigmoid(linear(v, p["router"], precision))
    _, idx = jax.lax.top_k(s + p["b_corr"].astype(F32)[None, :],
                           cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, idx, axis=1)
    if cfg.get("norm_topk_prob", True):
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-6)
    picked = picked * cfg.get("routed_scaling_factor", 1.0)
    rows = jnp.arange(v.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, idx].set(picked)


def moe(cfg, p, v, precision, share=None):
    """The routed sum over the experts held (`share` = (first, count),
    default the configuration's), each as a dense product over every
    token, scaled by the token's weight for it (0 where not chosen)."""
    first, count = share or held(cfg)
    weights = route(cfg, p, v, precision)[:, first:first + count]

    @jax.checkpoint
    def expert(out, args):
        w1, w3, w2, w = args
        h = jax.nn.silu(linear(v, w1, precision)) * linear(v, w3, precision)
        return out + w[:, None] * linear(h, w2, precision), None
    out, _ = jax.lax.scan(expert, jnp.zeros_like(v),
                          (p["w1"], p["w3"], p["w2"], weights.T))
    return out


def swiglu(p, v, precision):
    gate = jax.nn.silu(linear(v, p["wg"], precision))
    return linear(gate * linear(v, p["wu"], precision), p["wd"], precision)


def block(cfg, i, p, x, precision):
    """Block i on one sequence x [T, d] (float32)."""
    u = rms_norm(x, p["operator_norm"], cfg["norm_eps"])
    if cfg["layer_types"][i] == "conv":
        x = x + short_conv(cfg, p, u, precision)
    else:
        x = x + attention(cfg, p, u, precision)
    v = rms_norm(x, p["ffn_norm"], cfg["norm_eps"])
    if is_sparse(cfg, i):
        return x + moe(cfg, p, v, precision)
    return x + swiglu(p, v, precision)


def layer_params(cfg, weights, i):
    return {k: weights[f"layers.{i}.{k}"] for k in layer_leaves(cfg, i)}


def _freeze(cfg):
    keys = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "num_hidden_layers", "vocab_size",
            "rope_theta", "norm_eps", "head_dim", "conv_L_cache",
            "num_dense_layers", "num_experts", "num_experts_per_tok",
            "moe_intermediate_size", "norm_topk_prob",
            "routed_scaling_factor", "experts_first", "torch_dtype")
    return tuple((k, cfg[k]) for k in keys if k in cfg) + (
        ("layer_types", tuple(cfg["layer_types"])),
        ("router_width", router_width(cfg)))


def _kind(cfg, i):
    """Blocks of one kind share a compiled program."""
    return cfg["layer_types"][i], is_sparse(cfg, i)


def _first_of_kind(cfg, i):
    return next(j for j in range(cfg["num_hidden_layers"])
                if _kind(cfg, j) == _kind(cfg, i))


@functools.lru_cache(maxsize=None)
def _jit_block(frozen_cfg, i, precision):
    cfg = dict(frozen_cfg)
    return jax.jit(lambda p, x: block(cfg, i, p, x, precision))


@functools.lru_cache(maxsize=None)
def _jit_block_vjp(frozen_cfg, i, precision):
    cfg = dict(frozen_cfg)

    def run(p, x, dy):
        p32 = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
        _, pull = jax.vjp(lambda p_, x_: block(cfg, i, p_, x_, precision),
                          p32, x)
        return pull(dy)
    return jax.jit(run)


def _head_logits(cfg, norm_w, embed, x, precision):
    return linear(rms_norm(x, norm_w, cfg["norm_eps"]), embed.T, precision)


@functools.lru_cache(maxsize=None)
def _jit_head_loss(frozen_cfg, precision):
    cfg = dict(frozen_cfg)

    def loss_sum(norm_w, embed, x, labels):
        lg = _head_logits(cfg, norm_w, embed, x, precision)
        lse = jax.nn.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - picked)
    return jax.jit(jax.value_and_grad(loss_sum, argnums=(0, 1, 2)))


def logits(cfg, weights, ids, precision="f32"):
    """Full causal forward over one sequence ids [T]: logits [T, V]."""
    fz = _freeze(cfg)
    x = jnp.take(weights["embed"], jnp.asarray(ids), axis=0).astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        x = _jit_block(fz, _first_of_kind(cfg, i), precision)(
            layer_params(cfg, weights, i), x)
    return _head_logits(cfg, weights["norm"], weights["embed"], x, precision)


def backward(cfg, params, ids, labels, precision, sink):
    """The mean next-token cross entropy over the batch ids, labels
    [B, T]; `sink(leaf, float32 gradient)` is called once a leaf, as soon
    as its gradient is whole (a frozen leaf's is zero), so that no more
    than a block's gradients exist at a time. One sequence and one block
    at a time."""
    fz = _freeze(cfg)
    head_loss = _jit_head_loss(fz, precision)
    n_layers = cfg["num_hidden_layers"]
    n_rows, seq = ids.shape
    inv = 1.0 / (n_rows * seq)
    fwd = [_jit_block(fz, _first_of_kind(cfg, i), precision)
           for i in range(n_layers)]
    vjp = [_jit_block_vjp(fz, _first_of_kind(cfg, i), precision)
           for i in range(n_layers)]
    acts = []                               # acts[r][l]: input of block l
    for r in range(n_rows):
        x = jnp.take(params["embed"], jnp.asarray(ids[r]), axis=0).astype(F32)
        row = []
        for i in range(n_layers):
            row.append(x)
            x = fwd[i](layer_params(cfg, params, i), x)
        row.append(x)
        acts.append(row)
    loss, dxs = 0.0, []
    g_norm = g_embed = 0.0
    for r in range(n_rows):
        val, (gn, ge, dx) = head_loss(params["norm"].astype(F32),
                                      params["embed"].astype(F32),
                                      acts[r][n_layers],
                                      jnp.asarray(labels[r]))
        loss += float(val) * inv
        g_norm, g_embed = g_norm + gn * inv, g_embed + ge * inv
        dxs.append(dx * inv)
    sink("norm", g_norm)
    for i in reversed(range(n_layers)):
        p_i = layer_params(cfg, params, i)
        total = None
        for r in range(n_rows):
            gp, dxs[r] = vjp[i](p_i, acts[r][i], dxs[r])
            total = gp if total is None else jax.tree_util.tree_map(
                jnp.add, total, gp)
        for k in p_i:
            sink(f"layers.{i}.{k}",
                 jnp.zeros_like(total[k]) if frozen(k) else total[k])
        del total, gp, p_i
    for r in range(n_rows):
        g_embed = g_embed.at[jnp.asarray(ids[r])].add(dxs[r])
    sink("embed", g_embed)
    return loss


def loss_and_grads(cfg, params, ids, labels, precision="f32"):
    """(loss, {leaf: float32 gradient}) of `backward`, all kept."""
    grads = {}
    return backward(cfg, params, ids, labels, precision,
                    grads.__setitem__), grads


def train_steps(cfg, seed, batches, opt, steps, precision="f32", rows=None):
    """Follow `steps` optimizer steps from the seeded weights on
    `batches` [(ids, labels)], each [B, T]. Returns {"loss": [per step],
    "grad_norm": {leaf: norm of the first step's gradient}, "change_norm":
    {leaf: norm of (parameters after the last step - seeded parameters)}}.
    `rows` (a slice) keeps only those rows of every batch, the mean taken
    over them: the half-batch fault."""
    mdt = jnp.dtype(opt.get("moment_dtype") or "float32")
    params = make_weights(cfg, seed)
    m = {k: jnp.zeros(a.shape, mdt) for k, a in params.items()
         if not frozen(k)}
    v = {k: jnp.zeros(a.shape, mdt) for k, a in params.items()
         if not frozen(k)}
    hyper = (opt["learning_rate"], opt["beta1"], opt["beta2"],
             opt["epsilon"], opt["weight_decay"])
    losses, grad_norm = [], {}
    for t in range(1, steps + 1):
        ids, labels = batches[t - 1]
        if rows is not None:
            ids, labels = ids[rows], labels[rows]

        def update(name, grad):
            if t == 1:
                grad_norm[name] = float(_norm(
                    grad.astype(params[name].dtype)))
            if not frozen(name):
                params[name], m[name], v[name] = _adamw(
                    params[name], grad, m[name], v[name], float(t), *hyper)
        losses.append(backward(cfg, params, ids, labels, precision, update))
    return {"loss": losses, "grad_norm": grad_norm,
            "change_norm": change_norms(cfg, seed, params)}
