"""The `lfm2_moe` family: gated short convolutions + GQA attention with
QK-norm + sparse SwiGLU experts (LiquidAI LFM2-8B-A1B is the published
member trained here).

Block `l` on x [tokens, d], no bias anywhere:

    u = RMSNorm(x; operator_norm)
    x = x + ShortConv(u)   if layer_types[l] == "conv"   else   x + Attention(u)
    v = RMSNorm(x; ffn_norm)
    x = x + W_d(silu(W_g v) * (W_u v))      if l < num_dense_layers
    x = x + MoE(v)                          otherwise

- ShortConv: `[B, C, z] = split(u W_in, 3)`; `s = B * z`; `c_t = sum_j
  k[:, j] s_{t-(L-1)+j}` (depthwise, causal, `conv_L_cache` taps, zeros
  before the sequence); out = `(C * c) W_out`.
- Attention: GQA; q and k normalised per head (RMSNorm over the head's
  width, one weight vector each a layer) BEFORE the rotary term
  (rotate-half); causal softmax(q k^T / sqrt(head_dim)) v through the
  flash kernel.
- MoE: `s = sigmoid(v W_r)` in float32 over the whole published router;
  `choice = top_k(s + b)` (the bias takes part in the choice only, is
  held fixed and is a buffer: no gradient, no optimizer state);
  `w = s[choice] / (sum + 1e-6) * routed_scaling_factor`; out = `sum_k
  w_k W2_e(silu(W1_e v) * (W3_e v))`.
- Ends: `x = E[ids]`; `RMSNorm(x; norm)`; logits = `x E^T` (tied).

The blocks are pure functions of (weights, activations);
`Lfm2ForCausalLM.forward` calls them, and the model trains through
`pt.jit.TrainStep` as any `Layer` does. The expert layer is told which
experts it holds (`experts_held = (first, count)`, as `nemotron_h`'s):
the pairs of held experts are sorted by expert and run through
`grouped_matmul_sorted`, forward and backward, over exactly the rows each
expert got: no capacity, no dropped pair. Pairs of experts held
elsewhere are not computed and nothing stands in for them. The routing,
the sort and the counts are `nemotron_h`'s own functions.
"""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp

from ..framework.tensor import Parameter, Tensor
from ..nn.layer.layers import Layer
from .decode import _rms
from .llama import LlamaPretrainingCriterion, _rope_tables
from .nemotron_h import (NO_COUNTS, merge_counts, moe_route, pair_counts,
                         sort_pairs)

__all__ = ["Lfm2Config", "Lfm2ForCausalLM", "Lfm2PretrainingCriterion",
           "lfm2_tiny"]

F32 = jnp.float32
COUNTERS = ("moe_pairs_here", "moe_pairs_all", "moe_experts_touched",
            "moe_max_load")
# rows of a grouped kernel's tile: a step brings each expert about a
# thousand rows, and 512 against a weight tile run at 106 TFLOP/s forward
# where the serving default of 128 is bound by re-reading the tile (64)
ROW_TILE = 512


class Lfm2Config:
    """The published keys of an `lfm2_moe` `config.json` under their own
    names, plus `experts_held` (which routed experts this chip holds;
    default all) and `dtype`."""

    def __init__(self, vocab_size=65536, hidden_size=2048,
                 intermediate_size=7168, num_hidden_layers=None,
                 layer_types=("conv", "conv", "full_attention"),
                 num_dense_layers=2, num_attention_heads=32,
                 num_key_value_heads=8, head_dim=None, conv_L_cache=3,
                 conv_bias=False, num_experts=32, num_experts_per_tok=4,
                 moe_intermediate_size=1792, norm_topk_prob=True,
                 routed_scaling_factor=1.0, use_expert_bias=True,
                 norm_eps=1e-5, rope_theta=1000000.0,
                 max_position_embeddings=128000, tie_word_embeddings=True,
                 experts_held=None, dtype="float32"):
        layer_types = tuple(layer_types)
        if set(layer_types) - {"conv", "full_attention"} or not layer_types:
            raise ValueError(f"layer_types {layer_types!r} holds a kind "
                             f"other than conv and full_attention")
        if num_hidden_layers is not None and \
                int(num_hidden_layers) != len(layer_types):
            raise ValueError(
                f"num_hidden_layers {num_hidden_layers} against "
                f"{len(layer_types)} layer_types")
        if conv_bias or not tie_word_embeddings or not use_expert_bias:
            raise NotImplementedError(
                "conv_bias, an untied head and a router without its "
                "choice-only bias are not among the published members")
        if num_attention_heads % num_key_value_heads:
            raise ValueError("num_attention_heads must divide into "
                             "num_key_value_heads")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.layer_types = layer_types
        self.num_hidden_layers = len(layer_types)
        self.num_dense_layers = min(int(num_dense_layers),
                                    self.num_hidden_layers)
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim or hidden_size // num_attention_heads
        self.conv_L_cache = int(conv_L_cache)
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.norm_topk_prob = bool(norm_topk_prob)
        self.norm_topk_eps = 1e-6
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_eps = norm_eps
        self.rope_theta = float(rope_theta)
        self.max_position_embeddings = max_position_embeddings
        first, count = experts_held or (0, num_experts)
        if not 0 <= first <= first + count <= num_experts:
            raise ValueError(f"experts_held {(first, count)} outside the "
                             f"router's {num_experts}")
        self.experts_held = (int(first), int(count))
        self.dtype = dtype

    def is_sparse(self, i):
        return i >= self.num_dense_layers

    def param_shapes(self):
        """Ordered {parameter name: (shape, float32 only?)}. Matrices are
        [in, out]; an expert stack is [experts held, in, out]."""
        d, v = self.hidden_size, self.vocab_size
        nh, nkv, hd = (self.num_attention_heads, self.num_key_value_heads,
                       self.head_dim)
        f, fe = self.intermediate_size, self.moe_intermediate_size
        held = self.experts_held[1]
        out = {"embed": ((v, d), False)}
        for i, kind in enumerate(self.layer_types):
            pre = f"layers.{i}."
            out[pre + "operator_norm"] = ((d,), False)
            if kind == "conv":
                out.update({pre + "in_proj": ((d, 3 * d), False),
                            pre + "conv_w": ((d, self.conv_L_cache), False),
                            pre + "out_proj": ((d, d), False)})
            else:
                out.update({pre + "wq": ((d, nh * hd), False),
                            pre + "wk": ((d, nkv * hd), False),
                            pre + "wv": ((d, nkv * hd), False),
                            pre + "q_norm": ((hd,), False),
                            pre + "k_norm": ((hd,), False),
                            pre + "wo": ((nh * hd, d), False)})
            out[pre + "ffn_norm"] = ((d,), False)
            if self.is_sparse(i):
                out.update({pre + "router": ((d, self.num_experts), True),
                            pre + "w1": ((held, d, fe), False),
                            pre + "w3": ((held, d, fe), False),
                            pre + "w2": ((held, fe, d), False)})
            else:
                out.update({pre + "wg": ((d, f), False),
                            pre + "wu": ((d, f), False),
                            pre + "wd": ((f, d), False)})
        out["norm"] = ((d,), False)
        return out

    def buffer_shapes(self):
        """Ordered {buffer name: shape}: the routers' choice-only bias,
        float32, one a sparse layer. Held fixed: no gradient, no
        optimizer state."""
        return {f"layers.{i}.b_corr": (self.num_experts,)
                for i in range(self.num_hidden_layers) if self.is_sparse(i)}


def lfm2_tiny(**overrides):
    """A CPU-sized member with every mechanism: both operator kinds, a
    leading dense layer, 8 experts top-2 of which any share can be held."""
    base = dict(vocab_size=96, hidden_size=32, intermediate_size=48,
                layer_types=("conv", "full_attention", "conv",
                             "full_attention"),
                num_dense_layers=1, num_attention_heads=4,
                num_key_value_heads=2, num_experts=8,
                num_experts_per_tok=2, moe_intermediate_size=24,
                max_position_embeddings=64)
    base.update(overrides)
    return Lfm2Config(**base)


# -- the operators, as functions of (weights, activations) ---------------------------

def short_conv(cfg, p, u):
    """The gated short convolution on u [B, T, d]."""
    b, c, z = jnp.split(u @ p["in_proj"].astype(u.dtype), 3, axis=-1)
    s = b * z
    taps, t = cfg.conv_L_cache, u.shape[1]
    padded = jnp.pad(s, ((0, 0), (taps - 1, 0), (0, 0)))
    w = p["conv_w"].astype(F32)
    conv = sum(padded[:, j:j + t].astype(F32) * w[:, j] for j in range(taps))
    return (c * conv.astype(u.dtype)) @ p["out_proj"].astype(u.dtype)


def rope(x, theta):
    """Rotate-half rotary term on x [B, T, heads, hd], positions 0..T-1,
    in float32."""
    cos, sin = _rope_tables(x.shape[-1], x.shape[1], theta)
    xf = x.astype(F32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return (xf * cos[None, :, None, :]
            + rot * sin[None, :, None, :]).astype(x.dtype)


def causal_attention(q, k, v):
    """softmax(q k^T / sqrt(hd)) v, causal, on [B, T, heads, hd] with K
    and V at the query heads' count: the flash kernel where it serves
    the shape (nn.functional's own rule), else the plain product."""
    from ..nn.functional.flash_attention import _use_pallas
    scale = 1.0 / math.sqrt(q.shape[-1])
    if _use_pallas(q):
        from ..kernels.pallas.flash_attention import flash_attention_jax
        return flash_attention_jax(q, k, v, causal=True, scale=scale)
    t = q.shape[1]
    att = jnp.einsum("bqhd,bkhd->bhqk", q.astype(F32), k.astype(F32)) * scale
    att = jnp.where(jnp.tril(jnp.ones((t, t), bool)), att, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(att, axis=-1),
                      v.astype(F32)).astype(q.dtype)


def attention(cfg, p, u):
    """GQA with per-head RMSNorm on q and k before the rotary term, on
    u [B, T, d]."""
    b, t, _ = u.shape
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    q = (u @ p["wq"].astype(u.dtype)).reshape(b, t, nh, hd)
    k = (u @ p["wk"].astype(u.dtype)).reshape(b, t, nkv, hd)
    v = (u @ p["wv"].astype(u.dtype)).reshape(b, t, nkv, hd)
    q = rope(_rms(q, p["q_norm"], cfg.norm_eps), cfg.rope_theta)
    k = rope(_rms(k, p["k_norm"], cfg.norm_eps), cfg.rope_theta)
    k, v = (jnp.repeat(a, nh // nkv, axis=2) for a in (k, v))
    with jax.named_scope("attn"):
        o = causal_attention(q, k, v)
    return o.reshape(b, t, nh * hd) @ p["wo"].astype(u.dtype)


def swiglu(p, v):
    gate = jax.nn.silu(v @ p["wg"].astype(v.dtype))
    return (gate * (v @ p["wu"].astype(v.dtype))) @ p["wd"].astype(v.dtype)


def _take_rows(v, token):
    return jnp.take(v, token, axis=0)


def _sum_pairs(y, back):
    """r[t] = sum_j y[back[t, j]], summed in float32, for y [T * k, d]
    and the places back [T, k] of each token's pairs."""
    t, k = back.shape
    return jnp.sum(jnp.take(y, back.reshape(-1), axis=0)
                   .reshape(t, k, -1).astype(F32), axis=1).astype(y.dtype)


# The way to the sorted buffer and back, each the other's transpose. Plain
# differentiation would turn either gather into a scatter-add over the
# tokens; with the sort's permutation and its inverse both at hand, both
# directions of both are gathers.

@jax.custom_vjp
def rows_to_experts(v, token, back):
    """xs[i] = v[token[i]]: each sorted place's token row."""
    return _take_rows(v, token)


rows_to_experts.defvjp(
    lambda v, token, back: (_take_rows(v, token), back),
    lambda back, g: (_sum_pairs(g, back), None, None))


@jax.custom_vjp
def rows_from_experts(y, token, back):
    """r[t] = the sum of token t's pairs' rows of y."""
    return _sum_pairs(y, back)


rows_from_experts.defvjp(
    lambda y, token, back: (_sum_pairs(y, back), token),
    lambda token, g: (_take_rows(g, token), None, None))


def moe_experts(cfg, p, v, idx, weights):
    """The held experts' part of the routed sum for v [T, d]: `sum over
    chosen k held here of w_k W2_k(silu(W1_k v) * (W3_k v))`. The pairs
    are sorted by held expert (pairs of experts held elsewhere go last
    and are not computed) and the three products run grouped over exactly
    the rows each expert got: no capacity, no dropped pair, however the
    routing leans. Returns (r [T, d], counts int32 [4]: `COUNTERS`)."""
    from ..kernels.pallas.grouped_matmul import grouped_matmul_sorted
    t, k = idx.shape
    order, sizes, rows = sort_pairs(cfg, idx)
    n_here = jnp.sum(sizes, dtype=jnp.int32)
    place = jnp.arange(t * k, dtype=jnp.int32)
    back = jnp.zeros((t * k,), jnp.int32).at[order].set(place).reshape(t, k)
    token = order // k
    inside = (place < n_here)[:, None]
    grouped = dict(row_tile=ROW_TILE, out_dtype=v.dtype)
    xs = rows_to_experts(v, token, back)

    def written(a):
        # the kernel leaves the rows past the held pairs unwritten: select
        # them away BEFORE anything multiplies them, or a backward pass
        # multiplies their zero cotangent by whatever the memory held
        return jnp.where(inside, a, jnp.zeros((), a.dtype))
    with jax.named_scope("moe.experts"):
        gate = written(grouped_matmul_sorted(xs, p["w1"], sizes, **grouped))
        up = written(grouped_matmul_sorted(xs, p["w3"], sizes, **grouped))
        h = (jax.nn.silu(gate.astype(F32)) * up.astype(F32)).astype(v.dtype)
        y = written(grouped_matmul_sorted(h, p["w2"], sizes, **grouped))
    w_sorted = jnp.take(weights.reshape(-1), order)
    wy = (y.astype(F32) * w_sorted[:, None]).astype(v.dtype)
    r = rows_from_experts(wy, token, back)
    return r, pair_counts(n_here, sizes, rows, k)


def sparse_moe(cfg, p, v):
    """The expert layer on v [B, T, d]: (out, counts)."""
    b, t, d = v.shape
    flat = v.reshape(b * t, d)
    with jax.named_scope("moe.route"):
        idx, weights = moe_route(cfg, p, flat)
    r, counts = moe_experts(cfg, p, flat, idx, weights)
    return r.reshape(b, t, d), counts


def forward(cfg, params, ids):
    """Full causal forward over ids [B, T]: (logits [B, T, V] float32,
    counts int32 [4] over the sparse layers)."""
    x = jnp.take(params["embed"], ids, axis=0)
    counts = jnp.asarray(NO_COUNTS)
    for i, kind in enumerate(cfg.layer_types):
        p = params["layers"][i]
        u = _rms(x, p["operator_norm"], cfg.norm_eps)
        if kind == "conv":
            with jax.named_scope("lfm2.conv"):
                x = x + short_conv(cfg, p, u)
        else:
            x = x + attention(cfg, p, u)
        v = _rms(x, p["ffn_norm"], cfg.norm_eps)
        if cfg.is_sparse(i):
            out, c = sparse_moe(cfg, p, v)
            x, counts = x + out, merge_counts(counts, c)
        else:
            x = x + swiglu(p, v)
    x = _rms(x, params["norm"], cfg.norm_eps)
    logits = jnp.einsum("btd,vd->btv", x, params["embed"],
                        preferred_element_type=F32)
    return logits, counts


# -- the dygraph model ----------------------------------------------------------------

class Lfm2ForCausalLM(Layer):
    """The dygraph model: parameters under the names of
    `Lfm2Config.param_shapes`, the routers' fixed bias as buffers
    (`buffer_shapes`), `forward(input_ids [B, T])` gives logits [B, T, V]
    float32. `arrays` ({name: jax array}, parameters and buffers) become
    the model's own as they are, without a second copy on the device;
    without it they are drawn (normal(0, 0.02), norm weights 1, conv taps
    uniform(-1/2, 1/2), the bias normal(0, 0.01)), which is what the CPU
    tests use.

    `moe_counts` (a buffer, int32 [4]: `COUNTERS`) is written by every
    forward: what the sparse layers counted, summed over the layers (the
    load: the largest). `pt.jit.TrainStep` returns it with the step's
    other buffers and puts it on `train_step:call` (`step_counters`)."""

    step_counters = {"moe_counts": COUNTERS}

    def __init__(self, config: Lfm2Config, arrays=None):
        super().__init__()
        self.config = config
        dt = jnp.bfloat16 if config.dtype == "bfloat16" else jnp.float32
        shapes = {name: (shape, F32 if f32_only else dt)
                  for name, (shape, f32_only)
                  in config.param_shapes().items()}
        buffers = {name: (shape, F32)
                   for name, shape in config.buffer_shapes().items()}
        if arrays is not None:
            missing = (set(shapes) | set(buffers)) - set(arrays)
            if missing:
                raise KeyError(f"no array for {sorted(missing)}")
        rng = np.random.default_rng(0)
        self._names = {}
        for name, (shape, want) in {**shapes, **buffers}.items():
            if arrays is not None:
                data = arrays[name]
                if tuple(data.shape) != tuple(shape) or data.dtype != want:
                    raise ValueError(
                        f"{name}: given {tuple(data.shape)} {data.dtype}, "
                        f"the model wants {tuple(shape)} {want.__name__}")
            else:
                data = jnp.asarray(self._draw(rng, name, shape), want)
            attr = name.replace(".", "_")
            self._names[name] = attr
            if name in buffers:
                self.register_buffer(attr, Tensor(data, stop_gradient=True))
            else:
                setattr(self, attr, Parameter(data))
        self.register_buffer("moe_counts", Tensor(jnp.asarray(NO_COUNTS),
                                                  stop_gradient=True))

    @staticmethod
    def _draw(rng, name, shape):
        kind = name.rsplit(".", 1)[-1]
        if kind.endswith("norm"):
            return np.ones(shape, np.float32)
        if kind == "conv_w":
            return rng.uniform(-0.5, 0.5, shape)
        if kind == "b_corr":
            return rng.normal(0.0, 0.01, shape)
        return rng.normal(0.0, 0.02, shape)

    def array(self, name):
        """The array of the parameter or buffer `name`."""
        return getattr(self, self._names[name])._data

    def param_tree(self):
        """Parameters and buffers as the operators take them: {"embed",
        "norm", "layers": [one dict a block]}; the arrays themselves, no
        copy."""
        tree = {"layers": [{} for _ in self.config.layer_types]}
        for name in self._names:
            data = self.array(name)
            if name.startswith("layers."):
                _, i, leaf = name.split(".")
                tree["layers"][int(i)][leaf] = data
            else:
                tree[name] = data
        return tree

    def forward(self, input_ids):
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        logits, counts = forward(self.config, self.param_tree(),
                                 ids.astype(jnp.int32))
        self.moe_counts._data = counts
        return Tensor(logits)


class Lfm2PretrainingCriterion(LlamaPretrainingCriterion):
    """Mean next-token cross entropy over the vocabulary held; the caller
    pre-shifts the labels. No auxiliary loss."""
