"""The `mimo_v2_flash` family: sliding-window and full attention mixed
5 : 1, each kind with its own KV head count, K rows wider than V rows, a
learned sink in the window layers, and sparse SwiGLU experts behind a
leading dense layer (XiaomiMiMo MiMo-V2-Flash is the published member
served here).

Block `l` on x [tokens, hidden], no bias anywhere:

    kind(l) = full if hybrid_layer_pattern[l] == 0 else window
    h = RMSNorm(x; ln1)
    q = h Wq [nh, dk];  k = h Wk [nkv(kind), dk];  v = (h Wv) * value_scale
    q, k: rotate-half rotary on the first int(partial_rotary_factor * dk)
          dims, base rope_theta (full) | swa_rope_theta (window)
    s_ij = q_i . k_j / sqrt(dk) for j <= i, window: also i - j < window
    full:    p = softmax_j(s);
    window:  p_ij = exp(s_ij) / (sum_j' exp(s_ij') + exp(sink_head))
    x = x + (sum_j p_ij v_j) Wo
    h2 = RMSNorm(x; ln2)
    x = x + Wd(silu(Wg h2) * (Wu h2))            where moe_layer_freq[l] == 0
    x = x + sum over the chosen experts HELD HERE of w_e W2_e(silu(W1_e h2)
            * (W3_e h2))                          elsewhere
    (s = sigmoid(h2 Wr) float32; choice = top_k(s + b_corr); w = s_chosen
    / sum(s_chosen); no group limit, no shared expert)

The operators are pure functions of (weights, activations), written once:
`MimoV2ForCausalLM.forward` (whole sequences) and `WindowPagedDecoder`
(serving: a prompt prefilled in chunks against the cache, then decoded
through it) share the projections, the rotary term, the MLP and the
expert layer; what differs is what a query attends through. Routing, the
sort by held expert and the counts are `nemotron_h`'s own functions, the
grouped product `grouped_matmul_sorted`.

Serving keeps two kinds of KV cache side by side, both reached through
the one `serving.batcher.serve_loop`: the full layers' paged pools, found
through the block tables and priced by the allocator, and for the window
layers a ring of blocks a slot whose size does not grow with the
sequence (`WindowPagedDecoder`).
"""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp

from ..framework.tensor import Parameter, Tensor
from ..nn.layer.layers import Layer
from .decode import _rms
from .nemotron_h import (NO_COUNTS, merge_counts, moe_route, pair_counts,
                         sort_pairs)
from .paged_decode import PagedDecoder

__all__ = ["MimoV2Config", "MimoV2ForCausalLM", "WindowPagedDecoder",
           "mimo_v2_tiny"]

F32 = jnp.float32
FULL, WINDOW = "full", "window"


class MimoV2Config:
    """The published keys of a `mimo_v2_flash` `config.json` that shape
    the language model, under their own names, plus `experts_held`
    (which routed experts this chip holds; default all) and `dtype`."""

    def __init__(self, vocab_size=152576, hidden_size=4096,
                 intermediate_size=16384, moe_intermediate_size=2048,
                 num_hidden_layers=None, hybrid_layer_pattern=(0, 1),
                 moe_layer_freq=None, num_attention_heads=64,
                 num_key_value_heads=4, head_dim=192, v_head_dim=128,
                 swa_num_attention_heads=None, swa_num_key_value_heads=8,
                 swa_head_dim=None, swa_v_head_dim=None, sliding_window=128,
                 partial_rotary_factor=0.334, rope_theta=5e6,
                 swa_rope_theta=1e4, attention_value_scale=0.707,
                 add_swa_attention_sink_bias=True,
                 add_full_attention_sink_bias=False, n_routed_experts=256,
                 num_experts_per_tok=8, norm_topk_prob=True,
                 routed_scaling_factor=None, layernorm_epsilon=1e-5,
                 max_position_embeddings=262144, experts_held=None,
                 dtype="float32"):
        pattern = tuple(int(k) for k in hybrid_layer_pattern)
        if not pattern or set(pattern) - {0, 1}:
            raise ValueError(f"hybrid_layer_pattern {pattern!r} holds a "
                             f"layer kind other than 0 (full) and 1 "
                             f"(window)")
        if num_hidden_layers is not None and \
                int(num_hidden_layers) != len(pattern):
            raise ValueError(
                f"num_hidden_layers {num_hidden_layers} against a pattern "
                f"of {len(pattern)} layers")
        freq = tuple(int(k) for k in (moe_layer_freq
                                      or (0,) + (1,) * (len(pattern) - 1)))
        if len(freq) != len(pattern):
            raise ValueError("moe_layer_freq and hybrid_layer_pattern "
                             "differ in length")
        for name, swa, full in (
                ("swa_num_attention_heads", swa_num_attention_heads,
                 num_attention_heads),
                ("swa_head_dim", swa_head_dim, head_dim),
                ("swa_v_head_dim", swa_v_head_dim, v_head_dim)):
            if swa is not None and swa != full:
                raise NotImplementedError(
                    f"{name} {swa} differs from the full layers' {full}: "
                    f"the layer kinds share the query projection's shape")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.hybrid_layer_pattern = pattern
        self.moe_layer_freq = freq
        self.num_hidden_layers = len(pattern)
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.swa_num_key_value_heads = swa_num_key_value_heads
        self.head_dim, self.v_head_dim = head_dim, v_head_dim
        self.sliding_window = int(sliding_window)
        self.partial_rotary_factor = partial_rotary_factor
        self.rope_theta, self.swa_rope_theta = rope_theta, swa_rope_theta
        self.attention_value_scale = attention_value_scale
        self.add_swa_attention_sink_bias = bool(add_swa_attention_sink_bias)
        self.add_full_attention_sink_bias = \
            bool(add_full_attention_sink_bias)
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = bool(norm_topk_prob)
        self.routed_scaling_factor = float(routed_scaling_factor or 1.0)
        self.layernorm_epsilon = layernorm_epsilon
        self.max_position_embeddings = max_position_embeddings
        first, count = experts_held or (0, n_routed_experts)
        if not 0 <= first <= first + count <= n_routed_experts:
            raise ValueError(f"experts_held {(first, count)} outside the "
                             f"router's {n_routed_experts}")
        self.experts_held = (int(first), int(count))
        self.dtype = dtype

    def kind(self, l):
        return FULL if self.hybrid_layer_pattern[l] == 0 else WINDOW

    @property
    def cache_kinds(self):
        """What each layer keeps a slot between steps: the rule
        `PagedDecoder(model)` picks its engine by."""
        return tuple(self.kind(l) for l in range(self.num_hidden_layers))

    def count(self, kind):
        return self.cache_kinds.count(kind)

    def is_sparse(self, l):
        return bool(self.moe_layer_freq[l])

    @property
    def rotary_dim(self):
        return int(self.partial_rotary_factor * self.head_dim)

    def kv_heads(self, kind):
        return self.num_key_value_heads if kind == FULL \
            else self.swa_num_key_value_heads

    def theta(self, kind):
        return self.rope_theta if kind == FULL else self.swa_rope_theta

    def has_sink(self, kind):
        return self.add_full_attention_sink_bias if kind == FULL \
            else self.add_swa_attention_sink_bias

    def param_shapes(self):
        """Ordered {parameter name: (shape, float32 only?)}. Matrices are
        [in, out]; an expert stack is [experts held, in, out]."""
        h, v = self.hidden_size, self.vocab_size
        nh, dk, dv = self.num_attention_heads, self.head_dim, self.v_head_dim
        f, fe = self.intermediate_size, self.moe_intermediate_size
        held = self.experts_held[1]
        out = {"embed": ((v, h), False)}
        for i in range(self.num_hidden_layers):
            pre, kind = f"layers.{i}.", self.kind(i)
            nkv = self.kv_heads(kind)
            out.update({pre + "ln1": ((h,), False),
                        pre + "wq": ((h, nh * dk), False),
                        pre + "wk": ((h, nkv * dk), False),
                        pre + "wv": ((h, nkv * dv), False),
                        pre + "wo": ((nh * dv, h), False)})
            if self.has_sink(kind):
                out[pre + "sink"] = ((nh,), True)
            out[pre + "ln2"] = ((h,), False)
            if self.is_sparse(i):
                out.update({
                    pre + "router": ((h, self.n_routed_experts), True),
                    pre + "b_corr": ((self.n_routed_experts,), True),
                    pre + "w1": ((held, h, fe), False),
                    pre + "w3": ((held, h, fe), False),
                    pre + "w2": ((held, fe, h), False)})
            else:
                out.update({pre + "wg": ((h, f), False),
                            pre + "wu": ((h, f), False),
                            pre + "wd": ((f, h), False)})
        out["norm"] = ((h,), False)
        out["head"] = ((h, v), False)
        return out


def mimo_v2_tiny(**overrides):
    """A CPU-sized member with every mechanism: both layer kinds with
    their own KV head counts, K rows wider than V rows, a partial rotary
    term, sinks, a leading dense layer, 16 experts top-4 of which any
    share can be held."""
    base = dict(vocab_size=96, hidden_size=32, intermediate_size=64,
                moe_intermediate_size=24,
                hybrid_layer_pattern=(0, 1, 1, 0, 1),
                moe_layer_freq=(0, 1, 1, 1, 1), num_attention_heads=8,
                num_key_value_heads=2, swa_num_key_value_heads=4,
                head_dim=24, v_head_dim=16, sliding_window=8,
                partial_rotary_factor=0.334, n_routed_experts=16,
                num_experts_per_tok=4, max_position_embeddings=128)
    base.update(overrides)
    return MimoV2Config(**base)


# -- the operators, as functions of (weights, activations) ---------------------------

def rope(x, pos, rot, theta):
    """Rotate-half rotary term on the first `rot` dims of x [T, heads,
    D] at positions pos [T]; the other dims pass. In float32."""
    half = rot // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float32) * 2.0 / rot))
    ang = pos.astype(F32)[:, None] * inv[None, :]            # [T, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xf = x.astype(F32)
    x1, x2 = xf[..., :half], xf[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            xf[..., rot:]], axis=-1).astype(x.dtype)


def project(cfg, kind, p, u, pos):
    """(q [T, nh, dk], k [T, nkv, dk], v [T, nkv, dv]) of one layer for
    u [T, H] at positions pos [T]: q and k carry the rotary term of the
    layer's kind, v its scale."""
    t, dtype = u.shape[0], u.dtype
    nh, nkv = cfg.num_attention_heads, cfg.kv_heads(kind)
    dk, dv = cfg.head_dim, cfg.v_head_dim
    q = (u @ p["wq"].astype(dtype)).reshape(t, nh, dk)
    k = (u @ p["wk"].astype(dtype)).reshape(t, nkv, dk)
    v = (u @ p["wv"].astype(dtype)).reshape(t, nkv, dv)
    v = (v.astype(F32) * cfg.attention_value_scale).astype(dtype)
    rot, theta = cfg.rotary_dim, cfg.theta(kind)
    return rope(q, pos, rot, theta), rope(k, pos, rot, theta), v


def attend_chunk(cfg, kind, p, q, k, v, q_start, kv_first=0):
    """Queries q [Tq, nh, dk] at key positions q_start .. q_start + Tq - 1
    against k [Tk, nkv, dk], v [Tk, nkv, dv] from key `kv_first` on: the
    causal triangle in a full layer, the band of `sliding_window` keys
    (the query's own among them) in a window layer, with the layer's
    sink. Never more than a tile of scores (`flash_prefill`). Returns
    [Tq, nh * dv]."""
    from ..kernels.pallas.flash_prefill import flash_prefill_attention
    o = flash_prefill_attention(
        q, k, v, q_start, kv_first,
        window=cfg.sliding_window if kind == WINDOW else None,
        sinks=p["sink"] if cfg.has_sink(kind) else None,
        scale=1.0 / math.sqrt(cfg.head_dim))
    return o.reshape(q.shape[0], -1)


def swiglu(p, v):
    gate = jax.nn.silu(v @ p["wg"].astype(v.dtype))
    return (gate * (v @ p["wu"].astype(v.dtype))) @ p["wd"].astype(v.dtype)


def moe_experts(cfg, p, v, idx, weights, active=None, scope="moe.experts"):
    """The held experts' part of the routed sum for v [T, H]: `sum over
    chosen k held here of w_k W2_k(silu(W1_k v) * (W3_k v))`. The pairs
    are sorted by held expert (pairs of experts held elsewhere, and of
    rows that are not `active`, go last and are not computed) and the
    three products run grouped over exactly the rows each expert got: no
    capacity, no dropped pair. Returns (r [T, H] float32, counts int32
    [4] as `nemotron_h.pair_counts` gives them). The grouped products run
    under the named `scope`."""
    from ..kernels.pallas.grouped_matmul import grouped_matmul_sorted
    t, k = idx.shape
    order, sizes, rows = sort_pairs(cfg, idx, active)
    with jax.named_scope(scope):
        xs = jnp.take(v, order // k, axis=0)
        gate = grouped_matmul_sorted(xs, p["w1"], sizes)
        up = grouped_matmul_sorted(xs, p["w3"], sizes)
        h = (jax.nn.silu(gate) * up).astype(v.dtype)
        y = grouped_matmul_sorted(h, p["w2"], sizes)
    n_here = jnp.sum(sizes, dtype=jnp.int32)
    w_sorted = jnp.take(weights.reshape(-1), order)
    # rows past the held pairs were never written: select, do not scale
    wy = jnp.where((jnp.arange(t * k, dtype=jnp.int32) < n_here)[:, None],
                   y * w_sorted[:, None], 0.0)
    back = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    r = jnp.sum(jnp.take(wy, back, axis=0).reshape(t, k, -1), axis=1)
    return r, pair_counts(n_here, sizes, rows, k)


def sparse_moe(cfg, p, v, active=None):
    """The expert layer on v [T, H]: (out [T, H], counts)."""
    with jax.named_scope("moe.route"):
        idx, weights = moe_route(cfg, p, v)
    r, counts = moe_experts(cfg, p, v, idx, weights, active)
    return r.astype(v.dtype), counts


def mlp(cfg, l, p, x, active=None):
    """The second half of block l on the residual stream x [T, H]: (x,
    MoE counts; `NO_COUNTS` from a dense layer)."""
    h2 = _rms(x, p["ln2"], cfg.layernorm_epsilon)
    if cfg.is_sparse(l):
        out, counts = sparse_moe(cfg, p, h2, active)
        return x + out, counts
    return x + swiglu(p, h2), jnp.asarray(NO_COUNTS)


def forward_sequence(cfg, params, ids):
    """Full causal forward over one sequence ids [T]: logits [T, V]
    float32."""
    t = ids.shape[0]
    pos = jnp.arange(t, dtype=jnp.int32)
    x = jnp.take(params["embed"], ids, axis=0)
    for l in range(cfg.num_hidden_layers):
        p, kind = params["layers"][l], cfg.kind(l)
        h = _rms(x, p["ln1"], cfg.layernorm_epsilon)
        q, k, v = project(cfg, kind, p, h, pos)
        with jax.named_scope("prefill.attend"):
            o = attend_chunk(cfg, kind, p, q, k, v, 0)
        x = x + o @ p["wo"].astype(x.dtype)
        x, _ = mlp(cfg, l, p, x)
    x = _rms(x, params["norm"], cfg.layernorm_epsilon)
    return x.astype(F32) @ params["head"].astype(F32)


# -- the dygraph model ----------------------------------------------------------------

class MimoV2ForCausalLM(Layer):
    """The dygraph model: parameters under the names of
    `MimoV2Config.param_shapes`, `forward(input_ids [B, T])` gives logits
    [B, T, V]. `arrays` ({name: jax array}) become the parameters as they
    are, without a second copy on the device; without it the parameters
    are drawn normal(0, 0.02) (norms one, sinks normal(0, 1), the
    routers' choice bias normal(0, 0.01)), which is what the CPU tests
    use."""

    def __init__(self, config: MimoV2Config, arrays=None, seed=0):
        super().__init__()
        self.config = config
        dt = jnp.bfloat16 if config.dtype == "bfloat16" else F32
        shapes = config.param_shapes()
        if arrays is not None:
            missing = set(shapes) - set(arrays)
            if missing:
                raise KeyError(f"no array for {sorted(missing)}")
        rng = np.random.default_rng(seed)
        self._names = {}
        for name, (shape, f32_only) in shapes.items():
            want = F32 if f32_only else dt
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
            setattr(self, attr, Parameter(data))

    @staticmethod
    def _draw(rng, name, shape):
        kind = name.rsplit(".", 1)[-1]
        if kind in ("ln1", "ln2", "norm"):
            return np.ones(shape, np.float32)
        if kind == "sink":
            return rng.normal(0.0, 1.0, shape)
        if kind == "b_corr":
            return rng.normal(0.0, 0.01, shape)
        return rng.normal(0.0, 0.02, shape)

    def param_tree(self):
        """The parameters as the operators take them: {"embed", "norm",
        "head", "layers": [one dict a block]}; the arrays themselves, no
        copy."""
        tree = {"layers": [{} for _ in range(self.config.num_hidden_layers)]}
        for name, attr in self._names.items():
            data = getattr(self, attr)._data
            if name.startswith("layers."):
                _, i, leaf = name.split(".")
                tree["layers"][int(i)][leaf] = data
            else:
                tree[name] = data
        return tree

    def forward(self, input_ids):
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        cfg, params = self.config, self.param_tree()
        logits = jax.vmap(lambda row: forward_sequence(cfg, params, row))(
            ids.astype(jnp.int32))
        return Tensor(logits)


# -- serving: paged pools for the full layers, a ring a slot for the window layers ----------

class WindowPagedDecoder(PagedDecoder):
    """`PagedDecoder` for a model whose layers are of two attention
    kinds (`PagedDecoder(model)` builds this class when the model's
    `cache_kinds` name a window). The serve loop, the allocator and the
    spans are the ones every engine runs; what differs is the cache it
    carries chunk to chunk:

        (kf [Lf, NB, bs * nkv_f, dk'], vf [Lf, NB, bs * nkv_f, dv],
         kw [Lw, 1 + slots * R, bs * nkv_w, dk'], vw [.., bs * nkv_w, dv])

    A block's rows are its tokens and KV heads merged (row t * nkv + g
    is token t, KV head g), which is how the decode kernel reads a block:
    with 4 or 8 KV heads of wide rows that shape and `[bs, nkv, d]` are
    not the same bytes under the chip's tiled layouts, and a reshape
    between them in a step would copy the pool.

    all four donated and updated in place. The full layers' pools are
    paged: a token keeps its row for the life of its request, in blocks
    the allocator hands out and the block tables address; an admission
    is priced in these blocks alone. A window layer attends the last
    `sliding_window` positions whatever the sequence's length, so a slot
    owns a RING of `R = ceil(window / bs) + 1` blocks in the window
    pools (block 0 is the trash block): position t lives in ring block
    `(t // bs) % R`, row `t % bs`, and writing it overwrites position
    `t - R * bs`, which left the window `bs` steps ago. A ring and not
    blocks freed as they slide out: the bytes are the same (window + one
    block), a slot's are found from its index without a table or an
    allocator call a step, and nothing can run out mid-sequence. The one
    block of slack is also what a look-ahead chunk needs: steps written
    past an eos's cut overwrite positions that a rewound slot no longer
    attends, as long as a chunk is no longer than a block (`serve`
    refuses a longer one beside an eos).

    K rows are stored `dk'` wide: `dk` rounded up to whole lanes when it
    is wider than one (192 -> 256), zeros behind, because the chip lays
    out an array's last axis in whole lanes anyway and a DMA takes whole
    tiles. A window layer's step hands the kernel the table of its live
    ring blocks, first live block first (`_ring_view`): no block before
    the window is in it, so none is copied.

    A prompt is prefilled in chunks of `prefill_chunk` rows by ONE
    program, each chunk attending the keys before it from the cache (the
    full layers' through the block table, the window layers' last
    `window` from the ring) and its own: no `[T, T]` scores, no bucket
    by prompt length, and a chunk's temporaries whatever the prompt's
    length. What does not compose yet refuses at construction (or, for
    `serve()` options, at the call) with a NotImplementedError that
    names the option."""

    # K and V written past the host's view are rewritten by the next
    # chunk and the ring's slack block keeps a rewound slot's window
    # whole (see above)
    _cache_rewinds = True
    _prefill_donate = (6, 7, 8, 9)
    LANES = 128

    REFUSED = {
        "weight_quant": "the per-kind weights have no quantized form",
        "kv_quant": "the ring and the pools would need a codec by layer "
                    "kind",
        "prefix_cache": "a shared prefix has blocks to map in the full "
                        "layers but no copy of the window layers' ring at "
                        "its end",
        "prefix_cache_blocks": "it sizes the prefix cache",
        "attn_shards": "context-sharded attention has not been tried "
                       "beside the ring",
        "shard_block_budget": "it picks attn_shards",
        "kv_offload": "page-out moves the full layers' blocks, not a "
                      "slot's ring",
        "hbm_budget_gib": "it prices kv_offload",
    }

    def __init__(self, model, max_len=None, block_size=64, num_blocks=None,
                 max_slots=8, headroom_guard=None, ragged_kernel=None,
                 pipelined_admission=False, prefill_chunk=None, **refused):
        for name, value in refused.items():
            if name not in self.REFUSED:
                raise TypeError(f"unexpected argument {name!r}")
            if value not in (None, False):
                raise NotImplementedError(
                    f"{name} does not compose with window layers: "
                    f"{self.REFUSED[name]}")
        cfg = model.config
        block_size = int(block_size)
        self.ring_blocks = -(-cfg.sliding_window // block_size) + 1
        self.ring_tokens = self.ring_blocks * block_size
        limit = int(max_len or cfg.max_position_embeddings)
        limit -= limit % block_size
        chunk = int(prefill_chunk or min(1024, limit))
        if chunk % block_size or chunk < self.ring_tokens:
            raise ValueError(
                f"prefill_chunk {chunk} must be whole blocks of "
                f"{block_size} and hold a slot's ring of "
                f"{self.ring_tokens} tokens")
        super().__init__(model, max_len=max_len, block_size=block_size,
                         num_blocks=num_blocks, max_slots=max_slots,
                         headroom_guard=headroom_guard,
                         ragged_kernel=ragged_kernel,
                         pipelined_admission=pipelined_admission,
                         prefill_chunk=chunk)
        # same programs as the parent's, with the ring pools donated too
        self._paged_chunk_state_jit = jax.jit(
            self._paged_chunk_state_impl,
            donate_argnums=(1, 2, 4, 5, 7, 8, 9, 10),
            static_argnums=(11, 12))
        # the parent's other programs (verify, COW copy) serve options
        # this engine refuses
        self._spec_verify_jit = self._cow_copy_jit = None
        self._admit_counts = [0] * len(self.ADMIT_COUNTERS)

    def _prepare_weights(self, model, max_len, weight_quant):
        cfg = model.config
        self.cfg = cfg
        self.max_len = int(max_len or cfg.max_position_embeddings)
        self.nh, self.nkv = cfg.num_attention_heads, cfg.kv_heads(FULL)
        self.hd, self.eps = cfg.head_dim, cfg.layernorm_epsilon
        self.weight_quant = None
        self.kv_layers = cfg.count(FULL)
        self.window_layers = cfg.count(WINDOW)
        if not self.kv_layers:
            raise NotImplementedError(
                "a pattern without a full-attention layer has no paged "
                "cache for the block tables to address")
        dk = cfg.head_dim
        self.k_row = -(-dk // self.LANES) * self.LANES if dk > self.LANES \
            else dk
        self._params = model.param_tree()
        body = sum(x.size * x.dtype.itemsize for x in
                   jax.tree_util.tree_leaves(self._params["layers"]))
        body += self._params["head"].size * self._params["head"].dtype.itemsize
        self.weight_stream_bytes = {"quant": int(body), "bf16eq": int(body)}

    # -- the cache ----------------------------------------------------------------
    def new_pools(self):
        cfg = self.cfg
        dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else F32
        bs, dv = self.block_size, cfg.v_head_dim
        pools = []
        for layers, blocks, nkv in (
                (self.kv_layers, self.num_blocks, cfg.kv_heads(FULL)),
                (self.window_layers, 1 + self.max_slots * self.ring_blocks,
                 cfg.kv_heads(WINDOW))):
            pools += [jnp.zeros((layers, blocks, bs * nkv, self.k_row), dt),
                      jnp.zeros((layers, blocks, bs * nkv, dv), dt)]
        return tuple(pools)

    def _row_bytes(self, kind):
        """K and V bytes of one token in one layer of `kind`, as
        stored."""
        itemsize = 2 if self.cfg.dtype == "bfloat16" else 4
        return self.cfg.kv_heads(kind) * (self.k_row + self.cfg.v_head_dim) \
            * itemsize

    @property
    def full_token_bytes(self):
        """Bytes a token keeps in the full layers' pools (all of them)
        for the life of its request."""
        return self.kv_layers * self._row_bytes(FULL)

    @property
    def slot_window_bytes(self):
        """Bytes of a slot's rings (all window layers): whatever the
        sequence's length."""
        return self.window_layers * self.ring_tokens * self._row_bytes(WINDOW)

    def kv_token_bytes(self):
        """K (or V) bytes of a row of the full layers' pools, as the
        parent's bills read it: the mean of the two, which differ."""
        return self._row_bytes(FULL) // 2

    def pool_bytes(self):
        """Both kinds as they are: the paged pools and every slot's
        rings (and their trash block)."""
        ring_blocks = 1 + self.max_slots * self.ring_blocks
        return (self.num_blocks * self.block_size * self.full_token_bytes
                + ring_blocks * self.block_size * self.window_layers
                * self._row_bytes(WINDOW))

    def bytes_per_block(self):
        """What one block of an admission costs: the full layers' rows
        (the rings are there whether a slot is taken or not)."""
        return self.block_size * self.full_token_bytes

    def _refuse(self, what, why):
        raise NotImplementedError(
            f"{what} does not compose with window layers: {why}")

    def export_blocks(self, *a, **kw):
        self._refuse("block export", "a request's paged blocks are part "
                     "of its cache; its rings have no transport yet")

    def import_blocks(self, *a, **kw):
        self._refuse("block import", "a request's paged blocks are part "
                     "of its cache; its rings have no transport yet")

    def page_out_blocks(self, *a, **kw):
        self._refuse("page-out", "it moves paged blocks, not a slot's rings")

    def page_in_blocks(self, *a, **kw):
        self._refuse("page-in", "it moves paged blocks, not a slot's rings")

    def serve(self, requests, spec_decode=None, **kw):
        if spec_decode is not None:
            self._refuse("spec_decode", "a draft's rows would overwrite "
                         "ring positions that a rejected draft still needs")
        if kw.get("eos_token_id") is not None \
                and kw.get("chunk", 8) > self.block_size:
            self._refuse(f"a chunk of {kw['chunk']} steps beside an eos",
                         f"a look-ahead chunk cut by the eos rewinds further "
                         f"than the ring's slack block of {self.block_size}")
        return super().serve(requests, spec_decode=None, **kw)

    # -- addressing ---------------------------------------------------------------
    def _ring_block(self, slots, pos):
        """The window pools' block that holds position `pos` of `slots`
        (arrays of one shape)."""
        return 1 + slots * self.ring_blocks \
            + (pos // self.block_size) % self.ring_blocks

    def _ring_view(self, slots, pos):
        """What a window layer's step hands the kernel for slots [S] at
        positions pos [S] (the token just written): (tables [S, R] of the
        ring blocks from the first live one on, the position counted from
        that block's start, the first position attended counted the same
        way)."""
        bs = self.block_size
        lo = jnp.maximum(pos - (self.cfg.sliding_window - 1), 0)
        first = lo // bs
        blocks = first[:, None] + jnp.arange(self.ring_blocks,
                                             dtype=jnp.int32)[None, :]
        tables = self._ring_block(slots[:, None], blocks * bs)
        return tables, pos - first * bs, lo - first * bs

    @staticmethod
    def _by_kind(kf, vf, kw, vw):
        """The four pools as the layer loops carry them: {kind: [K, V
        flat over layers and blocks ([L, NB, ..] -> [L * NB, ..], no data
        moves), NB, layers of the kind met so far]}; layer a of a kind
        has its blocks at a * NB."""
        flat = lambda x: x.reshape((-1,) + x.shape[2:])
        return {FULL: [flat(kf), flat(vf), kf.shape[1], 0],
                WINDOW: [flat(kw), flat(vw), kw.shape[1], 0]}

    @staticmethod
    def _stacked(pools, *like):
        """Undo `_by_kind`: the four pools in `new_pools()`'s order and
        shapes."""
        flat = pools[FULL][:2] + pools[WINDOW][:2]
        return tuple(f.reshape(x.shape) for f, x in zip(flat, like))

    @staticmethod
    def _write(pool, rows, widx):
        """Scatter token rows [n, nkv, d] into a flat pool [blocks, bs *
        nkv, d'] at flat token indices widx [n] (token t's KV head g is
        row t * nkv + g); rows narrower than the pool's are zero
        behind."""
        n, nkv, d = rows.shape
        if d < pool.shape[-1]:
            rows = jnp.pad(rows, ((0, 0), (0, 0), (0, pool.shape[-1] - d)))
        at = widx[:, None] * nkv + jnp.arange(nkv, dtype=jnp.int32)[None, :]
        flat = pool.reshape(-1, pool.shape[-1])
        return flat.at[at.reshape(-1)].set(
            rows.reshape(n * nkv, -1).astype(pool.dtype)).reshape(pool.shape)

    def _gather(self, pool, blocks, nkv):
        """The token rows [len(blocks) * bs, nkv, d'] of `blocks` of a
        flat pool, one after another."""
        rows = jnp.take(pool, blocks, axis=0)
        return rows.reshape(-1, nkv, pool.shape[-1])

    def _attend(self, kind, p, q, kc, vc, tables, lens, lows):
        """Decode attention of q [S, nh, dk] through `tables` of block
        ids on the flat pools' block axis; `lows` None in a full layer.
        The ragged kernel, or (off the chip by default) the gathered
        window in plain XLA, which stays the kernel's numerical
        reference."""
        cfg = self.cfg
        scale = 1.0 / math.sqrt(cfg.head_dim)
        sinks = p["sink"] if cfg.has_sink(kind) else None
        S, nkv = q.shape[0], cfg.kv_heads(kind)
        # q as wide as the stored K rows, outside the scope: its device
        # time is the kernel's alone
        qp = jnp.pad(q, ((0, 0), (0, 0), (0, kc.shape[-1] - q.shape[-1])))
        with jax.named_scope("decode.attend"), \
                jax.named_scope(f"decode.attend.{kind}"):
            if self.use_ragged_kernel:
                from ..kernels.pallas.ragged_paged_attention import (
                    ragged_paged_attention)
                o = ragged_paged_attention(qp, kc, vc, tables, lens,
                                           scale=scale, lows=lows,
                                           sinks=sinks, kv_heads=nkv)
                return o.reshape(S, -1)
            kw = jnp.take(kc, tables, axis=0).reshape(S, -1, nkv,
                                                      kc.shape[-1])
            vw = jnp.take(vc, tables, axis=0).reshape(S, -1, nkv,
                                                      vc.shape[-1])
            qg = q.reshape(S, nkv, self.nh // nkv, -1).astype(F32)
            att = jnp.einsum("sgnd,swgd->sgnw", qg,
                             kw[..., :q.shape[-1]].astype(F32)) * scale
            at = jnp.arange(kw.shape[1], dtype=jnp.int32)[None, :]
            sees = at <= lens[:, None]
            if lows is not None:
                sees = sees & (at >= lows[:, None])
            att = jnp.where(sees[:, None, None, :], att, -1e30)
            top = att.max(axis=-1, keepdims=True)
            if sinks is not None:
                sink = sinks.astype(F32).reshape(1, nkv, -1, 1)
                top = jnp.maximum(top, sink)
            e = jnp.exp(att - top)
            den = e.sum(axis=-1, keepdims=True)
            if sinks is not None:
                den = den + jnp.exp(sink - top)
            o = jnp.einsum("sgnw,swgd->sgnd", e / den, vw.astype(F32))
            return o.astype(q.dtype).reshape(S, -1)

    # -- programs -------------------------------------------------------------------
    COUNTERS = ("moe_pairs_here", "moe_pairs_all", "moe_experts_touched",
                "moe_max_load", "attn_rows", "attn_tokens_full",
                "attn_tokens_window")
    ADMIT_COUNTERS = COUNTERS[:4] + ("kv_blocks_full",)

    def _step(self, params, tokens, seqlens, tables, active, kf, vf, kw,
              vw):
        """One decode step for every slot through the pattern. Returns
        (logits [S, V], the four pools, the step's MoE counts)."""
        cfg, bs = self.cfg, self.block_size
        S = tokens.shape[0]
        slots = jnp.arange(S, dtype=jnp.int32)
        x = jnp.take(params["embed"], tokens, axis=0)
        dtype = x.dtype
        blk = jnp.take_along_axis(tables, (seqlens // bs)[:, None],
                                  axis=1)[:, 0]
        at = {FULL: jnp.where(active, blk, 0) * bs + seqlens % bs,
              WINDOW: jnp.where(active, self._ring_block(slots, seqlens), 0)
              * bs + seqlens % bs}
        ring, ring_lens, ring_lows = self._ring_view(slots, seqlens)
        through = {FULL: (tables, seqlens, None),
                   WINDOW: (ring, ring_lens, ring_lows)}
        pools = self._by_kind(kf, vf, kw, vw)
        counts = jnp.asarray(NO_COUNTS)
        for l in range(cfg.num_hidden_layers):
            p, kind = params["layers"][l], cfg.kind(l)
            kc, vc, nb, a = pools[kind]
            h = _rms(x, p["ln1"], self.eps)
            q, k, v = project(cfg, kind, p, h, seqlens)
            with jax.named_scope("decode.kv_pool"):
                kc = self._write(kc, k, a * (nb * bs) + at[kind])
                vc = self._write(vc, v, a * (nb * bs) + at[kind])
            tabs, lens, lows = through[kind]
            o = self._attend(kind, p, q, kc, vc, tabs + a * nb, lens, lows)
            pools[kind] = [kc, vc, nb, a + 1]
            x = x + o @ p["wo"].astype(dtype)
            x, c = mlp(cfg, l, p, x, active)
            counts = merge_counts(counts, c)
        x = _rms(x, params["norm"], self.eps)
        return (self._head_logits(params, x),
                *self._stacked(pools, kf, vf, kw, vw), counts)

    def _paged_chunk_state_impl(self, params, tok0, seqlens0, tables, live,
                                budgets, poison, kf, vf, kw, vw, n, eos_id):
        """The state-carrying chunk of `PagedDecoder` (same arithmetic
        of liveness, budgets and eos), with the four pools in the step
        loop's carry and, after them in what it returns, the chunk's
        counters `COUNTERS` (int32 [7]) that ride home with the
        tokens."""
        window = jnp.int32(self.cfg.sliding_window)

        def step(tok, lens, act, pools):
            logits, *pools, c = self._step(params, tok, lens, tables, act,
                                           *pools)
            return logits, pools, c

        def tally(acc, c, act, lens):
            stats, seen = acc
            keys = jnp.where(act, lens + 1, 0)
            return merge_counts(stats, c), seen + jnp.stack([
                jnp.sum(act, dtype=jnp.int32),
                jnp.sum(keys, dtype=jnp.int32),
                jnp.sum(jnp.minimum(keys, window), dtype=jnp.int32)])

        out, (stats, seen) = self._chunk_scan(
            step, tok0, seqlens0, live, budgets, poison, (kf, vf, kw, vw), n,
            eos_id, tally,
            lambda: (jnp.asarray(NO_COUNTS), jnp.zeros(3, jnp.int32)))
        return out + (jnp.concatenate([stats, seen]),)

    def chunk_counters(self, aux):
        """The chunk's counters as `serve:commit` metadata; `aux` is
        what the chunk program returned after the pools, already on the
        host's side of the token read. The attention counts are of one
        layer of each kind: rows that attended, the keys a full layer
        gave them and the keys a window layer did."""
        return dict(zip(self.COUNTERS, (int(v) for v in np.asarray(aux[0]))))

    # -- the chunked prefill ------------------------------------------------------------
    def prefill_bucket(self, n):
        """Rows of the one prefill program, whatever the prompt's
        length: a prompt takes as many calls as it has chunks."""
        return self.prefill_chunk

    def _prefill_calls(self, bucket, members, tables, pad):
        """The inputs of each call of the chunk program for one prompt:
        chunk c holds rows c * bucket .. (c + 1) * bucket of it, padded
        behind in the last."""
        (slot, prompt, _), = members
        n = len(prompt)
        table = jnp.asarray(tables[slot])
        calls = []
        for start in range(0, max(n, 1), bucket):
            ids = np.full(bucket, pad, np.int32)
            piece = prompt[start:start + bucket]
            ids[:len(piece)] = piece
            calls.append(((jnp.asarray(ids), jnp.int32(start), jnp.int32(n),
                           table, jnp.int32(slot)), ()))
        return calls

    def _prefill_paged(self, params, ids, start, true_len, table, slot, kf,
                       vf, kw, vw):
        """One chunk of a prompt: ids [C] are its rows start .. start +
        C (those from true_len on are padding). K and V of the chunk go
        into the slot's pages (full layers) and, its last rows, into the
        slot's rings (window layers); each layer attends the keys before
        the chunk from the cache and the chunk's own. The first chunk
        overwrites the whole ring, zeros where the prompt has no token,
        so that nothing of the slot's last tenant is left in it. Returns
        int32 [1 + 5] (the encoded token after the prompt's last row,
        which only the last chunk's call has; then `ADMIT_COUNTERS`: the
        chunk's MoE counts and the blocks the table holds) and the
        pools."""
        cfg, bs, R = self.cfg, self.block_size, self.ring_blocks
        C = ids.shape[0]
        rel = jnp.arange(C, dtype=jnp.int32)
        pos = start + rel
        valid = pos < true_len
        x = jnp.take(params["embed"], ids, axis=0)
        dtype = x.dtype
        blk = jnp.where(valid, jnp.take(
            table, jnp.minimum(pos // bs, table.shape[0] - 1)), 0)
        at_full = blk * bs + pos % bs
        # the ring takes the chunk's last `ring_tokens` real rows; rows
        # before the chunk's first are the chunk before's (left alone)
        # or, before the prompt's first, nothing (zeros)
        n_tail = self.ring_tokens
        tail = jnp.clip(true_len - start, 0, C) - n_tail \
            + jnp.arange(n_tail, dtype=jnp.int32)
        tail_pos = start + tail
        at_ring = jnp.where(
            (tail >= 0) | (start == 0),
            self._ring_block(slot, tail_pos) * bs + tail_pos % bs,
            tail_pos % bs)
        # the `window` keys before the chunk, whole blocks of the ring
        prev_blocks = R - 1
        prev = prev_blocks * bs
        before = self._ring_block(
            slot, start + (jnp.arange(prev_blocks, dtype=jnp.int32)
                           - prev_blocks) * bs)
        kv_first = prev - jnp.minimum(start, prev)
        from_prompt = (jnp.arange(prev, dtype=jnp.int32)
                       >= kv_first)[:, None, None]
        pools = self._by_kind(kf, vf, kw, vw)
        counts = jnp.asarray(NO_COUNTS)
        for l in range(cfg.num_hidden_layers):
            p, kind = params["layers"][l], cfg.kind(l)
            kc, vc, nb, a = pools[kind]
            h = _rms(x, p["ln1"], self.eps)
            q, k, v = project(cfg, kind, p, h, pos)
            if kind == FULL:
                kc = self._write(kc, k, a * (nb * bs) + at_full)
                vc = self._write(vc, v, a * (nb * bs) + at_full)
                nkv = k.shape[1]
                keys = self._gather(kc, table + a * nb, nkv)[..., :k.shape[-1]]
                vals = self._gather(vc, table + a * nb, nkv)
                q_start, first = start, 0
            else:
                nkv = k.shape[1]
                keys = jnp.concatenate(
                    [self._gather(kc, before + a * nb, nkv)[..., :k.shape[-1]],
                     k])
                # what the ring holds before the prompt's first token is
                # its last tenant's: masked keys, and values made zero
                # (a masked p is 0, and 0 x NaN is NaN)
                vals = self._gather(vc, before + a * nb, nkv)
                vals = jnp.concatenate(
                    [jnp.where(from_prompt, vals, 0), v])
                q_start, first = prev, kv_first
                own = jnp.clip(tail, 0, C - 1)
                keep = (tail >= 0)[:, None, None]
                kc = self._write(kc, jnp.where(keep, jnp.take(k, own, 0), 0),
                                 a * (nb * bs) + at_ring)
                vc = self._write(vc, jnp.where(keep, jnp.take(v, own, 0), 0),
                                 a * (nb * bs) + at_ring)
            with jax.named_scope("prefill.attend"):
                o = attend_chunk(cfg, kind, p, q, keys, vals, q_start, first)
            pools[kind] = [kc, vc, nb, a + 1]
            x = x + o @ p["wo"].astype(dtype)
            x, c = mlp(cfg, l, p, x, valid)
            counts = merge_counts(counts, c)
        last = jnp.take(x, jnp.clip(true_len - 1 - start, 0, C - 1), axis=0)
        logits = self._head_logits(
            params, _rms(last[None], params["norm"], self.eps))[0]
        enc = jnp.concatenate([
            self._encode_first_token(logits)[None], counts,
            jnp.sum(table != 0, dtype=jnp.int32)[None]])
        return (enc, *self._stacked(pools, kf, vf, kw, vw))

    def decode_first_token(self, encs, seg=0):
        """The prompt's first token from its last chunk's result. The
        counts behind the token are summed over the prompt's chunks (the
        largest load is the largest of them) and kept for
        `admit_metadata`."""
        chunks = np.stack([np.asarray(e) for e in encs])
        counts = chunks[:, 1:]
        self._admit_counts = [int(v) for v in counts[:, :3].sum(axis=0)] \
            + [int(counts[:, 3].max()), int(counts[-1, 4])]
        return super().decode_first_token([chunks[-1, 0]])

    def admit_metadata(self):
        """The prompt's MoE counts under the chunk counters' names, the
        full layers' blocks the admission reserved and the bytes of the
        slot's rings it overwrote."""
        return {"kv_bytes_window": self.slot_window_bytes,
                **dict(zip(self.ADMIT_COUNTERS, self._admit_counts))}

    def _record_traffic(self, seqlens, steps, live, budgets, launches=None):
        """The parent's telemetry, a layer kind at a time: the window
        layers read their ring's live blocks, never a whole sequence."""
        self.record_weight_fetch(steps)
        if not self.use_ragged_kernel:
            return
        from ..kernels.pallas.ragged_paged_attention import (
            record_ragged_step)
        itemsize = 2 if self.cfg.dtype == "bfloat16" else 4
        row = (self.k_row + self.cfg.v_head_dim) // 2
        lens = np.asarray(seqlens)
        ring_lens = np.minimum(lens, self.cfg.sliding_window - 1)
        for layers, kind, at, blocks in (
                (self.kv_layers, FULL, lens, self.blocks_per_seq),
                (self.window_layers, WINDOW, ring_lens, self.ring_blocks)):
            record_ragged_step(at, blocks, self.block_size,
                               self.cfg.kv_heads(kind), row, itemsize,
                               layers=layers, steps=steps, live=live,
                               budgets=budgets, launches=launches)
