"""The `nemotron_h` family: Mamba-2 + attention + LatentMoE hybrids
(NVIDIA-Nemotron-3-Super-120B-A12B is the published member served here).

A block holds ONE mixer and no MLP: `x <- x + Mixer_l(RMSNorm(x))`, the
mixer's kind read from `hybrid_override_pattern` (M: Mamba-2, *: GQA
attention without a positional term, E: LatentMoE with a shared expert).
The mixers are pure functions of (weights, activations), written once:
`NemotronHForCausalLM.forward` (the dygraph model, full sequences) and
`HybridPagedDecoder` (serving: prefill into the caches, then decode
through them) call the same ones.

Serving keeps two kinds of cache side by side: the paged K and V pools
of the attention blocks only, found through the block tables, and a
fixed-size recurrent state per slot for the Mamba blocks (the SSM state
in float32 and the conv's last rows). `PagedDecoder.serve` drives both
through the one `serving.batcher.serve_loop`.

The expert layer is told which experts it holds (`experts_held = (first,
count)`): it routes over the published router width, computes the part
of the result its own experts give and adds the shared expert. On one
chip there is no exchange and nothing stands in for the absent chips.
"""
from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp

from ..framework.tensor import Parameter, Tensor
from ..kernels.pallas.ssm_update import SCOPE as SSM_UPDATE, ssm_update
from ..nn.layer.layers import Layer
from .decode import _rms
from .paged_decode import PagedDecoder

__all__ = ["NemotronHConfig", "NemotronHForCausalLM", "HybridPagedDecoder",
           "nemotron_h_tiny"]

F32 = jnp.float32


class NemotronHConfig:
    """The published keys of a `nemotron_h` `config.json` that shape the
    language model, under their own names, plus `experts_held` (which
    routed experts this chip holds; default all) and `dtype`."""

    def __init__(self, vocab_size=131072, hidden_size=4096,
                 num_hidden_layers=None, hybrid_override_pattern="M*E",
                 num_attention_heads=32, num_key_value_heads=2,
                 head_dim=128, mamba_num_heads=128, mamba_head_dim=64,
                 ssm_state_size=128, n_groups=8, conv_kernel=4,
                 chunk_size=128, n_routed_experts=512,
                 num_experts_per_tok=22, moe_latent_size=1024,
                 moe_intermediate_size=2688,
                 moe_shared_expert_intermediate_size=5376,
                 routed_scaling_factor=5.0, norm_topk_prob=True,
                 layer_norm_epsilon=1e-5, max_position_embeddings=4096,
                 experts_held=None, dtype="float32"):
        pattern = str(hybrid_override_pattern)
        if set(pattern) - set("M*E") or not pattern:
            raise ValueError(f"hybrid_override_pattern {pattern!r} holds "
                             f"a block kind other than M, * and E")
        if num_hidden_layers is not None and \
                int(num_hidden_layers) != len(pattern):
            raise ValueError(
                f"num_hidden_layers {num_hidden_layers} against a pattern "
                f"of {len(pattern)} blocks")
        if mamba_num_heads % n_groups:
            raise ValueError("mamba_num_heads must divide into n_groups")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.hybrid_override_pattern = pattern
        self.num_hidden_layers = len(pattern)
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.mamba_num_heads = mamba_num_heads
        self.mamba_head_dim = mamba_head_dim
        self.ssm_state_size = ssm_state_size
        self.n_groups = n_groups
        self.conv_kernel = conv_kernel
        self.chunk_size = chunk_size
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_latent_size = moe_latent_size
        self.moe_intermediate_size = moe_intermediate_size
        self.moe_shared_expert_intermediate_size = \
            moe_shared_expert_intermediate_size
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.layer_norm_epsilon = layer_norm_epsilon
        self.max_position_embeddings = max_position_embeddings
        first, count = experts_held or (0, n_routed_experts)
        if not 0 <= first <= first + count <= n_routed_experts:
            raise ValueError(f"experts_held {(first, count)} outside the "
                             f"router's {n_routed_experts}")
        self.experts_held = (int(first), int(count))
        self.dtype = dtype

    @property
    def mamba_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size

    def count(self, kind):
        return self.hybrid_override_pattern.count(kind)

    @property
    def cache_kinds(self):
        """What each block keeps a slot between steps: the rule
        `PagedDecoder(model)` picks its engine by."""
        return tuple({"M": "state", "*": "kv"}.get(k)
                     for k in self.hybrid_override_pattern)

    def param_shapes(self):
        """Ordered {parameter name: (shape, float32 only?)}. Matrices are
        [in, out]; an expert stack is [experts held, in, out]."""
        h, v = self.hidden_size, self.vocab_size
        heads, d_in = self.mamba_num_heads, self.mamba_inner
        nh, nkv, ad = (self.num_attention_heads, self.num_key_value_heads,
                       self.head_dim)
        lat, f = self.moe_latent_size, self.moe_intermediate_size
        fs = self.moe_shared_expert_intermediate_size
        held = self.experts_held[1]
        out = {"embed": ((v, h), False)}
        for i, kind in enumerate(self.hybrid_override_pattern):
            pre = f"layers.{i}."
            out[pre + "norm"] = ((h,), False)
            if kind == "M":
                out.update({
                    pre + "in_proj": ((h, d_in + self.conv_dim + heads),
                                      False),
                    pre + "conv_w": ((self.conv_dim, self.conv_kernel),
                                     False),
                    pre + "conv_b": ((self.conv_dim,), False),
                    pre + "A_log": ((heads,), True),
                    pre + "D": ((heads,), True),
                    pre + "dt_bias": ((heads,), True),
                    pre + "gnorm": ((d_in,), False),
                    pre + "out_proj": ((d_in, h), False)})
            elif kind == "*":
                out.update({
                    pre + "wq": ((h, nh * ad), False),
                    pre + "wk": ((h, nkv * ad), False),
                    pre + "wv": ((h, nkv * ad), False),
                    pre + "wo": ((nh * ad, h), False)})
            else:
                out.update({
                    pre + "router": ((h, self.n_routed_experts), True),
                    pre + "b_corr": ((self.n_routed_experts,), True),
                    pre + "w_down": ((h, lat), False),
                    pre + "w_up": ((lat, h), False),
                    pre + "w1": ((held, lat, f), False),
                    pre + "w2": ((held, f, lat), False),
                    pre + "ws1": ((h, fs), False),
                    pre + "ws2": ((fs, h), False)})
        out["norm"] = ((h,), False)
        out["head"] = ((h, v), False)
        return out


def nemotron_h_tiny(**overrides):
    """A size the CPU tests hold: every mechanism of the family (grouped
    B and C, a conv of 4 taps, GQA, a router wider than the experts
    held, a shared expert) at toy widths."""
    kw = dict(vocab_size=256, hidden_size=64,
              hybrid_override_pattern="MEM*E", num_attention_heads=4,
              num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
              mamba_head_dim=8, ssm_state_size=16, n_groups=2,
              conv_kernel=4, chunk_size=8, n_routed_experts=16,
              num_experts_per_tok=4, moe_latent_size=32,
              moe_intermediate_size=48,
              moe_shared_expert_intermediate_size=96,
              max_position_embeddings=256)
    kw.update(overrides)
    return NemotronHConfig(**kw)


# -- the mixers, as pure functions ------------------------------------------------

def relu2(x):
    return jnp.square(jnp.maximum(x, 0))


def mamba_project(cfg, p, u):
    """`[z | xBC | dt] = u W_in` for u [T, H]."""
    d_in, cd = cfg.mamba_inner, cfg.conv_dim
    zxbcdt = u @ p["in_proj"].astype(u.dtype)
    return (zxbcdt[:, :d_in], zxbcdt[:, d_in:d_in + cd],
            zxbcdt[:, d_in + cd:])


def mamba_split(cfg, xbc):
    """conv output [T, conv_dim] -> x [T, heads, hd], B and C [T, G, N]."""
    t, d_in = xbc.shape[0], cfg.mamba_inner
    gn = cfg.n_groups * cfg.ssm_state_size
    return (xbc[:, :d_in].reshape(t, cfg.mamba_num_heads,
                                  cfg.mamba_head_dim),
            xbc[:, d_in:d_in + gn].reshape(t, cfg.n_groups,
                                           cfg.ssm_state_size),
            xbc[:, d_in + gn:].reshape(t, cfg.n_groups,
                                       cfg.ssm_state_size))


def step_sizes(p, dt):
    """(dt after softplus [T, heads], A [heads]), both float32."""
    return (jax.nn.softplus(dt.astype(F32) + p["dt_bias"].astype(F32)),
            -jnp.exp(p["A_log"].astype(F32)))


def one_segment(t):
    """(starts, lens) of a token axis that holds one whole sequence."""
    return jnp.zeros((1,), jnp.int32), jnp.full((1,), t, jnp.int32)


def segment_rows(starts, lens, t):
    """A token axis of `t` rows holds a pack of sequences laid one after
    another: segment k is rows starts[k] .. starts[k] + lens[k]; starts
    ascend from 0, an unused segment starts at `t` with length 0, and
    the rows between a segment's end and the next start are padding.
    Returns (the segment of each row [t], that segment's start [t],
    whether the row is real [t]). One whole sequence is `one_segment`."""
    pos = jnp.arange(t, dtype=jnp.int32)
    seg = jnp.maximum(
        jnp.sum(pos[:, None] >= starts[None, :], axis=1, dtype=jnp.int32)
        - 1, 0)
    first = jnp.take(starts, seg)
    return seg, first, (pos >= first) & (pos < first + jnp.take(lens, seg))


def conv_sequence(xbc, w, b, starts, lens):
    """Causal depthwise conv over the segments of xbc [T, C] (zeros
    before a segment's start: no tap crosses it) and silu. Also the conv
    state a decode step continues from, for each segment: the last K-1
    rows before its end (zeros where it is shorter) [segments, K-1, C],
    so rows padded behind hand over the state of the segment's length,
    not of its padding."""
    k = w.shape[1]
    t = xbc.shape[0]
    first = segment_rows(starts, lens, t)[1]
    pos = jnp.arange(t, dtype=jnp.int32)
    padded = jnp.concatenate(
        [jnp.zeros((k - 1, xbc.shape[1]), xbc.dtype), xbc])
    wf = w.astype(F32)
    out = b.astype(F32)[None, :]
    for j in range(k):
        inside = (pos - (k - 1 - j) >= first)[:, None]
        out = out + jnp.where(inside, padded[j:j + t], 0).astype(F32) \
            * wf[:, j][None, :]
    at = (starts + lens)[:, None] - (k - 1) \
        + jnp.arange(k - 1, dtype=jnp.int32)[None, :]       # [segments, K-1]
    state = jnp.where((at >= starts[:, None])[..., None],
                      jnp.take(xbc, jnp.clip(at, 0, t - 1), axis=0), 0)
    return jax.nn.silu(out).astype(xbc.dtype), state


def conv_step(state, xbc, w, b):
    """One position for every slot: state [S, K-1, C] (the rows before),
    xbc [S, C]. Returns (silu(conv) [S, C], the state one row on)."""
    window = jnp.concatenate([state, xbc[:, None].astype(state.dtype)], 1)
    out = jnp.einsum("skc,ck->sc", window.astype(F32), w.astype(F32)) \
        + b.astype(F32)[None, :]
    return jax.nn.silu(out).astype(xbc.dtype), window[:, 1:]


def ssm_step(state, x, b, c, dt, a, d, m=0, active=None):
    """The recurrence's one step for every slot, by the kernel that reads
    a slot's state once and writes it back where it was
    (`kernels/pallas/ssm_update.py`). state: the pool [blocks, S, heads,
    hd, N] float32, of which block `m` is stepped in place, or one
    block's [S, heads, hd, N]; x [S, heads, hd]; b, c [S, G, N]; dt
    [S, heads] (after softplus); a, d [heads]; a slot that is not
    `active` [S] keeps its state. `S_t = exp(dt A) S + dt x (x)
    B`, `y = S_t C + D x`. Returns (y [S, heads, hd] float32, the state
    in the form it came in)."""
    pool = state[None] if state.ndim == 4 else state
    y, pool = ssm_update(pool, m, x, b, c, dt, a, active)
    y = y + d.astype(F32)[None, :, None] * x.astype(F32)
    return y, pool[0] if state.ndim == 4 else pool


def last_chunk(starts, lens, chunk, t):
    """The chunk of `ssd_chunked` that holds each segment's last
    position [segments]: the scan's state after it is the segment's (the
    chunks padded behind it would carry it on unchanged)."""
    q = min(int(chunk), t)
    return jnp.clip((starts + jnp.maximum(lens, 1) - 1) // q, 0,
                    -(-t // q) - 1)


def ssd_chunked(x, b, c, dt, a, d, chunk, starts):
    """The same sum as `ssm_step` over the segments of a token axis
    (`segment_rows`), in chunks (the SSD form): inside a chunk a masked,
    decay-weighted product of `C B^T` with x; between chunks the state
    carried by a short scan, which starts from zero at a segment's first
    chunk: every start is a multiple of `chunk`, so no chunk holds rows
    of two segments. x [T, heads, hd]; b, c [T, G, N]; dt [T, heads]
    float32 after softplus, 0 at padded positions (a position with
    dt = 0 neither decays nor feeds the state); a, d [heads]. T is
    padded up to a multiple of `chunk` here. Returns (y [T, heads, hd]
    float32, the state after each chunk [chunks, heads, hd, N] float32:
    a segment's own is the one after its `last_chunk`)."""
    t, heads, hd = x.shape
    g, n = b.shape[1], b.shape[2]
    r = heads // g
    q = min(int(chunk), t)
    pad = (-t) % q
    if pad:
        x, b, c = (jnp.pad(v, ((0, pad), (0, 0), (0, 0))) for v in (x, b, c))
        dt = jnp.pad(dt, ((0, pad), (0, 0)))
    nc = (t + pad) // q
    xf = x.astype(F32).reshape(nc, q, g, r, hd)
    bf = b.astype(F32).reshape(nc, q, g, n)
    cf = c.astype(F32).reshape(nc, q, g, n)
    dtc = dt.reshape(nc, q, g, r)
    cum = jnp.cumsum(dtc * a.reshape(1, 1, g, r), axis=1)  # [nc,q,g,r] <= 0
    # inside a chunk: y_t = sum_{s<=t} exp(cum_t - cum_s) dt_s (C_t.B_s) x_s
    cb = jnp.einsum("ctgn,csgn->cgts", cf, bf)
    diff = cum.transpose(0, 2, 3, 1)[..., :, None] \
        - cum.transpose(0, 2, 3, 1)[..., None, :]          # [nc,g,r,t,s]
    mask = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(mask, diff, -jnp.inf))
    m = decay * cb[:, :, None] * dtc.transpose(0, 2, 3, 1)[..., None, :]
    y = jnp.einsum("cgrts,csgrp->ctgrp", m, xf)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cum[:, -1:] - cum) * dtc               # [nc,q,g,r]
    own = jnp.einsum("csgr,csgrp,csgn->cgrpn", to_end, xf, bf)
    total = jnp.exp(cum[:, -1])                             # [nc,g,r]
    opens = jnp.any((jnp.arange(nc, dtype=jnp.int32) * q)[:, None]
                    == starts[None, :], axis=1)             # [nc]

    def carry(state, xs):
        own_c, total_c, opens_c = xs
        state = jnp.where(opens_c, 0.0, state)
        after = state * total_c[..., None, None] + own_c
        return after, (state, after)
    _, (before, after) = jax.lax.scan(
        carry, jnp.zeros((g, r, hd, n), F32), (own, total, opens))
    # what the state before each chunk adds: exp(cum_t) C_t S_before
    y = y + jnp.einsum("ctgn,cgrpn->ctgrp", cf, before) \
        * jnp.exp(cum)[..., None]
    y = y + d.astype(F32).reshape(1, 1, g, r, 1) * xf
    return (y.reshape(nc * q, heads, hd)[:t],
            after.reshape(nc, heads, hd, n))


def gated_group_norm(y, z, w, groups, eps):
    """`RMSNorm_group(y * silu(z); w)`: the gate first, then each of the
    `groups` groups of channels normalised on its own. y, z [T, d_in]."""
    t, width = y.shape
    gated = (y.astype(F32) * jax.nn.silu(z.astype(F32))) \
        .reshape(t, groups, width // groups)
    gated = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, -1, keepdims=True) + eps)
    return gated.reshape(t, width) * w.astype(F32)


def mamba_sequence(cfg, p, u, starts, lens):
    """The Mamba-2 mixer over the segments of u [T, H] (`segment_rows`).
    Returns (out [T, H], the SSM state after each chunk [chunks, heads,
    hd, N] (a segment's: `last_chunk`), the conv state at each segment's
    end [segments, K-1, C])."""
    z, xbc, dt = mamba_project(cfg, p, u)
    xbc, conv_state = conv_sequence(xbc, p["conv_w"], p["conv_b"], starts,
                                    lens)
    x, b, c = mamba_split(cfg, xbc)
    dt, a = step_sizes(p, dt)
    valid = segment_rows(starts, lens, u.shape[0])[2]
    dt = jnp.where(valid[:, None], dt, 0.0)
    with jax.named_scope("prefill.ssm_scan"):
        y, state = ssd_chunked(x, b, c, dt, a, p["D"], cfg.chunk_size,
                               starts)
    y = gated_group_norm(y.reshape(u.shape[0], -1), z, p["gnorm"],
                         cfg.n_groups, cfg.layer_norm_epsilon)
    return y.astype(u.dtype) @ p["out_proj"].astype(u.dtype), state, \
        conv_state


def mamba_decode(cfg, p, u, ssm, m, conv_state, active=None):
    """One position for every slot: u [S, H], ssm the state pool
    [blocks, S, heads, hd, N] float32 of which this is block `m`,
    conv_state [S, K-1, C]. A slot that is not `active` keeps its state.
    Returns (out [S, H], the pool, conv_state)."""
    z, xbc, dt = mamba_project(cfg, p, u)
    xbc, conv_new = conv_step(conv_state, xbc, p["conv_w"], p["conv_b"])
    x, b, c = mamba_split(cfg, xbc)
    dt, a = step_sizes(p, dt)
    with jax.named_scope(SSM_UPDATE):
        y, ssm = ssm_step(ssm, x, b, c, dt, a, p["D"], m, active)
    if active is not None:
        conv_new = jnp.where(active[:, None, None], conv_new, conv_state)
    y = gated_group_norm(y.reshape(u.shape[0], -1), z, p["gnorm"],
                         cfg.n_groups, cfg.layer_norm_epsilon)
    return y.astype(u.dtype) @ p["out_proj"].astype(u.dtype), ssm, conv_new


def attention_sequence(cfg, p, u, starts, lens):
    """Causal GQA inside each segment of u [T, H] (`segment_rows`), with
    no positional term. Returns (out [T, H], k [T, nkv, hd], v [T, nkv,
    hd])."""
    t = u.shape[0]
    nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    q = (u @ p["wq"].astype(u.dtype)).reshape(t, nkv, nh // nkv, hd)
    k = (u @ p["wk"].astype(u.dtype)).reshape(t, nkv, hd)
    v = (u @ p["wv"].astype(u.dtype)).reshape(t, nkv, hd)
    pos = jnp.arange(t, dtype=jnp.int32)
    seg = segment_rows(starts, lens, t)[0]
    sees = (pos[None, :] <= pos[:, None]) & (seg[None, :] == seg[:, None])
    att = jnp.einsum("qgnd,kgd->gnqk", q.astype(F32), k.astype(F32)) \
        / math.sqrt(hd)
    att = jnp.where(sees[None, None], att, -1e30)
    o = jnp.einsum("gnqk,kgd->qgnd", jax.nn.softmax(att, axis=-1),
                   v.astype(F32)).astype(u.dtype)
    return o.reshape(t, nh * hd) @ p["wo"].astype(u.dtype), k, v


def moe_route(cfg, p, u):
    """Routing over the whole published router: (expert ids [T, k],
    weights [T, k] float32). `s = sigmoid(u W_r)` in float32; the
    correction bias takes part in the choice only; the weights are `s`
    normalised over all k chosen, held here or not, times the routed
    scaling factor. With `n_group` > 1 (a configuration without the key
    has one group) the choice is limited to the `topk_group` groups of
    experts whose two best choice scores sum highest."""
    logits = jnp.dot(u.astype(F32), p["router"].astype(F32),
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    choice = s + p["b_corr"].astype(F32)[None, :]
    groups = getattr(cfg, "n_group", 1)
    if groups > 1:
        t, e = choice.shape
        by_group = choice.reshape(t, groups, e // groups)
        score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
        _, kept = jax.lax.top_k(score, cfg.topk_group)
        allowed = jnp.zeros((t, groups), bool).at[
            jnp.arange(t, dtype=jnp.int32)[:, None], kept].set(True)
        choice = jnp.where(jnp.repeat(allowed, e // groups, axis=1), choice,
                           -jnp.inf)
    _, idx = jax.lax.top_k(choice, cfg.num_experts_per_tok)
    picked = jnp.take_along_axis(s, idx, axis=1)
    if cfg.norm_topk_prob:
        picked = picked / (jnp.sum(picked, -1, keepdims=True)
                           + getattr(cfg, "norm_topk_eps", 1e-20))
    return idx.astype(jnp.int32), picked * cfg.routed_scaling_factor


def sort_pairs(cfg, idx, active=None):
    """The token-expert pairs idx [T, k] sorted by held expert: (order
    [T*k] int32, the pair at each sorted place; sizes [count] int32, the
    pairs each held expert got; rows [T] bool). Pairs of experts held
    elsewhere, and of rows that are not `active`, go last."""
    first, count = cfg.experts_held
    t = idx.shape[0]
    local = idx - first
    here = (local >= 0) & (local < count)
    rows = jnp.ones((t,), bool) if active is None else active
    here = here & rows[:, None]
    key = jnp.where(here, local, count).reshape(-1)          # [T*k]
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
    return order, sizes, rows


def pair_counts(n_here, sizes, rows, k):
    """int32 [4] of one expert layer's call: pairs computed here
    (`n_here`, the sum of `sizes`), pairs routed anywhere, held experts
    that got a row, the most rows one expert got; `merge_counts` adds
    them up over calls."""
    return jnp.stack([n_here,
                      jnp.sum(rows, dtype=jnp.int32) * jnp.int32(k),
                      jnp.sum(sizes > 0, dtype=jnp.int32),
                      jnp.max(sizes)])


# rows of `grouped_matmul_sorted`'s kernel tile: a sorted buffer is
# whole tiles
ROW_TILE = 128


def buffer_rows(cfg, pairs):
    """Rows of the sorted buffer an expert layer of `pairs` token-expert
    pairs (T x k) works on in one pass: 1.5 times the share of the
    router this chip holds (`experts_held` over `n_routed_experts`), in
    whole row tiles, and at most every pair. Where the pairs computed
    here do not fit, the layer runs another pass: no routing can lose a
    pair."""
    share = cfg.experts_held[1] / cfg.n_routed_experts
    return min(pairs,
               -(-math.ceil(1.5 * share * pairs) // ROW_TILE) * ROW_TILE)


@functools.partial(jax.jit, static_argnames="cap")
def _experts_pass(r, v, order, sizes, weights, w1, w2, start, cap):
    """Adds into r [T, latent] float32 the held experts' part of the
    routed sum for the pairs at sorted places start .. start + cap of
    `order` (padded to whole buffers), each row into its token's.
    Jitted with the weights as operands: the expert blocks of a program
    share one trace of it (a Pallas body's trace is paid at every start
    of a process), and the scope is opened inside, since a jitted
    function names the instructions in it."""
    from ..kernels.pallas.grouped_matmul import grouped_matmul_sorted
    k = weights.shape[1]
    here = jax.lax.dynamic_slice_in_dim(order, start, cap)
    tok = here // k
    # each held expert's rows inside this buffer
    ends = jnp.cumsum(sizes, dtype=jnp.int32) - start
    part = jnp.clip(ends, 0, cap) - jnp.clip(ends - sizes, 0, cap)
    with jax.named_scope("moe.experts"):
        # in bounds by construction: "clip" spares the select over the
        # buffer that "fill" would add
        xs = jnp.take(v, tok, axis=0, mode="clip")
        h = grouped_matmul_sorted(xs, w1, part, row_tile=ROW_TILE)
        y = grouped_matmul_sorted(relu2(h).astype(v.dtype), w2, part,
                                  row_tile=ROW_TILE)
    # rows past the held pairs were never written: select, do not scale
    wy = jnp.where((jnp.arange(cap, dtype=jnp.int32)
                    < jnp.sum(part, dtype=jnp.int32))[:, None],
                   y * jnp.take(weights.reshape(-1), here)[:, None], 0.0)
    return r.at[tok].add(wy)


def moe_experts(cfg, p, v, idx, weights, active=None):
    """The held experts' part of the routed sum: `sum over chosen k held
    here of w_k relu(v W1_k)^2 W2_k` for v [T, latent]. The token-expert
    pairs are sorted by held expert (pairs of experts held elsewhere,
    and of rows that are not `active`, go last and are not computed) and
    the two products run grouped over exactly the rows each expert got:
    no capacity, no dropped pair. They run in buffers of `buffer_rows`,
    as many passes as the pairs computed here take (counted on the
    device; none where there are none), each row added into its token's.
    Returns (r [T, latent] float32, counts int32 [4]: pairs computed
    here, pairs routed anywhere, held experts that got a row, the most
    rows one expert got (`merge_counts` adds them up), the rows of the
    buffers int32)."""
    t, k = idx.shape
    order, sizes, rows = sort_pairs(cfg, idx, active)
    n_here = jnp.sum(sizes, dtype=jnp.int32)
    cap = buffer_rows(cfg, t * k)
    order = jnp.pad(order, (0, -(t * k) % cap))
    passes = (n_here + cap - 1) // cap
    r = jax.lax.fori_loop(
        0, passes, lambda i, r: _experts_pass(
            r, v, order, sizes, weights, p["w1"], p["w2"], i * cap,
            cap=cap),
        jnp.zeros(v.shape, F32))
    return r, pair_counts(n_here, sizes, rows, k), passes * cap


NO_COUNTS = np.zeros(4, np.int32)


def merge_counts(a, b):
    """Counts of `moe_experts` over two calls: sums, and the larger of
    the two largest loads."""
    return jnp.concatenate([a[:3] + b[:3], jnp.maximum(a[3:], b[3:])])


def latent_moe(cfg, p, u, active=None):
    """The LatentMoE mixer for u [T, H]: the routed experts work in the
    latent space (`u W_down`, back through `W_up`), the shared expert on
    the hidden state itself. Returns (out [T, H], counts, the sorted
    buffer's rows)."""
    with jax.named_scope("moe.route"):
        idx, weights = moe_route(cfg, p, u)
    v = u @ p["w_down"].astype(u.dtype)
    r, counts, buffered = moe_experts(cfg, p, v, idx, weights, active)
    shared = relu2(u @ p["ws1"].astype(u.dtype)) @ p["ws2"].astype(u.dtype)
    return (r.astype(u.dtype) @ p["w_up"].astype(u.dtype) + shared, counts,
            buffered)


def forward_sequence(cfg, params, ids):
    """Full causal forward over one sequence ids [T]: logits [T, V]
    float32."""
    x = jnp.take(params["embed"], ids, axis=0)
    whole = one_segment(ids.shape[0])
    for i, kind in enumerate(cfg.hybrid_override_pattern):
        p = params["layers"][i]
        u = _rms(x, p["norm"], cfg.layer_norm_epsilon)
        if kind == "M":
            x = x + mamba_sequence(cfg, p, u, *whole)[0]
        elif kind == "*":
            x = x + attention_sequence(cfg, p, u, *whole)[0]
        else:
            x = x + latent_moe(cfg, p, u)[0]
    x = _rms(x, params["norm"], cfg.layer_norm_epsilon)
    return x.astype(F32) @ params["head"].astype(F32)


# -- the dygraph model ----------------------------------------------------------------

class NemotronHForCausalLM(Layer):
    """The dygraph model: parameters under the names of
    `NemotronHConfig.param_shapes`, `forward(input_ids [B, T])` gives
    logits [B, T, V]. `arrays` ({name: jax array}) become the parameters
    as they are, without a second copy on the device; without it the
    parameters are drawn normal(0, 0.02) (norms and D one, a modest
    seeded step size), which is what the CPU tests use."""

    def __init__(self, config: NemotronHConfig, arrays=None):
        super().__init__()
        self.config = config
        dt = jnp.bfloat16 if config.dtype == "bfloat16" else jnp.float32
        shapes = config.param_shapes()
        if arrays is not None:
            missing = set(shapes) - set(arrays)
            if missing:
                raise KeyError(f"no array for {sorted(missing)}")
        rng = np.random.default_rng(0)
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
        if kind in ("norm", "gnorm", "D"):
            return np.ones(shape, np.float32)
        if kind == "A_log":
            return np.log(rng.uniform(1.0, 16.0, shape))
        if kind == "dt_bias":
            step = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), shape))
            return step + np.log(-np.expm1(-step))
        if kind in ("conv_w", "conv_b"):
            return rng.uniform(-0.5, 0.5, shape)
        if kind == "b_corr":
            return rng.normal(0.0, 0.01, shape)
        return rng.normal(0.0, 0.02, shape)

    def param_tree(self):
        """The parameters as the mixers take them: {"embed", "norm",
        "head", "layers": [one dict a block]}; the arrays themselves,
        no copy."""
        tree = {"layers": [{} for _ in self.config.hybrid_override_pattern]}
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


# -- serving: the paged pools and the recurrent state side by side -----------------------

class HybridPagedDecoder(PagedDecoder):
    """`PagedDecoder` for a `nemotron_h` model (`PagedDecoder(model)`
    builds this class when the model's configuration carries a layer
    pattern). The serve loop is the one every engine runs; what differs
    is the cache it carries chunk to chunk:

        (kpool, vpool,                       attention blocks only
         ssm  [M layers, slots, heads, hd, N] float32,
         conv [M layers, slots, K-1, conv_dim])

    all four donated and updated in place. Admission overwrites the
    slot's rows of `ssm` and `conv` with the prefill's state at the
    prompt's true length; a slot that is not live may step, but its
    state is never read before the next admission overwrites it.

    The layer loop is unrolled over the pattern with static indices into
    per-block weights: each block's arrays are the model's own, never
    stacked or copied. What does not compose with a recurrent state
    refuses at construction (or, for `serve()` options, at the call)
    with a NotImplementedError that names the option."""

    # a cache written past the host's view cannot be rewound: the loop
    # must not run a look-ahead chunk whose length an eos may cut
    _cache_rewinds = False

    REFUSED = {
        "weight_quant": "the per-kind weights have no quantized form",
        "kv_quant": "the pools would quantize, the recurrent state not",
        "prefix_cache": "a shared prefix has KV blocks to map but no "
                        "snapshot of the recurrent state at its end",
        "prefix_cache_blocks": "it sizes the prefix cache",
        "attn_shards": "context-sharded attention has not been tried "
                       "beside the state",
        "shard_block_budget": "it picks attn_shards",
        "prefill_chunk": "chunked prefill runs through the warm prefill",
        "kv_offload": "page-out moves KV blocks, not recurrent state",
        "hbm_budget_gib": "it prices kv_offload",
    }

    def __init__(self, model, max_len=None, block_size=64, num_blocks=None,
                 max_slots=8, headroom_guard=None, ragged_kernel=None,
                 pipelined_admission=False, **refused):
        for name, value in refused.items():
            if name not in self.REFUSED:
                raise TypeError(f"unexpected argument {name!r}")
            if value not in (None, False):
                raise NotImplementedError(
                    f"{name} does not compose with recurrent layers: "
                    f"{self.REFUSED[name]}")
        super().__init__(model, max_len=max_len, block_size=block_size,
                         num_blocks=num_blocks, max_slots=max_slots,
                         headroom_guard=headroom_guard,
                         ragged_kernel=ragged_kernel,
                         pipelined_admission=pipelined_admission)
        # same programs as the parent's, with the state pools donated too
        self._paged_chunk_state_jit = jax.jit(
            self._paged_chunk_state_impl,
            donate_argnums=(1, 2, 4, 5, 7, 8, 9, 10),
            static_argnums=(11, 12))
        # the parent's other programs (verify, COW copy) serve options
        # this engine refuses
        self._spec_verify_jit = self._cow_copy_jit = None

    def _prepare_weights(self, model, max_len, weight_quant):
        cfg = model.config
        self.cfg = cfg
        self.max_len = int(max_len or cfg.max_position_embeddings)
        self.nh, self.nkv = cfg.num_attention_heads, cfg.num_key_value_heads
        self.hd, self.eps = cfg.head_dim, cfg.layer_norm_epsilon
        self.weight_quant = None
        self.kv_layers = cfg.count("*")
        self.state_layers = cfg.count("M")
        if not self.kv_layers:
            raise NotImplementedError(
                "a pattern without an attention block has no paged cache "
                "for the block tables to address")
        self._params = model.param_tree()
        body = sum(x.size * x.dtype.itemsize for x in
                   jax.tree_util.tree_leaves(self._params["layers"]))
        body += self._params["head"].size * self._params["head"].dtype.itemsize
        self.weight_stream_bytes = {"quant": int(body), "bf16eq": int(body)}

    # -- the cache ----------------------------------------------------------------
    _prefill_donate = (5, 6, 7, 8)

    def new_pools(self):
        cfg = self.cfg
        kpool, vpool = super().new_pools()
        dt = kpool.dtype
        ssm = jnp.zeros((self.state_layers, self.max_slots,
                         cfg.mamba_num_heads, cfg.mamba_head_dim,
                         cfg.ssm_state_size), F32)
        conv = jnp.zeros((self.state_layers, self.max_slots,
                          cfg.conv_kernel - 1, cfg.conv_dim), dt)
        return kpool, vpool, ssm, conv

    @property
    def slot_state_bytes(self):
        """Bytes of recurrent state one slot holds (all Mamba blocks)."""
        cfg = self.cfg
        itemsize = 2 if cfg.dtype == "bfloat16" else 4
        return self.state_layers * (
            cfg.mamba_inner * cfg.ssm_state_size * 4
            + (cfg.conv_kernel - 1) * cfg.conv_dim * itemsize)

    def _refuse(self, what, why):
        raise NotImplementedError(
            f"{what} does not compose with recurrent layers: {why}")

    def export_blocks(self, *a, **kw):
        self._refuse("block export", "a request's KV blocks are half of "
                     "its cache; its recurrent state has no transport yet")

    def import_blocks(self, *a, **kw):
        self._refuse("block import", "a request's KV blocks are half of "
                     "its cache; its recurrent state has no transport yet")

    def page_out_blocks(self, *a, **kw):
        self._refuse("page-out", "it moves KV blocks, not recurrent state")

    def page_in_blocks(self, *a, **kw):
        self._refuse("page-in", "it moves KV blocks, not recurrent state")

    def serve(self, requests, spec_decode=None, **kw):
        if spec_decode is not None:
            self._refuse("spec_decode", "the verify pass would advance the "
                         "recurrent state over drafts it then rejects")
        return super().serve(requests, spec_decode=None, **kw)

    # -- programs -------------------------------------------------------------------
    def _hybrid_step(self, params, tokens, seqlens, tables, active, kpool,
                     vpool, ssm, conv):
        """One decode step for every slot through the pattern. Returns
        (logits [S, V], the four pools, per-step MoE counts, the rows of
        the expert blocks' sorted buffers)."""
        cfg, bs = self.cfg, self.block_size
        S = tokens.shape[0]
        x = jnp.take(params["embed"], tokens, axis=0)
        dtype = x.dtype
        blk = jnp.take_along_axis(tables, (seqlens // bs)[:, None],
                                  axis=1)[:, 0]
        blk = jnp.where(active, blk, 0)
        widx = blk * bs + seqlens % bs
        kflat, vflat, NB, _ = self._flat_pools(kpool, vpool)
        m = a = 0
        counts, buffered = jnp.asarray(NO_COUNTS), jnp.int32(0)
        for i, kind in enumerate(cfg.hybrid_override_pattern):
            p = params["layers"][i]
            u = _rms(x, p["norm"], self.eps)
            if kind == "M":
                out, ssm, c_new = mamba_decode(cfg, p, u, ssm, m, conv[m],
                                               active)
                conv = conv.at[m].set(c_new)
                m += 1
            elif kind == "*":
                q = (u @ p["wq"].astype(dtype)).reshape(S, self.nh, self.hd)
                k = (u @ p["wk"].astype(dtype)).reshape(S, self.nkv, self.hd)
                v = (u @ p["wv"].astype(dtype)).reshape(S, self.nkv, self.hd)
                with jax.named_scope("decode.kv_pool"):
                    kflat, vflat = self._pool_write(
                        kflat, vflat, k, v, a * (NB * bs) + widx)
                o = self._pool_attend(q, kflat, vflat, tables + a * NB,
                                      seqlens, dtype)
                out = o @ p["wo"].astype(dtype)
                a += 1
            else:
                out, c, b = latent_moe(cfg, p, u, active)
                counts, buffered = merge_counts(counts, c), buffered + b
            x = x + out
        kpool = self._stacked_pools(kflat, kpool)
        vpool = self._stacked_pools(vflat, vpool)
        x = _rms(x, params["norm"], self.eps)
        return (self._head_logits(params, x), kpool, vpool, ssm, conv,
                counts, buffered)

    def _paged_chunk_state_impl(self, params, tok0, seqlens0, tables, live,
                                budgets, poison, kpool, vpool, ssm, conv,
                                n, eos_id):
        """The state-carrying chunk of `PagedDecoder` (same arithmetic
        of liveness, budgets and eos), with the four pools in the step
        loop's carry and, after them in what it returns, the chunk's
        counters `COUNTERS` (int32 [6]) that ride home with the tokens."""
        def step(tok, lens, act, pools):
            logits, *pools, c, b = self._hybrid_step(
                params, tok, lens, tables, act, *pools)
            return logits, pools, (c, b)

        def tally(acc, aux, act, lens):
            (stats, rows, buffered), (c, b) = acc, aux
            rows = rows + jnp.sum(act, dtype=jnp.int32) \
                * jnp.int32(self.state_layers)
            return merge_counts(stats, c), rows, buffered + b

        out, (stats, rows, buffered) = self._chunk_scan(
            step, tok0, seqlens0, live, budgets, poison,
            (kpool, vpool, ssm, conv), n, eos_id, tally,
            lambda: (jnp.asarray(NO_COUNTS), jnp.int32(0), jnp.int32(0)))
        return out + (jnp.concatenate([stats, rows[None], buffered[None]]),)

    COUNTERS = ("moe_pairs_here", "moe_pairs_all", "moe_experts_touched",
                "moe_max_load", "ssm_rows", "moe_rows_buffered")
    # what a prefill program counts: the MoE counts and the buffers' rows
    ADMIT_COUNTERS = COUNTERS[:4] + COUNTERS[5:]

    def chunk_counters(self, aux):
        """The chunk's counters as `serve:commit` metadata; `aux` is
        what the chunk program returned after the pools, already on the
        host's side of the token read."""
        return dict(zip(self.COUNTERS, (int(v) for v in np.asarray(aux[0]))))

    # -- the packed prefill ------------------------------------------------------------
    def prefill_bucket(self, n):
        """As `PagedDecoder`'s, for `n` rows brought up to whole chunks:
        a pack's segments start on chunk boundaries."""
        q = self.cfg.chunk_size
        return super().prefill_bucket(min(-(-n // q) * q, self.max_len))

    def prefill_buckets(self):
        """Every row count a prefill program can be asked for."""
        return sorted({self.prefill_bucket(n) for n in range(
            1, self.max_len + 1, self.cfg.chunk_size)})

    def prefill_packs(self, lengths):
        """How the prompts a scan staged (their lengths, in the order of
        admission) go into prefill programs: [(bucket, [(index, start
        row)])]. Each prompt starts on a chunk boundary behind the one
        before it in its pack; a pack holds `max_len` rows at most and
        runs in the smallest bucket that holds it. A padded row costs
        what a real one costs, so the packs are filled first-fit with
        the longest prompt first, not in the order of admission. A
        prompt admitted alone is a pack of one from row 0."""
        q = self.cfg.chunk_size
        packs = []                      # [rows taken, members]
        for j in sorted(range(len(lengths)), key=lambda j: -lengths[j]):
            pack = next((p for p in packs
                         if p[0] + lengths[j] <= self.max_len), None)
            if pack is None:
                pack = [0, []]
                packs.append(pack)
            pack[1].append((j, pack[0]))
            pack[0] = -(-(pack[0] + lengths[j]) // q) * q
        return [(self.prefill_bucket(rows), members)
                for rows, members in packs]

    def _prefill_inputs(self, bucket, members, tables, pad):
        """What `_prefill_paged` takes before and after the pools for a
        pack `members` [(slot, prompt ids, start row)]. The program has
        a segment a chunk of the bucket; the unused ones, behind those
        in use, start at the bucket's end with length 0."""
        k = -(-bucket // self.cfg.chunk_size)
        ids = np.full(bucket, pad, np.int32)
        starts = np.full(k, bucket, np.int32)
        lens = np.zeros(k, np.int32)
        rows = np.zeros((k, self.blocks_per_seq), np.int32)
        slots = np.zeros(k, np.int32)
        for j, (slot, prompt, start) in enumerate(members):
            ids[start:start + len(prompt)] = prompt
            starts[j], lens[j], slots[j] = start, len(prompt), slot
            rows[j] = tables[slot]
        return (jnp.asarray(ids), jnp.asarray(starts), jnp.asarray(lens),
                jnp.asarray(rows)), (jnp.asarray(slots),)

    def warm_prefill(self, pools, pad):
        """Run every bucket's program that has not run yet on an empty
        pack, which writes the trash block and nothing else: a scan's
        prompts come out in whatever buckets their lengths add up to, so
        none may wait for its first pack to be compiled. Returns the
        pools."""
        for bucket in self.prefill_buckets():
            if bucket not in self._prefill_cache:
                head, tail = self._prefill_inputs(bucket, [], (), pad)
                _, *pools = self._prefill_exec(bucket)(
                    self._params, *head, *pools, *tail)
        return tuple(pools)

    def _prefill_paged(self, params, ids, starts, lens, tables, kpool,
                       vpool, ssm, conv, slots):
        """Prefill a pack of prompts in one pass over the weights: ids
        [rows] holds them one after another, segment k in rows
        starts[k] .. starts[k] + lens[k] from a chunk boundary
        (`segment_rows`), with its block table tables[k] and its slot
        slots[k]. Whatever reads weights by the row (projections, the
        router, the experts, the head) runs once over all rows; the
        convolution, the scan and attention keep to a row's segment. K
        and V of the attention blocks go into each segment's pages, the
        state of every Mamba block at a segment's end (padded rows
        contribute nothing) into its slot's rows of `ssm` and `conv`.
        Returns int32 [segments + 5] (each segment's encoded first
        token, then the pack's `ADMIT_COUNTERS`: the MoE counts as
        `moe_experts` gives them, merged over the expert blocks, and the
        rows of their sorted buffers) and the pools."""
        cfg, bs = self.cfg, self.block_size
        rows = ids.shape[0]
        x = jnp.take(params["embed"], ids, axis=0)
        seg, first, valid = segment_rows(starts, lens, rows)
        at = jnp.arange(rows, dtype=jnp.int32) - first
        blk = jnp.where(valid, jnp.take(
            tables.reshape(-1), seg * tables.shape[1] + at // bs), 0)
        widx = blk * bs + at % bs
        kflat, vflat, NB, _ = self._flat_pools(kpool, vpool)
        # the segments in use come first; only their states are written
        used = jnp.sum(lens > 0, dtype=jnp.int32)
        ends = last_chunk(starts, lens, cfg.chunk_size, rows)
        m = a = 0
        counts, buffered = jnp.asarray(NO_COUNTS), jnp.int32(0)
        for i, kind in enumerate(cfg.hybrid_override_pattern):
            p = params["layers"][i]
            u = _rms(x, p["norm"], self.eps)
            if kind == "M":
                out, after, cstate = mamba_sequence(cfg, p, u, starts, lens)

                def put(k, pools):
                    # segment k's states into its slot's rows, in place
                    pick = jax.lax.dynamic_index_in_dim
                    at = (jnp.int32(m), slots[k]) + (jnp.int32(0),) * 3
                    return (jax.lax.dynamic_update_slice(
                                pools[0], pick(after, ends[k])[None], at),
                            jax.lax.dynamic_update_slice(
                                pools[1], pick(cstate, k)[None].astype(
                                    conv.dtype), at[:4]))
                ssm, conv = jax.lax.fori_loop(0, used, put, (ssm, conv))
                m += 1
            elif kind == "*":
                out, k, v = attention_sequence(cfg, p, u, starts, lens)
                kflat, vflat = self._pool_write(
                    kflat, vflat, k, v, a * (NB * bs) + widx)
                a += 1
            else:
                out, c, b = latent_moe(cfg, p, u, valid)
                counts, buffered = merge_counts(counts, c), buffered + b
            x = x + out
        kpool = self._stacked_pools(kflat, kpool)
        vpool = self._stacked_pools(vflat, vpool)
        last = jnp.take(x, jnp.clip(starts + lens - 1, 0, rows - 1), axis=0)
        logits = self._head_logits(
            params, _rms(last, params["norm"], self.eps))
        enc = jnp.concatenate(
            [jax.vmap(self._encode_first_token)(logits), counts,
             buffered[None]])
        return enc, kpool, vpool, ssm, conv

    def decode_first_token(self, encs, seg=0):
        """Segment `seg`'s first token as `PagedDecoder` encodes it. The
        pack's counts ride behind the tokens on the same wire and are
        kept for `admit_metadata`: on the pack's first admission, the
        others carry none, so that the sums over admissions are the
        programs' own."""
        v = np.asarray(encs[-1])
        n = len(self.ADMIT_COUNTERS)
        self._admit_counts = [int(c) if seg == 0 else 0 for c in v[-n:]]
        return super().decode_first_token([v[seg]])

    def admit_metadata(self):
        """The slot's recurrent state that the prefill overwrote, and
        the pack's counts under the chunk counters' names."""
        return {"state_bytes": self.slot_state_bytes,
                **dict(zip(self.ADMIT_COUNTERS, self._admit_counts))}
