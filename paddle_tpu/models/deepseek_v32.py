"""The `deepseek_v32` family: multi-head latent attention (MLA) whose
keys are chosen by a learned indexer (DeepSeek sparse attention), YaRN
rotary positions, and sparse SwiGLU experts with a group-limited sigmoid
router and a shared expert behind leading dense layers (deepseek-ai
DeepSeek-V3.2 is the published member served here). A configuration
without `index_topk` is the DENSE member of the family: no indexer, every
causal latent row attended (`models/glm4_moe_lite.py`); one with
`num_nextn_predict_layers` keeps a multi-token-prediction (MTP) layer.

Block `l` on x [tokens, hidden], no bias anywhere but the indexer's
LayerNorm; RMS is RMSNorm (eps `rms_norm_eps`):

    h = RMS(x; ln1)
    cq = RMS(h Wqa; q_norm)                              [T, q_lora_rank]
    q = cq Wqb -> per head q_nope (dn), q_pe (dr, interleaved rotary)
    [c, k_pe] = h Wkva;  c = RMS(c; kv_norm) [T, kv_lora_rank],
                         k_pe (dr, interleaved rotary, shared by heads)
    k_nope_h, v_h = c Wkvb_h                             (dn, dv)
    indexer: qI = cq WqI [T, ih, id], kI = LayerNorm(h WkI) [T, id]
             (rotate-half rotary on the first dr dims of both),
             wI = (h WwI) / sqrt(ih * id)
             I(t, j) = sum_h wI_h(t) relu(qI_h(t) . kI(j)),  j <= t
             sel(t) = the top min(index_topk, t + 1) of I(t, .), exact,
                      ties to the lower position
             (no indexer: sel(t) = every j <= t)
    s_h(t, j) = (q_nope_h . k_nope_h(j) + q_pe_h . k_pe(j)) * tau,
                tau = (dn + dr)^-1/2 * m^2, m = 0.1 ln(factor) + 1
                (m = 1 without YaRN)
    x = x + (sum_h softmax_{j in sel(t)} s_h(t, j) v_h(j)) Wo
    h2 = RMS(x; ln2)
    x = x + Wd(silu(Wg h2) * (Wu h2))            l < first_k_dense_replace
    x = x + sum over the chosen experts HELD HERE of w_e W2_e(silu(W1_e h2)
            * (W3_e h2)) + shared(h2)            elsewhere
    (s = sigmoid(h2 Wr) float32; choice = s + b_corr; a group's score is
    the sum of its two best choices; the top `topk_group` of `n_group`
    groups, then top-k among their experts; w = s_chosen / sum(s_chosen)
    * routed_scaling_factor)
    logits = RMS(x; norm) Whead

The MTP layer (DeepSeek-V3 report, 2.2) drafts the token after next from
the main model's last hidden state AFTER its final norm, hn_i = RMS(x_i;
norm), and the embedding of the next token; it shares the embedding and
the head:

    u_i = Weh [RMS(emb(t_{i+1}); enorm) ; RMS(hn_i; hnorm)]
    u = block L (an expert block of the main blocks' shape, with its own
        latent rows) on u, at the positions i
    draft logits_i = RMS(u_i; mtp norm) Whead            predicts t_{i+2}

The operators are pure functions of (weights, activations), shared by
`DeepseekV32ForCausalLM.forward` (whole sequences) and
`LatentPagedDecoder` (serving). Routing and the sort by held expert are
`nemotron_h`'s, the expert layer `mimo_v2.moe_experts`.

Serving keeps a LATENT cache (`LatentPagedDecoder`): a token keeps one
row [c | k_pe] a layer (the MTP layer's too) and, in the sparse
configuration, its indexer key beside it, both paged by the same block
tables and priced by the allocator. Decode attends in the absorbed form
(the key up-projection folded into the query, the value up-projection
into the output). In the sparse configuration a decode step scores every
cached indexer key of a slot, takes the exact top-k and reads only the
chosen latent rows; in the dense one `mla_paged_decode_attention` reads
every latent row of the slot through its block table. A prompt is
prefilled in chunks against the cache: a flash kernel forms the heads'
keys and values from the latent rows and attends them, masked to each
query's selection (sparse) or causal (dense). A dense configuration with
an MTP layer also serves `spec_decode="mtp"`: each step of the decode
chunk is one verify pass that drafts its next token on the device
(`LatentPagedDecoder._verify_step`).
"""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp

from ..framework.tensor import Parameter, Tensor
from ..nn.layer.layers import Layer
from .decode import _rms
from .mimo_v2 import moe_experts, swiglu
from .nemotron_h import NO_COUNTS, merge_counts, moe_route
from .paged_decode import PagedDecoder

__all__ = ["DeepseekV32Config", "DeepseekV32ForCausalLM",
           "LatentPagedDecoder", "deepseek_v32_tiny"]

F32 = jnp.float32
LATENT = "latent"
# the indexer's LayerNorm on its key (the published code's default; the
# configuration names no epsilon for it)
INDEX_NORM_EPS = 1e-6


class DeepseekV32Config:
    """The published keys of a `deepseek_v32` `config.json` that shape the
    language model, under their own names, plus `experts_held` (which
    routed experts this chip holds; default all) and `dtype`.
    `index_topk=None` makes the dense member (no indexer);
    `num_nextn_predict_layers` 1 keeps an MTP layer."""

    def __init__(self, vocab_size=129280, hidden_size=7168,
                 intermediate_size=18432, moe_intermediate_size=2048,
                 num_hidden_layers=61, first_k_dense_replace=3,
                 num_attention_heads=128, q_lora_rank=1536,
                 kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, index_n_heads=64,
                 index_head_dim=128, index_topk=2048, n_routed_experts=256,
                 num_experts_per_tok=8, n_group=8, topk_group=4,
                 n_shared_experts=1, routed_scaling_factor=2.5,
                 norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=10000,
                 rope_scaling=None, max_position_embeddings=163840,
                 num_nextn_predict_layers=0, experts_held=None,
                 dtype="float32"):
        if n_routed_experts % n_group or topk_group > n_group:
            raise ValueError(f"{n_routed_experts} experts do not split into "
                             f"{n_group} groups of which {topk_group} are "
                             f"kept")
        if qk_rope_head_dim % 2 or (index_topk is not None
                                    and qk_rope_head_dim > index_head_dim):
            raise ValueError("the rotary dims must be even and fit the "
                             "indexer's head")
        if int(num_nextn_predict_layers or 0) > 1:
            raise ValueError(f"{num_nextn_predict_layers} MTP layers: one "
                             f"is served")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = int(num_hidden_layers)
        self.first_k_dense_replace = int(first_k_dense_replace)
        self.num_attention_heads = num_attention_heads
        self.q_lora_rank, self.kv_lora_rank = q_lora_rank, kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.index_n_heads, self.index_head_dim = index_n_heads, index_head_dim
        self.index_topk = None if index_topk is None else int(index_topk)
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.n_group, self.topk_group = int(n_group), int(topk_group)
        self.n_shared_experts = int(n_shared_experts or 0)
        self.routed_scaling_factor = float(routed_scaling_factor or 1.0)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.rope_scaling = dict(rope_scaling or {})
        self.max_position_embeddings = max_position_embeddings
        self.num_nextn_predict_layers = int(num_nextn_predict_layers or 0)
        first, count = experts_held or (0, n_routed_experts)
        if not 0 <= first <= first + count <= n_routed_experts:
            raise ValueError(f"experts_held {(first, count)} outside the "
                             f"router's {n_routed_experts}")
        self.experts_held = (int(first), int(count))
        self.dtype = dtype

    @property
    def cache_kinds(self):
        """What each layer keeps a slot between steps: the rule
        `PagedDecoder(model)` picks its engine by."""
        return (LATENT,) * self.num_hidden_layers

    def is_sparse(self, l):
        """Whether block l is an expert block (the MTP block, l =
        num_hidden_layers, is one)."""
        return l >= self.first_k_dense_replace

    @property
    def has_indexer(self):
        """The sparse member: a learned indexer chooses the keys."""
        return self.index_topk is not None

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self):
        """A latent cache row: c and the shared rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self):
        """(dn + dr)^-1/2, times YaRN's m^2 where positions are scaled."""
        scale = self.qk_head_dim ** -0.5
        factor = self.rope_scaling.get("factor", 1.0)
        if factor > 1.0:
            m = 0.1 * self.rope_scaling.get("mscale_all_dim", 1.0) \
                * math.log(factor) + 1.0
            scale *= m * m
        return scale

    def param_shapes(self):
        """Ordered {parameter name: (shape, float32 only?)}. Matrices are
        [in, out]; an expert stack is [experts held, in, out]. The MTP
        layer's parameters are `mtp.<leaf>`, its block's under the main
        blocks' leaf names."""
        h, v = self.hidden_size, self.vocab_size
        out = {"embed": ((v, h), False)}
        for i in range(self.num_hidden_layers):
            out.update(self._block_shapes(f"layers.{i}.", i))
        out["norm"] = ((h,), False)
        out["head"] = ((h, v), False)
        if self.num_nextn_predict_layers:
            out.update({"mtp.enorm": ((h,), False),
                        "mtp.hnorm": ((h,), False),
                        "mtp.eh_proj": ((2 * h, h), False)})
            out.update(self._block_shapes("mtp.", self.num_hidden_layers))
            out["mtp.norm"] = ((h,), False)
        return out

    def _block_shapes(self, pre, i):
        h = self.hidden_size
        nh, qr, kvr = self.num_attention_heads, self.q_lora_rank, \
            self.kv_lora_rank
        dr, dv = self.qk_rope_head_dim, self.v_head_dim
        ih, idim = self.index_n_heads, self.index_head_dim
        f, fe = self.intermediate_size, self.moe_intermediate_size
        held = self.experts_held[1]
        out = {
            pre + "ln1": ((h,), False), pre + "wq_a": ((h, qr), False),
            pre + "q_norm": ((qr,), False),
            pre + "wq_b": ((qr, nh * self.qk_head_dim), False),
            pre + "wkv_a": ((h, kvr + dr), False),
            pre + "kv_norm": ((kvr,), False),
            pre + "wkv_b": ((kvr, nh * (self.qk_nope_head_dim + dv)),
                            False),
            pre + "wo": ((nh * dv, h), False)}
        if self.has_indexer:
            out.update({
                pre + "wq_idx": ((qr, ih * idim), False),
                pre + "wk_idx": ((h, idim), False),
                pre + "k_norm": ((idim,), False),
                pre + "k_norm_b": ((idim,), False),
                pre + "w_idx": ((h, ih), False)})
        out[pre + "ln2"] = ((h,), False)
        if self.is_sparse(i):
            fs = fe * self.n_shared_experts
            out.update({
                pre + "router": ((h, self.n_routed_experts), True),
                pre + "b_corr": ((self.n_routed_experts,), True),
                pre + "w1": ((held, h, fe), False),
                pre + "w3": ((held, h, fe), False),
                pre + "w2": ((held, fe, h), False),
                pre + "ws_g": ((h, fs), False),
                pre + "ws_u": ((h, fs), False),
                pre + "ws_d": ((fs, h), False)})
        else:
            out.update({pre + "wg": ((h, f), False),
                        pre + "wu": ((h, f), False),
                        pre + "wd": ((f, h), False)})
        return out


def deepseek_v32_tiny(**overrides):
    """A CPU-sized member with every mechanism: a query and a KV latent,
    a shared rotary key, an indexer whose top-k is far below the
    contexts served, YaRN positions, a leading dense layer, 16 experts in
    4 groups (2 kept) top-4 with a shared expert, of which any share can
    be held."""
    base = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
                moe_intermediate_size=24, num_hidden_layers=3,
                first_k_dense_replace=1, num_attention_heads=4,
                q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8,
                qk_rope_head_dim=8, v_head_dim=8, index_n_heads=4,
                index_head_dim=16, index_topk=16, n_routed_experts=16,
                num_experts_per_tok=4, n_group=4, topk_group=2,
                n_shared_experts=1, routed_scaling_factor=2.5,
                rope_scaling=dict(type="yarn", factor=40,
                                  original_max_position_embeddings=32,
                                  beta_fast=32, beta_slow=1, mscale=1,
                                  mscale_all_dim=1),
                max_position_embeddings=256)
    base.update(overrides)
    return DeepseekV32Config(**base)


# -- positions ---------------------------------------------------------------------

def yarn_inv_freq(cfg):
    """The rotary frequencies [dr / 2] float32: base `rope_theta`; with a
    YaRN `factor`, divided by it below the slow correction dim, left as
    they are above the fast one, and a linear ramp between."""
    dim, base = cfg.qk_rope_head_dim, float(cfg.rope_theta)
    freqs = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    rs = cfg.rope_scaling
    factor = float(rs.get("factor", 1.0))
    if factor <= 1.0:
        return freqs
    original = rs["original_max_position_embeddings"]

    def corr(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(corr(rs.get("beta_fast", 32))), 0)
    high = min(math.ceil(corr(rs.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    return (freqs / factor * ramp + freqs * (1 - ramp)).astype(np.float32)


def _angles(cfg, pos):
    return pos.astype(F32)[:, None] * jnp.asarray(yarn_inv_freq(cfg))[None]


def rope_interleaved(cfg, x, pos):
    """MLA's rotary term on x [T, ..., dr]: pairs (2i, 2i + 1)."""
    ang = _angles(cfg, pos).reshape((x.shape[0],) + (1,) * (x.ndim - 2)
                                    + (-1,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(F32)
    a, b = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def rope_halves(cfg, x, pos):
    """The indexer's rotary term on the first dr dims of x [T, ..., D]:
    pairs (i, i + dr / 2); the other dims pass."""
    dr = cfg.qk_rope_head_dim
    half = dr // 2
    ang = _angles(cfg, pos).reshape((x.shape[0],) + (1,) * (x.ndim - 2)
                                    + (-1,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(F32)
    a, b = xf[..., :half], xf[..., half:dr]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            xf[..., dr:]], axis=-1).astype(x.dtype)


# -- the operators -------------------------------------------------------------------

def project(cfg, p, h, pos):
    """One layer's projections of h [T, H] at positions pos [T]: (q [T,
    nh, dn + dr] with the rotary term on its last dr dims, the latent
    rows [T, kvr + dr] = [c | k_pe], and the indexer's qI [T, ih, id], kI
    [T, id], wI [T, ih] float32; three Nones without an indexer)."""
    t, dtype = h.shape[0], h.dtype
    eps, nh = cfg.rms_norm_eps, cfg.num_attention_heads
    dn, kvr = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    cq = _rms(h @ p["wq_a"].astype(dtype), p["q_norm"], eps)
    q = (cq @ p["wq_b"].astype(dtype)).reshape(t, nh, cfg.qk_head_dim)
    q = jnp.concatenate([q[..., :dn], rope_interleaved(cfg, q[..., dn:], pos)],
                        axis=-1)
    kv = h @ p["wkv_a"].astype(dtype)
    latent = jnp.concatenate([_rms(kv[:, :kvr], p["kv_norm"], eps),
                              rope_interleaved(cfg, kv[:, kvr:], pos)],
                             axis=-1)
    if not cfg.has_indexer:
        return q, latent, None, None, None
    ih, idim = cfg.index_n_heads, cfg.index_head_dim
    qi = (cq @ p["wq_idx"].astype(dtype)).reshape(t, ih, idim)
    qi = rope_halves(cfg, qi, pos)
    ki = (h @ p["wk_idx"].astype(dtype)).astype(F32)
    mu = ki.mean(-1, keepdims=True)
    var = jnp.mean(jnp.square(ki - mu), -1, keepdims=True)
    ki = (ki - mu) * jax.lax.rsqrt(var + INDEX_NORM_EPS) \
        * p["k_norm"].astype(F32) + p["k_norm_b"].astype(F32)
    ki = rope_halves(cfg, ki.astype(dtype), pos)
    wi = (h @ p["w_idx"].astype(dtype)).astype(F32) / math.sqrt(ih * idim)
    return q, latent, qi, ki, wi


def index_scores(qi, wi, ki):
    """I [Tq, Tk] float32 = sum_h wi[t, h] relu(qi[t, h] . ki[j]) for qi
    [Tq, ih, id], wi [Tq, ih], ki [Tk, id] (or [Tq, Tk, id]: each query
    its own keys): plain XLA, which forms [Tq, ih, Tk]."""
    spec = "thd,jd->thj" if ki.ndim == 2 else "thd,tjd->thj"
    s = jnp.einsum(spec, qi, ki, preferred_element_type=F32)
    return jnp.einsum("thj,th->tj", jnp.maximum(s, 0.0), wi)


def topk_mask(scores, valid, k):
    """The exact top `k` of each row of scores [R, N] among the `valid`
    entries (all of them where fewer are valid), ties to the lower
    position: bool [R, N]. No sort: the k-th largest score is found by a
    search over its float32 bits, four at a time, each pass counting the
    entries at or above 15 candidates; the ties at it are then taken in
    order of position."""
    n = scores.shape[-1]
    if n < k:
        raise ValueError(f"{n} keys to choose {k} of")
    s = jnp.where(scores == 0, 0.0, scores).astype(F32)     # -0.0 is +0.0
    bits = jax.lax.bitcast_convert_type(s, jnp.int32)
    bits = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    u = jax.lax.bitcast_convert_type(bits, jnp.uint32) ^ jnp.uint32(1 << 31)
    u = jnp.where(valid, u, jnp.uint32(0))         # the order of the scores
    thr = jnp.zeros(u.shape[:-1], jnp.uint32)
    digits = jnp.arange(1, 16, dtype=jnp.uint32)
    for shift in range(28, -1, -4):
        cand = thr[..., None] | (digits << jnp.uint32(shift))      # [R, 15]
        at_least = jnp.sum(u[..., None, :] >= cand[..., None],
                           axis=-1, dtype=jnp.int32)
        d = jnp.sum(at_least >= k, axis=-1, dtype=jnp.int32)
        thr = thr | (d.astype(jnp.uint32) << jnp.uint32(shift))
    above = u > thr[..., None]
    need = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    tied = u == thr[..., None]
    rank = jnp.cumsum(tied, axis=-1, dtype=jnp.int32)
    return valid & (above | (tied & (rank <= need[..., None])))


def mask_positions(mask, k):
    """The positions [R, k] int32 of each row's chosen entries (bool
    [R, N], at most k a row), in order, and how many there are [R]; the
    places past a row's count hold 0. The i-th chosen entry is where the
    row's running count first reaches i + 1, i.e. the number of places
    whose running count is below it: one fused compare-and-count over
    [R, k, N], no scatter and no sequential search."""
    n = mask.shape[-1]
    seen = jnp.cumsum(mask, axis=-1, dtype=jnp.int32)
    want = jnp.arange(1, k + 1, dtype=jnp.int32)
    pos = jnp.sum(seen[:, None, :] < want[None, :, None], axis=-1,
                  dtype=jnp.int32)
    count = seen[:, -1]
    live = jnp.arange(k, dtype=jnp.int32)[None] < count[:, None]
    return jnp.where(live, jnp.minimum(pos, n - 1), 0), count


def expand(cfg, p, latent):
    """Every head's keys [T, nh, dn + dr] and values [T, nh, dv] from
    latent rows [T, >= kvr + dr]: k_nope, v = c Wkvb; the rotary key is
    shared by the heads."""
    t, dtype = latent.shape[0], latent.dtype
    nh, dn, kvr = cfg.num_attention_heads, cfg.qk_nope_head_dim, \
        cfg.kv_lora_rank
    kv = (latent[:, :kvr] @ p["wkv_b"].astype(dtype)).reshape(t, nh, -1)
    k_pe = jnp.broadcast_to(latent[:, None, kvr:cfg.latent_width],
                            (t, nh, cfg.qk_rope_head_dim))
    return jnp.concatenate([kv[..., :dn], k_pe], axis=-1), kv[..., dn:]


def attend_expanded(cfg, q, k, v, sel):
    """Plain masked attention: q [T, nh, dn + dr] against k, v [N, nh,
    .] where sel [T, N] is True. Returns [T, nh * dv]."""
    s = jnp.einsum("thd,jhd->htj", q, k, preferred_element_type=F32) \
        * cfg.softmax_scale
    s = jnp.where(sel[None], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("htj,jhd->thd", pr.astype(v.dtype), v,
                   preferred_element_type=F32)
    return o.astype(q.dtype).reshape(q.shape[0], -1)


def mlp(cfg, l, p, x, active=None, experts_scope="moe.experts"):
    """The second half of block l on the residual stream x [T, H]: (x,
    MoE counts; `NO_COUNTS` from a dense layer). The routed experts'
    grouped products run under `experts_scope`."""
    h2 = _rms(x, p["ln2"], cfg.rms_norm_eps)
    if not cfg.is_sparse(l):
        return x + swiglu(p, h2), jnp.asarray(NO_COUNTS)
    with jax.named_scope("moe.route"):
        idx, weights = moe_route(cfg, p, h2)
    r, counts = moe_experts(cfg, p, h2, idx, weights, active, experts_scope)
    shared = swiglu({"wg": p["ws_g"], "wu": p["ws_u"], "wd": p["ws_d"]}, h2)
    return x + r.astype(x.dtype) + shared, counts


def block_sequence(cfg, l, p, x, pos):
    """Block l on one whole sequence x [T, H] at positions pos [T] (plain
    XLA, [T, T] scores)."""
    t = x.shape[0]
    causal = pos[None, :] <= pos[:, None]
    h = _rms(x, p["ln1"], cfg.rms_norm_eps)
    q, latent, qi, ki, wi = project(cfg, p, h, pos)
    sel = causal if qi is None else topk_mask(
        index_scores(qi, wi, ki), causal, min(cfg.index_topk, t))
    k, v = expand(cfg, p, latent)
    o = attend_expanded(cfg, q, k, v, sel)
    x = x + o @ p["wo"].astype(x.dtype)
    x, _ = mlp(cfg, l, p, x)
    return x


def mtp_input(cfg, params, hn, next_ids):
    """The MTP block's input [T, H]: Weh [RMS(emb(next); enorm) ;
    RMS(hn; hnorm)] for the main model's normed last hidden states hn [T,
    H] and the ids of the tokens after them."""
    m, eps = params["mtp"], cfg.rms_norm_eps
    e = jnp.take(params["embed"], next_ids, axis=0).astype(hn.dtype)
    both = jnp.concatenate([_rms(e, m["enorm"], eps),
                            _rms(hn, m["hnorm"], eps)], axis=-1)
    return both @ m["eh_proj"].astype(hn.dtype)


def forward_sequence(cfg, params, ids, with_mtp=False):
    """Full causal forward over one sequence ids [T]: logits [T, V]
    float32 (plain XLA, [T, T] scores: for tests and short sequences).
    `with_mtp`: (logits, the MTP layer's draft logits [T, V]), row i's
    from (hn_i, emb(ids[i + 1])), the last row's from the greedy token
    after the sequence."""
    t = ids.shape[0]
    pos = jnp.arange(t, dtype=jnp.int32)
    x = jnp.take(params["embed"], ids, axis=0)
    for l in range(cfg.num_hidden_layers):
        x = block_sequence(cfg, l, params["layers"][l], x, pos)
    hn = _rms(x, params["norm"], cfg.rms_norm_eps)
    logits = hn.astype(F32) @ params["head"].astype(F32)
    if not with_mtp:
        return logits
    nxt = jnp.concatenate([ids[1:], jnp.argmax(logits[-1:], axis=-1)
                           .astype(ids.dtype)])
    u = block_sequence(cfg, cfg.num_hidden_layers, params["mtp"],
                       mtp_input(cfg, params, hn, nxt), pos)
    u = _rms(u, params["mtp"]["norm"], cfg.rms_norm_eps)
    return logits, u.astype(F32) @ params["head"].astype(F32)


# -- the dygraph model ----------------------------------------------------------------

class DeepseekV32ForCausalLM(Layer):
    """The dygraph model: parameters under the names of
    `DeepseekV32Config.param_shapes`, `forward(input_ids [B, T])` gives
    logits [B, T, V]. `arrays` ({name: jax array}) become the parameters
    as they are, without a second copy on the device; without it the
    parameters are drawn normal(0, 0.02) (norms one, the LayerNorm's bias
    zero, the routers' choice bias normal(0, 0.01)), which is what the CPU
    tests use."""

    def __init__(self, config: DeepseekV32Config, arrays=None, seed=0):
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
        if kind in ("ln1", "ln2", "norm", "q_norm", "kv_norm", "k_norm",
                    "enorm", "hnorm"):
            return np.ones(shape, np.float32)
        if kind == "k_norm_b":
            return np.zeros(shape, np.float32)
        if kind == "b_corr":
            return rng.normal(0.0, 0.01, shape)
        return rng.normal(0.0, 0.02, shape)

    def param_tree(self):
        """The parameters as the operators take them: {"embed", "norm",
        "head", "layers": [one dict a block]} and, with an MTP layer,
        "mtp": {its leaves}; the arrays themselves, no copy."""
        tree = {"layers": [{} for _ in range(self.config.num_hidden_layers)]}
        for name, attr in self._names.items():
            data = getattr(self, attr)._data
            if name.startswith("layers."):
                _, i, leaf = name.split(".")
                tree["layers"][int(i)][leaf] = data
            elif name.startswith("mtp."):
                tree.setdefault("mtp", {})[name[4:]] = data
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


# -- serving: latent rows and indexer keys, paged --------------------------------------

class LatentPagedDecoder(PagedDecoder):
    """`PagedDecoder` for a model whose layers keep a latent cache
    (`PagedDecoder(model)` builds this class when the model's
    `cache_kinds` name one). The serve loop, the allocator and the spans
    are the ones every engine runs; the cache it carries chunk to chunk
    is

        (lat [L, NB, bs, W], idx [L, NB, bs, id])    sparse configuration
        (lat [L (+ 1: the MTP layer), NB, bs, W],)    dense configuration

    donated and updated in place and paged by the one block table: a
    token's latent row [c | k_pe] (`W` = kv_lora_rank + rope dims
    rounded up to whole lanes, zeros behind) and, sparse, its indexer
    key. An admission is priced in these blocks.

    Sparse: a decode step scores every cached indexer key of a slot
    (`lightning_index_decode` copies the slot's live key blocks itself,
    through its table), takes the exact top `index_topk` (`topk_mask`)
    and gathers only the chosen latent rows, attended in the absorbed
    form: q_nope Wuk_h^T scores against c directly and the latent output
    is taken through Wuv_h afterwards. A prompt is prefilled in chunks of
    `prefill_chunk` rows by ONE program against the cache: its indexer
    scores every key before each row (`lightning_index_scores`), and
    `mla_prefill_attention` attends the heads' keys and values, formed
    from the latent rows inside the kernel, masked to each row's
    selection: no [rows, k, width] gather and no expanded keys in HBM.

    Dense: a decode step reads every latent row of a slot through its
    block table (`mla_paged_decode_attention`) and a prefill chunk is
    causal. With an MTP layer, a prompt's prefill also writes the MTP
    layer's rows and returns the first draft beside the first token, and
    `serve(spec_decode="mtp")` runs each step of the decode chunk as one
    verify pass that drafts the next token on the device
    (`_verify_step`), so it composes with the pipelined loop.

    What does not compose yet refuses at construction (or, for `serve()`
    options, at the call) with a NotImplementedError that names the
    option and the configuration it holds for."""

    _prefill_donate = (5, 6)
    LANES = 128

    REFUSED = {
        "weight_quant": "the latent projections have no quantized form "
                        "(sparse and dense)",
        "kv_quant": "the latent pool and the sparse configuration's "
                    "indexer pool have no codec",
        "prefix_cache": "a shared prefix would need its latent rows (and, "
                        "sparse, its indexer keys) mapped by the radix "
                        "tree",
        "prefix_cache_blocks": "it sizes the prefix cache",
        "attn_shards": "the sparse selection and the dense kernel's walk "
                       "are over a slot's whole context",
        "shard_block_budget": "it picks attn_shards",
        "kv_offload": "page-out has not been tried on the latent pools "
                      "(sparse and dense)",
        "hbm_budget_gib": "it prices kv_offload",
        "ragged_kernel": "there are no K and V blocks to attend: sparse, "
                         "the indexer kernel reads the keys through the "
                         "block table and the chosen latent rows are "
                         "gathered; dense, mla_paged_decode_attention "
                         "reads the latent rows through the block table",
    }
    DENSE_COUNTERS = ("moe_pairs_here", "moe_pairs_all",
                      "moe_experts_touched", "moe_max_load", "attn_rows",
                      "attn_pairs", "latent_rows_read")

    def __init__(self, model, max_len=None, block_size=64, num_blocks=None,
                 max_slots=8, headroom_guard=None,
                 pipelined_admission=False, prefill_chunk=None, **refused):
        for name, value in refused.items():
            if name not in self.REFUSED:
                raise TypeError(f"unexpected argument {name!r}")
            if value not in (None, False):
                raise NotImplementedError(
                    f"{name} does not compose with a latent cache: "
                    f"{self.REFUSED[name]}")
        cfg = model.config
        block_size = int(block_size)
        limit = int(max_len or cfg.max_position_embeddings)
        limit -= limit % block_size
        if cfg.has_indexer and limit < cfg.index_topk:
            raise ValueError(f"max_len {limit} below index_topk "
                             f"{cfg.index_topk}")
        chunk = int(prefill_chunk or min(1024, limit))
        if chunk % block_size:
            raise ValueError(f"prefill_chunk {chunk} must be whole blocks "
                             f"of {block_size}")
        super().__init__(model, max_len=max_len, block_size=block_size,
                         num_blocks=num_blocks, max_slots=max_slots,
                         headroom_guard=headroom_guard, ragged_kernel=False,
                         pipelined_admission=pipelined_admission,
                         prefill_chunk=chunk)
        # the parent's other programs (host-side verify, COW copy) serve
        # options this engine refuses
        self._spec_verify_jit = self._cow_copy_jit = None
        self._admit_counts = [0] * len(self.ADMIT_COUNTERS)
        self._admit_draft = None
        if not cfg.has_indexer:
            # one pool: the chunk program's static arguments move up one
            self._paged_chunk_state_jit = jax.jit(
                self._dense_chunk_impl, donate_argnums=(1, 2, 4, 5, 7),
                static_argnums=(8, 9))
            self._prefill_donate = (5,)
            self.COUNTERS = self.DENSE_COUNTERS

    def _prepare_weights(self, model, max_len, weight_quant):
        cfg = model.config
        self.cfg = cfg
        self.max_len = int(max_len or cfg.max_position_embeddings)
        self.nh, self.nkv = cfg.num_attention_heads, 1
        self.hd, self.eps = cfg.latent_width, cfg.rms_norm_eps
        self.weight_quant = None
        # an MTP layer keeps latent rows of its own, behind the main ones
        self.draft_layers = 0 if cfg.has_indexer \
            else cfg.num_nextn_predict_layers
        self.kv_layers = cfg.num_hidden_layers + self.draft_layers
        w = cfg.latent_width
        self.lat_row = -(-w // self.LANES) * self.LANES
        self._params = model.param_tree()
        body = sum(x.size * x.dtype.itemsize for x in
                   jax.tree_util.tree_leaves(self._params["layers"]))
        body += sum(x.size * x.dtype.itemsize for x in
                    jax.tree_util.tree_leaves(self._params.get("mtp", {})))
        body += self._params["head"].size * self._params["head"].dtype.itemsize
        self.weight_stream_bytes = {"quant": int(body), "bf16eq": int(body)}

    # -- the cache ----------------------------------------------------------------
    def new_pools(self):
        dt = jnp.bfloat16 if self.cfg.dtype == "bfloat16" else F32
        shape = (self.kv_layers, self.num_blocks, self.block_size)
        lat = jnp.zeros(shape + (self.lat_row,), dt)
        if not self.cfg.has_indexer:
            return (lat,)
        return (lat, jnp.zeros(shape + (self.cfg.index_head_dim,), dt))

    def kv_token_bytes(self):
        """Bytes a token keeps in ONE layer, as stored: its latent row and,
        sparse, its indexer key."""
        itemsize = 2 if self.cfg.dtype == "bfloat16" else 4
        index = self.cfg.index_head_dim if self.cfg.has_indexer else 0
        return (self.lat_row + index) * itemsize

    def pool_bytes(self):
        return self.num_blocks * self.bytes_per_block()

    def bytes_per_block(self):
        return self.kv_layers * self.block_size * self.kv_token_bytes()

    def _refuse(self, what, why):
        raise NotImplementedError(
            f"{what} does not compose with a latent cache: {why}")

    def export_blocks(self, *a, **kw):
        self._refuse("block export", "the latent and indexer pools have no "
                     "transport yet")

    def import_blocks(self, *a, **kw):
        self._refuse("block import", "the latent and indexer pools have no "
                     "transport yet")

    def page_out_blocks(self, *a, **kw):
        self._refuse("page-out", "it has not been tried on the latent pools")

    def page_in_blocks(self, *a, **kw):
        self._refuse("page-in", "it has not been tried on the latent pools")

    def serve(self, requests, spec_decode=None, **kw):
        """`PagedDecoder.serve`; `spec_decode="mtp"` (dense, with an MTP
        layer) drafts on the device, every other draft refuses."""
        if spec_decode is not None:
            if self.cfg.has_indexer:
                self._refuse("spec_decode", "the sparse configuration's "
                             "verify pass would select keys for several "
                             "rows of a slot at once")
            from .spec_decode import resolve_spec
            spec, _ = resolve_spec(spec_decode, self)
            if spec.draft != "mtp":
                self._refuse(
                    "a host-side draft (spec_decode=k, n-gram or a draft "
                    "model)", "the dense configuration verifies the draft "
                    "its MTP layer makes on the device: spec_decode='mtp'")
        return super().serve(requests, spec_decode=spec_decode, **kw)

    # -- addressing ---------------------------------------------------------------
    @staticmethod
    def _flat(pool):
        """[L, NB, bs, W] -> [L * NB * bs, W]: layer a's block b is row
        (a * NB + b) * bs on. No data moves."""
        return pool.reshape(-1, pool.shape[-1])

    def _rows(self, layer, tables, pos):
        """Flat pool rows of positions pos [..] through block tables of
        the same leading shape (tables [.., MB])."""
        nb, bs = self.num_blocks, self.block_size
        blk = jnp.take_along_axis(tables, pos // bs, axis=-1)
        return (blk + layer * nb) * bs + pos % bs

    def _chosen_rows(self, layer, tables, pos):
        """`_rows` of decode's chosen positions pos [S, k]: each table
        entry is picked by one compare-and-select over [S, k, MB], where
        XLA's gather of single entries takes about a millisecond a layer
        at 48 x 2,048 on a TPU v5e."""
        nb, bs = self.num_blocks, self.block_size
        hit = (pos // bs)[..., None] == jnp.arange(tables.shape[-1],
                                                   dtype=pos.dtype)
        blk = jnp.sum(jnp.where(hit, tables[:, None], 0), axis=-1,
                      dtype=tables.dtype)
        return (blk + layer * nb) * bs + pos % bs

    def _write(self, pool, rows, at):
        """Scatter rows [n, w] into a flat pool at flat rows at [n];
        rows narrower than the pool's are zero behind."""
        if rows.shape[-1] < pool.shape[-1]:
            rows = jnp.pad(rows, ((0, 0), (0, pool.shape[-1] - rows.shape[-1])))
        return pool.at[at].set(rows.astype(pool.dtype))

    def _context(self, pool, layer, tables):
        """Every row the block tables [.., MB] address in `layer` of a flat
        pool, one position after another: [.., MB * bs, W], gathered a
        whole block at a time."""
        bs, width = self.block_size, pool.shape[-1]
        blocks = jnp.take(pool.reshape(-1, bs, width),
                          tables + layer * self.num_blocks, axis=0)
        return blocks.reshape(tables.shape[:-1] + (-1, width))

    # -- programs -------------------------------------------------------------------
    COUNTERS = ("moe_pairs_here", "moe_pairs_all", "moe_experts_touched",
                "moe_max_load", "attn_rows", "index_keys", "latent_rows_read")
    ADMIT_COUNTERS = COUNTERS[:4] + ("kv_blocks",)

    def _select(self, qi, wi, idx, layer, tables, pos):
        """The positions [S, k] each decode row attends and how many
        [S]: the exact top-k of the indexer's scores over the slot's
        cached keys up to its position pos [S], which the kernel reads
        from `layer` of the flat indexer pool idx through the block
        tables [S, MB]."""
        from ..kernels.pallas.lightning_index import lightning_index_decode
        k, bs = self.cfg.index_topk, self.block_size
        with jax.named_scope("decode.index"):
            scores = lightning_index_decode(
                qi, wi, idx.reshape(-1, bs, idx.shape[-1]), tables, pos,
                layer * self.num_blocks)
        valid = jnp.arange(scores.shape[1], dtype=jnp.int32)[None] \
            <= pos[:, None]
        return mask_positions(topk_mask(scores, valid, k), k)

    def _attend_absorbed(self, p, q, lat, rows, count):
        """Absorbed MLA of decode rows q [S, nh, dn + dr] over the latent
        rows at flat pool rows rows [S, k], the first count [S] of each
        row's. Returns [S, nh * dv]."""
        from ..kernels.pallas.mla_decode import mla_decode_attention
        cfg = self.cfg
        S, nh = q.shape[:2]
        dn, kvr, dv = cfg.qk_nope_head_dim, cfg.kv_lora_rank, cfg.v_head_dim
        wkv = p["wkv_b"].astype(q.dtype).reshape(kvr, nh, dn + dv)
        with jax.named_scope("decode.attend"):
            q_c = jnp.einsum("shd,chd->shc", q[..., :dn], wkv[..., :dn])
            # every row is in the pool: no select of a fill over the copy
            chosen = jnp.take(lat, rows, axis=0, mode="clip")  # [S, k, W]
            with jax.named_scope("decode.attend.sparse"):
                o_c = mla_decode_attention(q_c, q[..., dn:], chosen, count,
                                           kvr, cfg.softmax_scale)
            o = jnp.einsum("shc,chd->shd", o_c, wkv[..., dn:])
        return o.reshape(S, nh * dv)

    def _step(self, params, tokens, seqlens, tables, active, lat, idx):
        """One decode step for every slot. Returns (logits [S, V], the two
        pools, the step's counts: MoE int32 [4], then the latent rows ONE
        layer's attention read)."""
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0)
        dtype = x.dtype
        at = jnp.where(active, self._rows(0, tables, seqlens[:, None])[:, 0],
                       seqlens % self.block_size)   # trash block 0 when idle
        lat_f, idx_f = self._flat(lat), self._flat(idx)
        counts = jnp.asarray(NO_COUNTS)
        read = jnp.int32(0)
        layer_rows = self.num_blocks * self.block_size
        for l in range(cfg.num_hidden_layers):
            p = params["layers"][l]
            h = _rms(x, p["ln1"], self.eps)
            q, latent, qi, ki, wi = project(cfg, p, h, seqlens)
            with jax.named_scope("decode.kv_pool"):
                lat_f = self._write(lat_f, latent, at + l * layer_rows)
                idx_f = self._write(idx_f, ki, at + l * layer_rows)
            pos, count = self._select(qi, wi, idx_f, l, tables, seqlens)
            rows = self._chosen_rows(l, tables, pos)
            o = self._attend_absorbed(p, q, lat_f, rows, count)
            if l == 0:
                read = jnp.sum(jnp.where(active, count, 0), dtype=jnp.int32)
            x = x + o @ p["wo"].astype(dtype)
            x, c = mlp(cfg, l, p, x, active)
            counts = merge_counts(counts, c)
        x = _rms(x, params["norm"], self.eps)
        return (self._head_logits(params, x), lat_f.reshape(lat.shape),
                idx_f.reshape(idx.shape), counts, read)

    def _paged_chunk_state_impl(self, params, tok0, seqlens0, tables, live,
                                budgets, poison, lat, idx, n, eos_id):
        """The state-carrying chunk of `PagedDecoder` (same arithmetic
        of liveness, budgets and eos), with the two pools in the step
        loop's carry and, after them in what it returns, the chunk's
        counters `COUNTERS` (int32 [7]) that ride home with the
        tokens."""
        def step(tok, lens, act, pools):
            logits, *pools, c, read = self._step(params, tok, lens, tables,
                                                 act, *pools)
            return logits, pools, (c, read)

        def tally(acc, aux, act, lens):
            (stats, seen), (c, read) = acc, aux
            return merge_counts(stats, c), seen + jnp.stack([
                jnp.sum(act, dtype=jnp.int32),
                jnp.sum(jnp.where(act, lens + 1, 0), dtype=jnp.int32),
                read])

        out, (stats, seen) = self._chunk_scan(
            step, tok0, seqlens0, live, budgets, poison, (lat, idx), n,
            eos_id, tally,
            lambda: (jnp.asarray(NO_COUNTS), jnp.zeros(3, jnp.int32)))
        return out + (jnp.concatenate([stats, seen]),)

    def chunk_counters(self, aux):
        """The chunk's counters as `serve:commit` metadata; `aux` is
        what the chunk program returned after the pools, already on the
        host's side of the token read. The attention counts are of one
        layer: rows that attended, the keys its indexer scored and the
        latent rows its attention read."""
        return dict(zip(self.COUNTERS, (int(v) for v in np.asarray(aux[0]))))

    # -- the dense configuration: every latent row, and the MTP draft -----------------
    def _attend_paged(self, p, q, lat, layer, tables, lens, scope):
        """Absorbed MLA of decode rows q [S, R, nh, dn + dr] over every
        latent row of their slot that each sees (lens [S, R] keys), read
        by the kernel, under the named `scope`, from `layer` of the flat
        latent pool through the block tables [S, MB]. Returns [S * R, nh
        * dv]."""
        from ..kernels.pallas.mla_paged_decode import (
            mla_paged_decode_attention)
        cfg = self.cfg
        S, R, nh = q.shape[:3]
        dn, kvr, dv = cfg.qk_nope_head_dim, cfg.kv_lora_rank, cfg.v_head_dim
        wkv = p["wkv_b"].astype(q.dtype).reshape(kvr, nh, dn + dv)
        with jax.named_scope("decode.attend"):
            q_c = jnp.einsum("srhd,chd->srhc", q[..., :dn], wkv[..., :dn])
            with jax.named_scope(scope):
                o_c = mla_paged_decode_attention(
                    q_c, q[..., dn:], lat.reshape(-1, self.block_size,
                                                  lat.shape[-1]),
                    tables, lens, layer * self.num_blocks, kvr,
                    cfg.softmax_scale)
            o = jnp.einsum("srhc,chd->srhd", o_c, wkv[..., dn:])
        return o.reshape(S * R, nh * dv)

    def _decode_block(self, p, l, x, pos, tables, act, lat_f, at,
                      scope=None):
        """Block l (the MTP block where l = num_hidden_layers) on decode
        rows x [S * R, H], row r of slot s at position pos[s, r]: its
        latent rows written at the flat rows `at` [S * R] of layer 0 (the
        trash block where a slot is not `act` [S, R]), then attended up to
        their own positions. Its kernels run under `scope` where one is
        given (the MTP block's, so that the trace tells them apart), else
        under `decode.attend.dense` and `moe.experts`. Returns (x, lat_f,
        MoE counts)."""
        cfg = self.cfg
        S, R = pos.shape
        h = _rms(x, p["ln1"], self.eps)
        q, latent, *_ = project(cfg, p, h, pos.reshape(-1))
        with jax.named_scope("decode.kv_pool"):
            lat_f = self._write(lat_f, latent,
                                at + l * self.num_blocks * self.block_size)
        o = self._attend_paged(p, q.reshape((S, R) + q.shape[1:]), lat_f, l,
                               tables, pos + 1, scope or "decode.attend.dense")
        x = x + o @ p["wo"].astype(x.dtype)
        x, counts = mlp(cfg, l, p, x, act.reshape(-1),
                        scope or "moe.experts")
        return x, lat_f, counts

    def _decode_rows(self, params, ids, pos, tables, act, lat):
        """The main blocks on decode rows: ids [S, R] at positions pos [S,
        R] (`act` [S, R]: the rows of slots that advance). Returns (the
        normed last hidden states [S * R, H], the flat pool, MoE counts,
        attention counts int32 [3]: the rows, the (row, key) pairs and the
        latent rows ONE block reads, a slot's once), and the flat rows
        the rows' latent rows went to in layer 0."""
        cfg = self.cfg
        x = jnp.take(params["embed"], ids.reshape(-1), axis=0)
        at = jnp.where(act, self._rows(0, tables, pos),
                       pos % self.block_size).reshape(-1)
        lat_f = self._flat(lat)
        counts = jnp.asarray(NO_COUNTS)
        for l in range(cfg.num_hidden_layers):
            x, lat_f, c = self._decode_block(params["layers"][l], l, x, pos,
                                             tables, act, lat_f, at)
            counts = merge_counts(counts, c)
        attn = jnp.stack([
            jnp.sum(act, dtype=jnp.int32),
            jnp.sum(jnp.where(act, pos + 1, 0), dtype=jnp.int32),
            jnp.sum(jnp.where(act[:, 0], pos[:, -1] + 1, 0),
                    dtype=jnp.int32)])
        return _rms(x, params["norm"], self.eps), lat_f, counts, attn, at

    def _dense_step(self, params, tokens, seqlens, tables, active, lat):
        """One plain decode step for every slot (dense configuration).
        Returns (logits [S, V], the pool, MoE counts int32 [4], attention
        counts int32 [3])."""
        hn, lat_f, counts, attn, _ = self._decode_rows(
            params, tokens[:, None], seqlens[:, None], tables,
            active[:, None], lat)
        return (self._head_logits(params, hn), lat_f.reshape(lat.shape),
                counts, attn)

    def _verify_step(self, params, tok, draft, seqlens, tables, active,
                     lat):
        """One verify pass for every slot: its current token tok [S] at
        position seqlens [S] and its draft [S] one position on go through
        the main blocks as two rows (their latent rows written, each
        attending up to itself): the target's logits [S, 2, V] and its
        tokens g [S, 2]. Then the MTP block on (hn_p, emb g0) and
        (hn_{p+1}, emb g1) at the same two positions (its own latent rows
        written) proposes the draft that would follow either: cand [S,
        2]. Rows past a rejected draft are rewritten by the next pass.
        Returns (logits, g, cand, the pool, the main blocks' MoE counts,
        attention counts of one main block)."""
        cfg = self.cfg
        S = tok.shape[0]
        pos = seqlens[:, None] + jnp.arange(2, dtype=jnp.int32)[None]
        act = jnp.broadcast_to(active[:, None], pos.shape)
        hn, lat_f, counts, attn, at = self._decode_rows(
            params, jnp.stack([tok, draft], axis=1), pos, tables, act, lat)
        logits = self._head_logits(params, hn).reshape(S, 2, -1)
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        with jax.named_scope("decode.mtp"):
            m = params["mtp"]
            u, lat_f, _ = self._decode_block(
                m, cfg.num_hidden_layers,
                mtp_input(cfg, params, hn, g.reshape(-1)), pos, tables, act,
                lat_f, at, "decode.mtp")
            cand = jnp.argmax(self._head_logits(
                params, _rms(u, m["norm"], self.eps)), axis=-1)
        return (logits, g, cand.astype(jnp.int32).reshape(S, 2),
                lat_f.reshape(lat.shape), counts, attn)

    def _dense_chunk_impl(self, params, tok0, seqlens0, tables, live,
                          budgets, poison, lat, n, eos_id):
        """The dense configuration's decode chunk: `_chunk_scan` of plain
        steps where tok0 is [S], `_draft_scan` of verify passes where it
        is [S, 2] (each slot's token and its draft). The counters behind
        the pool are `DENSE_COUNTERS` (int32 [7])."""
        def tally(acc, aux, act, lens):
            (stats, seen), (c, attn) = acc, aux
            return merge_counts(stats, c), seen + attn

        def start():
            return jnp.asarray(NO_COUNTS), jnp.zeros(3, jnp.int32)

        if tok0.ndim == 1:
            def step(tok, lens, act, pools):
                logits, pool, c, attn = self._dense_step(
                    params, tok, lens, tables, act, *pools)
                return logits, (pool,), (c, attn)
            out, (stats, seen) = self._chunk_scan(
                step, tok0, seqlens0, live, budgets, poison, (lat,), n,
                eos_id, tally, start)
        else:
            def step(tok, draft, lens, act, pools):
                logits, g, cand, pool, c, attn = self._verify_step(
                    params, tok, draft, lens, tables, act, *pools)
                return logits, g, cand, (pool,), (c, attn)
            out, (stats, seen) = self._draft_scan(
                step, tok0, seqlens0, live, budgets, poison, (lat,), n,
                eos_id, tally, start)
        return out + (jnp.concatenate([stats, seen]),)

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
            # dense: the token after the chunk, for its last MTP row
            tail = () if self.cfg.has_indexer else (jnp.int32(
                prompt[start + bucket] if start + bucket < n else pad),)
            calls.append(((jnp.asarray(ids), jnp.int32(start), jnp.int32(n),
                           table), tail))
        return calls

    def _prefill_paged(self, params, ids, start, true_len, table, lat, idx):
        """One chunk of a prompt: ids [C] are its rows start .. start + C
        (those from true_len on are padding). The chunk's latent rows and
        indexer keys go into the slot's pages; each layer's indexer
        scores the chunk's rows against every key up to them, selects,
        and the heads attend keys and values formed from the latent rows,
        masked to the selection. Returns int32 [1 + 5] (the encoded token after the
        prompt's last row, which only the last chunk's call has; then
        `ADMIT_COUNTERS`: the chunk's MoE counts and the blocks the table
        holds) and the pools."""
        from ..kernels.pallas.lightning_index import lightning_index_scores
        from ..kernels.pallas.mla_prefill import mla_prefill_attention
        cfg = self.cfg
        C = ids.shape[0]
        pos = start + jnp.arange(C, dtype=jnp.int32)
        valid = pos < true_len
        x = jnp.take(params["embed"], ids, axis=0)
        dtype = x.dtype
        at = jnp.where(valid, self._rows(0, table[None], pos[None])[0],
                       pos % self.block_size)
        lat_f, idx_f = self._flat(lat), self._flat(idx)
        layer_rows = self.num_blocks * self.block_size
        n_keys = table.shape[0] * self.block_size
        sees = jnp.arange(n_keys, dtype=jnp.int32)[None] <= pos[:, None]
        counts = jnp.asarray(NO_COUNTS)
        for l in range(cfg.num_hidden_layers):
            p = params["layers"][l]
            h = _rms(x, p["ln1"], self.eps)
            q, latent, qi, ki, wi = project(cfg, p, h, pos)
            lat_f = self._write(lat_f, latent, at + l * layer_rows)
            idx_f = self._write(idx_f, ki, at + l * layer_rows)
            with jax.named_scope("prefill.index"):
                keys = self._context(idx_f, l, table)
                scores = lightning_index_scores(qi, wi, keys, start)
                sel = topk_mask(scores, sees, cfg.index_topk)
            with jax.named_scope("prefill.attend"):
                o = mla_prefill_attention(
                    q, self._context(lat_f, l, table),
                    p["wkv_b"].astype(dtype), sel, start, cfg.kv_lora_rank,
                    cfg.qk_rope_head_dim, cfg.softmax_scale).reshape(C, -1)
            x = x + o @ p["wo"].astype(dtype)
            x, c = mlp(cfg, l, p, x, valid)
            counts = merge_counts(counts, c)
        last = jnp.take(x, jnp.clip(true_len - 1 - start, 0, C - 1), axis=0)
        logits = self._head_logits(
            params, _rms(last[None], params["norm"], self.eps))[0]
        enc = jnp.concatenate([
            self._encode_first_token(logits)[None], counts,
            jnp.sum(table != 0, dtype=jnp.int32)[None]])
        return enc, lat_f.reshape(lat.shape), idx_f.reshape(idx.shape)

    def _prefill_block(self, p, l, x, pos, valid, table, lat_f, at, start,
                       scope=None):
        """Block l (the MTP block where l = num_hidden_layers) on a
        prompt's chunk x [C, H] at positions pos [C] (dense
        configuration): its latent rows written at the flat rows `at` of
        layer 0, then the causal attention over the slot's latent rows.
        Its kernels run under `scope` where one is given, else under
        `prefill.attend` and `moe.experts`. Returns (x, lat_f, MoE
        counts)."""
        from ..kernels.pallas.mla_prefill import mla_prefill_attention
        cfg = self.cfg
        h = _rms(x, p["ln1"], self.eps)
        q, latent, *_ = project(cfg, p, h, pos)
        lat_f = self._write(lat_f, latent,
                            at + l * self.num_blocks * self.block_size)
        with jax.named_scope(scope or "prefill.attend"):
            o = mla_prefill_attention(
                q, self._context(lat_f, l, table), p["wkv_b"].astype(x.dtype),
                None, start, cfg.kv_lora_rank, cfg.qk_rope_head_dim,
                cfg.softmax_scale).reshape(x.shape[0], -1)
        x = x + o @ p["wo"].astype(x.dtype)
        x, counts = mlp(cfg, l, p, x, valid, scope or "moe.experts")
        return x, lat_f, counts

    def _prefill_dense(self, params, ids, start, true_len, table, lat, nxt):
        """`_prefill_paged` for the dense configuration: causal, no
        indexer. With an MTP layer the chunk's MTP rows are written too:
        row i from (hn_i, emb of the token after it), which is the
        chunk's next row, `nxt` (the next chunk's first token) behind
        its last row, and the first generated token behind the prompt's
        last row. Returns int32 [1 + 5] as `_prefill_paged` does, then,
        with an MTP layer, the draft after the first token (the last
        chunk's call holds it), and the pool."""
        cfg = self.cfg
        C = ids.shape[0]
        pos = start + jnp.arange(C, dtype=jnp.int32)
        valid = pos < true_len
        x = jnp.take(params["embed"], ids, axis=0)
        at = jnp.where(valid, self._rows(0, table[None], pos[None])[0],
                       pos % self.block_size)
        lat_f = self._flat(lat)
        counts = jnp.asarray(NO_COUNTS)
        for l in range(cfg.num_hidden_layers):
            x, lat_f, c = self._prefill_block(params["layers"][l], l, x, pos,
                                              valid, table, lat_f, at, start)
            counts = merge_counts(counts, c)
        hn = _rms(x, params["norm"], self.eps)
        last = jnp.clip(true_len - 1 - start, 0, C - 1)
        logits = self._head_logits(params, jnp.take(hn, last, axis=0)[None])[0]
        enc = [self._encode_first_token(logits)[None], counts,
               jnp.sum(table != 0, dtype=jnp.int32)[None]]
        if self.draft_layers:
            with jax.named_scope("prefill.mtp"):
                m = params["mtp"]
                after = jnp.concatenate([ids[1:], nxt[None]])
                after = jnp.where(pos == true_len - 1,
                                  jnp.argmax(logits).astype(ids.dtype), after)
                u, lat_f, _ = self._prefill_block(
                    m, cfg.num_hidden_layers,
                    mtp_input(cfg, params, hn, after), pos, valid, table,
                    lat_f, at, start, "prefill.mtp")
                u = _rms(jnp.take(u, last, axis=0)[None], m["norm"], self.eps)
                enc.append(jnp.argmax(self._head_logits(params, u)[0])
                           .astype(jnp.int32)[None])
        return jnp.concatenate(enc), lat_f.reshape(lat.shape)

    def _prefill_exec(self, bucket):
        """The jitted prefill chunk program (`_prefill_paged`, or
        `_prefill_dense` in the dense configuration)."""
        if bucket not in self._prefill_cache:
            fn = self._prefill_paged if self.cfg.has_indexer \
                else self._prefill_dense
            self._prefill_cache[bucket] = jax.jit(
                fn, donate_argnums=self._prefill_donate)
        return self._prefill_cache[bucket]

    def decode_first_token(self, encs, seg=0):
        """The prompt's first token from its last chunk's result. The
        counts behind the token are summed over the prompt's chunks (the
        largest load is the largest of them) and kept for
        `admit_metadata`, the MTP layer's first draft for
        `first_draft`."""
        chunks = np.stack([np.asarray(e) for e in encs])
        counts = chunks[:, 1:]
        self._admit_counts = [int(v) for v in counts[:, :3].sum(axis=0)] \
            + [int(counts[:, 3].max()), int(counts[-1, 4])]
        self._admit_draft = int(chunks[-1, 6]) if self.draft_layers else None
        return super().decode_first_token([chunks[-1, 0]])

    def first_draft(self):
        """The token the MTP layer drafted after the first token that
        `decode_first_token` just read."""
        return self._admit_draft

    def admit_metadata(self):
        """The prompt's MoE counts under the chunk counters' names and
        the blocks the admission reserved."""
        return dict(zip(self.ADMIT_COUNTERS, self._admit_counts))

    def _record_traffic(self, seqlens, steps, live, budgets, launches=None):
        """The weight stream only: no ragged kernel reads this cache."""
        self.record_weight_fetch(steps)
