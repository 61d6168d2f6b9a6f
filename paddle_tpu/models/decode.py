"""KV-cache decode engine for serving (VERDICT r3 item 4).

Reference capability: the fused decode kernels
(phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu,
block_multi_head_attention_kernel.cu) — one token per step attends against
an in-place KV cache.

TPU formulation: fixed-shape caches + one compiled step. prefill() runs a
single causal forward over the prompt that also RETURNS every layer's K/V
(written into [L, B, max_len, Hkv, D] caches); step() is ONE jitted
single-token executable — layer loop as lax.scan over the stacked weights
with the caches as scanned-over/updated leaves, cache buffers donated so
XLA updates them in place. No per-length recompiles (position is a traced
scalar; attention masks by `arange(T) <= pos`), no dynamic shapes.

Weight-only int8 (`weight_quant="int8"`): per-output-channel symmetric
quantization of every matmul weight; the dequant (int8 -> bf16 * scale)
fuses into the matmul, halving the weight HBM traffic that dominates
small-batch decode.

`weight_quant="int8_blockwise"` upgrades the codec to the per-block
scales of kernels/pallas/quant_matmul (one scale per 128 contraction
rows per output column — tighter error than one scale per column) and
routes every projection through the quant_matmul kernel, which
dequantizes in VMEM: codes+scales are the ONLY weight HBM stream
(~0.52x the bf16 bytes; `weight_stream_bytes` holds the per-forward
ledger the <0.6x traffic gate checks).
"""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp

from ..framework.tensor import Tensor
from ..framework import random as random_mod

__all__ = ["CachedDecoder"]


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


class CachedDecoder:
    """Serving engine over a (non-pipelined) LlamaForCausalLM."""

    def __init__(self, model, max_len=None, weight_quant=None):
        cfg = model.config
        if getattr(cfg, "pipeline_parallel", False) or \
                getattr(cfg, "context_parallel", False):
            raise NotImplementedError(
                "CachedDecoder serves the single-program model; export "
                "the pipelined trainer's weights into a plain config "
                "first (state dicts are layout-portable)")
        self.cfg = cfg
        self.max_len = int(max_len or cfg.max_position_embeddings)
        self.nh = cfg.num_attention_heads
        self.nkv = cfg.num_key_value_heads
        self.hd = cfg.head_dim
        self.eps = cfg.rms_norm_eps
        self.weight_quant = weight_quant
        if weight_quant not in (None, "int8", "int8_blockwise"):
            raise ValueError(f"unknown weight_quant {weight_quant!r}")

        llama = model.llama
        layers = list(llama.layers)

        def stack(get):
            return jnp.stack([jnp.asarray(get(l)._data) for l in layers])

        w = {
            "wq": stack(lambda l: l.self_attn.q_proj.weight),
            "wk": stack(lambda l: l.self_attn.k_proj.weight),
            "wv": stack(lambda l: l.self_attn.v_proj.weight),
            "wo": stack(lambda l: l.self_attn.o_proj.weight),
            "wg": stack(lambda l: l.mlp.gate_proj.weight),
            "wu": stack(lambda l: l.mlp.up_proj.weight),
            "wd": stack(lambda l: l.mlp.down_proj.weight),
            "ln1": stack(lambda l: l.input_layernorm.weight),
            "ln2": stack(lambda l: l.post_attention_layernorm.weight),
        }
        # biases: the reference LlamaConfig ships bias-free projections;
        # Linear(bias) support would stack them the same way
        self.embed = jnp.asarray(llama.embed_tokens.weight._data)
        self.norm_w = jnp.asarray(llama.norm.weight._data)
        if model.lm_head is not None:
            self.head = jnp.asarray(model.lm_head.weight._data)
        else:
            self.head = self.embed.T
        cos, sin = (jnp.asarray(llama.rope_cos._data),
                    jnp.asarray(llama.rope_sin._data))
        if cos.shape[0] < self.max_len:
            raise ValueError(f"max_len {self.max_len} exceeds the model's "
                             f"rope tables ({cos.shape[0]})")
        self.cos, self.sin = cos[:self.max_len], sin[:self.max_len]

        # per-forward weight HBM ledger: what one full fetch of every
        # projection + the head costs in this engine's storage format,
        # and what the same fetches would cost at bf16 — the yardstick
        # the <0.6x traffic gate divides by (record_weight_fetch books
        # both into the observability registry per decode step)
        quant_b = bf16eq_b = 0
        if weight_quant == "int8":
            self.wq8, self.wscale = {}, {}
            for k in ("wq", "wk", "wv", "wo", "wg", "wu", "wd"):
                a = w[k].astype(jnp.float32)           # [L, in, out]
                s = jnp.max(jnp.abs(a), axis=1, keepdims=True) / 127.0
                s = jnp.maximum(s, 1e-12)
                self.wq8[k] = jnp.round(a / s).astype(jnp.int8)
                self.wscale[k] = s.astype(jnp.float32)
                quant_b += self.wq8[k].size + self.wscale[k].size * 4
                bf16eq_b += a.size * 2
            self.w = {k: w[k] for k in ("ln1", "ln2")}
            hf = self.head.astype(jnp.float32)
            hs = jnp.maximum(jnp.max(jnp.abs(hf), axis=0,
                                     keepdims=True) / 127.0, 1e-12)
            self.head_q8 = jnp.round(hf / hs).astype(jnp.int8)
            self.head_scale = hs.astype(jnp.float32)
            quant_b += self.head_q8.size + self.head_scale.size * 4
            bf16eq_b += hf.size * 2
            # the dense head (~vocab x hidden) is dead weight once
            # quantized — on a 16 GB chip it costs real batch/context
            self.head = None
        elif weight_quant == "int8_blockwise":
            from ..kernels.pallas.quant_matmul import (
                blockwise_weight_bytes, quantize_weight_blockwise)
            self.wq8, self.wscale = {}, {}
            for k in ("wq", "wk", "wv", "wo", "wg", "wu", "wd"):
                # [L, in, out]: the codec quantizes the trailing
                # [in, out] per (in-block, out column) across all layers
                q, s = quantize_weight_blockwise(w[k])
                self.wq8[k], self.wscale[k] = q, s
                nl, kin, nout = w[k].shape
                qb, bb = blockwise_weight_bytes(kin, nout)
                quant_b += nl * qb
                bf16eq_b += nl * bb
            self.w = {k: w[k] for k in ("ln1", "ln2")}
            hq, hs = quantize_weight_blockwise(self.head)
            self.head_q8, self.head_scale = hq, hs
            qb, bb = blockwise_weight_bytes(*self.head.shape)
            quant_b += qb
            bf16eq_b += bb
            self.head = None
        else:
            self.w = w
            for k in ("wq", "wk", "wv", "wo", "wg", "wu", "wd"):
                quant_b += w[k].size * w[k].dtype.itemsize
                bf16eq_b += w[k].size * 2
            quant_b += self.head.size * self.head.dtype.itemsize
            bf16eq_b += self.head.size * 2
        self.weight_stream_bytes = {"quant": int(quant_b),
                                    "bf16eq": int(bf16eq_b)}

        # weights enter as jit ARGUMENTS (closure capture would bake
        # multi-GB constants into both executables)
        if weight_quant == "int8":
            head_p = (self.head_q8, self.head_scale)
        elif weight_quant == "int8_blockwise":
            head_p = {"q": self.head_q8, "s": self.head_scale}
        else:
            head_p = self.head
        self._params = {
            "layers": self._layer_weights(),
            "embed": self.embed, "norm": self.norm_w,
            "head": head_p,
            "cos": self.cos, "sin": self.sin,
        }
        self._step_jit = jax.jit(self._step_impl, donate_argnums=(3, 4))
        self._prefill_jit = jax.jit(self._prefill_impl,
                                    donate_argnums=(2, 3))
        # greedy chunk: CHUNK decode steps fused into one executable
        # (lax.scan with argmax feedback) — one dispatch per CHUNK tokens
        # instead of one per token, which is the dominant cost when every
        # dispatch is a host round trip
        self._chunk_jit = jax.jit(self._chunk_impl, donate_argnums=(3, 4),
                                  static_argnums=(5,))
        # sampled chunk (VERDICT r4 #4): top-k/top-p/temperature + the
        # categorical draw INSIDE the fused executable, per-step PRNG
        # keys threaded as a scanned input — do_sample stops paying a
        # host round trip per token. Only (n, top_k, use_top_p) shape
        # the program; temperature/top_p are traced operands, so varying
        # them per request reuses the same executable.
        self._sample_chunk_jit = jax.jit(
            self._sample_chunk_impl, donate_argnums=(3, 4),
            static_argnums=(8, 9, 10))
        # greedy tokens per fused dispatch (instance knob; tests shrink
        # it to exercise the chunk/tail mix on tiny prompts)
        self.CHUNK = 32

    def _chunk_impl(self, params, tok0, pos0, kcache, vcache, n):
        """Run n greedy steps on-device: feed argmax back as the next
        token. Returns ([B, n] generated tokens, caches)."""
        def body(carry, i):
            tok, kc, vc = carry
            logits, kc, vc = self._step_impl(params, tok, pos0 + i, kc, vc)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, kc, vc), nxt

        (tok, kcache, vcache), toks = jax.lax.scan(
            body, (tok0, kcache, vcache), jnp.arange(n, dtype=jnp.int32))
        return jnp.swapaxes(toks, 0, 1), kcache, vcache

    def _sample_chunk_impl(self, params, tok0, pos0, kcache, vcache,
                           keys, temperature, top_p, n, top_k, use_top_p):
        """n fused SAMPLED steps: the next token is drawn on-device with
        the exact host sampler math (generation._sample_next_traced)
        under keys[i] — one PRNG key per step, stacked by the caller in
        the same order the per-token host loop consumes them, so
        fixed-seed token streams are identical to the unfused path.
        temperature/top_p are traced; n/top_k/use_top_p are static."""
        from .generation import _sample_next_traced

        def body(carry, inp):
            tok, kc, vc = carry
            i, key = inp
            logits, kc, vc = self._step_impl(params, tok, pos0 + i, kc, vc)
            nxt = _sample_next_traced(logits, temperature, top_k,
                                      use_top_p, top_p,
                                      key).astype(jnp.int32)
            return (nxt, kc, vc), nxt

        (tok, kcache, vcache), toks = jax.lax.scan(
            body, (tok0, kcache, vcache),
            (jnp.arange(n, dtype=jnp.int32), keys))
        return jnp.swapaxes(toks, 0, 1), kcache, vcache

    @staticmethod
    def _layer_mm(x, wl, dtype):
        """x @ one layer's weight; wl is a dense array, an (int8, scale)
        pair (per-channel), or a {"q", "s"} dict (per-block codes +
        scales routed through the quant_matmul kernel — the dequant
        happens in VMEM, never as a materialized full-width weight)."""
        if isinstance(wl, dict):
            from ..kernels.pallas.quant_matmul import quant_matmul
            return quant_matmul(x, wl["q"], wl["s"], impl="auto")
        if isinstance(wl, tuple):
            q, s = wl
            return x @ (q.astype(dtype) * s.astype(dtype))
        return x @ wl.astype(dtype)

    def _layer_weights(self):
        """Pytree scanned over the layer dim by prefill/step."""
        keys = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")
        if self.weight_quant == "int8":
            mats = {k: (self.wq8[k], self.wscale[k]) for k in keys}
        elif self.weight_quant == "int8_blockwise":
            mats = {k: {"q": self.wq8[k], "s": self.wscale[k]}
                    for k in keys}
        else:
            mats = {k: self.w[k] for k in keys}
        mats["ln1"] = self.w["ln1"]
        mats["ln2"] = self.w["ln2"]
        return mats

    def _head_logits(self, params, x):
        h = params["head"]
        if isinstance(h, dict):
            from ..kernels.pallas.quant_matmul import quant_matmul
            return quant_matmul(x.astype(jnp.float32), h["q"], h["s"],
                                impl="auto")
        if isinstance(h, tuple):
            q, s = h
            return x.astype(jnp.float32) @ (q.astype(jnp.float32) * s)
        return x.astype(jnp.float32) @ h.astype(jnp.float32)

    def record_weight_fetch(self, steps=1):
        """Book `steps` full weight fetches into the quant-weight HBM
        counters (host-side, concrete values — callers invoke this once
        per recorded decode step, the record_ragged_step pattern)."""
        from ..kernels.pallas.quant_matmul import record_weight_stream
        record_weight_stream(quant_bytes=self.weight_stream_bytes["quant"],
                             bf16_bytes=self.weight_stream_bytes["bf16eq"],
                             fetches=steps)

    def _rope_at(self, x, cos, sin):
        # x [..., Hn, D]; cos/sin broadcastable [..., 1, D]; rotate-half
        c = cos.astype(x.dtype)
        s = sin.astype(x.dtype)
        x1, x2 = jnp.split(x, 2, axis=-1)
        rot = jnp.concatenate([-x2, x1], axis=-1)
        return x * c + rot * s

    # -- one decode step ---------------------------------------------------
    def _step_impl(self, params, tokens, pos, kcache, vcache):
        """tokens [B] int32; pos scalar int32 (index being written);
        caches [L, B, T, Hkv, D] -> (logits [B, V], caches)."""
        x = jnp.take(params["embed"], tokens, axis=0)  # [B, H]
        cos = jax.lax.dynamic_index_in_dim(params["cos"], pos, 0,
                                           keepdims=False)  # [D]
        sin = jax.lax.dynamic_index_in_dim(params["sin"], pos, 0,
                                           keepdims=False)
        T = kcache.shape[2]
        mask = (jnp.arange(T, dtype=jnp.int32) <= pos)   # [T]
        dtype = x.dtype
        scale = 1.0 / math.sqrt(self.hd)
        nrep = self.nh // self.nkv

        def layer(x, wl_kc_vc):
            wl, kc, vc = wl_kc_vc                      # kc/vc [B, T, Hkv, D]
            h1 = _rms(x, wl["ln1"], self.eps)
            q = self._layer_mm(h1, wl["wq"], dtype).reshape(
                -1, self.nh, self.hd)
            k = self._layer_mm(h1, wl["wk"], dtype).reshape(
                -1, self.nkv, self.hd)
            v = self._layer_mm(h1, wl["wv"], dtype).reshape(
                -1, self.nkv, self.hd)
            q = self._rope_at(q, cos[None, None, :], sin[None, None, :])
            k = self._rope_at(k, cos[None, None, :], sin[None, None, :])
            kc = jax.lax.dynamic_update_slice_in_dim(
                kc, k[:, None].astype(kc.dtype), pos, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(
                vc, v[:, None].astype(vc.dtype), pos, axis=1)
            # grouped attention DIRECTLY against the unrepeated cache —
            # a jnp.repeat would read n_rep x the cache bytes per token,
            # exactly the traffic GQA exists to avoid
            qg = q.reshape(-1, self.nkv, nrep, self.hd)
            att = jnp.einsum("bgnd,btgd->bgnt", qg.astype(jnp.float32),
                             kc.astype(jnp.float32)) * scale
            att = jnp.where(mask[None, None, None, :], att, -1e30)
            p = jax.nn.softmax(att, axis=-1)
            o = jnp.einsum("bgnt,btgd->bgnd", p,
                           vc.astype(jnp.float32)).astype(dtype)
            o = o.reshape(-1, self.nh * self.hd)
            x = x + self._layer_mm(o, wl["wo"], dtype)
            h2 = _rms(x, wl["ln2"], self.eps)
            g = self._layer_mm(h2, wl["wg"], dtype)
            u = self._layer_mm(h2, wl["wu"], dtype)
            x = x + self._layer_mm(jax.nn.silu(g) * u, wl["wd"], dtype)
            return x, (kc, vc)

        def scan_body(x, xs):
            x, (kc, vc) = layer(x, xs)
            return x, (kc, vc)

        x, (kcache, vcache) = jax.lax.scan(
            scan_body, x, (params["layers"], kcache, vcache))
        x = _rms(x, params["norm"], self.eps)
        return self._head_logits(params, x), kcache, vcache

    # -- prefill -----------------------------------------------------------
    def _prefill_impl(self, params, ids, kcache, vcache):
        """ids [B, S0] -> (last-token logits [B, V], filled caches).
        Attention runs the Pallas flash kernel when shapes allow (seq a
        multiple of 128): the dense-attn probs [B,H,S,S] are what OOM
        long prompts at batch — flash never materializes them."""
        B, S0 = ids.shape
        x = jnp.take(params["embed"], ids, axis=0)     # [B, S0, H]
        cos, sin = params["cos"][:S0], params["sin"][:S0]
        dtype = x.dtype
        scale = 1.0 / math.sqrt(self.hd)
        nrep = self.nh // self.nkv
        use_flash = S0 % 128 == 0
        causal = None if use_flash else jnp.tril(jnp.ones((S0, S0), bool))

        def layer(x, wl_kc_vc):
            wl, kc, vc = wl_kc_vc
            h1 = _rms(x, wl["ln1"], self.eps)
            q = self._layer_mm(h1, wl["wq"], dtype).reshape(
                B, S0, self.nh, self.hd)
            k = self._layer_mm(h1, wl["wk"], dtype).reshape(
                B, S0, self.nkv, self.hd)
            v = self._layer_mm(h1, wl["wv"], dtype).reshape(
                B, S0, self.nkv, self.hd)
            q = self._rope_at(q, cos[None, :, None, :], sin[None, :, None, :])
            k = self._rope_at(k, cos[None, :, None, :], sin[None, :, None, :])
            kc = jax.lax.dynamic_update_slice_in_dim(
                kc, k.astype(kc.dtype), 0, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(
                vc, v.astype(vc.dtype), 0, axis=1)
            if use_flash:
                # the MHA Pallas kernel wants repeated heads (prefill
                # reads k/v once; the repeat is activation-sized here)
                keys = jnp.repeat(k, nrep, axis=2) if nrep > 1 else k
                vals = jnp.repeat(v, nrep, axis=2) if nrep > 1 else v
                from ..kernels.pallas.flash_attention import _flash_bhsd

                def fold(a):
                    return jnp.swapaxes(a, 1, 2).reshape(
                        B * self.nh, S0, self.hd)

                o = _flash_bhsd(fold(q), fold(keys), fold(vals), True,
                                scale)
                o = jnp.swapaxes(o.reshape(B, self.nh, S0, self.hd), 1, 2)
                o = o.astype(dtype)
            else:
                qg = q.reshape(B, S0, self.nkv, nrep, self.hd)
                att = jnp.einsum("bqgnd,bkgd->bgnqk",
                                 qg.astype(jnp.float32),
                                 k.astype(jnp.float32)) * scale
                att = jnp.where(causal[None, None, None], att, -1e30)
                p = jax.nn.softmax(att, axis=-1)
                o = jnp.einsum("bgnqk,bkgd->bqgnd", p,
                               v.astype(jnp.float32)).astype(dtype)
            o = o.reshape(B, S0, self.nh * self.hd)
            x = x + self._layer_mm(o, wl["wo"], dtype)
            h2 = _rms(x, wl["ln2"], self.eps)
            g = self._layer_mm(h2, wl["wg"], dtype)
            u = self._layer_mm(h2, wl["wu"], dtype)
            x = x + self._layer_mm(jax.nn.silu(g) * u, wl["wd"], dtype)
            return x, (kc, vc)

        x, (kcache, vcache) = jax.lax.scan(
            layer, x, (params["layers"], kcache, vcache))
        x = _rms(x[:, -1], params["norm"], self.eps)
        return self._head_logits(params, x), kcache, vcache

    # -- public ------------------------------------------------------------
    def new_caches(self, batch):
        cfg = self.cfg
        dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        shape = (cfg.num_hidden_layers, batch, self.max_len, self.nkv,
                 self.hd)
        return jnp.zeros(shape, dt), jnp.zeros(shape, dt)

    def generate(self, input_ids, max_new_tokens=32, do_sample=False,
                 temperature=1.0, top_k=0, top_p=1.0, eos_token_id=None,
                 pad_token_id=0):
        """Same TOKEN contract as models.generation.generate, O(1) work
        per token through the KV cache.

        PRNG note: do_sample consumes one global key per generated
        token, in step order — fixed-seed streams match the per-token
        host loop exactly. The one divergence: with eos_token_id set,
        keys are drawn per fused CHUNK, so an early eos exit can leave
        the global stream up to CHUNK-1 keys further along than the
        per-token loop would (visible tokens are identical either way).
        """
        from .generation import _sample_next
        ids = np.asarray(input_ids.numpy()
                         if isinstance(input_ids, Tensor) else input_ids)
        b, s0 = ids.shape
        total = s0 + max_new_tokens
        if total > self.max_len:
            raise ValueError(f"{total} tokens exceed max_len {self.max_len}")
        buf = np.full((b, total), pad_token_id, np.int64)
        buf[:, :s0] = ids
        kc, vc = self.new_caches(b)
        logits, kc, vc = self._prefill(jnp.asarray(ids, jnp.int32), kc, vc)

        # both lanes run CHUNK fused steps per dispatch; greedy feeds
        # argmax back inside the executable, sampled draws with the exact
        # host-sampler math under per-step keys. Post-masking after eos
        # is equivalent to the step-by-step contract — every token after
        # a row's first eos is replaced by pad either way.
        if max_new_tokens <= 0:
            return Tensor(buf)
        if do_sample:
            first = _sample_next(logits, True, temperature, top_k, top_p,
                                 random_mod.next_key())
        else:
            first = jnp.argmax(logits, axis=-1)
        buf[:, s0] = np.asarray(first)
        t = s0
        # eos_token_id None => nothing can stop generation early, so
        # chunk dispatches are queued WITHOUT reading results back and
        # one sync at the end collects them (no host round trip per
        # chunk)
        pending = []
        while t + 1 < total:
            remaining = total - 1 - t
            n = min(remaining, self.CHUNK)
            if n < self.CHUNK:
                # tails round DOWN to powers of two so the compiled
                # chunk-size set stays bounded ({CHUNK, 16, 8, 4, 2})
                # across arbitrary max_new_tokens values
                n = 1 << (n.bit_length() - 1)
            if n >= 2:
                tok_in = (jnp.asarray(buf[:, t], jnp.int32)
                          if not pending else pending[-1][2])
                if do_sample:
                    keys = jnp.stack([random_mod.next_key()
                                      for _ in range(n)])
                    use_temp = bool(temperature) and temperature != 1.0
                    toks, kc, vc = self._sample_chunk_jit(
                        self._params, tok_in, jnp.int32(t), kc, vc, keys,
                        jnp.float32(temperature if use_temp else 1.0),
                        jnp.float32(top_p), n, int(top_k),
                        bool(top_p) and top_p < 1.0)
                else:
                    toks, kc, vc = self._chunk_jit(
                        self._params, tok_in, jnp.int32(t), kc, vc, n)
                if eos_token_id is None:
                    pending.append((t, n, toks[:, -1], toks))
                else:
                    buf[:, t + 1:t + 1 + n] = np.asarray(toks)
                t += n
            else:
                if pending:           # flush before a host-fed step
                    for pt_, pn, _, ptoks in pending:
                        buf[:, pt_ + 1:pt_ + 1 + pn] = np.asarray(ptoks)
                    pending = []
                logits, kc, vc = self._step(
                    jnp.asarray(buf[:, t], jnp.int32), jnp.int32(t),
                    kc, vc)
                t += 1
                if do_sample:
                    nxt = _sample_next(logits, True, temperature, top_k,
                                       top_p, random_mod.next_key())
                else:
                    nxt = jnp.argmax(logits, axis=-1)
                buf[:, t] = np.asarray(nxt)
            if eos_token_id is not None:
                gen = buf[:, s0:t + 1]
                if (gen == eos_token_id).any(axis=1).all():
                    break
        for pt_, pn, _, ptoks in pending:
            buf[:, pt_ + 1:pt_ + 1 + pn] = np.asarray(ptoks)
        if eos_token_id is not None:
            for row in buf:
                hits = np.where(row[s0:] == eos_token_id)[0]
                if len(hits):
                    row[s0 + hits[0] + 1:] = pad_token_id
        return Tensor(buf)

    def _step(self, tokens, pos, kc, vc):
        return self._step_jit(self._params, tokens, pos, kc, vc)

    def _prefill(self, ids, kc, vc):
        return self._prefill_jit(self._params, ids, kc, vc)

    @property
    def step_cache_size(self):
        """Compiled-executable count of the decode step (the cache-reuse
        regression gate: stays 1 across positions/steps)."""
        return self._step_jit._cache_size()

    @property
    def chunk_cache_size(self):
        """Compiled-executable count of the fused greedy chunk (one per
        DISTINCT chunk length; repeated serving with the same max_new
        adds none)."""
        return self._chunk_jit._cache_size()
