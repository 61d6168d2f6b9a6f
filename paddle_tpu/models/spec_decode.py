"""Speculative decoding: draft proposal + greedy batched verification.

Decode is HBM-bound — every token pays a full weight + KV pass for one
token of progress. Speculative decoding amortizes that pass: a cheap
DRAFT proposes k candidate tokens, the target model verifies all k (+1
bonus position) in ONE batched forward through the existing paged
attention path (PagedDecoder._spec_verify_impl expands each slot into
k+1 query rows at positions seqlens..seqlens+k; per-row seq_lens give
each row exactly its causal window, so the UNMODIFIED ragged kernel is
the verifier), and the accepted prefix advances in one step.

Greedy verification is exact: a draft token is accepted iff it equals
the target's own argmax at that position, so the emitted stream is
token-identical to plain greedy decode — the draft only changes HOW
FAST tokens appear, never WHICH tokens (tier-1 gate in
tests/test_kv_quant_spec.py).

A model with a multi-token-prediction (MTP) layer drafts for itself ON
THE DEVICE (`spec_decode="mtp"`): its engine's decode chunk runs verify
passes whose MTP rows propose the next pass's draft
(`PagedDecoder._draft_scan`; the dense latent engine,
models/deepseek_v32.py), so no host work sits between passes and the
pipelined loop keeps its look-ahead. The host-side providers below draft
between device calls instead.

Draft providers (one host-side interface, swappable):

- NGramDraft — self-speculative prompt-lookup (no extra model): match
  the history's trailing n-gram earlier in the history and propose the
  tokens that followed it. Free to run, strong on repetitive /
  copy-heavy decodes, accept rate degrades gracefully to ~0 on
  incompressible streams (where the verify step still emits >= 1
  token, so the floor is plain decode + one cheap batched pass).
- ModelDraft — the small-draft-model hook: any model with a greedy
  `generate()` proposes the continuation. The reference implementation
  runs the draft full-forward (correct, O(S) per proposed token); a
  production draft would keep its own KV cache behind this same
  interface.

Pick k with kernels.autotune.tune_spec_decode (times the verify
executable per candidate k against an expected-accept model) or pass
SpecConfig(k=...) explicitly.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

__all__ = ["SpecConfig", "DraftProvider", "NGramDraft", "ModelDraft",
           "resolve_spec"]


class DraftProvider:
    """propose(history, k) -> list[int] of exactly k candidate tokens
    continuing `history` (prompt + emitted so far, host-side ints)."""

    def propose(self, history, k):
        raise NotImplementedError


class NGramDraft(DraftProvider):
    """Prompt-lookup / self-speculative draft: find the most recent
    earlier occurrence of the history's trailing n-gram (longest n
    first, n <= max_ngram) and propose the k tokens that followed it.
    No match falls back to repeating the last token — a cheap draft
    that is simply rejected when wrong.

    `window` caps how far back the match scan looks (most recent
    tokens first): proposals run between device dispatches in the
    serve loop, so per-call host work must stay bounded — O(window)
    here instead of O(history), which over a long request would grow
    the total draft cost quadratically and stall the accelerator the
    drafts exist to feed."""

    def __init__(self, max_ngram=3, window=1024):
        self.max_ngram = int(max_ngram)
        self.window = int(window)

    def propose(self, history, k):
        h = list(history)
        if not h:
            return [0] * k
        lo = max(0, len(h) - self.window)
        for n in range(min(self.max_ngram, len(h) - 1), 0, -1):
            tail = h[-n:]
            # scan right-to-left over earlier positions: recency wins
            for start in range(len(h) - n - 1, lo - 1, -1):
                if h[start:start + n] == tail:
                    cont = h[start + n:start + n + k]
                    if cont:
                        return (cont + [h[-1]] * (k - len(cont)))[:k]
        return [h[-1]] * k


class ModelDraft(DraftProvider):
    """Small-draft-model hook: greedy continuation from `model` (any
    module with paddle-style generate()). `window` caps the history fed
    to the draft so a long serve never outruns the draft's rope table."""

    def __init__(self, model, window=None):
        self.model = model
        self.window = window

    def propose(self, history, k):
        import paddle_tpu as pt
        h = list(history)
        if not h:
            return [0] * k
        if self.window is not None:
            h = h[-int(self.window):]
        ids = pt.to_tensor(np.asarray(h, np.int64)[None])
        out = self.model.generate(ids, max_new_tokens=k)
        return [int(t) for t in out.numpy()[0, len(h):]]


@dataclass
class SpecConfig:
    """k: drafted tokens per verify pass (the verify executable row
    count is k+1; one executable per distinct k). draft: "ngram", a
    DraftProvider instance, or "mtp" (the model's own MTP layer drafts on
    the device: no host-side provider)."""
    k: int = 4
    draft: object = "ngram"
    max_ngram: int = 3

    def provider(self):
        if isinstance(self.draft, DraftProvider):
            return self.draft
        if self.draft == "mtp":
            return None
        if self.draft == "ngram":
            return NGramDraft(max_ngram=self.max_ngram)
        raise ValueError(f"unknown draft kind {self.draft!r}")


def resolve_spec(spec, decoder=None):
    """Normalize serve(spec_decode=...) inputs to (SpecConfig, provider).
    Accepts None, an int k, "auto" (autotune-cached draft length for
    this model geometry, default 4), "mtp" (k = the decoder's MTP layers,
    drafted on the device; provider None), a dict of SpecConfig fields,
    or a SpecConfig."""
    if spec is None:
        return None, None
    if spec == "mtp":
        layers = getattr(decoder, "draft_layers", 0)
        if not layers:
            raise NotImplementedError(
                "spec_decode='mtp' needs an engine whose model drafts on "
                "the device with a multi-token-prediction layer; this one "
                "has none")
        spec = SpecConfig(k=int(layers), draft="mtp")
    elif spec == "auto":
        k = None
        if decoder is not None:
            from ..kernels.autotune import lookup_spec_decode
            cfg = decoder.cfg
            k = lookup_spec_decode(cfg.hidden_size,
                                   cfg.num_hidden_layers, decoder.nh,
                                   decoder.nkv, decoder.hd,
                                   cfg.vocab_size, cfg.dtype)
        spec = SpecConfig(k=int(k) if k else 4)
    elif isinstance(spec, int):
        spec = SpecConfig(k=spec)
    elif isinstance(spec, dict):
        spec = SpecConfig(**spec)
    if not isinstance(spec, SpecConfig):
        raise TypeError(f"spec_decode: expected None/int/'auto'/dict/"
                        f"SpecConfig, got {type(spec).__name__}")
    if spec.k < 1:
        raise ValueError("spec_decode k must be >= 1")
    return spec, spec.provider()
