"""The `glm4_moe_lite` family (zai-org GLM-4.7-Flash): the dense member of
the latent family of `models/deepseek_v32.py`. Multi-head latent
attention over EVERY causal latent row (no indexer), plain rotary
positions, one dense SwiGLU block and then sparse SwiGLU experts with a
sigmoid router (one group) and a shared expert, and one
multi-token-prediction (MTP) layer that drafts the token after next.

Block `l` on x [tokens, hidden]; RMS is RMSNorm (eps `rms_norm_eps`), no
bias anywhere:

    h = RMS(x; ln1)
    cq = RMS(h Wqa; q_norm)                              [T, q_lora_rank]
    q = cq Wqb -> per head q_nope (dn), q_pe (dr, interleaved rotary)
    [c, k_pe] = h Wkva;  c = RMS(c; kv_norm) [T, kv_lora_rank],
                         k_pe (dr, interleaved rotary, shared by heads)
    k_nope_h, v_h = c Wkvb_h                             (dn, dv)
    s_h(t, j) = (q_nope_h . k_nope_h(j) + q_pe_h . k_pe(j)) (dn + dr)^-1/2
    x = x + (sum_h softmax_{j <= t} s_h(t, j) v_h(j)) Wo
    h2 = RMS(x; ln2)
    x = x + Wd(silu(Wg h2) * (Wu h2))            l < first_k_dense_replace
    x = x + sum over the chosen experts HELD HERE of w_e W2_e(silu(W1_e h2)
            * (W3_e h2)) + shared(h2)            elsewhere
    (s = sigmoid(h2 Wr) float32; top-k of s + b_corr; w = s_chosen /
    sum(s_chosen) * routed_scaling_factor)
    hn = RMS(x; norm);  logits = hn Whead

    rotary: angle_i(t) = t * rope_theta^(-2i / dr), pairs (2i, 2i + 1)

The MTP layer takes the main model's last hidden state after its final
norm and the embedding of the next token, and shares the embedding and
the head:

    u_i = Weh [RMS(emb(t_{i+1}); enorm) ; RMS(hn_i; hnorm)]    [T, H]
    u = an expert block of the main blocks' shape on u, positions i,
        with latent rows of its own
    draft logits_i = RMS(u_i; mtp norm) Whead             predicts t_{i+2}

Serving runs through `PagedDecoder(model)`, which builds
`deepseek_v32.LatentPagedDecoder` in its dense configuration;
`serve(spec_decode="mtp")` drafts one token a pass on the device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework.tensor import Tensor
from .deepseek_v32 import (DeepseekV32Config, DeepseekV32ForCausalLM,
                           forward_sequence)

__all__ = ["Glm4MoeLiteConfig", "Glm4MoeLiteForCausalLM",
           "glm4_moe_lite_tiny"]


class Glm4MoeLiteConfig(DeepseekV32Config):
    """The published keys of a `glm4_moe_lite` `config.json` that shape
    the language model, under their own names (GLM-4.7-Flash's values by
    default), plus `experts_held` and `dtype`. There is no indexer: the
    latent family's dense member."""

    def __init__(self, vocab_size=154880, hidden_size=2048,
                 intermediate_size=10240, moe_intermediate_size=1536,
                 num_hidden_layers=47, first_k_dense_replace=1,
                 num_attention_heads=20, q_lora_rank=768, kv_lora_rank=512,
                 qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
                 n_routed_experts=64, num_experts_per_tok=4, n_group=1,
                 topk_group=1, n_shared_experts=1,
                 routed_scaling_factor=1.8, norm_topk_prob=True,
                 rms_norm_eps=1e-5, rope_theta=1000000, rope_scaling=None,
                 max_position_embeddings=202752, num_nextn_predict_layers=1,
                 partial_rotary_factor=1, experts_held=None,
                 dtype="float32"):
        if partial_rotary_factor != 1:
            raise ValueError("the rotary term covers the qk_rope_head_dim "
                             "dims whole (partial_rotary_factor 1)")
        super().__init__(
            vocab_size=vocab_size, hidden_size=hidden_size,
            intermediate_size=intermediate_size,
            moe_intermediate_size=moe_intermediate_size,
            num_hidden_layers=num_hidden_layers,
            first_k_dense_replace=first_k_dense_replace,
            num_attention_heads=num_attention_heads, q_lora_rank=q_lora_rank,
            kv_lora_rank=kv_lora_rank, qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            index_n_heads=None, index_head_dim=None, index_topk=None,
            n_routed_experts=n_routed_experts,
            num_experts_per_tok=num_experts_per_tok, n_group=n_group,
            topk_group=topk_group, n_shared_experts=n_shared_experts,
            routed_scaling_factor=routed_scaling_factor,
            norm_topk_prob=norm_topk_prob, rms_norm_eps=rms_norm_eps,
            rope_theta=rope_theta, rope_scaling=rope_scaling,
            max_position_embeddings=max_position_embeddings,
            num_nextn_predict_layers=num_nextn_predict_layers,
            experts_held=experts_held, dtype=dtype)


def glm4_moe_lite_tiny(**overrides):
    """A CPU-sized member with every mechanism: a query and a KV latent
    whose value head is wider than its key head, a shared rotary key, a
    leading dense block, 16 experts top-4 with a shared expert (any
    share of them held), and the MTP layer."""
    base = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
                moe_intermediate_size=24, num_hidden_layers=3,
                first_k_dense_replace=1, num_attention_heads=4,
                q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8,
                qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16,
                num_experts_per_tok=4, routed_scaling_factor=1.8,
                rope_theta=10000, max_position_embeddings=256)
    base.update(overrides)
    return Glm4MoeLiteConfig(**base)


class Glm4MoeLiteForCausalLM(DeepseekV32ForCausalLM):
    """The dygraph model under `Glm4MoeLiteConfig.param_shapes`' names
    (the MTP layer's under `mtp.`). `forward(input_ids [B, T])` gives
    (logits [B, T, V], the MTP layer's draft logits [B, T, V]); row i of
    the second predicts token i + 2 from the token at i + 1, the last
    row's from the greedy token after the sequence."""

    def forward(self, input_ids):
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        cfg, params = self.config, self.param_tree()
        if not cfg.num_nextn_predict_layers:
            return super().forward(input_ids)
        logits, draft = jax.vmap(
            lambda row: forward_sequence(cfg, params, row, with_mtp=True))(
                ids.astype(jnp.int32))
        return Tensor(logits), Tensor(draft)
