"""Llama model family — the flagship benchmark model.

Architecture parity with the reference's auto-parallel Llama test model
(test/auto_parallel/hybrid_strategy/semi_auto_parallel_llama_model.py:
LlamaAttention/LlamaMLP/LlamaRMSNorm/LlamaDecoderLayer stack with rotary
embeddings, SwiGLU MLP, RMSNorm, optional GQA) but TPU-native:

  - tensor parallel = ColumnParallel/RowParallel/VocabParallel layers whose
    weights carry 'mp'-axis GSPMD shardings (fleet/meta_parallel/mp_layers.py
    here) instead of explicit _c_identity/_mp_allreduce collectives;
  - sequence parallel = activation shard constraints on the seq dim ('sp');
  - attention = flash_attention (Pallas kernel on TPU, XLA softmax fallback);
  - recompute = per-decoder-layer jax.checkpoint via fleet.recompute.

Everything is global-shaped: shapes never change with the mesh; the
partitioner materialises per-device shards and inserts collectives.
"""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp

from ..framework.op_registry import primitive
from ..framework.tensor import Tensor
from ..nn import functional as F
from ..nn.layer.layers import Layer
from ..nn.layer.common import Linear, Embedding
from ..nn.layer.norm import RMSNorm
from ._tp_utils import parallel_linears

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM",
           "LlamaPretrainingCriterion", "llama_tiny", "llama_2_7b"]


def check_recompute_granularity(value):
    """Shared validator for the pipeline remat granularity knob (used by
    LlamaConfig and GPTConfig — one source of truth for the values)."""
    if value not in ("layer", "stage"):
        raise ValueError(
            f"recompute_granularity must be 'layer' or 'stage', got "
            f"{value!r}")
    return value


def check_pipeline_save_mode(value, virtual_pp_degree=1):
    """Shared validator for the pipeline backward-save restructuring knob
    (LlamaConfig and GPTConfig; see gspmd_pipeline's save_mode)."""
    if value not in ("scan", "unroll", "buffer"):
        raise ValueError(
            f"pipeline_save_mode must be 'scan', 'unroll' or 'buffer', "
            f"got {value!r}")
    if value == "buffer" and virtual_pp_degree > 1:
        raise ValueError(
            "pipeline_save_mode='buffer' applies to the non-interleaved "
            "pipeline; use 'unroll' with virtual_pp_degree > 1")
    return value


class LlamaConfig:
    """Mirrors the reference test model's LlamaConfig fields
    (semi_auto_parallel_llama_model.py) plus TPU-parallel knobs."""

    def __init__(self, vocab_size=32000, hidden_size=4096,
                 intermediate_size=11008, num_hidden_layers=32,
                 num_attention_heads=32, num_key_value_heads=None,
                 max_position_embeddings=4096, rms_norm_eps=1e-5,
                 rope_theta=10000.0, tie_word_embeddings=False,
                 use_flash_attention=True, tensor_parallel=False,
                 sequence_parallel=False, recompute=False,
                 recompute_policy=None, recompute_granularity="layer",
                 dtype="float32",
                 pipeline_parallel=False, pp_microbatches=None,
                 virtual_pp_degree=1, head_dim=None,
                 pin_pipeline_carry=False, pipeline_save_mode="scan",
                 context_parallel=False, context_parallel_mode="ring",
                 context_parallel_axis="sep", num_experts=0,
                 moe_top_k=2, moe_intermediate_size=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads or num_attention_heads
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.tie_word_embeddings = tie_word_embeddings
        self.use_flash_attention = use_flash_attention
        self.tensor_parallel = tensor_parallel
        self.sequence_parallel = sequence_parallel
        self.recompute = recompute
        self.recompute_policy = recompute_policy
        # pipeline remat granularity: "layer" checkpoints every decoder
        # block (scan saves a per-(tick x layer) activation stack — the
        # buffer that OOMs 7B at mp<=4 on v5e when XLA's assignment
        # re-materializes it); "stage" checkpoints the WHOLE stage per
        # pipeline tick — the save stack shrinks by layers-per-stage at
        # the cost of one extra stage forward in backward (~5/3 total
        # forward flops vs 4/3)
        self.recompute_granularity = check_recompute_granularity(
            recompute_granularity)
        self.dtype = dtype
        # pipeline_parallel stores the decoder stack STACKED with its layer
        # axis sharded over the 'pp' mesh axis (real per-stage parameter
        # placement) and pipelines microbatches through it; see llama_pipe.py
        self.pipeline_parallel = pipeline_parallel
        self.pp_microbatches = pp_microbatches
        # interleaved VPP chunks per stage (reference interleaved 1F1B,
        # pipeline_parallel.py:987): bubble shrinks by this factor
        self.virtual_pp_degree = virtual_pp_degree
        # pin the pipeline carry (and therefore the scan-transpose's saved
        # activation stacks) to a CONCRETE dp x mp(seq) layout instead of
        # leaving the trailing dims UNCONSTRAINED. With sequence parallel
        # the saves shrink by the mp degree and the backward consumes them
        # at the saved layout — the "constrain the scan-save shardings"
        # optimization BASELINE.md records against the mp/sp comm family.
        self.pin_pipeline_carry = pin_pipeline_carry
        # how the pipeline's BACKWARD saves are stored (gspmd_pipeline
        # save_mode): "scan" = the classic scan-transpose stack; "unroll"
        # = unrolled ticks with independent dp-sharded per-tick saves;
        # "buffer" = manual remat into ONE pre-allocated dp(+mp)-sharded
        # save buffer written per tick (per-tick recompute in backward).
        # unroll/buffer exist because XLA's buffer assignment re-layouts
        # the scan-transpose stack UNSHARDED across dp at mp<=4 on the
        # v5e-256 7B compile (41.8 GiB/chip -> OOM; BASELINE.md r5/r6)
        # and value-level pins (pin_pipeline_carry) cannot reach it.
        self.pipeline_save_mode = check_pipeline_save_mode(
            pipeline_save_mode, virtual_pp_degree)
        # explicit head_dim decouples attention width from hidden size —
        # needed to express the PER-CHIP shard of an mp-sharded model
        # (e.g. 7B under mp=8: hidden 4096, 4 local heads of 128)
        self._head_dim = head_dim
        # context parallelism (long sequences): shard the SEQUENCE over
        # the 'sep' mesh axis and run ring attention (kv blocks rotate on
        # ICI with an online softmax, memory O(S/P) per chip) or Ulysses
        # (alltoall seq<->head reshard around dense attention). SURVEY §5
        # long-context plan — the reference has neither in-tree.
        self.context_parallel = context_parallel
        self.context_parallel_mode = context_parallel_mode
        self.context_parallel_axis = context_parallel_axis
        # Llama-MoE (r17 composed dp x mp x pp x ep lane): num_experts
        # > 0 replaces the SwiGLU MLP with a top-k routed mixture whose
        # expert stacks are 'ep'-sharded (models/llama_moe_pipe.py;
        # pipeline_parallel only — the non-pipelined family keeps its
        # dense MLP)
        self.num_experts = int(num_experts or 0)
        self.moe_top_k = int(moe_top_k)
        self.moe_intermediate_size = moe_intermediate_size
        if context_parallel_mode not in ("ring", "ulysses"):
            raise ValueError(
                f"context_parallel_mode must be 'ring' or 'ulysses', got "
                f"{context_parallel_mode!r}")

    @property
    def head_dim(self):
        return self._head_dim or self.hidden_size // self.num_attention_heads


# -- rotary embedding ---------------------------------------------------------

@primitive("rope_apply")
def _rope_apply(x, cos, sin):
    # x: [B, S, H, D]; cos/sin: [S, D]. Neox-style rotate-half (reference:
    # semi_auto_parallel_llama_model.py apply_rotary_pos_emb).
    c = cos[None, :, None, :].astype(x.dtype)
    s = sin[None, :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return x * c + rot * s


def _rope_tables(head_dim, max_pos, theta):
    inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                                / head_dim))
    t = np.arange(max_pos, dtype=np.float64)
    freqs = np.outer(t, inv_freq)
    emb = np.concatenate([freqs, freqs], axis=-1)
    return (np.cos(emb).astype(np.float32), np.sin(emb).astype(np.float32))


def apply_rotary_pos_emb(q, k, cos, sin):
    """q,k: [B, S, H, D] Tensors; cos/sin: [S, D] Tensors."""
    return _rope_apply(q, cos, sin), _rope_apply(k, cos, sin)


@primitive("flash_attn_tp")
def _flash_tp(q, k, v, *, causal, scale, mesh):
    """Flash attention per-shard on a multi-device mesh: batch over dp,
    heads over mp (attention is head-local under TP; Mosaic kernels are
    not GSPMD-partitionable — see kernels/pallas flash_bhsd_sharded)."""
    from ..kernels.pallas.flash_attention import flash_bhsd_sharded
    return flash_bhsd_sharded(q, k, v, causal, scale, mesh,
                              batch_axes=("dp",), head_axis="mp")


@primitive("repeat_kv")
def _repeat_kv(x, *, n_rep):
    # [B, S, Hkv, D] -> [B, S, Hkv*n_rep, D] (GQA head broadcast)
    b, s, h, d = x.shape
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d))
    return x.reshape(b, s, h * n_rep, d)


def _causal_fold(attn_mask, seq_len):
    """Fold the causal mask into a caller-supplied padding/attention mask
    (reference: the model's _prepare_decoder_attention_mask combines both).
    Bool masks AND with tril; additive masks get -inf above the diagonal."""
    from ..ops.creation import ones, tril, triu, full
    from ..ops.logic import logical_and
    causal = tril(ones([seq_len, seq_len], dtype="bool"))
    if attn_mask.dtype.name == "bool":
        return logical_and(attn_mask, causal)
    neg = float(np.finfo(np.float32).min)
    additive = triu(full([seq_len, seq_len], neg, dtype=attn_mask.dtype),
                    diagonal=1)
    return attn_mask + additive


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.head_dim
        h = config.hidden_size
        col, row = parallel_linears(config)
        self.q_proj = col(h, self.num_heads * self.head_dim)
        self.k_proj = col(h, self.num_kv_heads * self.head_dim)
        self.v_proj = col(h, self.num_kv_heads * self.head_dim)
        self.o_proj = row(self.num_heads * self.head_dim, h)

    def forward(self, x, cos, sin, attn_mask=None):
        B, S = x.shape[0], x.shape[1]
        # named scopes thread through to HLO op metadata so the compiled
        # HBM ledger (observability/memory_profile.py) attributes buffers
        # to decoder.N/attn/qkv instead of fusion.1847
        with jax.named_scope("qkv"):
            q = self.q_proj(x).reshape(
                [B, S, self.num_heads, self.head_dim])
            k = self.k_proj(x).reshape(
                [B, S, self.num_kv_heads, self.head_dim])
            v = self.v_proj(x).reshape(
                [B, S, self.num_kv_heads, self.head_dim])
            q, k = apply_rotary_pos_emb(q, k, cos, sin)
        if self.num_kv_heads != self.num_heads:
            n_rep = self.num_heads // self.num_kv_heads
            k = _repeat_kv(k, n_rep=n_rep)
            v = _repeat_kv(v, n_rep=n_rep)
        if self.config.context_parallel:
            if attn_mask is not None:
                raise ValueError("context_parallel Llama supports causal "
                                 "attention only (attn_mask must be None)")
            from ..distributed.fleet.meta_parallel.ring_attention import (
                ring_attention, ulysses_attention)
            cp_fn = ring_attention \
                if self.config.context_parallel_mode == "ring" \
                else ulysses_attention
            out = cp_fn(q, k, v, axis=self.config.context_parallel_axis,
                        causal=True, batch_axes="dp",
                        head_axis="mp" if self.config.tensor_parallel
                        else None)
        elif attn_mask is not None:
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=_causal_fold(attn_mask, S))
        elif self.config.use_flash_attention:
            from ..distributed import mesh as mesh_mod
            mesh = mesh_mod.get_mesh()
            # shard_map flash ONLY for models that are themselves TP —
            # gating on the ambient mesh alone would impose head/batch
            # divisibility on unsharded models that ran fine before
            if self.config.tensor_parallel and mesh is not None and any(
                    mesh.shape.get(a, 1) > 1 for a in ("dp", "mp")):
                # the Pallas kernel is not GSPMD-partitionable — run
                # per-shard (batch over dp, heads over mp; attention is
                # head-local under TP)
                out = _flash_tp(q, k, v, causal=True,
                                scale=1.0 / math.sqrt(self.head_dim),
                                mesh=mesh)
            else:
                out, _ = F.flash_attention(q, k, v, causal=True)
        else:
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        out = out.reshape([B, S, self.num_heads * self.head_dim])
        with jax.named_scope("o"):
            return self.o_proj(out)


class LlamaMLP(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        col, row = parallel_linears(config)
        self.gate_proj = col(config.hidden_size, config.intermediate_size)
        self.up_proj = col(config.hidden_size, config.intermediate_size)
        self.down_proj = row(config.intermediate_size, config.hidden_size)

    def forward(self, x):
        with jax.named_scope("gate"):
            g = F.silu(self.gate_proj(x))
        with jax.named_scope("up"):
            u = self.up_proj(x)
        with jax.named_scope("down"):
            return self.down_proj(g * u)


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps)
        self.mlp = LlamaMLP(config)
        self._seq_parallel = config.sequence_parallel
        self._context_parallel = config.context_parallel
        self._cp_axis = config.context_parallel_axis

    def forward(self, x, cos, sin, attn_mask=None):
        if self._seq_parallel:
            # Megatron-SP: norm/residual regions sequence-sharded over the
            # mp axis (fleet/utils/sequence_parallel_utils.py convention);
            # batch/hidden stay FREE so dp/pp sharding survives
            from ..distributed.shard_util import shard_constraint, \
                pinned_spec
            x = shard_constraint(x, pinned_spec(3, {1: "mp"}))
        elif getattr(self, "_context_parallel", False):
            # activations sequence-sharded over the sep axis end to end:
            # the norm/MLP regions are elementwise over seq, so only
            # attention needs communication (the ring)
            from ..distributed.shard_util import shard_constraint, axes_spec
            from ..distributed import mesh as mesh_mod
            mesh = mesh_mod.get_mesh()
            x = shard_constraint(
                x, axes_spec(mesh, "dp", self._cp_axis, None), mesh)
        with jax.named_scope("attn"):
            h = x + self.self_attn(self.input_layernorm(x), cos, sin,
                                   attn_mask)
        with jax.named_scope("mlp"):
            out = h + self.mlp(self.post_attention_layernorm(h))
        return out


class _PipelineStateDictMixin:
    """Checkpoint portability for the stacked pipelined decoder: saved
    state dicts always carry natural layer order regardless of the
    virtual-pipeline storage layout (llama_pipe.reorder_state_dict)."""

    def _pipe_stack(self):
        stack = getattr(self, "decoder_stack", None)
        if stack is not None:
            return stack
        for sub in self._sub_layers.values():
            s = getattr(sub, "decoder_stack", None)
            if s is not None:
                return s
        return None

    def state_dict(self, *args, **kwargs):
        sd = Layer.state_dict(self, *args, **kwargs)
        stack = self._pipe_stack()
        if stack is not None:
            sd = stack.reorder_state_dict(sd, inbound=False)
        return sd

    def set_state_dict(self, state_dict, *args, **kwargs):
        stack = self._pipe_stack()
        if stack is None:
            return Layer.set_state_dict(self, state_dict, *args, **kwargs)
        # stacked weights are applied DIRECTLY (natural -> storage order,
        # with placement restored): Layer.set_state_dict round-trips
        # through self.state_dict(), which for vpp>1 returns reordered
        # copies, not the live parameters
        sd = dict(state_dict)
        handled = {}
        for name in list(sd):
            head, _, leaf = name.rpartition(".")
            if leaf in stack._stack_keys and (
                    head == "" or head.endswith("decoder_stack")):
                handled[leaf] = sd.pop(name)
        missing, unexpected = Layer.set_state_dict(self, sd, *args,
                                                   **kwargs)
        for leaf, src in handled.items():
            stack.set_stacked(leaf,
                              src._data if hasattr(src, "_data") else src)
        missing = [m for m in missing
                   if m.rpartition(".")[2] not in handled]
        return missing, unexpected



class LlamaModel(_PipelineStateDictMixin, Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        if config.tensor_parallel:
            from ..distributed.fleet.meta_parallel.mp_layers import (
                VocabParallelEmbedding)
            self.embed_tokens = VocabParallelEmbedding(
                config.vocab_size, config.hidden_size)
        else:
            self.embed_tokens = Embedding(config.vocab_size,
                                          config.hidden_size)
        if config.pipeline_parallel:
            self.layers = None
            if getattr(config, "num_experts", 0):
                from .llama_moe_pipe import LlamaMoEStackedDecoder
                self.decoder_stack = LlamaMoEStackedDecoder(config)
            else:
                from .llama_pipe import LlamaStackedDecoder
                self.decoder_stack = LlamaStackedDecoder(config)
        elif getattr(config, "num_experts", 0):
            raise ValueError(
                "num_experts > 0 requires pipeline_parallel=True (this "
                "family's experts ship as the stacked pipelined decoder; "
                "the sorted, dropless expert layer that trains without a "
                "pipeline is models/lfm2.py's `sparse_moe` over "
                "`grouped_matmul_sorted`)")
        else:
            from ..nn.layer.container import LayerList
            self.layers = LayerList(
                [LlamaDecoderLayer(config)
                 for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        cos, sin = _rope_tables(config.head_dim,
                                config.max_position_embeddings,
                                config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)
        if config.dtype != "float32":
            self._cast_all(config.dtype)

    def forward(self, input_ids, attn_mask=None):
        S = input_ids.shape[1]
        with jax.named_scope("embed"):
            x = self.embed_tokens(input_ids)
        cos = self.rope_cos[:S]
        sin = self.rope_sin[:S]
        if self.config.pipeline_parallel:
            if attn_mask is not None:
                raise ValueError(
                    "pipeline_parallel Llama supports causal attention "
                    "only (attn_mask must be None)")
            return self.norm(self.decoder_stack(x, cos, sin))
        recompute = self.config.recompute and self.training
        if recompute:
            from ..distributed.fleet.recompute import recompute as ckpt
        pol = self.config.recompute_policy
        if isinstance(pol, (list, tuple)) and len(pol) < len(self.layers):
            raise ValueError(
                f"recompute_policy list has {len(pol)} entries for "
                f"{len(self.layers)} layers; provide one per layer")
        for i, layer in enumerate(self.layers):
            # per-layer named scope: HLO op metadata (and therefore the
            # memory profiler's attribution) reads decoder.<i>/...
            with jax.named_scope(f"decoder.{i}"):
                if recompute:
                    # a list/tuple policy assigns one entry per layer
                    # (mixed selective remat: trade HBM for recompute
                    # where it fits)
                    layer_pol = pol[i] if isinstance(pol, (list, tuple)) \
                        else pol
                    x = ckpt(layer, x, cos, sin, attn_mask,
                             policy=layer_pol)
                else:
                    x = layer(x, cos, sin, attn_mask)
        with jax.named_scope("final_norm"):
            return self.norm(x)


class LlamaForCausalLM(_PipelineStateDictMixin, Layer):
    # generation mixin methods attached below class defs (avoids import
    # cycle at module load)
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        # the stacked decoder microbatches + pipelines internally; fleet's
        # PipelineParallel wrapper must not split the batch a second time
        self._internal_pipeline = bool(config.pipeline_parallel)
        self.lm_head = None
        if not config.tie_word_embeddings:
            if config.tensor_parallel:
                from ..distributed.fleet.meta_parallel.mp_layers import (
                    ColumnParallelLinear)
                self.lm_head = ColumnParallelLinear(
                    config.hidden_size, config.vocab_size, has_bias=False,
                    gather_output=False)
            else:
                self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                      bias_attr=False)
            if config.dtype != "float32":
                self.lm_head._cast_all(config.dtype)

    def forward(self, input_ids, attn_mask=None):
        hidden = self.llama(input_ids, attn_mask)
        with jax.named_scope("lm_head"):
            if self.lm_head is None:
                # tied head: logits = h @ wte^T ([vocab, hidden] embedding
                # weight; its vocab axis stays mp-sharded under TP,
                # matching the class-sharded logits the criterion expects)
                return F.linear(hidden, self.llama.embed_tokens.weight.T)
            return self.lm_head(hidden)


class LlamaPretrainingCriterion(Layer):
    """Shifted next-token CE (reference: the pretraining criterion in
    semi_auto_parallel_llama_model.py). With tensor_parallel, uses
    ParallelCrossEntropy over class-sharded logits."""

    def __init__(self, config: LlamaConfig = None):
        super().__init__()
        self._parallel = bool(config and config.tensor_parallel)
        if self._parallel:
            from ..distributed.fleet.meta_parallel.mp_layers import (
                ParallelCrossEntropy)
            self._pce = ParallelCrossEntropy()

    def forward(self, logits, labels):
        # logits: [B, S, V]; labels: [B, S] — caller pre-shifts, as the
        # reference does in its data pipeline.
        logits = logits.astype("float32")
        if self._parallel:
            loss = self._pce(logits, labels.unsqueeze(-1))
            return loss.mean()
        return F.cross_entropy(logits, labels.unsqueeze(-1))


def llama_tiny(**overrides):
    """A tiny config for tests and dry-runs."""
    kw = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
              num_hidden_layers=2, num_attention_heads=4,
              num_key_value_heads=2, max_position_embeddings=128)
    kw.update(overrides)
    return LlamaConfig(**kw)


def llama_2_7b(**overrides):
    kw = dict(vocab_size=32000, hidden_size=4096, intermediate_size=11008,
              num_hidden_layers=32, num_attention_heads=32,
              max_position_embeddings=4096)
    kw.update(overrides)
    return LlamaConfig(**kw)


from .generation import GenerationMixin as _GenMixin  # noqa: E402

LlamaForCausalLM.generate = _GenMixin.generate
