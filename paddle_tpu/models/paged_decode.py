"""Paged KV cache + continuous batching (VERDICT r4 #2).

Reference capability: block-table attention —
phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu:609
`BlockMultiheadAttentionKernel`: paged KV with per-sequence block lists,
in-batch admission of new requests, per-slot sequence lengths. The fixed
engine (models/decode.py, matching masked_multihead_attention_kernel.cu)
allocates [L, B, max_len, Hkv, D] per batch — every sequence pays max_len
HBM and the batch is frozen at prefill.

TPU formulation (everything static-shaped, three compiled executables):

- **Block pool**: K/V live in [L, num_blocks, block_size, Hkv, D] pools.
  HBM is bounded by the POOL (≈ active tokens rounded up to blocks), not
  by slots × max_len. Block 0 is the TRASH block: inactive slots and
  post-eos writes land there, so the step needs no active-branching.
- **Block tables**: [max_slots, blocks_per_seq] int32 indices into the
  pool, handed out by a host-side free-list allocator at admission /
  growth and reclaimed at retirement. A token t of slot s lives at
  pool[table[s, t // bs], t % bs] — gathered back as a contiguous
  [W = blocks_per_seq * bs] window whose index IS the token position.
- **One decode step for all slots**: tokens [Smax], per-slot seq_lens
  [Smax] (ragged positions are data, not shapes), scatter the new K/V by
  flat block index, attend against the gathered window under an
  arange(W) <= pos mask. Greedy chunks fuse CHUNK steps into one
  executable with argmax feedback (the fixed engine's r4 trick, kept).
- **Admission between chunks**: new requests prefill into their pages
  with a bucketed-length prompt executable (pad to the next power-of-two
  multiple of `block_size`, capped at `max_len`; the compiled set stays
  bounded at ~log2(max_len / block_size) executables), then join the
  next decode chunk.
  Prefill and decode stay two specialized programs: prefill is
  MXU-bound at full tile, decode is HBM-bound — a padded union program
  would run both at the worse regime. Continuous batching = the serving
  loop interleaving them, which is exactly what the reference's
  block_multi_head_attention + in-batch admission achieve on GPU.

- **Ragged fused attention** (`ragged_kernel=True`, default on TPU):
  the decode step attends via the Pallas ragged paged-attention kernel
  (kernels/pallas/ragged_paged_attention.py) which streams KV blocks
  HBM -> VMEM straight through the block table and early-exits past
  each slot's true length — no `[S, W, Hkv, D]` gathered window is ever
  materialized in HBM. The dense-gather `_attend` path stays as the
  fallback and numerical reference.

`PagedDecoder.serve()` is the continuous-batching driver: a request
queue, slot admission/retirement, per-slot eos, block reclaim. Peak pool
usage is tracked so tests can assert HBM ∝ active tokens. Requests may
carry a per-request token budget ((req_id, prompt, max_new) triples);
decode chunks gate every slot on its remaining budget ON DEVICE, so a
slot whose budget runs out mid-chunk stops advancing — its writes are
routed to the trash block instead of clobbering pool KV through the
clamped out-of-range gather.
"""
from __future__ import annotations

import math
import time
import weakref
from dataclasses import dataclass, field

import numpy as np
import jax
import jax.numpy as jnp

from .. import observability as _obs
from ..framework.flags import flag as _flag
from ..resilience import faults as _faults
from .decode import CachedDecoder, _rms

__all__ = ["PagedDecoder", "BlockAllocator"]

# live decoders, so the observability registry's pool collector can report
# block watermarks without holding engines alive
_LIVE_DECODERS = weakref.WeakSet()


class BlockAllocator:
    """Host-side free-list over pool blocks. Block 0 is reserved as the
    trash block (inactive-slot and overflow writes); real sequences get
    blocks 1..num_blocks-1.

    Blocks are REFCOUNTED (ISSUE 18): the prefix cache maps one block
    into several tables (copy-on-write sharing), so a block is owned by
    every table that maps it PLUS the radix tree if it's cached.
    ``alloc`` births blocks at rc=1; ``retain`` adds a reference;
    ``free`` drops one and only returns the block to the free list at
    rc=0 — a retiring request can never yank shared KV out from under
    another request or the cache. Double-frees now raise instead of
    corrupting the free list."""

    def __init__(self, num_blocks):
        self.num_blocks = int(num_blocks)
        self._free = list(range(self.num_blocks - 1, 0, -1))
        self._rc = {}                 # block id -> refcount (absent = free)
        self.peak_in_use = 0

    @property
    def free_count(self):
        return len(self._free)

    @property
    def in_use(self):
        return (self.num_blocks - 1) - len(self._free)

    def refcount(self, block):
        return self._rc.get(int(block), 0)

    def alloc(self, n):
        # chaos site: transient pool-allocation failure — serve()'s
        # admission loop recovers via requeue+replay, never a crash
        _faults.inject("paged_kv_alloc")
        if n > len(self._free):
            raise MemoryError(
                f"KV pool exhausted: need {n} blocks, {len(self._free)} "
                f"free (raise num_blocks or lower max_slots)")
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._rc[b] = 1
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return out

    def retain(self, block):
        """Add one reference to a live block (COW sharing / cache
        adoption). Retaining a free block is a bug — it would alias
        fresh allocations onto cached KV."""
        b = int(block)
        rc = self._rc.get(b, 0)
        if rc <= 0:
            raise ValueError(f"retain of free block {b}")
        self._rc[b] = rc + 1

    def free(self, blocks):
        for b in blocks:
            b = int(b)
            if not 0 < b < self.num_blocks:
                raise ValueError(f"bad block id {b}")
            rc = self._rc.get(b, 0)
            if rc <= 0:
                raise ValueError(f"double free of block {b}")
            if rc == 1:
                del self._rc[b]
                self._free.append(b)
            else:
                self._rc[b] = rc - 1


@dataclass
class _Slot:
    req_id: object = None
    length: int = 0            # tokens written into the pages
    blocks: list = field(default_factory=list)
    emitted: list = field(default_factory=list)   # generated tokens
    prompt: list = field(default_factory=list)    # for draft providers
    budget: int = 0            # max_new_tokens remaining
    done: bool = False
    # drafts the model made on the device for the slot's passes
    # (`spec_decode="mtp"`): (index in prompt + emitted of the token each
    # predicts, the token)
    drafts: list = field(default_factory=list)
    # each pass's second row, whether its draft was accepted or not:
    # (index of the draft it verified, the target's token after the draft)
    verified: list = field(default_factory=list)


class PagedDecoder(CachedDecoder):
    """Serving engine with a paged KV cache and continuous batching.

    Weight preparation (stacking, optional int8) is inherited from
    CachedDecoder; the cache machinery is replaced wholesale.

    What the serve loop carries chunk to chunk is the tuple `new_pools()`
    returns, `(kpool, vpool)` here: every program takes it as its last
    array arguments and returns it in the same order. An engine whose
    model keeps further per-slot state (models/nemotron_h.py), a second
    kind of KV cache (models/mimo_v2.py) or latent rows and indexer keys
    in place of K and V (models/deepseek_v32.py), makes its own tuple;
    `PagedDecoder(model)` builds that engine when the model's
    configuration's layer pattern names such a cache (`cache_kinds`).
    """

    # writes past the host's view of a slot are harmless here: the next
    # chunk rewrites the same K and V. An engine with a recurrent state
    # says False, and the loop then keeps a look-ahead chunk's length
    # out of an eos's reach
    _cache_rewinds = True
    # the pools' places among `_prefill_paged`'s arguments
    _prefill_donate = (4, 5)

    # the engine for a model whose layers do not all keep K and V of
    # every token: by the kinds of cache its configuration's layer
    # pattern names (`cache_kinds`, one a layer), not by family
    ENGINE_OF_KIND = {"state": ("nemotron_h", "HybridPagedDecoder"),
                      "window": ("mimo_v2", "WindowPagedDecoder"),
                      "latent": ("deepseek_v32", "LatentPagedDecoder")}

    def __new__(cls, model, *args, **kwargs):
        if cls is PagedDecoder:
            kinds = getattr(model.config, "cache_kinds", ())
            engine = next((cls.ENGINE_OF_KIND[k] for k in kinds
                           if k in cls.ENGINE_OF_KIND), None)
            if engine is not None:
                import importlib
                cls = getattr(importlib.import_module(
                    "." + engine[0], __package__), engine[1])
        return super().__new__(cls)

    def __init__(self, model, max_len=None, weight_quant=None,
                 block_size=64, num_blocks=None, max_slots=8,
                 headroom_guard=None, ragged_kernel=None, kv_quant=None,
                 prefix_cache=None, prefix_cache_blocks=None,
                 attn_shards=None, shard_block_budget=None,
                 prefill_chunk=None, kv_offload=None,
                 hbm_budget_gib=None, pipelined_admission=False):
        self._prepare_weights(model, max_len, weight_quant)
        # pipelined admission: an admission scan of `serve` dispatches
        # the prefill of every prompt it admits before it reads the
        # first of their first tokens, so the device runs them back to
        # back instead of idling through the host's round trip after
        # each (and through whatever holds the host up meanwhile). Off
        # by default: the loop then reads each first token before it
        # admits the next prompt, as it always has
        self.pipelined_admission = bool(pipelined_admission)
        if self.pipelined_admission and prefix_cache not in (None, False):
            raise NotImplementedError(
                "pipelined_admission with prefix_cache: a scan's later "
                "prompts would be planned against a tree that its "
                "earlier ones have not joined")
        # kv_quant="int8": pool blocks are int8 codes + one f32 scale per
        # token row (kernels/pallas/ragged_paged_attention.kv_quantize_
        # rows), quantized at write time and dequantized INSIDE the
        # ragged kernel after the HBM fetch — the decode wire drops to
        # (nkv*hd + 4)/(2*nkv*hd) of bf16. The dense-gather path
        # dequantizes the gathered window and stays the exact numerical
        # reference for the quantized kernel.
        if kv_quant not in (None, "int8"):
            raise ValueError(f"kv_quant must be None or 'int8', got "
                             f"{kv_quant!r}")
        self.kv_quant = kv_quant
        # optional framework.memory.HeadroomGuard: admission consults it so
        # the pool defers newcomers under device-memory pressure instead of
        # dying RESOURCE_EXHAUSTED mid-serve
        self.headroom_guard = headroom_guard
        self.admission_deferrals = 0
        # per-request lifecycle ledger (observability/requests.py):
        # created by the first serve() and fed by every one, telemetry
        # on or off; persists across serve() calls so operators see one
        # continuous request stream
        self.request_ledger = None
        # overload-shedding tallies (host-side, always on — cheap dict
        # bumps; the telemetry causes land in the ledger/registry too)
        self.rejected_requests = {}
        # fault-recovery tallies (ISSUE 14): evictions free a victim's
        # blocks under pressure, replays re-admit via chunked prefill,
        # quarantines recycle slots whose logits went non-finite,
        # giveups hit the max_restarts cap, drained = rejected because
        # the watchdog declared a peer dead
        self.evictions = 0
        self.replays = 0
        self.quarantines = 0
        self.replay_giveups = 0
        self.drained_rejections = 0
        # ragged fused attention: None = auto (on for TPU, where the
        # Pallas kernel compiles natively; off elsewhere so CPU tests
        # default to the cheap dense XLA path — interpret mode is still
        # exercised by passing ragged_kernel=True explicitly)
        if ragged_kernel is None:
            ragged_kernel = jax.default_backend() == "tpu"
        self.use_ragged_kernel = bool(ragged_kernel)
        # block_size="auto": consult the autotune cache for a winner
        # recorded by kernels.autotune.tune_ragged_blocks for this
        # attention geometry (cached + hit/miss-counted like flash)
        if block_size == "auto":
            if self.kv_quant:
                from ..kernels.autotune import lookup_kv_quant_blocks
                block_size = lookup_kv_quant_blocks(
                    self.nh, self.nkv, self.hd, self.cfg.dtype) or 64
            else:
                from ..kernels.autotune import lookup_ragged_blocks
                block_size = lookup_ragged_blocks(
                    self.nh, self.nkv, self.hd, self.cfg.dtype) or 64
        # max_len is a capacity: round DOWN to a block multiple (rope
        # tables bound it above, so rounding up could exceed them)
        if self.max_len % block_size:
            if self.max_len < block_size:
                raise ValueError(f"block_size {block_size} exceeds "
                                 f"max_len {self.max_len}")
            self.max_len -= self.max_len % block_size
        self.block_size = int(block_size)
        self.blocks_per_seq = self.max_len // self.block_size
        self.max_slots = int(max_slots)
        # context-length-sharded decode attention (ISSUE 19 tentpole a):
        # when a slot's table span exceeds the per-chip block budget,
        # the ragged kernel runs once per contiguous sub-table and the
        # per-shard online-softmax partials merge via the lse rescale.
        # Static at construction — the decode executables bake the
        # shard count in, exactly like block_size.
        if attn_shards is None:
            if shard_block_budget and \
                    self.blocks_per_seq > int(shard_block_budget):
                attn_shards = -(-self.blocks_per_seq
                                // int(shard_block_budget))
            else:
                attn_shards = 1
        self.attn_shards = max(1, int(attn_shards))
        if self.attn_shards > self.blocks_per_seq:
            raise ValueError(
                f"attn_shards {self.attn_shards} exceeds blocks_per_seq "
                f"{self.blocks_per_seq}")
        if self.attn_shards > 1 and self.kv_quant:
            raise ValueError(
                "attn_shards > 1 is not supported with kv_quant: the "
                "partials kernel has no int8 variant yet — serve long "
                "contexts unquantized or raise shard_block_budget")
        # chunked prefill (long-context lane): cap the warm-prefill
        # bucket so a 128k prompt compiles ONE chunk-sized executable
        # run repeatedly instead of a prompt-sized one per pow2 bucket
        if prefill_chunk is not None:
            prefill_chunk = int(prefill_chunk)
            if prefill_chunk < self.block_size:
                raise ValueError(
                    f"prefill_chunk {prefill_chunk} below block_size "
                    f"{self.block_size}")
        self.prefill_chunk = prefill_chunk
        self.sharded_attn_calls = 0
        # default pool: half of what max_slots x max_len would need, +1
        # trash — the continuous-batching bet that mean length < max.
        # Tests/benches size it explicitly.
        self.num_blocks = int(num_blocks or
                              (self.max_slots * self.blocks_per_seq) // 2
                              + 1)
        # the programs index a pool's token rows flat over layers and
        # blocks (`_flat_pools`), in int32
        rows = self.kv_layers * self.num_blocks * self.block_size
        if rows >= 2**31:
            raise ValueError(
                f"KV pool of {self.kv_layers} layers x "
                f"{self.num_blocks} blocks x {self.block_size} tokens = "
                f"{rows} token rows, past the int32 row index (2**31): "
                f"lower num_blocks")
        self.allocator = BlockAllocator(self.num_blocks)
        self._slots = [_Slot(done=True) for _ in range(self.max_slots)]
        # prefix/radix cache (ISSUE 18): opt-in — True/"radix" builds a
        # serving.cache.RadixPrefixCache over this allocator; a
        # prebuilt cache instance is accepted for tests. Cache-on
        # engines keep their pools ALIVE across serve() calls
        # (self._persistent_pools) — cached KV must survive the call
        # that wrote it. Cache-off engines keep the historical
        # fresh-pools-per-serve behavior byte for byte.
        if prefix_cache in (True, "radix"):
            from ..serving.cache import RadixPrefixCache
            prefix_cache = RadixPrefixCache(
                self.block_size, self.allocator,
                max_blocks=prefix_cache_blocks)
        elif prefix_cache in (None, False):
            prefix_cache = None
        self.prefix_cache = prefix_cache
        self._persistent_pools = None
        # cold-block KV offload to host (ISSUE 19 tentpole a): the radix
        # cache pages rc==1 cold blocks to host memory through this
        # engine's pager and faults them back at admission, AHEAD of the
        # attention fetch. The resident-block budget is planner-priced —
        # cost_model.plan_kv_residency at this engine's KV footprint and
        # HBM budget — never a hand knob.
        self.kv_offload = bool(kv_offload)
        self.kv_residency = None
        if self.kv_offload:
            if self.prefix_cache is None:
                raise ValueError(
                    "kv_offload pages COLD blocks, which only the "
                    "prefix cache owns — build with prefix_cache=True")
            from ..distributed.auto_tuner.cost_model import (
                HBM_BUDGET_GIB, plan_kv_residency)
            budget = HBM_BUDGET_GIB if hbm_budget_gib is None \
                else float(hbm_budget_gib)
            self.kv_residency = plan_kv_residency(
                kv_gib=self.pool_bytes() / 2**30,
                hbm_budget_gib=budget,
                reserved_gib=self._weights_gib(),
                block_bytes=self.bytes_per_block())
            resident = max(1, int(self.kv_residency["resident_frac"]
                                  * (self.num_blocks - 1)))
            self.prefix_cache.enable_offload(self, resident)
        # admission-side device-work tallies: the warm-prefill gates
        # ("zero prefill-chunk device steps for the cached span") are
        # counter reads, not assertions about internals
        self.prefill_device_calls = 0
        self.prefill_tokens_computed = 0
        # zero-sync decode (ISSUE 20): the state-carrying decode chunk
        # — tokens/seqlens/live/budgets ride the device chunk-to-chunk
        # (donated, like the pools), tables/poison are NOT donated so
        # the same device copies serve every chunk until a composition
        # change re-uploads them. Host<->device sync tallies are plain
        # attrs (tests read them without telemetry); the registry
        # counters mirror them when telemetry is on.
        self._paged_chunk_state_jit = jax.jit(
            self._paged_chunk_state_impl,
            donate_argnums=(1, 2, 4, 5, 7, 8), static_argnums=(9, 10))
        self.h2d_uploads = 0          # decode-state host->device writes
        self.chunk_dispatches = 0     # decode chunk launches
        self.lookahead_dispatches = 0  # launched while one was in flight
        self.pipeline_drains = 0      # composition-change state drops
        # speculative-decode verifier: one executable per draft length
        # (the [S, k+1] token shape), pools donated like the chunk
        self._spec_verify_jit = jax.jit(
            self._spec_verify_impl, donate_argnums=(7, 8))
        # host-side accept-rate tallies (always on — cheap dict bumps);
        # mirrored into the observability registry when telemetry is on
        self.spec_stats = {"verify_calls": 0, "proposed": 0,
                           "accepted": 0, "emitted": 0}
        # MTP layers whose draft a decode chunk verifies on the device
        # (`spec_decode="mtp"`): an engine that has them says so
        self.draft_layers = getattr(self, "draft_layers", 0)
        # copy-on-write boundary-block copy: src/dst are traced scalars
        # so ONE executable serves every block pair
        self._cow_copy_jit = jax.jit(
            self._cow_copy_impl, donate_argnums=(0, 1))
        # prefill programs, one a bucket length (`_prefill_exec`), and
        # the warm (pool-mapped) prefill's (`_warmfill_exec`)
        self._prefill_cache = {}
        self._warm_cache = {}
        # telemetry's analysis records (observability/programs.py), by
        # kind and bucket / chunk length and eos / draft length
        self._analysed = {}
        _LIVE_DECODERS.add(self)

    def _prepare_weights(self, model, max_len, weight_quant):
        """The engine's weights as its programs take them (`_params`)
        and the sizes the cache machinery reads: `CachedDecoder`'s
        stacked Llama layers here, every one of which has K and V."""
        CachedDecoder.__init__(self, model, max_len=max_len,
                               weight_quant=weight_quant)
        self.kv_layers = self.cfg.num_hidden_layers

    def prefill_bucket(self, n):
        """Rows of the prefill program that takes a prompt of `n`
        tokens: the block size doubled until it holds them, `max_len`
        at most."""
        bucket = self.block_size
        while bucket < n:
            bucket *= 2
        return min(bucket, self.max_len)

    def _prefill_inputs(self, bucket, members, tables, pad):
        """What `_prefill_paged` takes before and after the pools for
        `members` [(slot, prompt ids, start row)]: one prompt from row 0
        here, padded behind to the bucket."""
        (slot, prompt, _), = members
        ids = np.full(bucket, pad, np.int32)
        ids[:len(prompt)] = prompt
        return (jnp.asarray(ids), jnp.int32(len(prompt)),
                jnp.asarray(tables[slot])), ()

    def _prefill_calls(self, bucket, members, tables, pad):
        """The (before, after the pools) inputs of each call of the
        bucket's program that prefills `members`: one call here, one a
        chunk on an engine that prefills a prompt in chunks.
        `decode_first_token` gets the list of their results."""
        return [self._prefill_inputs(bucket, members, tables, pad)]

    def chunk_counters(self, aux):
        """`serve:commit` metadata from what the chunk program returned
        after the pools (nothing here)."""
        return {}

    # -- pools -------------------------------------------------------------
    def new_pools(self):
        """Fresh zero K and V pools, [L, NB, bs, Hkv, D] each (int8
        codes paired with [L, NB, bs] f32 scales under kv_quant). That
        is the shape everything outside the jitted programs sees: axis 1
        is the block axis for the cache, the transport and the pager.
        The programs donate the pools and update them in place through
        the flat view of `_flat_pools`."""
        cfg = self.cfg
        shape = (self.kv_layers, self.num_blocks, self.block_size,
                 self.nkv, self.hd)
        if self.kv_quant:
            # codes + per-row scales as one pytree per side: every pool
            # consumer (the layer loop's carry, jit donation, AOT shape
            # keys) carries the pair without signature changes. Scales
            # init to 1 so zero codes dequantize to the zero pool.
            sshape = shape[:3]
            return ((jnp.zeros(shape, jnp.int8),
                     jnp.ones(sshape, jnp.float32)),
                    (jnp.zeros(shape, jnp.int8),
                     jnp.ones(sshape, jnp.float32)))
        dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        return jnp.zeros(shape, dt), jnp.zeros(shape, dt)

    def kv_token_bytes(self):
        """K (or V) bytes one pool token row costs on the wire/in HBM:
        the values at pool itemsize plus the codec scale when the pool
        is quantized. The ONE definition every byte bill below uses —
        pool sizing, guard admission, and telemetry must all see the
        quantized footprint or guard-driven admission under-admits."""
        if self.kv_quant:
            return self.nkv * self.hd * 1 + 4          # int8 codes + f32
        itemsize = 2 if self.cfg.dtype == "bfloat16" else 4
        return self.nkv * self.hd * itemsize

    def pool_bytes(self):
        return (2 * self.kv_layers * self.num_blocks
                * self.block_size * self.kv_token_bytes())

    def bytes_per_block(self):
        """K+V bytes one pool block holds across all layers — the unit the
        headroom guard prices admissions in (quantized-aware: the same
        guard limit admits proportionally more int8 blocks)."""
        return (2 * self.kv_layers * self.block_size
                * self.kv_token_bytes())

    # -- the pools inside a program ---------------------------------------
    @staticmethod
    def _flat_pools(kpool, vpool):
        """The pools as the layer loop carries them: every leaf viewed
        flat over layers and blocks ([L, NB, ...] -> [L*NB, ...], a
        reshape of a contiguous array: no data moves), so that layer l's
        block b is block l*NB + b and its token row r is row
        (l*NB + b)*bs + r. The loop updates that one buffer in place;
        pools passed as the scan's xs and returned as its ys would be
        sliced out and restacked whole, layer by layer. Returns (kflat,
        vflat, NB, layer ids [L] int32)."""
        L, NB = jax.tree_util.tree_leaves(kpool)[0].shape[:2]
        flat = lambda x: x.reshape((L * NB,) + x.shape[2:])
        return (jax.tree_util.tree_map(flat, kpool),
                jax.tree_util.tree_map(flat, vpool), NB,
                jnp.arange(L, dtype=jnp.int32))

    @staticmethod
    def _stacked_pools(flat, like):
        """Undo `_flat_pools` on one pool: back to ``like``'s
        [L, NB, ...] leaves, the shape every caller outside the jitted
        programs addresses (axis 1 is the block axis)."""
        return jax.tree_util.tree_map(
            lambda f, x: f.reshape(x.shape), flat, like)

    # -- core step ---------------------------------------------------------
    def _attend(self, q, kw, vw, pos, dtype):
        """q [S, nh, hd]; kw/vw gathered windows [S, W, nkv, hd]; pos [S]
        (index of the token just written). Grouped attention against the
        unrepeated window, masked to arange(W) <= pos per slot."""
        S, W = kw.shape[0], kw.shape[1]
        nrep = self.nh // self.nkv
        scale = 1.0 / math.sqrt(self.hd)
        qg = q.reshape(S, self.nkv, nrep, self.hd)
        att = jnp.einsum("bgnd,bwgd->bgnw", qg.astype(jnp.float32),
                         kw.astype(jnp.float32)) * scale
        mask = jnp.arange(W, dtype=jnp.int32)[None, :] <= pos[:, None]  # [S, W]
        att = jnp.where(mask[:, None, None, :], att, -1e30)
        p = jax.nn.softmax(att, axis=-1)
        o = jnp.einsum("bgnw,bwgd->bgnd", p,
                       vw.astype(jnp.float32)).astype(dtype)
        return o.reshape(S, self.nh * self.hd)

    def _pool_write(self, kc, vc, k, v, widx):
        """Scatter one K/V token row per query row into the pools at
        flat pool-token index widx: one scatter a pool leaf, in place
        when the pool is a loop's carry. The layer loops hand in the
        pools flat over layers ([L*NB, bs, Hkv, D], `_flat_pools`) and
        a widx that already holds the layer's l*NB*bs. Quantized pools
        ((codes, scales) pairs) quantize at write time: a token's
        append touches exactly its own codes and one f32 scale — no
        neighbor requantization."""
        if self.kv_quant:
            from ..kernels.pallas.ragged_paged_attention import (
                kv_quantize_rows)
            (kcod, ksc), (vcod, vsc) = kc, vc
            fk = kcod.reshape(-1, self.nkv, self.hd)
            fv = vcod.reshape(-1, self.nkv, self.hd)
            fks, fvs = ksc.reshape(-1), vsc.reshape(-1)
            qk, sk = kv_quantize_rows(k)
            qv, sv = kv_quantize_rows(v)
            return ((fk.at[widx].set(qk).reshape(kcod.shape),
                     fks.at[widx].set(sk).reshape(ksc.shape)),
                    (fv.at[widx].set(qv).reshape(vcod.shape),
                     fvs.at[widx].set(sv).reshape(vsc.shape)))
        fk = kc.reshape(-1, self.nkv, self.hd)
        fv = vc.reshape(-1, self.nkv, self.hd)
        return (fk.at[widx].set(k.astype(fk.dtype)).reshape(kc.shape),
                fv.at[widx].set(v.astype(fv.dtype)).reshape(vc.shape))

    def _pool_attend(self, q, kc, vc, tables, seqlens, dtype):
        """Attention for q [S, nh, hd] against the (possibly quantized)
        pools [blocks, bs, Hkv, D] through tables [S, MB] of ids on the
        pools' block axis: the layer loops pass the flat pool and
        tables offset by l*NB, so a layer reads its own blocks and no
        kernel knows of layers. Ragged path: the Pallas kernel streams
        blocks through the table (quantized variant dequantizes in VMEM
        after the fetch).
        Dense path: gather the window — dequantizing it for a quantized
        pool — and run the reference math; this stays the exact
        numerical oracle for BOTH kernels (PR 2/5 pattern)."""
        S = q.shape[0]
        scale = 1.0 / math.sqrt(self.hd)
        if self.use_ragged_kernel:
            # same decode.attend scope as the dense oracle below: the
            # memory profiler's top-K and the roofline waterfall must
            # attribute the quant/ragged kernel launch to the attention
            # bucket, not "other" (PR 9 threading predates these paths)
            with jax.named_scope("decode.attend"):
                if self.kv_quant:
                    from ..kernels.pallas.ragged_paged_attention import (
                        ragged_paged_attention_quant)
                    (kcod, ksc), (vcod, vsc) = kc, vc
                    o = ragged_paged_attention_quant(
                        q, kcod, ksc, vcod, vsc, tables, seqlens,
                        scale=scale)
                elif self.attn_shards > 1:
                    from ..kernels.pallas.ragged_paged_attention import (
                        ragged_paged_attention_sharded)
                    o = ragged_paged_attention_sharded(
                        q, kc, vc, tables, seqlens, self.attn_shards,
                        scale=scale)
                else:
                    from ..kernels.pallas.ragged_paged_attention import (
                        ragged_paged_attention)
                    o = ragged_paged_attention(q, kc, vc, tables,
                                               seqlens, scale=scale)
                return o.reshape(S, self.nh * self.hd)
        with jax.named_scope("decode.attend"):
            if self.kv_quant:
                (kcod, ksc), (vcod, vsc) = kc, vc
                kw = (jnp.take(kcod, tables, axis=0)
                      .astype(jnp.float32)
                      * jnp.take(ksc, tables, axis=0)[..., None, None]
                      ).reshape(S, -1, self.nkv, self.hd)
                vw = (jnp.take(vcod, tables, axis=0)
                      .astype(jnp.float32)
                      * jnp.take(vsc, tables, axis=0)[..., None, None]
                      ).reshape(S, -1, self.nkv, self.hd)
            else:
                # BLOCK-granular window gather ([S, MB] whole blocks,
                # not [S, W] tokens) — contiguous [bs, Hkv, D] reads per
                # index, which XLA lowers to wide HBM transfers
                kw = jnp.take(kc, tables, axis=0).reshape(
                    S, -1, self.nkv, self.hd)    # [S, W, Hkv, D]
                vw = jnp.take(vc, tables, axis=0).reshape(
                    S, -1, self.nkv, self.hd)
            return self._attend(q, kw, vw, seqlens, dtype)

    def _paged_step_impl(self, params, tokens, seqlens, tables,
                        kpool, vpool, active):
        """One decode step for every slot. tokens [S] int32; seqlens [S]
        int32 = tokens already in the pages (the new token is written at
        position seqlens); tables [S, MB] int32 block ids; pools
        [L, NB, bs, Hkv, D] donated; active [S] bool marks
        slots that really advance — inactive slots route their K/V
        writes to the trash block so an exhausted-budget slot can't
        clobber valid pool KV. Returns (logits [S, V], pools).

        The layer scan carries (x, K pool, V pool) and scans over the
        layers' weights and ids: each layer scatters its S rows into
        the one flat buffer (`_flat_pools`) and attends through tables
        offset to its blocks, so no layer of a pool is sliced out,
        restacked or copied, and the step's pool traffic is the rows it
        writes and the blocks the kernel reads."""
        S = tokens.shape[0]
        bs = self.block_size
        x = jnp.take(params["embed"], tokens, axis=0)       # [S, H]
        cos = jnp.take(params["cos"], seqlens, axis=0)      # [S, D]
        sin = jnp.take(params["sin"], seqlens, axis=0)
        dtype = x.dtype
        # flat pool index of the write target per slot
        blk = jnp.take_along_axis(tables, (seqlens // bs)[:, None],
                                  axis=1)[:, 0]             # [S]
        # budget gate (ADVICE r5): a slot past its budget must not
        # keep writing through the clamped gather — send it to the
        # trash block (block 0; lane seqlens % bs stays in range)
        blk = jnp.where(active, blk, 0)
        widx = blk * bs + seqlens % bs                      # [S]

        kflat, vflat, NB, layer_ids = self._flat_pools(kpool, vpool)

        def layer(carry, wl_l):
            x, kc, vc = carry              # kc/vc [L*NB, bs, Hkv, D]
            wl, l = wl_l
            h1 = _rms(x, wl["ln1"], self.eps)
            q = self._layer_mm(h1, wl["wq"], dtype).reshape(
                S, self.nh, self.hd)
            k = self._layer_mm(h1, wl["wk"], dtype).reshape(
                S, self.nkv, self.hd)
            v = self._layer_mm(h1, wl["wv"], dtype).reshape(
                S, self.nkv, self.hd)
            q = self._rope_at(q, cos[:, None, :], sin[:, None, :])
            k = self._rope_at(k, cos[:, None, :], sin[:, None, :])
            # scatter the new K/V into layer l's pages (trash-block
            # writes for retired slots collide harmlessly at index < bs
            # of that layer); one scope per role (the layer axis is a
            # scan — all layers share the body): the memory profiler's
            # top-K table reads decode.kv_pool / decode.attend instead
            # of fusion numbers
            with jax.named_scope("decode.kv_pool"):
                kc, vc = self._pool_write(kc, vc, k, v,
                                          l * (NB * bs) + widx)
            # layer l's blocks sit at l*NB.. in the flat pool. The
            # offset tables are made here, outside decode.attend: that
            # scope's device time is the kernel's alone
            o = self._pool_attend(q, kc, vc, tables + l * NB, seqlens,
                                  dtype)
            x = x + self._layer_mm(o, wl["wo"], dtype)
            h2 = _rms(x, wl["ln2"], self.eps)
            g = self._layer_mm(h2, wl["wg"], dtype)
            u = self._layer_mm(h2, wl["wu"], dtype)
            x = x + self._layer_mm(jax.nn.silu(g) * u, wl["wd"], dtype)
            return (x, kc, vc), None

        (x, kflat, vflat), _ = jax.lax.scan(
            layer, (x, kflat, vflat), (params["layers"], layer_ids))
        kpool = self._stacked_pools(kflat, kpool)
        vpool = self._stacked_pools(vflat, vpool)
        x = _rms(x, params["norm"], self.eps)
        return self._head_logits(params, x), kpool, vpool

    def _paged_chunk_state_impl(self, params, tok0, seqlens0, tables,
                                live, budgets, poison, kpool, vpool, n,
                                eos_id):
        """The decode chunk: n fused greedy steps with argmax feedback,
        the batch state advancing ON DEVICE so the next chunk's inputs
        are this chunk's outputs — the steady-state loop never uploads
        tokens/seqlens/live/budgets (ISSUE 20 tentpole a).

        live [S] bool masks slots that advance (retired slots keep
        writing into trash via their zeroed tables, their lengths stay
        put); budgets [S] int32 is each slot's REMAINING budget — at
        step i only slots with i < budget stay active, so a chunk sized
        by the largest budget can't run a smaller-budget slot past its
        allocation. poison [S] bool is the chaos harness's lane (NaN
        injected AFTER the real logits — KV stays clean); `bad` [S]
        reports any active step whose logits went non-finite, injected
        OR organic — the quarantine machinery keys off it.

        ``eos_id`` is static (-1 = no eos): the device retires a slot's
        liveness itself when its chunk emits eos or exhausts budget,
        mirroring exactly the host-side advance()/retire() arithmetic
        (take = min(n, budget) tokens consumed per live slot), so the
        host mirrors and the device state stay bit-identical between
        composition changes without a single download beyond the token
        block the host needs anyway.

        Returns (toks [S, n], bad [S], tok', seqlens', live', budgets',
        pools). tok0/seqlens0/live/budgets and the pools are donated
        (the chunk-to-chunk chain); tables/poison are not — the same
        device arrays serve every chunk until a composition change."""
        def step(tok, lens, act, pools):
            logits, kc, vc = self._paged_step_impl(
                params, tok, lens, tables, *pools, active=act)
            return logits, (kc, vc), None

        out, _ = self._chunk_scan(step, tok0, seqlens0, live, budgets,
                                  poison, (kpool, vpool), n, eos_id)
        return out

    @staticmethod
    def _chunk_scan(step, tok0, seqlens0, live, budgets, poison, pools, n,
                    eos_id, tally=None, acc=lambda: ()):
        """The liveness, budget, eos and poison arithmetic of every
        engine's decode chunk (see `_paged_chunk_state_impl`) around
        `step(tok, lens, act, pools) -> (logits, pools, aux)`, one
        engine's decode step. `tally(acc, aux, act, lens)` folds a step's
        `aux` into the running `acc` (`lens` as the step found them),
        which starts as `acc()`. Returns ((toks [S, n], bad, tok',
        seqlens', live', budgets') + pools, acc)."""
        def body(carry, i):
            tok, lens, bad, eos, acc, pools = carry
            act = live & (i < budgets)
            logits, pools, aux = step(tok, lens, act, pools)
            logits, bad = PagedDecoder._poisoned(logits, poison, act, bad)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            nxt = jnp.where(act, nxt, tok)
            after = jnp.where(act, lens + 1, lens)
            eos = PagedDecoder._ended(eos, act, nxt, eos_id)
            if tally is not None:
                acc = tally(acc, aux, act, lens)
            return (nxt, after, bad, eos, acc, tuple(pools)), nxt

        bad0 = jnp.zeros(tok0.shape, bool)
        (tok, lens, bad, eos, acc, pools), toks = jax.lax.scan(
            body, (tok0, seqlens0, bad0, jnp.zeros_like(bad0), acc(),
                   tuple(pools)),
            jnp.arange(n, dtype=jnp.int32))
        took = jnp.minimum(jnp.int32(n), jnp.maximum(budgets, 0))
        budgets = jnp.where(live, budgets - took, budgets)
        live_out = PagedDecoder._still_live(live, budgets, eos)
        return (jnp.swapaxes(toks, 0, 1), bad, tok, lens, live_out,
                budgets) + pools, acc

    # -- the arithmetic `_chunk_scan` and `_draft_scan` share ------------
    @staticmethod
    def _poisoned(logits, poison, act, bad):
        """A step's logits [S, ...] with a poisoned slot's made NaN, and
        `bad` raised where a live slot's are not finite."""
        lead = (slice(None),) + (None,) * (logits.ndim - 1)
        logits = jnp.where(poison[lead], jnp.asarray(jnp.nan, logits.dtype),
                           logits)
        return logits, bad | (act & jnp.any(
            ~jnp.isfinite(logits), axis=tuple(range(1, logits.ndim))))

    @staticmethod
    def _ended(eos, act, last, eos_id):
        """`eos` raised where a live slot's last token this step is eos
        (a slot stops at the chunk's end, or sooner where the step says)."""
        if eos_id >= 0:
            eos = eos | (act & (last == jnp.int32(eos_id)))
        return eos

    @staticmethod
    def _still_live(live, budgets, eos):
        """Live after a chunk: budget left and no eos emitted."""
        return live & (budgets > 0) & ~eos

    @staticmethod
    def _draft_scan(step, tok0, seqlens0, live, budgets, poison, pools, n,
                    eos_id, tally=None, acc=lambda: ()):
        """`_chunk_scan` for a model that drafts its own next token on the
        device (`spec_decode="mtp"`): each of the n steps is one verify
        pass, which yields one or two tokens a slot. tok0 [S, 2] holds
        each slot's current token (at position seqlens) and its draft.
        `step(tok, draft, lens, act, pools) -> (logits [S, 2, V], g [S,
        2], cand [S, 2], pools, aux)`: the target's logits and tokens at
        the current token's row and the draft's, and the draft that
        would follow either. A pass emits g0, and g1 too where the draft
        was g0 (greedy verification: the stream is plain greedy decode's),
        within the slot's budget and up to an eos, after which the slot
        stops; the next pass starts behind the last token emitted, with
        the draft that follows it. Returns ((passes [S, n, 4] int32 = (g0,
        g1, tokens emitted 0..2, the draft after them), bad, tok' [S, 2],
        seqlens', live', budgets') + pools, acc).

        Two things differ from `_chunk_scan`, because a pass emits a
        count the host cannot know before the chunk comes home: the
        budget left is carried (`_chunk_scan` reads it off the step's
        index), and a slot stops at its eos inside the chunk
        (`_chunk_scan`'s runs on to the chunk's end and the host trims),
        so each pass's count is what the host commits."""
        def body(carry, i):
            tok, draft, lens, left, bad, eos, acc, pools = carry
            act = live & (left > 0) & ~eos
            logits, g, cand, pools, aux = step(tok, draft, lens, act, pools)
            logits, bad = PagedDecoder._poisoned(logits, poison, act, bad)
            two = (g[:, 0] == draft) & (left >= 2)
            if eos_id >= 0:
                two = two & (g[:, 0] != jnp.int32(eos_id))
            emitted = jnp.where(act, 1 + two.astype(jnp.int32), 0)
            nxt = jnp.where(two, g[:, 1], g[:, 0])
            after = jnp.where(two, cand[:, 1], cand[:, 0])
            eos = PagedDecoder._ended(eos, act, nxt, eos_id)
            if tally is not None:
                acc = tally(acc, aux, act, lens)
            out = jnp.stack([g[:, 0], g[:, 1], emitted, after], axis=1)
            return (jnp.where(act, nxt, tok), jnp.where(act, after, draft),
                    lens + emitted, left - emitted, bad, eos, acc,
                    tuple(pools)), out

        bad0 = jnp.zeros(tok0.shape[:1], bool)
        (tok, draft, lens, left, bad, eos, acc, pools), passes = \
            jax.lax.scan(body, (tok0[:, 0], tok0[:, 1], seqlens0, budgets,
                                bad0, jnp.zeros_like(bad0), acc(),
                                tuple(pools)),
                         jnp.arange(n, dtype=jnp.int32))
        live_out = PagedDecoder._still_live(live, left, eos)
        return (jnp.swapaxes(passes, 0, 1), bad, jnp.stack([tok, draft], 1),
                lens, live_out, left) + pools, acc

    def _spec_verify_impl(self, params, toks, seqlens, tables, live,
                          budgets, poison, kpool, vpool):
        """Batched speculative verification: toks [S, k+1] — column 0 is
        each slot's current token, columns 1..k the draft proposals.
        Every slot expands into k+1 query rows at positions
        seqlens..seqlens+k, ALL pushed through the ordinary paged step
        (one batched forward): row i writes its token's K/V at position
        seqlens+i and attends with per-row seq_lens seqlens+i, so the
        unmodified ragged kernel (or dense reference) gives each row
        exactly its causal window — intra-draft causality is the same
        lens mask that makes raggedness work. Returns the greedy argmax
        grid [S, k+1]: g[s, i] is the target's next token after
        consuming input i; the host accepts the longest draft prefix
        with draft[j+1] == g[j] (exactly token-identical to plain
        greedy decode) plus the bonus token at the first mismatch.

        Rows past a slot's remaining budget route their writes to the
        trash block (the chunk path's gate) so an oversized draft can't
        write past the slot's allocation; the host never consumes their
        outputs. Rejected drafts' pool writes need no cleanup: lens
        only advance over accepted tokens, reads are lens-gated, and
        the next verify pass rewrites those positions."""
        S, K1 = toks.shape
        # scope the verify-specific row expansion and the post-forward
        # grid so spec executables attribute to decode.spec_verify in
        # the memory/roofline waterfalls instead of "other" (the inner
        # forward keeps its own decode.kv_pool / decode.attend buckets)
        with jax.named_scope("decode.spec_verify"):
            ii = jnp.arange(K1, dtype=jnp.int32)
            pos = seqlens[:, None] + ii[None, :]        # [S, K1]
            act = live[:, None] & (ii[None, :] < budgets[:, None])
            tabs = jnp.repeat(tables, K1, axis=0)       # [S*K1, MB]
        logits, kpool, vpool = self._paged_step_impl(
            params, toks.reshape(-1), pos.reshape(-1), tabs,
            kpool, vpool, active=act.reshape(-1))
        with jax.named_scope("decode.spec_verify"):
            logits = logits.reshape(S, K1, -1)
            # the chunk path's chaos poison + non-finite detection, on
            # the verify grid: bad[s] = any active row's logits
            # non-finite
            logits = jnp.where(poison[:, None, None],
                               jnp.asarray(jnp.nan, logits.dtype),
                               logits)
            bad = jnp.any(act & jnp.any(~jnp.isfinite(logits),
                                        axis=-1), axis=1)
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return g, bad, kpool, vpool

    @staticmethod
    def _encode_first_token(logits):
        """Fused first-token selection (ISSUE 20 tentpole c): argmax +
        the quarantine finiteness probe as ONE int32 on the wire —
        ``tok`` when every logit is finite, ``-(tok+1)`` (always
        negative) otherwise, so the host recovers the same argmax value
        either way and the non-finite flag rides for free. Decoded by
        `decode_first_token`."""
        tok = jnp.argmax(logits).astype(jnp.int32)
        ok = jnp.all(jnp.isfinite(logits))
        return jnp.where(ok, tok, -tok - 1)

    @staticmethod
    def decode_first_token(encs, seg=0):
        """Host side of `_encode_first_token`: (first_token,
        logits_nonfinite) from the results of a prompt's prefill calls,
        the last of which holds the one int32 (`seg`: which prompt of a
        packed prefill; this engine's holds one)."""
        v = int(np.asarray(encs[-1]))
        return (-v - 1, True) if v < 0 else (v, False)

    def admit_metadata(self):
        """What the engine adds to the `serve:admit` span of the
        admission whose first token `decode_first_token` just read."""
        return {}

    # prefill into pages: true_len is traced, bucket length is static
    def _prefill_paged(self, params, ids, true_len, table, kpool, vpool):
        """ids [S0pad] int32; true_len scalar; table [MB]. Writes K/V
        for positions < true_len, returns the ENCODED first token (the
        argmax of the logits at position true_len-1, fused on device —
        one int32 transfers instead of a vocab-wide row). The pools ride
        the layer scan's carry as in `_paged_step_impl`: a prefill moves
        the prompt's rows, not the pool."""
        S0 = ids.shape[0]
        bs = self.block_size
        x = jnp.take(params["embed"], ids, axis=0)          # [S0, H]
        cos, sin = params["cos"][:S0], params["sin"][:S0]
        dtype = x.dtype
        scale = 1.0 / math.sqrt(self.hd)
        nrep = self.nh // self.nkv
        pos = jnp.arange(S0, dtype=jnp.int32)
        valid = pos < true_len
        # pad positions write into the trash block
        blk = jnp.where(valid, jnp.take(table, pos // bs), 0)
        widx = blk * bs + pos % bs                          # [S0]
        causal = pos[None, :] <= pos[:, None]               # [S0, S0]

        kflat, vflat, NB, layer_ids = self._flat_pools(kpool, vpool)

        def layer(carry, wl_l):
            x, kc, vc = carry              # kc/vc [L*NB, bs, Hkv, D]
            wl, l = wl_l
            h1 = _rms(x, wl["ln1"], self.eps)
            q = self._layer_mm(h1, wl["wq"], dtype).reshape(
                S0, self.nh, self.hd)
            k = self._layer_mm(h1, wl["wk"], dtype).reshape(
                S0, self.nkv, self.hd)
            v = self._layer_mm(h1, wl["wv"], dtype).reshape(
                S0, self.nkv, self.hd)
            q = self._rope_at(q, cos[:, None, :], sin[:, None, :])
            k = self._rope_at(k, cos[:, None, :], sin[:, None, :])
            # prompt K/V land in layer l's pages, quantized when the
            # pool is (in-prompt attention below reads the
            # FULL-PRECISION k/v: the prompt is resident here, so its
            # own pass pays no quantization error — only later reads
            # through the pool do)
            kc, vc = self._pool_write(kc, vc, k, v,
                                      l * (NB * bs) + widx)
            # in-prompt causal attention (no window gather needed: the
            # prompt IS contiguous here)
            qg = q.reshape(S0, self.nkv, nrep, self.hd)
            att = jnp.einsum("qgnd,kgd->gnqk", qg.astype(jnp.float32),
                             k.astype(jnp.float32)) * scale
            att = jnp.where(causal[None, None], att, -1e30)
            p = jax.nn.softmax(att, axis=-1)
            o = jnp.einsum("gnqk,kgd->qgnd", p,
                           v.astype(jnp.float32)).astype(dtype)
            o = o.reshape(S0, self.nh * self.hd)
            x = x + self._layer_mm(o, wl["wo"], dtype)
            h2 = _rms(x, wl["ln2"], self.eps)
            g = self._layer_mm(h2, wl["wg"], dtype)
            u = self._layer_mm(h2, wl["wu"], dtype)
            x = x + self._layer_mm(jax.nn.silu(g) * u, wl["wd"], dtype)
            return (x, kc, vc), None

        (x, kflat, vflat), _ = jax.lax.scan(
            layer, (x, kflat, vflat), (params["layers"], layer_ids))
        kpool = self._stacked_pools(kflat, kpool)
        vpool = self._stacked_pools(vflat, vpool)
        last = jnp.take(x, jnp.maximum(true_len - 1, 0), axis=0)
        last = _rms(last[None], params["norm"], self.eps)
        logits = self._head_logits(params, last)[0]
        return self._encode_first_token(logits), kpool, vpool

    def _prefill_warm_impl(self, params, ids, start, true_len, table,
                           kpool, vpool):
        """Pool-mapped (warm) prefill: compute ONLY the uncached suffix
        of a prompt whose first ``start`` tokens already have KV
        resident in ``table``'s blocks (mapped from the prefix cache).
        ids [S0pad] holds the suffix tokens; true_len is the real
        suffix length. The spec-verify row trick, reused: each suffix
        token becomes one query row at position start+i pushed through
        the ordinary paged step — row i writes its K/V at start+i and
        attends with per-row seq_lens start+i, so the unmodified ragged
        kernel (or dense reference) READS the shared prefix blocks and
        never recomputes them. Rows past true_len route their writes to
        the trash block via the step's `active` gate. Returns (ENCODED
        first token of the last real suffix row — the fused on-device
        argmax, one int32 on the wire — and the pools).

        Cold prefill with the cache enabled also runs through THIS
        path (start=0): warm and cold then differ only in batch-row
        count through row-independent computations, which is what
        makes the cold/warm greedy streams token-identical — the
        tentpole's parity gate — rather than merely close."""
        S0 = ids.shape[0]
        with jax.named_scope("decode.warm_prefill"):
            ii = jnp.arange(S0, dtype=jnp.int32)
            pos = jnp.minimum(start + ii, self.max_len - 1)
            valid = ii < true_len
            tabs = jnp.broadcast_to(table[None, :], (S0, table.shape[0]))
        logits, kpool, vpool = self._paged_step_impl(
            params, ids, pos, tabs, kpool, vpool, active=valid)
        last = jnp.take(logits, jnp.maximum(true_len - 1, 0), axis=0)
        return self._encode_first_token(last), kpool, vpool

    def _cow_copy_impl(self, kpool, vpool, src, dst):
        """Device copy of one pool block (all layers, K and V): the
        copy-on-write fork for a fully-cached prompt's boundary block.
        Works on raw and quantized ((codes, scales)) pools alike —
        axis 1 is the block axis in every pool leaf."""
        with jax.named_scope("decode.cow_copy"):
            cp = lambda x: x.at[:, dst].set(x[:, src])
            return (jax.tree_util.tree_map(cp, kpool),
                    jax.tree_util.tree_map(cp, vpool))

    # -- pool persistence & KV transport (serving tier) --------------------
    def ensure_pools(self):
        """The engine's persistent pools, created on first use. Cache-on
        engines (and the disaggregation prefill side) must keep KV alive
        across serve() calls; the serve loop rebinds the donated pools
        back here after every device call."""
        if self._persistent_pools is None:
            self._persistent_pools = self.new_pools()
        return self._persistent_pools

    def release_pools(self):
        """Drop persistent pools and every cache entry referencing them
        (a failed serve may have consumed the pools via donation — the
        cached KV is unusable either way)."""
        self._persistent_pools = None
        if self.prefix_cache is not None:
            self.prefix_cache.clear()

    def export_blocks(self, kpool, vpool, block_ids):
        """Host copies of ``block_ids``' pool contents — the KV-block
        stream payload for prefill/decode disaggregation
        (serving/transport.py). Returns a (k, v) pytree of numpy arrays
        with the pool's block axis narrowed to len(block_ids)."""
        idx = jnp.asarray(np.asarray(block_ids, np.int32))
        take = lambda x: np.asarray(jnp.take(x, idx, axis=1))
        return (jax.tree_util.tree_map(take, kpool),
                jax.tree_util.tree_map(take, vpool))

    def import_blocks(self, kpool, vpool, block_ids, payload):
        """Write an exported payload into ``block_ids`` of these pools
        (the decode side of disaggregation). Shapes/dtypes must match —
        prefill and decode engines must be built with identical pool
        geometry and kv_quant."""
        idx = jnp.asarray(np.asarray(block_ids, np.int32))
        put = lambda x, d: x.at[:, idx].set(jnp.asarray(d, x.dtype))
        pk, pv = payload
        return (jax.tree_util.tree_map(put, kpool, pk),
                jax.tree_util.tree_map(put, vpool, pv))

    def _weights_gib(self):
        """GiB the prepared weights occupy — the HBM the residency
        planner must reserve before budgeting KV blocks."""
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(self._params)) \
            / 2**30

    # -- host KV offload pager (ISSUE 19) ----------------------------------
    def page_out_blocks(self, block_ids):
        """Copy ``block_ids``' KV to host memory and free their device
        slots. Caller (the cache's offload tier) must hold the ONLY
        reference (rc==1) — the free returns the slots to the
        allocator, so any later read of them through a table would be
        reading someone else's KV; the NaN-poison test proves no such
        read exists. Returns the host payload for page_in_blocks."""
        kp, vp = self.ensure_pools()
        payload = self.export_blocks(kp, vp, block_ids)
        self.allocator.free(block_ids)
        nbytes = len(block_ids) * self.bytes_per_block()
        if _obs.enabled():
            _obs.registry().counter(
                "paddle_tpu_kv_offload_out_bytes_total",
                "KV bytes paged out to host memory (cold cache "
                "blocks past the resident budget)").inc(nbytes)
        return payload

    def page_in_blocks(self, payload):
        """Fault a paged-out payload back: alloc fresh device blocks
        (rc=1, owned by the caller), import the host copy, rebind the
        persistent pools. Returns the new block ids."""
        n = jax.tree_util.tree_leaves(payload)[0].shape[1]
        blocks = self.allocator.alloc(n)
        kp, vp = self.ensure_pools()
        self._persistent_pools = self.import_blocks(kp, vp, blocks,
                                                    payload)
        nbytes = n * self.bytes_per_block()
        if _obs.enabled():
            _obs.registry().counter(
                "paddle_tpu_kv_offload_in_bytes_total",
                "KV bytes faulted back from host memory ahead of "
                "the attention fetch").inc(nbytes)
        return blocks

    def poison_blocks(self, block_ids):
        """Test/debug hook: NaN-poison blocks of the PERSISTENT pools
        in place (int8 code planes get saturated codes, float planes
        NaN). The refcount-safety proof (tests) frees a block, poisons
        it, and shows no other request ever reads it."""
        kp, vp = self.ensure_pools()
        idx = jnp.asarray(np.asarray(block_ids, np.int32))

        def bad(x):
            if jnp.issubdtype(x.dtype, jnp.floating):
                return x.at[:, idx].set(jnp.asarray(jnp.nan, x.dtype))
            return x.at[:, idx].set(jnp.asarray(127, x.dtype))

        self._persistent_pools = (jax.tree_util.tree_map(bad, kp),
                                  jax.tree_util.tree_map(bad, vp))
        return self._persistent_pools

    # -- per-bucket prefill programs ---------------------------------------
    def _prefill_exec(self, bucket):
        """The jitted prefill of this bucket length (made on its first
        use: one program a bucket, whoever asks)."""
        if bucket not in self._prefill_cache:
            self._prefill_cache[bucket] = jax.jit(
                self._prefill_paged, donate_argnums=self._prefill_donate)
        return self._prefill_cache[bucket]

    def _warmfill_exec(self, bucket):
        """The jitted warm (pool-mapped) prefill of this suffix bucket."""
        if bucket not in self._warm_cache:
            self._warm_cache[bucket] = jax.jit(
                self._prefill_warm_impl, donate_argnums=(5, 6))
        return self._warm_cache[bucket]

    def _record_traffic(self, seqlens, steps, live, budgets,
                        launches=None):
        """Ragged-kernel HBM telemetry for `steps` attention passes,
        quantization-aware: an int8 pool bills codes + f32 scales per
        token, and the bf16-equivalent counter prices the same fetches
        unquantized so the wire ratio is a pure counter read. `launches`
        corrects the kernel-call counter when one launch covers several
        positions (the batched spec verify)."""
        # the weight HBM stream rides the same per-step hook: every
        # decode step fetches all projections + head once, in whatever
        # storage format the engine quantized them to (decode.py's
        # weight_stream_bytes ledger) — the int8_blockwise <0.6x traffic
        # gate is a pure counter-ratio read
        self.record_weight_fetch(steps)
        if not self.use_ragged_kernel:
            return
        if self.attn_shards > 1:
            n = steps if launches is None else launches
            self.sharded_attn_calls += n
            if _obs.enabled():
                _obs.registry().counter(
                    "paddle_tpu_sharded_attn_calls_total",
                    "decode attention passes served by the context-"
                    "length-sharded partials kernel").inc(
                        self.kv_layers * n)
        from ..kernels.pallas.ragged_paged_attention import (
            record_ragged_step)
        record_ragged_step(
            seqlens, self.blocks_per_seq, self.block_size,
            self.nkv, self.hd,
            1 if self.kv_quant else
            (2 if self.cfg.dtype == "bfloat16" else 4),
            layers=self.kv_layers, steps=steps,
            live=live, budgets=budgets,
            scale_bytes=4 if self.kv_quant else 0, launches=launches)

    # -- continuous batching driver ---------------------------------------
    @staticmethod
    def _drain_reason():
        """Why serving should stop admitting (watchdog peer death), or
        None. Reads already-loaded watchdog state only — a process that
        never started the watchdog pays one dict lookup."""
        import sys
        m = sys.modules.get("paddle_tpu.distributed.comm_watchdog")
        if m is None:
            return None
        try:
            return m.draining_reason()
        except Exception:
            return None

    def serve(self, requests, max_new_tokens=32, eos_token_id=None,
              chunk=8, pad_token_id=0, admission_timeout_s=None,
              reject_oversized=False, spec_decode=None,
              max_restarts=3, evict_after_deferrals=2,
              max_deferrals=8, replay_backoff_s=0.05,
              max_chunk_retries=8, feed=None, feed_active=None,
              pipeline=None):
        """Continuous-batching serve loop. requests: iterable of
        (req_id, prompt_token_list) pairs, (req_id, prompt, max_new)
        triples — the triple form gives that request its own token
        budget (heterogeneous budgets share a chunk safely: steps are
        gated on-device per slot) — or (req_id, prompt, max_new,
        arrival_s) quads, where arrival_s is the request's arrival time
        in seconds RELATIVE to serve() entry: the open-loop form the
        sustained-load harness (benchmarks/serving_load.py) drives.
        Future arrivals are invisible to admission until their time
        passes; with nothing live the loop sleeps to the next arrival.
        Admits up to max_slots concurrent sequences, prefills newcomers
        into pool pages between decode chunks, retires slots at eos /
        budget, reclaims their blocks. Returns
        {req_id: [generated tokens]} (post-eos masked; rejected
        requests map to []).

        Overload shedding: `admission_timeout_s` rejects requests still
        queued past that wait (cause "rejected_timeout");
        `reject_oversized=True` rejects requests that can NEVER fit
        (prompt+budget past max_len or the whole pool) instead of
        raising — both recorded in the request ledger and
        `self.rejected_requests`.

        Fault recovery (ISSUE 14; disabled by
        FLAGS_serve_fault_recovery=0, the chaos drill's mutation
        teeth): a mid-serve failure — injected or organic pool/prefill
        faults, HeadroomGuard pressure, non-finite logits — is
        survived, never a crash:

        - **eviction**: sustained guard pressure on a queued head
          (>= `evict_after_deferrals` deferrals) evicts the live slot
          with the most remaining budget: its blocks are freed, its
          prompt + generated tokens retained, and the incarnation
          retires under cause "evicted";
        - **replay**: evicted/faulted requests are re-admitted via
          chunked-prefill replay (the retained prompt+tokens prefill
          into fresh pages, decode continues) with exponential backoff
          and a `max_restarts` cap — past the cap the partial stream
          is delivered and the request counts as a giveup. Greedy
          replay is token-identical to an uninterrupted serve — the
          chaos drill's correctness anchor;
        - **quarantine**: a slot whose decode logits go non-finite
          (FLAGS_serve_logit_quarantine) is recycled — the poisoned
          pass discarded, cause "quarantined", request replayed;
        - **deferral cap**: a head deferred `max_deferrals` times is
          rejected ("rejected_deferred") — a pressure storm degrades
          to rejection instead of wedging the queue;
        - **drain**: once the comm watchdog declares a peer dead,
          queued requests are rejected ("rejected_draining") and no
          new work is admitted while in-flight slots retire cleanly.

        Speculative decoding: `spec_decode` (None | k | "auto" | dict |
        models.spec_decode.SpecConfig) replaces each fused greedy chunk
        with a draft-propose -> batched-verify pass: a host-side draft
        proposes k tokens per live slot and ONE target forward through
        the paged attention path verifies all of them (plus the bonus
        position). Greedy verification is exact — the emitted stream is
        token-identical to plain decode; accept tallies land in
        `self.spec_stats` and the paddle_tpu_spec_decode_* counters.

        Pipelined admission (engines built with
        pipelined_admission=True): each admission scan dispatches all
        its prefills, then reads their first tokens in the same order;
        the tokens served are the same, `serve:prefill` then lies
        outside `serve:admit`, which spans the wait for the first token
        and the slot joining the batch. An engine whose prefill program
        takes a pack of prompts (`prefill_packs`; the hybrid engine)
        then sends the scan's prompts several a program.

        Prefix cache (ISSUE 18; engines built with prefix_cache=True):
        admission matches the prompt against the radix tree over the
        block pool, maps shared blocks copy-on-write into the new
        table, and prefills ONLY the uncached suffix via the
        pool-mapped warm executable (a fully-cached prompt pays one
        boundary-block device copy + a one-token recompute).
        Retirement adopts the retiree's full prefix blocks into the
        tree; pool and HeadroomGuard pressure evict cold LRU leaves
        before any live victim. Cache-on engines keep their pools
        ALIVE across serve() calls. Savings are counter-proven
        (paddle_tpu_prefix_cache_*_total) and greedy streams are
        token-identical cold-cache vs warm-cache.

        Streamed admission (prefill/decode disaggregation): `feed` is
        a callable drained every loop iteration for
        (rid, prompt_or_KVBlockPayload, max_new) records;
        `feed_active` keeps the loop alive while upstream prefill
        workers still run. A KVBlockPayload admits by IMPORTING its
        finished KV blocks — zero prefill device work on this engine.

        Zero-sync pipelined decode (ISSUE 20): the fused decode path
        keeps tokens/seqlens/live/budgets/poison DEVICE-RESIDENT — the
        chunk executable advances them on device and the next chunk
        consumes its predecessor's output buffers, so the steady-state
        loop performs zero host->device uploads (counter:
        `self.h2d_uploads` / paddle_tpu_serve_h2d_uploads_total); host
        writes happen only at batch-composition changes (admission,
        eviction, quarantine) as full-state delta updates. `pipeline`
        controls the one-chunk lookahead: None (default) dispatches
        chunk N+1 off the device-resident state before consuming chunk
        N's tokens, overlapping all host bookkeeping with device
        compute; False drains every chunk at dispatch (exact per-chunk
        walls, for chaos drills needing per-chunk determinism); True
        additionally REFUSES spec_decode
        (the verify pass is host-interactive by construction) instead
        of silently falling back. Greedy parity with the serial loop
        holds by construction — the fed-back tokens are the ones the
        device wrote.

        HBM: bounded by the block pool — `allocator.peak_in_use` blocks,
        not max_slots * max_len (the fixed engine's bill).

        Telemetry-on runs call the same programs and time them: every
        iteration is classified into the goodput ledger (source="serve"):
        backend compiles heard during it are `compile`, the loop's waits
        for prefill/chunk results `execute`, the admission/bookkeeping
        host loop `dispatch` — emitted per iteration to the JSONL sink
        like TrainStep's (analysis records: observability/programs.py).

        Every run, telemetry on or off, threads every request through
        the per-request lifecycle ledger (`self.request_ledger`,
        observability/requests.py): arrival/admit/prefill/first-token/
        chunk/retire timestamps, TTFT/TPOT, the {queue_wait, prefill,
        decode, overhead} buckets that telescope to the request wall,
        retire causes, and HeadroomGuard deferral counts. Telemetry adds
        the export: each retired request to the JSONL sink and the
        sliding-window SLO quantiles.
        """
        from ..serving.batcher import serve_loop
        return serve_loop(
            self, requests, max_new_tokens=max_new_tokens,
            eos_token_id=eos_token_id, chunk=chunk,
            pad_token_id=pad_token_id,
            admission_timeout_s=admission_timeout_s,
            reject_oversized=reject_oversized, spec_decode=spec_decode,
            max_restarts=max_restarts,
            evict_after_deferrals=evict_after_deferrals,
            max_deferrals=max_deferrals,
            replay_backoff_s=replay_backoff_s,
            max_chunk_retries=max_chunk_retries, feed=feed,
            feed_active=feed_active, pipeline=pipeline)
