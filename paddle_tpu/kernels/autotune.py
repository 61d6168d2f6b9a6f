"""Kernel autotune cache (reference: paddle/phi/kernels/autotune/cache.h:97
`AutoTuneCache`, switch_autotune.cc `AutoTuneStatus`, gpu_timer.h).

The reference caches the winning cudnn/transpose algorithm per input
signature after an exhaustive timed search. TPU-native: the tunable axis
is Pallas block shapes — candidates are timed eagerly on device (one
compile each, so tuning is explicit/opt-in) and the winner is cached by
(kernel, signature); traced code consults the cache only."""
from __future__ import annotations

import time
from collections import OrderedDict

__all__ = ["AutoTuneCache", "AutoTuneStatus", "autotune_run",
           "tune_flash_blocks", "tune_ragged_blocks",
           "lookup_ragged_blocks", "tune_kv_quant_blocks",
           "lookup_kv_quant_blocks", "tune_spec_decode",
           "lookup_spec_decode", "tune_grad_buckets",
           "lookup_grad_buckets", "tune_grouped_matmul",
           "lookup_grouped_matmul", "tune_collective_matmul",
           "lookup_collective_matmul", "enable_autotune",
           "disable_autotune"]


class AutoTuneCache:
    """Singleton (kernel, key) -> config LRU store with hit/miss/eviction
    stats. The raw counters are plain ints (zero overhead on the traced
    consult path); the observability registry mirrors them at scrape time
    via its autotune collector (paddle_tpu_autotune_cache_*)."""

    _instance = None

    def __init__(self, capacity=None):
        self._store = OrderedDict()
        self.capacity = capacity          # None = unbounded
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    @classmethod
    def instance(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def set_capacity(self, capacity):
        """Bound the cache; evicts least-recently-used entries to fit."""
        self.capacity = capacity
        if capacity is not None:
            while len(self._store) > capacity:
                self._store.popitem(last=False)
                self.evictions += 1

    def get(self, kernel, key):
        k = (kernel, tuple(key))
        entry = self._store.get(k)
        if entry is None:
            self.misses += 1
        else:
            self.hits += 1
            self._store.move_to_end(k)
        return entry

    def set(self, kernel, key, config):
        k = (kernel, tuple(key))
        if k in self._store:
            self._store.move_to_end(k)
        elif self.capacity is not None and \
                len(self._store) >= self.capacity:
            self._store.popitem(last=False)
            self.evictions += 1
        self._store[k] = config

    def size(self):
        return len(self._store)

    def cache_hit_rate(self):
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def clear(self):
        self._store.clear()
        self.hits = self.misses = self.evictions = 0


class AutoTuneStatus:
    """Global on/off switch (reference switch_autotune.cc); also settable
    via FLAGS_use_autotune."""

    _enabled = False

    @classmethod
    def enabled(cls):
        from ..framework.flags import get_flags
        flag = get_flags("FLAGS_use_autotune")
        if isinstance(flag, dict):
            flag = flag.get("FLAGS_use_autotune")
        return bool(cls._enabled or flag)

    @classmethod
    def enable(cls):
        cls._enabled = True

    @classmethod
    def disable(cls):
        cls._enabled = False


def enable_autotune():
    AutoTuneStatus.enable()


def disable_autotune():
    AutoTuneStatus.disable()


def _sync(out):
    import jax
    import numpy as np
    leaves = jax.tree_util.tree_leaves(out)
    if leaves:
        np.asarray(leaves[0])  # host transfer = hard device sync


def autotune_run(kernel, key, candidates, runner, iters=3):
    """Time `runner(candidate)` for each candidate, cache and return the
    winner. Failed candidates (compile errors etc.) are skipped."""
    cache = AutoTuneCache.instance()
    cached = cache.get(kernel, key)
    if cached is not None:
        return cached
    best, best_t = None, float("inf")
    for cand in candidates:
        try:
            out = runner(cand)  # warmup + compile
            _sync(out)
            t0 = time.perf_counter()
            for _ in range(iters):
                out = runner(cand)
            _sync(out)
            dt = (time.perf_counter() - t0) / iters
        except Exception:
            continue
        if dt < best_t:
            best, best_t = cand, dt
    if best is not None:
        cache.set(kernel, key, best)
    return best


def tune_flash_blocks(seq_len, head_dim, dtype="bfloat16", batch_heads=8):
    """Pick (bq, bk) for the Pallas flash-attention kernel on the local
    device; the kernel's _block_sizes consults the cache afterwards."""
    import numpy as np
    import jax.numpy as jnp
    from .pallas import flash_attention as fa

    key = (seq_len, head_dim, dtype)
    from .pallas.flash_attention import _use_streaming
    if _use_streaming(seq_len, head_dim):
        raise ValueError(
            f"seq_len {seq_len} uses the streaming flash kernel whose "
            "blocks are fixed; tuning applies to the resident kernel only")
    cands = [(bq, bk) for bq in (128, 256, 512) for bk in (128, 256, 512,
                                                           1024)
             if bq <= seq_len and bk <= seq_len
             and seq_len % bq == 0 and seq_len % bk == 0]
    q = jnp.asarray(np.random.randn(batch_heads, seq_len, head_dim),
                    jnp.dtype(dtype))

    def runner(cand):
        override = {"flash": cand}
        old = fa._BLOCK_OVERRIDE.get("flash")
        fa._BLOCK_OVERRIDE.update(override)
        try:
            return fa._mha_fwd(q, q, q, True, 1.0 / head_dim ** 0.5)
        finally:
            if old is None:
                fa._BLOCK_OVERRIDE.pop("flash", None)
            else:
                fa._BLOCK_OVERRIDE["flash"] = old

    best = autotune_run("flash_attention_fwd", key, cands, runner)
    if best is not None:
        AutoTuneCache.instance().set("flash_blocks", key, best)
    return best


def _ragged_key(num_heads, num_kv_heads, head_dim, dtype):
    return (int(num_heads), int(num_kv_heads), int(head_dim), str(dtype))


def lookup_ragged_blocks(num_heads, num_kv_heads, head_dim, dtype):
    """Cached pool block_size winner for the ragged paged-attention
    kernel at this attention geometry, or None. Reads the raw store —
    the consult path must not perturb hit/miss stats (the same contract
    flash_attention._block_sizes uses); tuning itself goes through
    autotune_run, which counts."""
    return AutoTuneCache.instance()._store.get(
        ("ragged_blocks", _ragged_key(num_heads, num_kv_heads, head_dim,
                                      dtype)))


def tune_ragged_blocks(num_heads, num_kv_heads, head_dim,
                       dtype="bfloat16", max_len=1024, slots=8,
                       candidates=(16, 32, 64, 128, 256)):
    """Pick the KV pool block_size for the ragged paged-attention kernel
    on the local device (one compile + timed run per candidate, the
    flash pattern). The kernel copies a sequence's live blocks in
    groups and attends several small blocks in one product, so the block
    size no longer buys grid steps: it trades the copies issued a token
    (small blocks = more, shorter DMAs) against ragged waste (big blocks
    = more dead tokens fetched past each sequence's length, and masked
    in the last product); the winner is cached under
    ("ragged_blocks", geometry) and consulted by
    PagedDecoder(block_size="auto")."""
    import numpy as np
    import jax.numpy as jnp
    from .pallas.ragged_paged_attention import ragged_paged_attention

    key = _ragged_key(num_heads, num_kv_heads, head_dim, dtype)
    rng = np.random.default_rng(11)
    lens = rng.integers(0, max_len, slots)

    def runner(bs):
        mb = max_len // bs
        nb = slots * mb + 1
        kp = jnp.asarray(rng.standard_normal(
            (nb, bs, num_kv_heads, head_dim)), jnp.dtype(dtype))
        vp = jnp.asarray(rng.standard_normal(
            (nb, bs, num_kv_heads, head_dim)), jnp.dtype(dtype))
        q = jnp.asarray(rng.standard_normal(
            (slots, num_heads, head_dim)), jnp.dtype(dtype))
        tables = jnp.asarray(
            (np.arange(slots * mb, dtype=np.int32) + 1).reshape(slots, mb))
        sl = jnp.asarray(lens.astype(np.int32))
        return ragged_paged_attention(q, kp, vp, tables, sl)

    cands = [bs for bs in candidates if max_len % bs == 0 and bs <= max_len]
    best = autotune_run("ragged_paged_attention", key, cands, runner)
    if best is not None:
        AutoTuneCache.instance().set("ragged_blocks", key, best)
    return best


def lookup_kv_quant_blocks(num_heads, num_kv_heads, head_dim, dtype):
    """Cached pool block_size winner for the QUANTIZED (int8-KV) ragged
    kernel at this attention geometry, or None. Separate cache key from
    the unquantized kernel — blocks of half the bytes shift the copies /
    ragged-waste trade, so winners don't transfer. Raw-store read, same
    no-stat-perturbation contract as lookup_ragged_blocks."""
    return AutoTuneCache.instance()._store.get(
        ("kv_quant_blocks", _ragged_key(num_heads, num_kv_heads,
                                        head_dim, dtype)))


def tune_kv_quant_blocks(num_heads, num_kv_heads, head_dim,
                         dtype="bfloat16", max_len=1024, slots=8,
                         candidates=(16, 32, 64, 128, 256)):
    """Pick the KV pool block_size for the int8-quantized ragged
    paged-attention kernel (one compile + timed run per candidate, the
    tune_ragged_blocks pattern, but timing the QUANT kernel over int8
    codes + f32 per-row scales). Winner cached under
    ("kv_quant_blocks", geometry) and consulted by
    PagedDecoder(block_size="auto", kv_quant="int8")."""
    import numpy as np
    import jax.numpy as jnp
    from .pallas.ragged_paged_attention import (kv_quantize_rows,
                                                ragged_paged_attention_quant)

    key = _ragged_key(num_heads, num_kv_heads, head_dim, dtype)
    rng = np.random.default_rng(11)
    lens = rng.integers(0, max_len, slots)

    def runner(bs):
        mb = max_len // bs
        nb = slots * mb + 1
        kc, ks = kv_quantize_rows(jnp.asarray(rng.standard_normal(
            (nb, bs, num_kv_heads, head_dim)), jnp.float32))
        vc, vs = kv_quantize_rows(jnp.asarray(rng.standard_normal(
            (nb, bs, num_kv_heads, head_dim)), jnp.float32))
        q = jnp.asarray(rng.standard_normal(
            (slots, num_heads, head_dim)), jnp.dtype(dtype))
        tables = jnp.asarray(
            (np.arange(slots * mb, dtype=np.int32) + 1).reshape(slots, mb))
        sl = jnp.asarray(lens.astype(np.int32))
        return ragged_paged_attention_quant(q, kc, ks, vc, vs, tables, sl)

    cands = [bs for bs in candidates if max_len % bs == 0 and bs <= max_len]
    best = autotune_run("ragged_paged_attention_quant", key, cands, runner)
    if best is not None:
        AutoTuneCache.instance().set("kv_quant_blocks", key, best)
    return best


def _spec_key(hidden, layers, nh, nkv, hd, vocab, dtype, accept_prob):
    """Model geometry + the accept probability binned to one decimal:
    the optimal draft length moves with how often drafts land, not with
    its exact value."""
    return (int(hidden), int(layers), int(nh), int(nkv), int(hd),
            int(vocab), str(dtype), round(float(accept_prob), 1))


def lookup_spec_decode(hidden, layers, nh, nkv, hd, vocab, dtype,
                       accept_prob=0.6):
    """Cached draft-length winner for speculative decoding at this model
    geometry / accept-rate class, or None. Raw-store read (the consult
    path — PagedDecoder.serve(spec_decode="auto") — must not perturb
    hit/miss stats, the lookup_ragged_blocks contract)."""
    return AutoTuneCache.instance()._store.get(
        ("spec_decode", _spec_key(hidden, layers, nh, nkv, hd, vocab,
                                  dtype, accept_prob)))


def tune_spec_decode(model, accept_prob=0.6, candidates=(2, 4, 8),
                     max_len=128, block_size=16, slots=2, iters=2):
    """Pick the speculative draft length k on the local device: each
    candidate runs the REAL batched-verify executable
    (PagedDecoder._spec_verify_impl, k+1 query rows through the paged
    attention path) enough times to emit a fixed expected token budget
    under a geometric acceptance model with per-draft probability
    `accept_prob` — so the timed quantity is time-per-expected-token
    and autotune_run's min-time winner IS the max-throughput k. Longer
    drafts amortize the weight/KV pass but waste verify rows once
    acceptance breaks; shorter drafts verify cheap but keep more of
    plain decode's per-token pass. Winner cached under
    ("spec_decode", geometry+accept-class) and consulted by
    serve(spec_decode="auto")."""
    import numpy as np
    import jax.numpy as jnp
    from ..models.paged_decode import PagedDecoder

    cfg = model.config if hasattr(model, "config") else model.cfg
    dec = PagedDecoder(model, max_len=max_len, block_size=block_size,
                       max_slots=slots,
                       num_blocks=slots * (max_len // block_size) + 1)
    key = _spec_key(cfg.hidden_size, cfg.num_hidden_layers, dec.nh,
                    dec.nkv, dec.hd, cfg.vocab_size, cfg.dtype,
                    accept_prob)
    p = min(max(float(accept_prob), 0.0), 0.99)

    def expected_tokens(k):
        # E[emitted per verify] under geometric acceptance: 1 bonus +
        # sum_{j=1..k} p^j
        return float((1.0 - p ** (k + 1)) / (1.0 - p)) if p > 0 else 1.0

    rng = np.random.default_rng(19)
    target = expected_tokens(max(candidates)) * 2

    def runner(k):
        kp, vp = dec.new_pools()
        mb = dec.blocks_per_seq
        tables = np.zeros((slots, mb), np.int32)
        blocks = dec.allocator.alloc(slots * mb)
        for i in range(slots):              # slot i gets its row of blocks
            tables[i] = blocks[i * mb:(i + 1) * mb]
        lens = jnp.asarray(np.full(slots, dec.max_len // 2, np.int32))
        live = jnp.ones((slots,), bool)
        budgets = jnp.full((slots,), dec.max_len // 2 - k - 1, jnp.int32)
        toks = jnp.asarray(rng.integers(
            0, cfg.vocab_size, (slots, k + 1)).astype(np.int32))
        m = max(1, int(round(target / expected_tokens(k))))
        g = None
        poison = jnp.zeros((slots,), bool)
        for _ in range(m):
            # pools are donated per call: thread the returned handles
            g, _, kp, vp = dec._spec_verify_jit(
                dec._params, toks, lens, jnp.asarray(tables), live,
                budgets, poison, kp, vp)
        dec.allocator.free(blocks)
        return g

    best = autotune_run("spec_decode", key, list(candidates), runner,
                        iters=iters)
    if best is not None:
        AutoTuneCache.instance().set("spec_decode", key, best)
    return best


def _grouped_key(n_routes, d_model, d_hidden, num_expert, dtype):
    """Power-of-two bin of the routed-token count + the GEMM geometry:
    tile winners transfer within a 2x token-count class (the tile/grid
    trade moves with tokens, not with the exact batch)."""
    t = max(1, int(n_routes))
    return (1 << (t.bit_length() - 1), int(d_model), int(d_hidden),
            int(num_expert), str(dtype))


def lookup_grouped_matmul(n_routes, d_model, d_hidden, num_expert,
                          dtype="float32"):
    """Cached (bm, bn) winner for the grouped-GEMM MoE kernel at this
    geometry, or None. Reads the raw store — the consult path
    (MoELayer(group_block="auto")) must not perturb hit/miss stats,
    same contract as lookup_ragged_blocks."""
    return AutoTuneCache.instance()._store.get(
        ("grouped_blocks", _grouped_key(n_routes, d_model, d_hidden,
                                        num_expert, dtype)))


def tune_grouped_matmul(n_routes, d_model, d_hidden, num_expert,
                        dtype="float32",
                        candidates=((8, 128), (16, 128), (32, 128),
                                    (64, 128), (128, 128), (128, 256)),
                        iters=3):
    """Pick (bm, bn) row/column tiles for the grouped-GEMM MoE kernel
    on the local device (one compile + timed run per candidate, the
    flash pattern). Small bm wastes less alignment padding on skewed
    groups but pays more grid steps; big bm amortizes the MXU but pads
    every group up to its tile. Times the REAL kernel (interpret mode
    off-TPU) on a balanced routing at this geometry; winner cached
    under ("grouped_blocks", key) and consulted by
    MoELayer(group_block="auto")."""
    import numpy as np
    import jax.numpy as jnp
    from .pallas.grouped_matmul import (aligned_group_size,
                                        grouped_matmul, grouped_metadata)

    key = _grouped_key(n_routes, d_model, d_hidden, num_expert, dtype)
    rng = np.random.default_rng(13)
    e_ids = jnp.asarray(
        rng.integers(0, num_expert, n_routes).astype(np.int32))
    w = jnp.asarray(rng.standard_normal(
        (num_expert, d_model, d_hidden)), jnp.dtype(dtype))
    x = jnp.asarray(rng.standard_normal((n_routes, d_model)),
                    jnp.dtype(dtype))

    def runner(cand):
        bm, bn = cand
        md = grouped_metadata(e_ids, num_expert, bm)
        tp = aligned_group_size(n_routes, num_expert, bm)
        buf = jnp.zeros((tp, d_model), jnp.dtype(dtype))
        buf = buf.at[md["dest"]].set(x)         # dest is per-route
        return grouped_matmul(buf, w, group_offsets=md["offsets"],
                              group_counts=md["counts"], bm=bm, bn=bn,
                              impl="kernel")

    cands = [c for c in candidates if c[0] <= max(int(n_routes), 8)]
    best = autotune_run("grouped_matmul", key, cands, runner, iters=iters)
    if best is not None:
        AutoTuneCache.instance().set("grouped_blocks", key, best)
    return best


def _cm_key(rows, k, o, n, dtype, compress):
    """Power-of-two bin of the row count (the dim the rings block) + the
    GEMM geometry, shard count, and codec: chunk winners transfer within
    a 2x row class, but not across shard counts (hop count changes the
    interleave budget) or codecs (quant/dequant cost moves the
    optimum)."""
    r = max(1, int(rows))
    return (1 << (r.bit_length() - 1), int(k), int(o), int(n),
            str(dtype), str(compress))


def lookup_collective_matmul(rows, k, o, n, dtype="float32",
                             compress=None):
    """Cached chunk-count winner for a decomposed collective matmul at
    this geometry, or None. Reads the raw store — the consult path
    (collective_matmul._resolve_chunks under chunks="auto") must not
    perturb hit/miss stats, same contract as lookup_ragged_blocks."""
    return AutoTuneCache.instance()._store.get(
        ("collective_matmul", _cm_key(rows, k, o, n, dtype, compress)))


def tune_collective_matmul(rows, k, o, kind="column_sp", dtype="float32",
                           compress=None, candidates=(1, 2, 4, 8),
                           iters=3):
    """Pick the per-ring-step matmul chunk count for the collective-
    matmul decomposition (fleet/meta_parallel/collective_matmul.py) on
    the local device mesh: the full mp ring of `kind` runs one jitted
    fwd+bwd per candidate over all local devices. More chunks give the
    latency-hiding scheduler more interleave points per permute leg but
    shrink each MXU call; fewer chunks amortize the MXU but can leave a
    leg with nothing scheduled behind it. Winner cached under
    ("collective_matmul", geometry-bin) and consulted by
    cm_matmul(chunks="auto")."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from ..distributed.fleet.meta_parallel.collective_matmul import (
        cm_matmul)

    devs = jax.devices()
    n = len(devs)
    key = _cm_key(rows, k, o, n, dtype, compress)
    mesh = Mesh(np.array(devs), ("mp",))
    rng = np.random.default_rng(17)
    s = max(n, int(rows) // n * n)      # ring-divisible row count
    x = jnp.asarray(rng.standard_normal((1, s, k)), jnp.dtype(dtype))
    w = jnp.asarray(rng.standard_normal((k, o)), jnp.dtype(dtype))

    def runner(chunks):
        def loss(x, w):
            y = cm_matmul(x, w, mesh=mesh, axis="mp", kind=kind,
                          chunks=chunks, compress=compress,
                          impl="overlap")
            return jnp.sum(y * y)
        return jax.jit(jax.grad(loss, argnums=(0, 1)))(x, w)

    cands = [c for c in candidates if c <= max(1, s // n)]
    best = autotune_run("collective_matmul", key, cands, runner,
                        iters=iters)
    if best is not None:
        AutoTuneCache.instance().set("collective_matmul", key, best)
    return best


def _grad_bucket_key(total_bytes, compress):
    """Power-of-two MiB bin of the model's total gradient bytes + the
    compression mode: bucket-size winners transfer within a 2x size
    class but not across compression modes (quantize/dequant cost moves
    the optimum)."""
    mb = max(1, int(total_bytes) >> 20)
    return (1 << (mb.bit_length() - 1), str(compress))


def lookup_grad_buckets(total_bytes, compress=None):
    """Cached bucket-MB winner for a model with `total_bytes` of
    gradients, or None. Reads the raw store — the consult path
    (GradBucketScheduler(bucket_mb="auto")) must not perturb hit/miss
    stats, same contract as lookup_ragged_blocks."""
    return AutoTuneCache.instance()._store.get(
        ("grad_buckets", _grad_bucket_key(total_bytes, compress)))


def tune_grad_buckets(total_mb=32, compress=None, layers=8,
                      candidates=(2, 4, 8, 16, 32), iters=3):
    """Pick grad_bucket_mb for the backward-overlapped gradient sync
    (fleet/grad_buckets.py) on the local device mesh: a synthetic
    `layers`-deep MLP totaling ~total_mb of fp32 parameters trains one
    fused step per candidate under shard_map over all local devices,
    with every bucket's (optionally compressed) all-reduce anchored by
    the scheduler's custom_vjp tags — exactly the lowering the real
    TrainStep path uses. Small buckets start syncing earlier but pay
    per-collective latency; large buckets amortize it but serialize the
    tail. Winner cached under ("grad_buckets", size-class) and consulted
    by GradBucketScheduler(bucket_mb="auto")."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from ..distributed.fleet.grad_buckets import (GradBucketScheduler,
                                                  tagged_mlp_step)

    devs = jax.devices()
    n = len(devs)
    key = _grad_bucket_key(int(total_mb) << 20, compress)
    # h*h*4*layers ~= total_mb MiB, h a multiple of 8
    h = max(8, int((float(total_mb) * 2**20 / (4 * layers)) ** 0.5) // 8 * 8)
    rng = np.random.default_rng(7)
    names = [f"w{i}" for i in range(layers)]
    ws = {nm: jnp.asarray(rng.standard_normal((h, h)) * 0.1,
                          jnp.float32) for nm in names}
    entries = [(nm, (h, h), "float32") for nm in names]
    x = jnp.asarray(rng.standard_normal((4 * n, h)), jnp.float32)
    mesh = Mesh(np.array(devs), ("dp",))

    def runner(bucket_mb):
        sched = GradBucketScheduler(entries, bucket_mb=bucket_mb,
                                    compress=compress, axis="dp",
                                    mesh=mesh)
        return tagged_mlp_step(sched, names, mesh)(ws, x)

    best = autotune_run("grad_buckets", key, list(candidates), runner,
                        iters=iters)
    if best is not None:
        AutoTuneCache.instance().set("grad_buckets", key, best)
    return best
