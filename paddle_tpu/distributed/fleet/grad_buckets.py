"""Gradient-bucket scheduler: backward-overlapped, optionally compressed
gradient synchronization for the dp/ZeRO training path.

Why (T3, arxiv 2401.16677 + EQuARX, arxiv 2506.17615): the training
configs synchronized gradients as ONE monolithic collective at step end,
so at dp>=8 the all-reduce wall time neither hides under backward compute
nor shrinks with precision. This module fixes both axes:

overlap — parameters are partitioned into ~`bucket_mb`-MB buckets in
    REVERSE-backward order (late layers' grads are final first), and each
    bucket's sync is anchored at the exact point in the backward graph
    where its gradients finalize, via a `jax.custom_vjp` identity tag
    applied where the parameters ENTER the loss computation: the tag's
    backward rule fires once all of the bucket's cotangents are complete,
    which for late layers is EARLY in backward — the XLA latency-hiding
    scheduler then interleaves each bucket's collective with the
    remaining backward compute instead of a tail-end sync
    (tools/overlap_evidence.py --mode gradsync evidences the schedule).

compression — `compress="int8" | "bf16" | None` rides the EQuARX-style
    block-quantized collective bodies (distributed/collective.py, scale
    per 256-value block; wire <= 0.27x fp32 for int8). Which physical
    form runs depends on the calling context:

    * shard_map traces (`sync_shardmap` / the tag with an explicit
      `axis`): the REAL two-stage quantized collective — int8 on the
      wire, int32 accumulation, documented error bound.
    * GSPMD traces (TrainStep; the tag with `mesh` + `axis`): GSPMD owns
      collective insertion and cannot express per-rank quantization of
      partial sums, so the tag applies the gather-stage fake-quant
      (numerics-faithful within the same error model) plus a per-leaf
      `with_sharding_constraint` to the ZeRO layout, anchoring each
      leaf's reduce-scatter at the bucket's backward position (grads
      rest axis-sharded; the all-gather lands at the consumer). Wire
      compression on this path is MODELED (the telemetry counters price
      it); the physical compressed wire needs the shard_map or
      multi-process eager path.
    * eager multi-process (`on_grad_ready` hooks): the real compressed
      `all_reduce` per flushed bucket over jax.distributed.
    * eager single-controller: grads are already globally reduced;
      fake-quant + ZeRO re-placement, counters still account the model.

Telemetry (all under the observability registry, enabled() gated):
    paddle_tpu_grad_sync_bytes_total              logical grad bytes
    paddle_tpu_grad_sync_compressed_bytes_total   wire bytes after compress
    paddle_tpu_grad_sync_buckets_total            bucket syncs issued
    paddle_tpu_grad_sync_seconds_total            eager flush wall time
plus a `grad_sync:<bucket>` chrome-trace span per eager flush.
"""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ... import observability as _obs
from .. import mesh as mesh_mod
from ..collective import QUANT_BLOCK as _QBLOCK

__all__ = ["GradBucket", "GradBucketScheduler", "partition_buckets",
           "wire_bytes", "DEFAULT_BUCKET_MB"]

# matches the reference DistributedStrategy.fuse_grad_size_in_MB default
DEFAULT_BUCKET_MB = 32


def wire_bytes(nbytes_logical, compress, stages=1, itemsize=4):
    """Wire bytes the compressed payload occupies per reduce stage:
    int8 = 1 byte/value + one fp32 scale per 256-value block (the
    <=0.27x-of-fp32 bound incl. ring traffic); bf16 = 2 bytes/value
    (no saving when the grads are already bf16); None = identity.
    `itemsize` is the LOGICAL gradient dtype's width — the wire cost is
    per VALUE, so bf16 grads compress 2x less than fp32 grads and the
    telemetry must say so. `stages=2` prices a two-stage all-reduce
    (reduce-scatter + all-gather both compressed)."""
    values = nbytes_logical // max(int(itemsize), 1)
    if compress == "bf16":
        return min(nbytes_logical, 2 * values) * stages
    if compress == "int8":
        per_stage = values + 4 * ((values + _QBLOCK - 1) // _QBLOCK)
        return per_stage * stages
    return nbytes_logical * stages


class GradBucket:
    """One sync unit: an ordered list of (name, shape, dtype) plus the
    precomputed byte totals."""

    def __init__(self, index, entries):
        self.index = index
        self.names = [e[0] for e in entries]
        self.shapes = {e[0]: tuple(e[1]) for e in entries}
        self.dtypes = {e[0]: e[2] for e in entries}
        self.nbytes = sum(
            int(np.prod(e[1])) * jnp.dtype(e[2]).itemsize for e in entries)

    def wire(self, compress):
        """Wire bytes for this bucket under `compress`, priced per entry
        at its OWN dtype width (bf16 grads compress 2x less than fp32)."""
        return sum(
            wire_bytes(int(np.prod(self.shapes[n]))
                       * jnp.dtype(self.dtypes[n]).itemsize,
                       compress,
                       itemsize=jnp.dtype(self.dtypes[n]).itemsize)
            for n in self.names)

    def __repr__(self):
        return (f"GradBucket({self.index}, params={len(self.names)}, "
                f"{self.nbytes / 2**20:.2f} MiB)")


def partition_buckets(named_shapes, bucket_mb=DEFAULT_BUCKET_MB):
    """[(name, shape, dtype)] in FORWARD registration order ->
    [GradBucket] in reverse-backward order (the order cotangents
    finalize): the LAST registered parameters land in bucket 0. A bucket
    closes when it reaches ~bucket_mb MiB; a single oversized parameter
    becomes its own bucket (never split — the tag is per-leaf)."""
    limit = float(bucket_mb) * 2**20
    buckets, cur, cur_bytes = [], [], 0.0
    for name, shape, dtype in reversed(list(named_shapes)):
        nb = int(np.prod(shape)) * jnp.dtype(dtype).itemsize
        if cur and cur_bytes + nb > limit:
            buckets.append(GradBucket(len(buckets), cur))
            cur, cur_bytes = [], 0.0
        cur.append((name, shape, dtype))
        cur_bytes += nb
    if cur:
        buckets.append(GradBucket(len(buckets), cur))
    return buckets


def _fake_quant_int8(flat):
    """Gather-stage quantization model: per-block int8
    quantize-dequantize of the (already reduced) flat gradient vector —
    the numerics the compressed wire imposes on the GSPMD / eager
    single-controller paths where GSPMD owns the physical collective.
    Reuses collective.py's quantizer so the model can never drift from
    the real wire numerics the error-bound tests assert."""
    from ..collective import (QUANT_BLOCK, _pad_flat,
                              dequantize_blockwise_int8,
                              quantize_blockwise_int8)
    padded, L = _pad_flat(flat, QUANT_BLOCK)
    q, scale = quantize_blockwise_int8(padded)
    return dequantize_blockwise_int8(q, scale)[:L].astype(flat.dtype)


def _apply_compress_flat(flat, compress):
    if compress == "int8":
        return _fake_quant_int8(flat)
    if compress == "bf16":
        return flat.astype(jnp.bfloat16).astype(flat.dtype)
    return flat


class GradBucketScheduler:
    """Owns the bucket partition and the three sync surfaces (trace tag,
    shard_map explicit collectives, eager hook).

    named_params: list of (name, shape, dtype) in forward registration
        order (or a dict of name -> Tensor/array).
    bucket_mb: MiB per bucket, or "auto" to consult the autotune cache
        (kernels/autotune.py tune_grad_buckets); falls back to
        DEFAULT_BUCKET_MB on a cold cache.
    compress: None | "int8" | "bf16".
    axis: the mesh axis the grad collective rides ("dp"/"sharding").
    """

    def __init__(self, named_params, bucket_mb=DEFAULT_BUCKET_MB,
                 compress=None, axis="dp", mesh=None):
        if isinstance(named_params, dict):
            named_params = [
                (k, tuple(v.shape), jnp.dtype(
                    getattr(getattr(v, "_data", v), "dtype", None)
                    or v.dtype).name)
                for k, v in named_params.items()]
        # only floating leaves sync (integer params/buffers have no
        # gradients; a float0 cotangent would break the tag's reshape)
        self.entries = [e for e in named_params
                        if jnp.issubdtype(jnp.dtype(e[2]), jnp.floating)]
        total = sum(int(np.prod(s)) * jnp.dtype(d).itemsize
                    for _, s, d in self.entries)
        if bucket_mb == "auto":
            from ...kernels.autotune import lookup_grad_buckets
            bucket_mb = lookup_grad_buckets(total, compress) \
                or DEFAULT_BUCKET_MB
        self.bucket_mb = float(bucket_mb)
        self.compress = compress
        self.axis = axis
        self._mesh = mesh
        self.buckets = partition_buckets(self.entries, self.bucket_mb)
        self._bucket_of = {}
        for b in self.buckets:
            for n in b.names:
                self._bucket_of[n] = b
        # per-step byte totals (host-side static; the counters use these
        # so the traced path needs no device sync to account)
        self.bytes_per_step = sum(b.nbytes for b in self.buckets)
        self.wire_bytes_per_step = sum(
            b.wire(compress) for b in self.buckets)
        # eager-hook accounting: per-bucket arrived-name sets + wall time
        self._seen = {}
        self._seen_seconds = {}
        # per-scheduler custom_vjp tag cache: repeated traces of the same
        # TrainStep reuse the identical primitive (stable jit keys), and
        # the tags die with the scheduler instead of accreting in a
        # module-global table
        self._tags = {}

    # -- trace path: custom_vjp bucket tags --------------------------------
    def tag_params(self, pvals):
        """{name: array} -> same structure with each bucket's leaves
        routed through one custom_vjp identity whose backward applies the
        bucket's grad-sync transform at the position where the bucket's
        cotangents finalize. Unknown names (buffers etc.) pass through;
        a trivial sync axis tags nothing."""
        if not self._axis_active():
            return dict(pvals)
        out = dict(pvals)
        for b in self.buckets:
            names = [n for n in b.names if n in pvals]
            if not names:
                continue
            tagged = _bucket_tag(self, b.index)(*[pvals[n] for n in names])
            out.update(zip(names, tagged))
        return out

    def _sync_cotangents(self, cots):
        """The tag's backward rule. Inside shard_map (axis name bound):
        flatten the bucket into ONE vector and run the REAL compressed
        collective body over the axis — int8/bf16 physically on the
        wire, one fused collective per bucket. Under GSPMD: apply the
        compression model per leaf, then constrain each leaf's
        cotangent to the ZeRO axis-sharded layout — a partial-sum value
        constrained sharded makes GSPMD materialize its reduce-scatter
        AT this backward position, with the all-gather deferred to the
        consumer (per-leaf, clean lowering; a flat-vector reshard
        constraint instead lowers to collective-permute chains on
        uneven shards)."""
        in_shard_map = False
        try:
            jax.lax.axis_index(self.axis)  # raises when axis is unbound
            in_shard_map = True
        except Exception:
            pass
        if in_shard_map:
            from ..collective import _body_all_reduce, ReduceOp
            sizes = [int(np.prod(c.shape)) for c in cots]
            # keep a uniform-dtype bucket in its own dtype (no f32
            # blow-up for bf16 grads); mixed buckets flatten through f32
            dts = {c.dtype for c in cots}
            flat_dt = dts.pop() if len(dts) == 1 else jnp.float32
            flat = jnp.concatenate([c.reshape(-1).astype(flat_dt)
                                    for c in cots])
            flat = _body_all_reduce(
                (flat,), (self.axis,),
                (ReduceOp.SUM, self.compress, self._axis_size()))
            outs = []
            off = 0
            for c, sz in zip(cots, sizes):
                outs.append(
                    flat[off:off + sz].reshape(c.shape).astype(c.dtype))
                off += sz
            return tuple(outs)
        mesh = self._mesh or mesh_mod.get_mesh()
        constrain = mesh is not None and mesh.shape.get(self.axis, 1) > 1
        if not constrain:
            # trivial axis: no collective exists — quantizing here would
            # add error (and report phantom wire savings) for nothing
            return tuple(cots)
        outs = []
        for c in cots:
            if self.compress is not None:
                c = _apply_compress_flat(
                    c.reshape(-1), self.compress).reshape(c.shape)
            outs.append(jax.lax.with_sharding_constraint(
                c, self._grad_sharding(mesh, c.shape)))
        return tuple(outs)

    def _grad_sharding(self, mesh, shape):
        """Where a bucket's synced gradient lives under GSPMD: the ZeRO
        layout (first unsharded dim divisible by the axis) so GSPMD
        anchors a reduce-scatter at the tag and defers the all-gather
        to the consumer — grads rest sharded, per the stage-2 contract.
        Leaves with no dividable dim pin replicated (a plain anchored
        all-reduce)."""
        from .meta_parallel.sharding_optimizer import shard_spec_for
        return NamedSharding(mesh, shard_spec_for(shape, self.axis, mesh))

    def sync_grads(self, grads):
        """Apply the per-bucket sync transform to a {name: grad} dict
        OUTSIDE autodiff — the fused-accumulation path: accumulated
        grads only finalize after the microbatch scan, so the sync runs
        ONCE on the final values (tagging inside the scan would
        multiply wire traffic by accum_steps and compound the
        quantization error per microbatch)."""
        if not self._axis_active():
            return dict(grads)
        out = dict(grads)
        for b in self.buckets:
            names = [n for n in b.names if n in grads]
            if not names:
                continue
            synced = self._sync_cotangents([grads[n] for n in names])
            out.update(zip(names, synced))
        return out

    def _axis_size(self):
        mesh = self._mesh or mesh_mod.get_mesh()
        return int(mesh.shape[self.axis]) if mesh is not None else 1

    def _axis_active(self):
        """A size-1 sync axis means no collective exists: the scheduler
        is inert (no fake-quant error, no phantom wire-savings
        telemetry)."""
        mesh = self._mesh or mesh_mod.get_mesh()
        return mesh is not None and mesh.shape.get(self.axis, 1) > 1

    # -- eager hook path (GroupShardedStage2) ------------------------------
    def on_grad_ready(self, name, grad_tensor, place_fn=None):
        """Hook entry: sync + place this grad IMMEDIATELY — the tape
        reads the hook's return value the moment the hook returns
        (framework/autograd._apply_hooks extracts ._data), so a deferred
        bucket flush would silently drop its mutations for every param
        but the bucket's last. The bucket is therefore the
        TELEMETRY/span boundary on this eager surface (counters fire
        when a bucket's last grad arrives; partial buckets — frozen or
        conditionally-unused params — never block their bucket-mates'
        sync); the traced surfaces (custom_vjp tags) are where buckets
        batch the physical collective."""
        from ...observability.tracing import span as trace_span
        from ..collective import _per_rank_mode
        if not self._axis_active():
            if place_fn is not None:
                place_fn(name, grad_tensor)
            return
        b = self._bucket_of.get(name)
        span = f"grad_sync:bucket{b.index}" if b is not None \
            else "grad_sync:unbucketed"
        t0 = time.perf_counter()
        with trace_span(span, param=name):
            grad = grad_tensor
            data = grad._data if hasattr(grad, "_data") else grad
            traced = isinstance(data, jax.core.Tracer)
            if not traced and _per_rank_mode():
                # true multi-process eager: the local grads NEED the
                # cross-process reduce — run the real (compressed)
                # wire collective, averaging per the dp contract
                from ..collective import all_reduce, ReduceOp
                data = all_reduce(data, op=ReduceOp.AVG,
                                  compress=self.compress)
                if hasattr(grad, "_data"):
                    grad._data = data
            elif self.compress is not None and not traced and \
                    jnp.issubdtype(data.dtype, jnp.floating):
                # single-controller: grads are already globally
                # reduced; apply the gather-stage quantization model
                data = _apply_compress_flat(
                    data.reshape(-1), self.compress).reshape(data.shape)
                if hasattr(grad, "_data"):
                    grad._data = data
            if place_fn is not None:
                place_fn(name, grad)
        if b is None:
            return
        seen = self._seen.setdefault(b.index, set())
        seen.add(name)
        self._seen_seconds[b.index] = \
            self._seen_seconds.get(b.index, 0.0) + time.perf_counter() - t0
        if seen == set(b.names):
            self._note_flush(b, self._seen_seconds.pop(b.index, 0.0))
            self._seen.pop(b.index, None)

    # -- telemetry ---------------------------------------------------------
    def _note_flush(self, b, seconds):
        if not _obs.enabled():
            return
        reg = _obs.registry()
        reg.counter("paddle_tpu_grad_sync_buckets_total",
                    "Gradient-sync bucket flushes").inc()
        reg.counter("paddle_tpu_grad_sync_bytes_total",
                    "Logical (uncompressed) gradient bytes synced").inc(
                        b.nbytes)
        reg.counter("paddle_tpu_grad_sync_compressed_bytes_total",
                    "Wire bytes after compression (incl. scales)").inc(
                        b.wire(self.compress))
        reg.counter("paddle_tpu_grad_sync_seconds_total",
                    "Wall time inside eager grad-sync flushes").inc(seconds)

    def record_step(self, repeats=1):
        """Account one traced step's grad sync (the collectives live
        inside the fused executable; the partition is host-side static,
        so the byte totals need no device sync). `repeats` = syncs per
        executed step — 1 for TrainStep (the accumulation path syncs the
        accumulated grads once after the scan)."""
        if not _obs.enabled() or not self._axis_active():
            return
        reg = _obs.registry()
        reg.counter("paddle_tpu_grad_sync_buckets_total",
                    "Gradient-sync bucket flushes").inc(
                        repeats * len(self.buckets))
        reg.counter("paddle_tpu_grad_sync_bytes_total",
                    "Logical (uncompressed) gradient bytes synced").inc(
                        repeats * self.bytes_per_step)
        reg.counter("paddle_tpu_grad_sync_compressed_bytes_total",
                    "Wire bytes after compression (incl. scales)").inc(
                        repeats * self.wire_bytes_per_step)
        reg.counter("paddle_tpu_grad_sync_seconds_total",
                    "Wall time inside eager grad-sync flushes")


def tagged_mlp_step(sched, layer_names, mesh, lr=0.01):
    """jit(shard_map) SGD step over a tanh MLP whose params route
    through `sched`'s bucket tags — the ONE synthetic harness both
    kernels/autotune.tune_grad_buckets (timing) and
    tools/overlap_evidence --mode gradsync (schedule analysis) compile,
    so the autotuner times exactly the lowering the evidence tool
    measures. Takes ({name: [h,h] array}, x sharded over sched.axis)."""
    from jax import shard_map

    def step(ws, xs):
        def loss(ws):
            tagged = sched.tag_params(ws)
            y = xs
            for name in layer_names:
                y = jnp.tanh(y @ tagged[name])
            return jnp.mean(y ** 2)

        g = jax.grad(loss)(ws)
        return {k: ws[k] - lr * g[k] for k in ws}

    return jax.jit(shard_map(step, mesh=mesh,
                             in_specs=(P(), P(sched.axis)),
                             out_specs=P(), check_vma=False))


def _bucket_tag(sched, bucket_index):
    """One custom_vjp identity per (scheduler, bucket), cached ON the
    scheduler (sched._tags) so repeated traces of the same TrainStep
    reuse the identical primitive (stable jit keys) while the tags —
    whose bwd closures pin the scheduler — die with it instead of
    accreting in a module-global table across TrainStep builds,
    autotune candidates and A/B runs."""
    tag = sched._tags.get(bucket_index)
    if tag is not None:
        return tag

    @jax.custom_vjp
    def tag(*leaves):
        return leaves

    def fwd(*leaves):
        return leaves, None

    def bwd(_, cots):
        return sched._sync_cotangents(list(cots))

    tag.defvjp(fwd, bwd)
    sched._tags[bucket_index] = tag
    return tag
