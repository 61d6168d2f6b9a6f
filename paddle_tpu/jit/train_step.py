"""TrainStep: fuse forward + backward + optimizer into ONE XLA executable.

This is the TPU-native answer to the reference's whole-graph static training
(dy2static + StandaloneExecutor + CINN fusion, SURVEY.md §3.4/§3.5): the
dygraph model, loss, and optimizer run once under jax tracing — parameters,
buffers, optimizer accumulators, lr, step index, and an RNG key all enter as
traced inputs — producing a single fused, donated-buffer executable per
input shape. Eager semantics are preserved because the very same Layer /
functional / optimizer code executes inside the trace.

Usage:
    step = paddle_tpu.jit.TrainStep(model, loss_fn, opt)
    loss = step(images, labels)        # one device dispatch per iteration
"""
from __future__ import annotations

import collections
import json
import logging
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.tensor import Tensor
from ..framework import autograd, random as random_mod
from .. import observability as _obs
from ..observability.programs import profile_program
from .trace import trace_scope

__all__ = ["TrainStep"]

_LOG = logging.getLogger("paddle_tpu.observability")


def _tree_to_arrays(obj):
    return jax.tree_util.tree_map(
        lambda t: t._data if isinstance(t, Tensor) else t, obj,
        is_leaf=lambda t: isinstance(t, Tensor))


class TrainStep:
    def __init__(self, model, loss_fn, optimizer, accum_steps=1,
                 accum_mean=True, master_grad=False, with_outputs=False,
                 grad_sync=None, plan=None):
        self.model = model
        self.loss_fn = loss_fn
        # auto-parallel Plan consumption (r17): a planner-emitted Plan
        # (auto_tuner.Plan) supplies the grad-sync configuration the
        # hand-set DistributedStrategy fields used to — an explicit
        # grad_sync/optimizer-carried config still wins (hand-set
        # values stay as overrides). The plan also rides on self._plan
        # so telemetry and tools can report which plan priced this step.
        self._plan = plan or getattr(
            getattr(optimizer, "_strategy", None), "_plan", None)
        # gradient accumulation INSIDE the fused executable: the traced step
        # scans accum_steps microbatches, averages grads (accum_mean=False
        # SUMS them — the gradient-merge avg=False contract), applies the
        # optimizer once (reference: passes/auto_parallel_gradient_merge.py
        # + pipeline micro-batch accumulation, pipeline_parallel.py:693)
        self.accum_steps = int(accum_steps)
        self.accum_mean = bool(accum_mean)
        # master_grad (reference passes/auto_parallel_master_grad.py):
        # grads are cast to and accumulated in fp32 INSIDE the fused step
        # — the eager-tape grad hooks amp.decorate installs cannot fire in
        # the functional value_and_grad path, so this is the fused-step
        # surface of the same knob
        self.master_grad = bool(master_grad)
        if self.accum_steps < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
        # unwrap delegating facades (fleet's HybridParallelOptimizer):
        # TrainStep must read AND write optimizer state on the same
        # object — a wrapper whose __getattr__ delegates reads while
        # attribute writes land on the wrapper would leak traced
        # accumulators out of step 1's trace into step 2's arguments.
        # A GradientMergeOptimizer wrapper is ADOPTED instead of called:
        # its k-step merge IS the fused step's accumulation (tracing its
        # python-side deferral counter would bake one branch forever).
        from ..incubate.optimizer import GradientMergeOptimizer
        # grad-sync config can ride on ANY wrapper layer (fleet's facade
        # for plain dp, the sharding wrapper for ZeRO) — collect it
        # before the layer is unwrapped away
        gs_cfg = None
        while True:
            gs_cfg = gs_cfg or getattr(optimizer, "_grad_sync_config", None)
            if hasattr(type(optimizer), "__getattr__") and \
                    hasattr(optimizer, "_inner_opt"):
                optimizer = optimizer._inner_opt
            elif isinstance(optimizer, GradientMergeOptimizer):
                # NOTE: adoption changes the batch contract vs the eager
                # wrapper (merge across k successive step() calls, one
                # update per k): here each TrainStep call must feed the
                # FULL k-step global batch, which is split into k
                # microbatches and updated once per call. Warn so callers
                # feeding per-call micro-batches notice the k x smaller
                # effective batch per update.
                import warnings
                warnings.warn(
                    "TrainStep adopted a GradientMergeOptimizer: each "
                    f"call now splits ONE input batch into {optimizer.k_steps} "
                    "microbatches and applies the optimizer every call. "
                    "Feed the full k-step global batch per call (not "
                    "per-call micro-batches).", stacklevel=3)
                self.accum_steps *= optimizer.k_steps
                self.accum_mean = self.accum_mean and optimizer.avg
                optimizer = optimizer.inner_optimizer
            else:
                break
        self.opt = optimizer
        # when True, the fused executable also returns the forward outputs
        # (for metrics) so callers don't need a second forward pass
        self.with_outputs = with_outputs
        self.last_outputs = None
        self._params = dict(model.named_parameters())
        self._buffers = {k: b for k, b in model.named_buffers()
                         if isinstance(b, Tensor)}
        self._pname_of_id = {id(p): k for k, p in self._params.items()}
        # device-side counters of a step: a model names the buffers its
        # forward writes them to and what each entry counts
        # (`step_counters = {buffer: (field, ...)}`). The step returns
        # them once more beside the buffers it threads (those are donated
        # to the next step); the host reads a step's counters at a later
        # call, once they are ready, never by waiting
        # (`_settled_counters`), into `last_counters` and onto
        # `train_step:call`
        self._counter_fields = {
            k: tuple(v) for k, v in
            (getattr(model, "step_counters", None) or {}).items()
            if k in self._buffers}
        self._counters_pending = collections.deque()
        self.last_counters = None
        # compressed/bucketed gradient sync (fleet/grad_buckets.py):
        # either an explicit scheduler, or built here from the config a
        # fleet wrapper carried, against THIS step's param-name space.
        # The bucket tags are applied where params enter the traced loss,
        # so each bucket's collective anchors at the backward position
        # where its grads finalize (T3 overlap); compress selects the
        # EQuARX quantization model (collective.py docstring).
        if gs_cfg is None and self._plan is not None and \
                getattr(self._plan, "grad_compress", None) and \
                self._plan.dp * getattr(self._plan, "sharding", 1) > 1:
            # the plan's grad-sync choice, lowest precedence: any
            # optimizer/strategy-carried config above already filled
            # gs_cfg and wins
            gs_cfg = {"compress": self._plan.grad_compress,
                      "bucket_mb": getattr(self._plan, "grad_bucket_mb",
                                           None),
                      "axis": "dp"}
        self._grad_sync = grad_sync
        if self._grad_sync is None and gs_cfg is not None:
            from ..distributed.fleet.grad_buckets import (
                GradBucketScheduler, DEFAULT_BUCKET_MB)
            entries = [(k, tuple(p.shape),
                        jnp.dtype(p._data.dtype).name)
                       for k, p in self._params.items()]
            self._grad_sync = GradBucketScheduler(
                entries,
                bucket_mb=gs_cfg.get("bucket_mb") or DEFAULT_BUCKET_MB,
                compress=gs_cfg.get("compress"),
                axis=gs_cfg.get("axis", "dp"))
        # optional {param_name: NamedSharding}: pins the UPDATED params to
        # their input placement. Without it, XLA's sharding propagation is
        # free to re-layout the optimizer update — on real hybrid meshes
        # it chooses ZeRO-style dp streaming (reduce-scatter grads, update
        # a shard, all-gather params INSIDE the pipeline loop), trading
        # large re-gather traffic for memory (observed on the v5e-256
        # topology, tools/overlap_evidence.py). Set via pin_param_shardings
        # to keep placements stable step-over-step.
        self._param_out_shardings = None
        # train_mode is static so train()/eval() toggles select different
        # executables instead of silently reusing the first-traced one
        self._jitted = jax.jit(self._traced, donate_argnums=(1, 2, 3),
                               static_argnums=(0,))
        # abstract-shape signatures this step has compiled for. Tracked
        # even with telemetry off (a set lookup per call) so the retrace
        # counter/warning never misses the first storm. The recompile
        # counter keys on SHAPES (train_mode + input/label abstract
        # shapes): the accums-materialize retrace on step 2 is expected
        # exactly once and is not a shape instability.
        self._shape_sigs = set()
        self.recompile_count = 0
        # tokens per __call__ for tokens/s; derived from the first
        # input's leading dims unless the caller sets it explicitly
        self.tokens_per_call = None
        # telemetry's analysis records (observability/programs.py), one a
        # signature (shape key + accumulator structure): FLOPs, modeled
        # exposed-collective seconds (the SAME hlo_analysis pricing
        # tools/overlap_evidence.py --mode gradsync/mp gate on), HBM
        # ledger, roofline record; bench.py reads the *_summary() views
        self._analysed = {}
        # goodput attribution (observability/attribution.py): built
        # lazily on the first telemetry-enabled call; classifies every
        # step's wall into {data_wait, compile, dispatch, execute,
        # grad_sync_exposed, checkpoint, other} and emits the ledger to
        # the JSONL sink
        self._ledger = None
        # how the last analysis compile was satisfied ("hit"/"miss"/
        # "off"): the persistent compile cache's per-step surface
        self.compile_cache_last = None

    # -- helpers -----------------------------------------------------------
    def _accums_to_named(self):
        out = {}
        for (accname, pid), arr in self.opt._accumulators.items():
            pname = self._pname_of_id.get(pid)
            if pname is not None:
                out[f"{pname}::{accname}"] = arr
        return out

    def _install_accums(self, named):
        name_to_param = self._params
        store = {}
        for key, arr in named.items():
            pname, accname = key.split("::", 1)
            store[(accname, id(name_to_param[pname]))] = arr
        self.opt._accumulators = store

    # -- the traced step ---------------------------------------------------
    def _traced(self, train_mode, params, buffers, accums, lr, step_idx, key,
                inputs, labels):
        random_mod.push_traced_key(key)
        saved_p = {k: p._data for k, p in self._params.items()}
        saved_b = {k: b._data for k, b in self._buffers.items()}
        saved_acc = self.opt._accumulators
        saved_training = self.model.training
        if train_mode:
            self.model.train()
        else:
            self.model.eval()
        try:
            def loss_of(pvals, bufvals, mb_inputs, mb_labels):
                if self._grad_sync is not None and self.accum_steps == 1:
                    # bucket tags: identity forward; backward anchors
                    # each bucket's (compressed) grad collective where
                    # its cotangents finalize. Accumulating steps sync
                    # AFTER the scan instead — per-microbatch tags would
                    # multiply wire traffic by accum_steps and compound
                    # the quantization error
                    pvals = self._grad_sync.tag_params(pvals)
                for k, p in self._params.items():
                    p._data = pvals[k]
                for k, b in self._buffers.items():
                    b._data = bufvals[k]
                with trace_scope():
                    t_in = jax.tree_util.tree_map(
                        lambda a: Tensor(a, stop_gradient=True),
                        list(mb_inputs))
                    t_lab = jax.tree_util.tree_map(
                        lambda a: Tensor(a, stop_gradient=True),
                        list(mb_labels))
                    with autograd.no_grad():
                        out = self.model(*t_in)
                        loss = self.loss_fn(out, *t_lab)
                new_buf = {k: b._data for k, b in self._buffers.items()}
                out_arrays = _tree_to_arrays(out) if self.with_outputs \
                    else None
                return loss._data.astype(jnp.float32), (new_buf, out_arrays)

            def gcast(g):
                # master_grad: fp32 gradient storage/accumulation for
                # low-precision params (no-op on fp32 grads)
                if self.master_grad and jnp.issubdtype(g.dtype,
                                                       jnp.floating):
                    return g.astype(jnp.float32)
                return g

            if self.accum_steps == 1:
                (loss, (new_buffers, outs)), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(params, buffers, inputs, labels)
                if self.master_grad:
                    grads = jax.tree_util.tree_map(gcast, grads)
            else:
                n = self.accum_steps

                def split(a):
                    if a.shape[0] % n != 0:
                        raise ValueError(
                            f"accum_steps {n} must divide the leading "
                            f"batch dim, got shape {a.shape}")
                    return a.reshape((n, a.shape[0] // n) + a.shape[1:])

                mb_in = jax.tree_util.tree_map(split, list(inputs))
                mb_lab = jax.tree_util.tree_map(split, list(labels))
                def zero_like(p):
                    dt = jnp.float32 if (
                        self.master_grad and jnp.issubdtype(
                            p.dtype, jnp.floating)) else p.dtype
                    return jnp.zeros(p.shape, dt)

                gzero = jax.tree_util.tree_map(zero_like, params)

                def micro(carry, xs):
                    bufs, gsum, lsum = carry
                    mi, ml = xs
                    (l, (nb, o)), g = jax.value_and_grad(
                        loss_of, has_aux=True)(params, bufs, mi, ml)
                    gsum = jax.tree_util.tree_map(
                        lambda a, b: jnp.add(a, gcast(b)), gsum, g)
                    return (nb, gsum, lsum + l), o

                (new_buffers, gsum, lsum), outs = jax.lax.scan(
                    micro, (buffers, gzero, jnp.float32(0.0)),
                    (mb_in, mb_lab))
                loss = lsum / n
                grads = jax.tree_util.tree_map(lambda g: g / n, gsum) \
                    if self.accum_mean else gsum
                if self._grad_sync is not None:
                    # one sync of the ACCUMULATED grads (see loss_of)
                    grads = self._grad_sync.sync_grads(grads)
                if self.with_outputs:
                    # [n, mb, ...] microbatch outputs -> full-batch layout
                    outs = jax.tree_util.tree_map(
                        lambda a: a.reshape((-1,) + a.shape[2:]), outs)

            # optimizer pass: same stateful code, shadowed by traced state
            for k, p in self._params.items():
                p._data = params[k]
                p.grad = Tensor(grads[k], stop_gradient=True)
            self._install_accums(accums)
            self.opt._lr_override = lr
            self.opt._step_override = step_idx
            count_before = self.opt._step_count
            try:
                self.opt.step()
                new_params = {k: p._data for k, p in self._params.items()}
                new_accums = self._accums_to_named()
            finally:
                self.opt._lr_override = None
                self.opt._step_override = None
                # undo the python-side counter advance from the traced step
                self.opt._step_count = count_before
            if self._param_out_shardings:
                new_params = {
                    k: (jax.lax.with_sharding_constraint(
                        v, self._param_out_shardings[k])
                        if k in self._param_out_shardings else v)
                    for k, v in new_params.items()}
            counters = {k: new_buffers[k] for k in self._counter_fields}
            return loss, new_params, new_buffers, new_accums, outs, counters
        finally:
            random_mod.pop_traced_key()
            for k, p in self._params.items():
                p._data = saved_p[k]
                p.grad = None
            for k, b in self._buffers.items():
                b._data = saved_b[k]
            self.opt._accumulators = saved_acc
            self.model.training = saved_training

    # -- public ------------------------------------------------------------
    def pin_param_shardings(self, mesh=None):
        """Pin every updated parameter's output sharding to its intended
        placement: the device_put_sharded record, else the live array's
        NamedSharding spec, else replicated (hybrid-parallel params not
        explicitly placed ARE replicated). XLA then keeps parameter
        layouts stable across steps instead of re-streaming them (see
        _param_out_shardings). Rebuilds the jit so pinning takes effect
        even after the step has already been traced."""
        import jax.sharding as jshard
        from jax.sharding import NamedSharding, PartitionSpec
        from ..distributed import mesh as mesh_mod
        from ..distributed.shard_util import recorded_spec
        mesh = mesh or mesh_mod.get_mesh()
        pinned = {}
        for k, p in self._params.items():
            spec = recorded_spec(p)
            if spec is None and not isinstance(p._data, jax.core.Tracer) \
                    and isinstance(getattr(p._data, "sharding", None),
                                   jshard.NamedSharding):
                spec = p._data.sharding.spec
            pinned[k] = NamedSharding(mesh, spec if spec is not None
                                      else PartitionSpec())
        self._param_out_shardings = pinned
        # the jit cache does not key on the pin map — rebuild so the next
        # call retraces with the constraints applied
        self._jitted = jax.jit(self._traced, donate_argnums=(1, 2, 3),
                               static_argnums=(0,))
        self._shape_sigs.clear()
        self._analysed.clear()
        return self

    # -- telemetry ---------------------------------------------------------
    def attribution_summary(self):
        """Aggregate goodput-ledger totals across telemetry-enabled steps
        (None before the first one) — bench.py's artifact surface."""
        return None if self._ledger is None else self._ledger.summary()

    def _records(self, layer):
        """{label: that layer's record} over the analysed signatures."""
        return {rec["label"]: rec[layer]
                for rec in self._analysed.values()
                if rec is not None and rec[layer] is not None}

    def analysed_executables(self):
        """{label: the executable telemetry compiled to analyse that
        signature} — for tools that lint the compiled HLO (shardings).
        Empty before the first telemetry-enabled call."""
        return self._records("executable")

    def memory_summary(self):
        """Per-executable HBM ledgers recorded on a signature's first
        telemetry-enabled call (None before it): {executable label:
        {peak_bytes, temp_bytes, argument_bytes, output_bytes,
        peak_live_bytes}} plus the max peak — bench.py's
        peak_hbm_bytes artifact surface, gated by tools/bench_smoke.py."""
        per = {}
        for label, led in self._records("hbm").items():
            live = led.get("live") or {}
            b = led["buckets"]
            per[label] = {
                "peak_bytes": led["peak_bytes"],
                "temp_bytes": b["temp"],
                "argument_bytes": b["argument"],
                "output_bytes": b["output"],
                "peak_live_bytes": live.get("peak_live_bytes"),
            }
        if not per:
            return None
        return {"executables": per,
                "max_peak_bytes": max(v["peak_bytes"]
                                      for v in per.values())}

    def roofline_summary(self):
        """Per-executable roofline records captured on a signature's
        first telemetry-enabled call (None before it): modeled step
        wall, modeled MFU, bound-class fractions, the per-scope MFU-gap
        waterfall, and the top ops by gap seconds — bench.py's roofline
        artifact surface, telescoping-gated by tools/bench_smoke.py and
        tools/roofline_report.py."""
        per = {}
        for label, rec in self._records("roofline").items():
            per[label] = {
                "total_modeled_s": rec["total_modeled_s"],
                "ideal_compute_s": rec["ideal_compute_s"],
                "modeled_mfu": rec["modeled_mfu"],
                "mfu_gap_s": rec["mfu_gap_s"],
                "class_time_frac": rec["class_time_frac"],
                "hbm_bound_flops_frac": rec["hbm_bound_flops_frac"],
                "flops_drift_frac": rec.get("flops_drift_frac"),
                "by_scope": {s: {"seconds": v["seconds"],
                                 "gap_s": v["gap_s"],
                                 "bound": v["bound"]}
                             for s, v in rec["by_scope"].items()},
                "top_ops": [{k: o[k] for k in ("name", "op", "scope",
                                               "class", "seconds",
                                               "gap_s")}
                            for o in rec["top_ops"][:5]],
            }
        return {"executables": per} if per else None

    def _shape_key(self, train_mode, in_arrays, lab_arrays):
        """Cheap abstract-shape signature of what can legitimately vary
        call-over-call: train mode + input/label shapes/dtypes. Built on
        EVERY call (telemetry on or off) so the retrace counter never
        misses a storm — keep it a few microseconds: no str(), no accums
        (params/buffers/accums are owned by this step and only change on
        the expected once-per-run accumulator materialization)."""
        leaves = jax.tree_util.tree_leaves([in_arrays, lab_arrays])
        return (train_mode,
                tuple((a.shape, a.dtype) for a in leaves))

    def _note_shape_key(self, key):
        if key in self._shape_sigs:
            return
        self._shape_sigs.add(key)
        if len(self._shape_sigs) == 1:
            return                        # first compile, not a retrace
        self.recompile_count += 1
        if _obs.enabled():
            # inc() at the transition (not set_total of the per-instance
            # count): several live TrainSteps accumulate into one
            # monotone family
            _obs.registry().counter(
                "paddle_tpu_train_step_recompiles_total",
                "TrainStep retraces caused by new abstract input "
                "signatures").inc()
        payload = {"event": "train_step_recompile",
                   "recompiles": self.recompile_count,
                   "signatures_seen": len(self._shape_sigs),
                   "train_mode": bool(key[0]),
                   "input_shapes": [list(s) for s, _ in key[1]]}
        _LOG.warning("%s", json.dumps(payload))
        warnings.warn(_obs.RecompileWarning(
            f"TrainStep retrace #{self.recompile_count}: abstract input "
            f"signature changed to {payload['input_shapes']} "
            f"({len(self._shape_sigs)} signatures seen). Repeated "
            "retraces mean unstable input shapes — pad or bucket "
            "inputs."), stacklevel=4)

    def _observe(self, rec, t0_ns, compiled0, compiled1, inputs):
        """Telemetry's record of the step whose program was called at
        `t0_ns` and has just been synced. `rec`: the signature's analysis
        record; `compiled0/1`: the compile listener's total before that
        analysis and before the call (what the call compiled is not its
        execution). Returns the step ledger's (compile, execute, modeled
        exposed) seconds."""
        t1_ns = time.perf_counter_ns()
        _obs.tracing.record_span("train_step:execute", t0_ns, t1_ns)
        now = _obs.tracing.compile_seconds()
        compile_dt = now - compiled0
        dt = max((t1_ns - t0_ns) * 1e-9 - (now - compiled1), 0.0)
        reg = _obs.registry()
        phases = reg.histogram("paddle_tpu_train_step_duration_seconds",
                               "TrainStep wall time by phase", ("phase",))
        if compile_dt > 0:
            phases.observe(compile_dt, phase="compile")
            reg.histogram("paddle_tpu_train_step_compile_seconds",
                          "TrainStep backend compile time (trace and "
                          "lowering are the step ledger's `dispatch`)"
                          ).observe(compile_dt)
        phases.observe(dt, phase="execute")
        if rec is not None:
            self.compile_cache_last = rec["cache"]
            reg.gauge("paddle_tpu_train_step_flops_per_step",
                      "Compiled-executable FLOPs per step "
                      "(cost_analysis)").set(rec["flops"])
        # register the family even before the first retrace (incremented
        # at the transition in _note_shape_key)
        reg.counter("paddle_tpu_train_step_recompiles_total",
                    "TrainStep retraces caused by new abstract input "
                    "signatures")
        tokens = self.tokens_per_call
        if tokens is None:
            ins = jax.tree_util.tree_leaves(inputs)
            if ins:
                shape = ins[0].shape
                # integer inputs are token ids [batch, seq]; float inputs
                # are features [batch, ...] and count one "token" per row
                if len(shape) >= 2 and jnp.issubdtype(ins[0].dtype,
                                                      jnp.integer):
                    tokens = int(shape[0] * shape[1])
                else:
                    tokens = int(shape[0]) if shape else 1
            else:
                tokens = 1
        tps = tokens / dt if dt > 0 else 0.0
        reg.counter("paddle_tpu_train_step_tokens_total",
                    "Tokens processed by TrainStep").inc(tokens)
        reg.gauge("paddle_tpu_train_step_tokens_per_second",
                  "Last-step TrainStep throughput").set(tps)
        _obs.log_step({"event": "train_step",
                       "step": int(self.opt._step_count),
                       "wall_s": dt, "tokens_per_s": tps,
                       "recompiles": self.recompile_count})
        return compile_dt, dt, rec["exposed_s"] if rec else 0.0

    def __call__(self, inputs, labels=()):
        """One fused step: loss = loss_fn(model(*inputs), *labels).
        `inputs`/`labels` may be a single Tensor or a tuple/list of them."""
        with _obs.span("train_step:call", step=int(self.opt._step_count),
                       **self._settled_counters()):
            return self._call(inputs, labels)

    def _settled_counters(self):
        """The newest counters a finished step left (`last_counters`:
        the fields of `model.step_counters` and `counters_step`, the step
        that counted them), read from the device only once they are
        there: a step still in flight keeps its own for a later call."""
        pending = self._counters_pending
        while pending and all(a.is_ready() for a in pending[0][1].values()):
            step, arrays = pending.popleft()
            self.last_counters = {"counters_step": step}
            for name, fields in self._counter_fields.items():
                self.last_counters.update(
                    zip(fields, np.asarray(arrays[name]).tolist()))
        return self.last_counters or {}

    def _call(self, inputs, labels):
        if isinstance(inputs, Tensor):
            inputs = (inputs,)
        if isinstance(labels, Tensor):
            labels = (labels,)
        telemetry = _obs.enabled()
        t_call0 = time.perf_counter() if telemetry else 0.0
        params = {k: p._data for k, p in self._params.items()}
        buffers = {k: b._data for k, b in self._buffers.items()}
        accums = self._accums_to_named()
        lr = jnp.asarray(self.opt.get_lr(), jnp.float32)
        step_idx = jnp.asarray(self.opt._step_count, jnp.int32)
        key = random_mod.next_key()
        in_arrays = _tree_to_arrays(list(inputs))
        lab_arrays = _tree_to_arrays(list(labels))
        shape_key = self._shape_key(self.model.training, in_arrays,
                                    lab_arrays)
        self._note_shape_key(shape_key)
        args = (self.model.training, params, buffers, accums, lr, step_idx,
                key, in_arrays, lab_arrays)
        if telemetry:
            # an analysis record a signature: the shapes and the optimizer
            # accumulators' structure (it changes once, when they
            # materialize after the first step)
            sig = (shape_key, tuple(sorted(accums)))
            compiled0 = _obs.tracing.compile_seconds()
            rec = profile_program(
                self._analysed, sig, "train_step",
                lambda: _obs.memory_profile.sig_label(sig),
                self._jitted, args)
            compiled1 = _obs.tracing.compile_seconds()
            t0_ns = time.perf_counter_ns()
        loss, new_params, new_buffers, new_accums, outs, counters = \
            self._jitted(*args)
        if counters:
            self._counters_pending.append(
                (int(self.opt._step_count), counters))
        if telemetry:
            # the sync that makes `execute` the step's device time
            jax.block_until_ready(loss)
            phases = self._observe(rec, t0_ns, compiled0, compiled1,
                                   in_arrays)
        with autograd.no_grad():
            for k, p in self._params.items():
                p._data = new_params[k]
            for k, b in self._buffers.items():
                b._data = new_buffers[k]
        self._install_accums(new_accums)
        if self.with_outputs:
            self.last_outputs = jax.tree_util.tree_map(
                lambda a: Tensor(a, stop_gradient=True), outs)
        if self._grad_sync is not None:
            # host-side static accounting (bucket partition is known);
            # one call per executed step, no device sync — the accum
            # path syncs the accumulated grads once, so no multiplier
            self._grad_sync.record_step()
        # the caller steps any LR scheduler per the paddle convention
        self.opt._step_count += 1
        if telemetry:
            # goodput ledger: classify THIS step's wall (gap since the
            # previous step + this call) and emit the attribution record
            if self._ledger is None:
                from ..observability.attribution import StepLedger
                self._ledger = StepLedger("train_step")
            compile_s, execute_s, exposed_s = phases
            self._ledger.step(
                t_call0, time.perf_counter(), compile_s=compile_s,
                execute_s=execute_s, modeled_exposed_s=exposed_s,
                step_index=self.opt._step_count)
        return Tensor(loss, stop_gradient=True)


def train_step(model, loss_fn, optimizer):
    return TrainStep(model, loss_fn, optimizer)
