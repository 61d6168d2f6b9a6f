"""Runtime telemetry: a near-zero-overhead-when-disabled metrics registry
wired through the whole stack.

Families (all Prometheus-scrapable via `scrape()`, JSON via `dump()`):

- step:       paddle_tpu_train_step_duration_seconds{phase},
              _compile_seconds, _recompiles_total, _tokens_total,
              _tokens_per_second, _flops_per_step
              (jit/train_step.py)
- memory:     paddle_tpu_device_bytes_in_use/_peak_bytes_in_use/_bytes_limit,
              paddle_tpu_memory_guard_checks_total,
              paddle_tpu_memory_headroom_violations_total
              (framework/memory.py HeadroomGuard + PJRT stats collector)
- collective: paddle_tpu_collective_calls_total{op}, _bytes_total{op},
              _seconds_total{op}, _bus_bandwidth_bytes_per_second{op},
              _traced_lowerings_total{op}, _tasks_in_flight, _stuck_total
              (distributed/collective.py + comm_watchdog.py; eager calls
              also emit profiler.RecordEvent spans into chrome traces)
- autotune:   paddle_tpu_autotune_cache_{hits,misses,evictions}_total, _size
- serving:    paddle_tpu_paged_pool_blocks_{in_use,free}, _peak_blocks,
              paddle_tpu_paged_admission_deferrals_total,
              paddle_tpu_ragged_attn_{calls,blocks_attended,
              blocks_skipped,hbm_bytes,dense_hbm_bytes}_total
              (kernels/pallas/ragged_paged_attention.py: the fused
              ragged kernel's launches, early-exit block skips, and KV
              HBM traffic vs the dense-gather bill)

Six layers (README "Observability" for the operator view):

- **metrics** (registry.py): the families above — how much.
- **traces** (tracing.py): rank/pid/tid-tagged spans in a ring buffer,
  exported as merged multi-process Perfetto/chrome-trace JSON — where.
- **attribution** (attribution.py): every TrainStep / serve() step's
  wall time classified into the goodput ledger {data_wait, compile,
  dispatch, execute, grad_sync_exposed, checkpoint, other}, emitted to
  the JSONL sink and reported by tools/step_attribution.py — why.
- **memory** (memory_profile.py): per-compiled-executable HBM ledger —
  PJRT memory_analysis buckets + the scheduled module's peak-live
  timeline with named-scope layer attribution, gauges
  paddle_tpu_hbm_{args,temps,outputs,peak}_bytes, fingerprinted and
  budget-gated by tools/memory_report.py — where the HBM goes.
- **roofline** (roofline.py): per-executable op-level roofline pricing
  against cost_model's chip rates — compute/HBM/ICI/host bound classes,
  the per-scope MFU-gap waterfall that sums to the modeled step wall,
  gauges paddle_tpu_roofline_{hbm_bound_flops_frac,modeled_mfu,
  modeled_step_seconds,mfu_gap_seconds}, drift-gated against the
  planner's cost model by tools/roofline_report.py — which OPS eat
  the MFU.
- **requests** (requests.py): the per-request serving lifecycle ledger
  threaded through PagedDecoder.serve() — TTFT/TPOT/queue-wait with
  sliding-window p50/p99 Quantile series
  (paddle_tpu_request_{ttft,tpot,queue_wait,wall}_seconds), retire
  causes, the sums-to-wall request buckets {queue_wait, prefill,
  decode, overhead}, per-request Perfetto tracks, and the in-flight
  request table flight dumps carry — what each USER experienced.

Plus the ops surfaces: cross-rank straggler flags (attribution.
publish_step_digest, k*MAD over per-step digests), the crash flight
recorder (flight_recorder.py — SIGTERM/watchdog/HeadroomGuard black
box), and a live Prometheus endpoint (exporter.py, FLAGS_telemetry_port).

Enable with `paddle_tpu.observability.enable()` or FLAGS_enable_telemetry=1;
per-step JSONL via `set_jsonl_path(path)`.

Spans need neither: start a JAX profiler trace (`jax.profiler.start_trace`
.. `stop_trace`) and every `span()` of the serve loop, the train step and
the collectives lies in the `.xplane.pb`'s host plane beside the device's
ops, on one clock, and in the ring (`tracing.tail()`); or arm the ring
alone with `tracing.enable_tracing()` / FLAGS_enable_tracing=1 and export
it with `tracing.export_chrome(path)`. `tracing.recording()` says whether
a span opened now would be recorded; with nothing recording a span is a
shared null object. Neither way changes the program that runs.
"""
from .registry import (  # noqa: F401
    Counter, Gauge, Histogram, Quantile, MetricsRegistry,
    RecompileWarning,
    registry, enabled, enable, disable, scrape, dump, reset,
    log_step, set_jsonl_path, close_jsonl, flush_jsonl,
)
from .hardware import PEAK_FLOPS, peak_flops, model_flops_per_token  # noqa: F401
from . import tasks  # noqa: F401
from . import tracing  # noqa: F401
from .tracing import (span, recording, enable_tracing,  # noqa: F401
                      disable_tracing, tracing_enabled)
from . import attribution  # noqa: F401
from . import memory_profile  # noqa: F401
from . import roofline  # noqa: F401
from . import requests  # noqa: F401
from . import flight_recorder  # noqa: F401
from . import exporter  # noqa: F401

__all__ = [
    "Counter", "Gauge", "Histogram", "Quantile", "MetricsRegistry",
    "RecompileWarning",
    "registry", "enabled", "enable", "disable", "scrape", "dump", "reset",
    "log_step", "set_jsonl_path", "close_jsonl", "flush_jsonl",
    "PEAK_FLOPS", "peak_flops", "model_flops_per_token", "tasks",
    "tracing", "span", "recording", "enable_tracing", "disable_tracing",
    "tracing_enabled", "attribution", "memory_profile", "roofline",
    "requests",
    "flight_recorder", "exporter",
]
