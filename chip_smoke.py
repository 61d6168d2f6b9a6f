"""Smoke run on the chip: train and serve one Llama-2-7B-width model.

    python chip_smoke.py             # one chip: train phase, then serve phase
    python chip_smoke.py --chips 4   # four chips: sharded train vs one device

Drives `pt.jit.TrainStep` and `PagedDecoder.serve` once at the published
widths of Llama-2-7B with depth cut to 4 layers (seeded weights), checks
what comes out, and prints one JSON line per phase. The last line is
`{"ok": true, "device": {...}}` with the device as JAX reports it. Any
failed check raises, so the run ends non-zero and prints no result. With
no TPU it exits non-zero at once; the CPU rehearsal at a tiny size lives
in tests/test_chip_smoke.py, which calls the phase functions below.

The walls printed here are smoke timings (where a cold run goes), not
benchmark results.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time

import numpy as np

SEED = 0
# Llama-2-7B (paddle_tpu.models.llama_2_7b) with only the depth cut, so
# that weights, AdamW state (bf16 moments) and activations fit 16 GB
MODEL = dict(vocab_size=32000, hidden_size=4096, intermediate_size=11008,
             num_hidden_layers=4, num_attention_heads=32,
             num_key_value_heads=32, max_position_embeddings=2048,
             dtype="bfloat16")
REDUCED = {"num_hidden_layers": {"published": 32, "here": 4}}
BATCH, SEQ = 6, 2048
TRAIN_STEPS, SHARDED_STEPS = 5, 3
PROMPT_LENS = (128, 256, 384, 512, 640, 768, 896, 1024)
BUDGETS = (32, 16, 32, 24, 32, 8, 32, 32)      # new tokens per request
# a served token may sit below the reference's best logit by at most this
# share of that row's (max - mean): bf16 rounding moves a logit by about a
# hundredth of the spread, a token from a wrong context sits a whole
# spread away
SERVE_BAND = 1.0 / 16
# sharded vs one-device loss, same weights and batch: bf16 matmuls reduce
# in another order across mp shards
SHARDED_LOSS_BAND = 0.02
KERNEL = "tpu_custom_call"                      # a compiled Pallas kernel
HERE = os.path.dirname(os.path.abspath(__file__))
IR_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke_ir")


def say(**record):
    print(json.dumps(record), flush=True)


def require(ok, what):
    if not ok:
        raise AssertionError(what)


class CompileMeter:
    """What JAX reports about its own compiles: seconds in the backend
    compiler (for a persistent-cache hit, its retrieval) in all and for
    each program that took a second or more, programs loaded from the
    persistent cache, programs compiled and stored there. Tracing and
    lowering are not in it: they stay in a phase's wall, and the steady
    walls a phase prints are free of both."""

    def __init__(self):
        import jax
        self.seconds, self.hits, self.stored, self.slow = 0.0, 0, 0, []
        jax.monitoring.register_event_duration_secs_listener(self._secs)
        jax.monitoring.register_event_listener(self._event)

    def _secs(self, event, seconds, fun_name="", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            if seconds >= 1.0:
                self.slow.append([fun_name, round(seconds, 2)])

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.stored += 1

    def phase(self, name, fn, *args, **kw):
        """Run one phase; return (its result, its record so far)."""
        s0, h0, m0, n0 = self.seconds, self.hits, self.stored, len(self.slow)
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        return out, {"phase": name,
                     "wall_s": round(time.perf_counter() - t0, 3),
                     "xla_compile_s": round(self.seconds - s0, 3),
                     "compiles_over_1s": self.slow[n0:],
                     "cache_hits": self.hits - h0,
                     "cache_stored": self.stored - m0}


def dump_programs():
    """Have JAX write every module it lowers from now on under IR_DIR
    (before the module is compiled or fetched from the cache)."""
    import jax
    shutil.rmtree(IR_DIR, ignore_errors=True)
    os.makedirs(IR_DIR)
    jax.config.update("jax_dump_ir_to", IR_DIR)


def kernel_in(name):
    """Whether every program lowered so far whose module name holds
    `name` (there must be one) holds a compiled Pallas kernel."""
    texts = []
    for f in sorted(os.listdir(IR_DIR)):
        if name in f:
            with open(os.path.join(IR_DIR, f)) as fh:
                texts.append(fh.read())
    return bool(texts) and all(KERNEL in t for t in texts)


def build_trainer(model_kw):
    import paddle_tpu as pt
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   LlamaPretrainingCriterion)
    cfg = LlamaConfig(**model_kw)
    model = LlamaForCausalLM(cfg)
    crit = LlamaPretrainingCriterion(cfg)
    opt = pt.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters(),
                             moment_dtype="bfloat16")
    return model, crit, opt


def batch_of(vocab, batch, seq):
    rng = np.random.default_rng(SEED)
    return (rng.integers(0, vocab, (batch, seq)),
            rng.integers(0, vocab, (batch, seq)))


def run_steps(step, ids, labels, steps):
    """`steps` calls on one repeated batch; the device is waited for after
    every call, so each wall is that step's own."""
    losses, walls = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = step((ids,), (labels,))
        loss._data.block_until_ready()
        walls.append(round(time.perf_counter() - t0, 3))
        losses.append(float(loss))
    require(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    require(losses[-1] < losses[0],
            f"loss did not fall on a repeated batch: {losses}")
    return losses, walls


def train_phase(model_kw, batch, seq, steps):
    """LlamaForCausalLM + criterion + AdamW under pt.jit.TrainStep."""
    import paddle_tpu as pt
    pt.seed(SEED)
    model, crit, opt = build_trainer(model_kw)
    step = pt.jit.TrainStep(model, lambda lg, lb: crit(lg, lb), opt)
    ids, labels = batch_of(model_kw["vocab_size"], batch, seq)
    losses, walls = run_steps(step, pt.to_tensor(ids, dtype="int64"),
                              pt.to_tensor(labels, dtype="int64"), steps)
    return model, {
        "params": int(sum(p.size for p in model.parameters())),
        "batch": batch, "seq": seq, "steps": steps, "losses": losses,
        "step_wall_s": walls,
        "kernel_in_step": kernel_in("_traced"),
    }


def serve_phase(model, prompt_lens, budgets):
    """The model's weights in a default PagedDecoder; serve() over mixed
    prompts and budgets. Checked by teacher forcing: the model's own full
    forward over prompt + served tokens must rank every served token
    within SERVE_BAND of its best logit (greedy tokens equal the
    reference's wherever no tie is nearer than the band)."""
    import paddle_tpu as pt
    from paddle_tpu.models.paged_decode import PagedDecoder
    vocab = model.config.vocab_size
    rng = np.random.default_rng(SEED + 1)
    reqs = [(rid, rng.integers(0, vocab, n).tolist(), b)
            for rid, (n, b) in enumerate(zip(prompt_lens, budgets))]
    dec = PagedDecoder(model)
    walls, outs = [], []
    for _ in range(2):           # the first call compiles, the second runs
        t0 = time.perf_counter()
        outs.append(dec.serve(reqs, max_new_tokens=max(budgets)))
        walls.append(round(time.perf_counter() - t0, 3))
    out = outs[0]
    for rid, _, b in reqs:
        require(len(out[rid]) == b,
                f"request {rid}: {len(out[rid])} tokens for budget {b}")
    require(outs[1] == out, "serve() answered the same requests twice "
            "with different tokens")

    # one padded batch through the model's plain forward (causal, so the
    # padding behind a row's tokens cannot reach them)
    width = -(-(max(prompt_lens) + max(budgets)) // 128) * 128
    ids = np.zeros((len(reqs), width), np.int64)
    for rid, prompt, _ in reqs:
        row = prompt + out[rid]
        ids[rid, :len(row)] = row
    model.eval()
    with pt.no_grad():
        logits = pt.jit.to_static(model.forward)(pt.to_tensor(ids))
    worst, exact, total = 0.0, 0, 0
    for rid, prompt, b in reqs:
        # row p predicts token p + 1
        rows = np.asarray(logits._data[rid, len(prompt) - 1:
                                       len(prompt) - 1 + b],
                          dtype=np.float32)
        require(np.isfinite(rows).all(), f"request {rid}: reference "
                "logits not finite")
        best = rows.max(axis=-1)
        got = rows[np.arange(b), out[rid]]
        gap = (best - got) / (best - rows.mean(axis=-1))
        require((gap <= SERVE_BAND).all(),
                f"request {rid}: served tokens fall {gap.max():.3f} of the "
                f"logit spread below the reference's best (band "
                f"{SERVE_BAND:.3f}) at steps "
                f"{np.nonzero(gap > SERVE_BAND)[0].tolist()}")
        worst = max(worst, float(gap.max()))
        exact += int((got == best).sum())
        total += b
    return {
        "requests": len(reqs), "prompt_lens": list(prompt_lens),
        "budgets": list(budgets), "returned": [len(out[r]) for r, _, _ in
                                               reqs],
        "ragged_kernel": dec.use_ragged_kernel,
        "serve_wall_s": {"first": walls[0], "again": walls[1]},
        "check": "teacher-forced full forward of the same model",
        "band": SERVE_BAND, "worst_gap": round(worst, 5),
        "argmax_equal": f"{exact}/{total}",
        "kernel_in_decode_step": kernel_in("_paged_chunk"),
    }


def sharded_train_phase(model_kw, batch, seq, steps, dp=2, mp=2):
    """The train step under fleet hybrid dp x mp (tensor_parallel=True, so
    flash attention runs per shard through _flash_tp) against the same
    weights and batch on one device of this process."""
    import jax
    import paddle_tpu as pt
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.shard_util import shard_constraint

    ids_np, labels_np = batch_of(model_kw["vocab_size"], batch, seq)

    # one device: no mesh yet, so everything lands on jax.devices()[0]
    pt.seed(SEED)
    model, crit, opt = build_trainer(model_kw)
    start = {k: np.asarray(v._data) for k, v in model.state_dict().items()}
    step = pt.jit.TrainStep(model, lambda lg, lb: crit(lg, lb), opt)
    ref_losses, ref_walls = run_steps(
        step, pt.to_tensor(ids_np, dtype="int64"),
        pt.to_tensor(labels_np, dtype="int64"), steps)
    del model, crit, opt, step
    gc.collect()

    strategy = dist.fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": dp, "mp_degree": mp,
                               "pp_degree": 1}
    dist.fleet.init(is_collective=True, strategy=strategy)
    pt.seed(SEED)
    model, crit, opt = build_trainer(dict(model_kw, tensor_parallel=True))
    model.set_state_dict({k: pt.to_tensor(v) for k, v in start.items()})
    step = pt.jit.TrainStep(dist.fleet.distributed_model(model)._layers,
                            lambda lg, lb: crit(lg, lb),
                            dist.fleet.distributed_optimizer(opt))
    ids = shard_constraint(pt.to_tensor(ids_np, dtype="int64"),
                           ("dp", None))
    labels = shard_constraint(pt.to_tensor(labels_np, dtype="int64"),
                              ("dp", None))
    losses, walls = run_steps(step, ids, labels, steps)

    diffs = [abs(a - b) for a, b in zip(losses, ref_losses)]
    require(max(diffs) <= SHARDED_LOSS_BAND,
            f"sharded losses {losses} leave one-device losses "
            f"{ref_losses} by more than {SHARDED_LOSS_BAND}")
    # placement: a tensor-parallel weight is spread over every device
    w = model.llama.layers[0].mlp.gate_proj.weight._data
    holders = sorted({s.device.id for s in w.addressable_shards})
    require(len(holders) == dp * mp,
            f"gate_proj.weight lives on devices {holders}")
    require(w.addressable_shards[0].data.shape[1] * mp == w.shape[1],
            f"gate_proj.weight shard {w.addressable_shards[0].data.shape} "
            f"of {w.shape} is not an mp shard")
    return (model, step), {     # kept alive: main reads the devices' bytes
        "mesh": {"dp": dp, "mp": mp}, "batch": batch, "seq": seq,
        "steps": steps, "losses": losses, "one_device_losses": ref_losses,
        "max_loss_diff": round(max(diffs), 5), "band": SHARDED_LOSS_BAND,
        "step_wall_s": walls, "one_device_step_wall_s": ref_walls,
        "weight_on_devices": holders,
        "kernel_in_step": kernel_in("_traced"),
    }


def memory(device):
    """The device's own account, whole. On this runtime peak_bytes_in_use
    follows the buffers that stay (weights, optimizer state, pools), not
    a program's temporaries."""
    stats = device.memory_stats()
    return {"peak_bytes": stats["peak_bytes_in_use"], "memory_stats": stats}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    chips = ap.parse_args(argv).chips

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke needs a TPU; JAX found {devices[0].platform}")
    if chips == 4 and len(devices) != 4:
        sys.exit(f"--chips 4 needs four chips; JAX found {len(devices)}")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}

    import paddle_tpu  # noqa: F401  (after the device check: it is slow)
    from paddle_tpu.distributed.resilience import compile_cache
    from paddle_tpu.framework import native_runtime
    dump_programs()
    meter = CompileMeter()
    say(phase="device", **device, jax=jax.__version__,
        compile_cache=compile_cache.enable_jax_cache(),
        runtime="native" if native_runtime.available() else "python",
        model=MODEL, reduced=REDUCED, seed=SEED)

    if chips == 4:
        (_alive, out), rec = meter.phase(
            "sharded_train", sharded_train_phase, MODEL, BATCH, SEQ,
            SHARDED_STEPS)
        per_device = [memory(d) for d in devices]
        require(min(m["memory_stats"]["bytes_in_use"]
                    for m in per_device) > 2**28,
                f"a device holds next to nothing: {per_device}")
        require(out["kernel_in_step"], "no Pallas kernel in a train step")
        say(**rec, **out, devices=per_device)
    else:
        (model, out), rec = meter.phase("train", train_phase, MODEL, BATCH,
                                        SEQ, TRAIN_STEPS)
        require(out["kernel_in_step"], "no Pallas kernel in the train step")
        say(**rec, **out, **memory(devices[0]))
        gc.collect()         # the trainer's AdamW state dies with its phase
        out, rec = meter.phase("serve", serve_phase, model, PROMPT_LENS,
                               BUDGETS)
        require(out["ragged_kernel"] and out["kernel_in_decode_step"],
                "no Pallas kernel in the decode step")
        say(**rec, **out, **memory(devices[0]))
    say(ok=True, device=device)


if __name__ == "__main__":
    main()
