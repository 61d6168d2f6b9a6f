#!/usr/bin/env python
"""Long-context drill (CI): sharded attention, host KV paging,
sequence-parallel training.

Proves the ISSUE 19 long-context lane end to end, gates with teeth:

1. **sharded_attn_parity** (in-process): the same prompts served
   through context-length-sharded decode attention (explicit
   `attn_shards` AND the budget-derived `shard_block_budget` route)
   vs the unsharded ragged engine. Gates: TOKEN-IDENTICAL greedy
   streams (the online-softmax merge must be exact-to-argmax at every
   step, not approximately right); the sharded path actually ran
   (`sharded_attn_calls` > 0 and the
   paddle_tpu_sharded_attn_calls_total counter is scrape()-live).
2. **chunked_prefill_parity** (in-process): `prefill_chunk` splits a
   long prompt into several prefill launches. Gates: token-identical
   to the single-launch engine; > 1 prefill device call (the chunking
   is real, not a renamed monolith).
3. **offload_roundtrip** (in-process): a tight `hbm_budget_gib` makes
   the planner choose a < 1.0 resident fraction, so cold chain blocks
   page to host after the slot retires. The freed DEVICE slots are
   NaN-poisoned, then the same prompt is served warm: every prefix
   block must fault back from the HOST copy (a single stale device
   read would turn logits NaN and break greedy parity). Gates:
   token-identical to a fully-resident engine, offload-out AND
   fault-in counters > 0, cache stats agree.
4. **seq_parallel_train** (subprocess, 8-virtual-device CPU mesh):
   the planner's Plan (dp from `best_plan`) composed with an explicit
   `sep_degree` strategy override trains a ring context-parallel
   llama, gated the llama_moe_4d.py way: loss + weight-delta-norm
   parity vs single-dimension references (pure / dp-only / sep-only),
   a compiled-HLO `assert_sharding` on the SEQUENCE axis of the
   attention operand, and a modeled-MFU floor on the plan.

`--verify-teeth` proves the gates can fail: a mutated token stream
trips parity; zeroed paging counters at an over-budget context trip
the counter gate; the NaN poison demonstrably lands in the pool;
PT_LC_TEETH=break_parity perturbs one weight of the composed train
run so its parity gate must trip; PT_LC_TEETH=skip_parity omits the
parity metric entirely and the tier harness must reject the run — a
silently-disabled parity check cannot pass CI.

Run from the repo root (CI: tools/run_ci.sh longcontext):
    python tools/longcontext_drill.py [--out DIR] [--verify-teeth]
Prints one JSON line; exit 0 iff every gate passes.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--lane" not in sys.argv:
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=1")
sys.path.insert(0, ".")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MODEL_CFG = dict(vocab_size=97, hidden_size=64, intermediate_size=128,
                 num_hidden_layers=3, num_attention_heads=4,
                 num_key_value_heads=2, max_position_embeddings=256,
                 use_flash_attention=False, dtype="float32")
ENGINE_CFG = dict(max_len=192, block_size=8, num_blocks=48, max_slots=2)

# train-lane shape (subprocess; 8 virtual devices = dp2 x sep4)
TRAIN_DIMS = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=4, max_position_embeddings=96,
                  use_flash_attention=False, dtype="float32")
TRAIN_SEQ = 64
TRAIN_STEPS = 3
SEP_DEGREE = 4


def _tiny_model():
    import paddle_tpu as pt
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    pt.seed(5)
    m = LlamaForCausalLM(LlamaConfig(**MODEL_CFG))
    m.eval()
    return m


def _decoder(model, cache=True, **kw):
    from paddle_tpu.models.paged_decode import PagedDecoder
    cfg = dict(ENGINE_CFG, **kw)
    return PagedDecoder(model, prefix_cache=cache or None, **cfg)


def _prompt(n, seed):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(0, MODEL_CFG["vocab_size"], n)]


# -- gates (pure functions so --verify-teeth can mutate their inputs) -------
def gate_token_parity(base, got):
    problems = []
    if set(base) != set(got):
        problems.append(f"request sets differ: {sorted(base)[:4]} vs "
                        f"{sorted(got)[:4]}")
        return problems
    for rid in sorted(base):
        if base[rid] != got[rid]:
            problems.append(f"request {rid!r} diverged: "
                            f"{got[rid][:8]} != {base[rid][:8]}")
    return problems


def gate_paging_counters(counters, over_budget):
    """Paging must fire exactly when the chain exceeds the resident
    budget: silent zero counters above the budget mean the offload
    tier is decorative; nonzero below it means the planner's
    resident fraction is being ignored."""
    out = counters.get("out_bytes", 0)
    faulted = counters.get("in_bytes", 0)
    problems = []
    if over_budget:
        if not out > 0:
            problems.append("context exceeds the resident budget but "
                            "0 bytes were paged out")
        if not faulted > 0:
            problems.append("warm serve over an offloaded chain "
                            "faulted 0 bytes back in")
    elif out or faulted:
        problems.append(f"paged {out}B out / {faulted}B in while fully "
                        f"under the resident budget")
    return problems


def gate_train_metrics(metrics, require_parity=True):
    """The tier harness's view of the train subprocess: the plan,
    sharding and parity metrics must all be PRESENT and passing —
    a run that silently skips one cannot pass."""
    required = ["longcontext_train_plan", "longcontext_train_sharding"]
    if require_parity:
        required.append("longcontext_train_parity")
    problems = []
    for name in required:
        doc = metrics.get(name)
        if doc is None:
            problems.append(f"metric {name} missing from the train "
                            f"run — a disabled gate cannot pass")
        elif not doc.get("pass"):
            problems.append(f"{name} failed: "
                            f"{json.dumps(doc, sort_keys=True)[:300]}")
    return problems


# -- lanes ------------------------------------------------------------------
def lane_sharded_parity():
    import paddle_tpu.observability as obs
    model = _tiny_model()
    reqs = [(f"p{i}", _prompt(n, seed=30 + i), 6)
            for i, n in enumerate((24, 40, 56))]
    base = _decoder(model, cache=False, ragged_kernel=True).serve(reqs)
    obs.registry().reset()
    obs.enable()
    try:
        sharded = _decoder(model, cache=False, ragged_kernel=True,
                           attn_shards=3)
        got = sharded.serve(reqs)
        budgeted = _decoder(model, cache=False, ragged_kernel=True,
                            shard_block_budget=3)
        got_b = budgeted.serve(reqs)
        scrape = obs.scrape()
        ctr = "paddle_tpu_sharded_attn_calls_total"
        ctr_val = obs.registry().counter(ctr, "").value()
    finally:
        obs.disable()
    problems = gate_token_parity(base, got)
    problems += gate_token_parity(base, got_b)
    if not sharded.sharded_attn_calls > 0:
        problems.append("attn_shards=3 engine never ran the sharded "
                        "kernel — the parity above is vacuous")
    if not budgeted.sharded_attn_calls > 0:
        problems.append("shard_block_budget engine never ran the "
                        "sharded kernel")
    if ctr not in scrape or not ctr_val > 0:
        problems.append(f"counter {ctr} not scrape()-live "
                        f"(value {ctr_val})")
    return {"pass": not problems, "problems": problems,
            "sharded_attn_calls": sharded.sharded_attn_calls,
            "budget_derived_shards": budgeted.attn_shards}


def lane_chunked_prefill():
    model = _tiny_model()
    P = _prompt(40, seed=7)
    base = _decoder(model, cache=True)
    cold = base.serve([("a", P, 6)])
    chunked = _decoder(model, cache=True, prefill_chunk=16)
    got = chunked.serve([("a", P, 6)])
    problems = gate_token_parity(cold, got)
    if chunked.prefill_device_calls < 3:
        problems.append(f"prefill_chunk=16 on a 40-token prompt made "
                        f"{chunked.prefill_device_calls} prefill "
                        f"launches, want >= 3 — chunking is fake")
    return {"pass": not problems, "problems": problems,
            "prefill_device_calls": chunked.prefill_device_calls}


def lane_offload_roundtrip():
    import paddle_tpu.observability as obs
    model = _tiny_model()
    P = _prompt(160, seed=12)        # 20 blocks; resident budget: 10
    mnt = 6
    ref = _decoder(model, cache=True)
    cold_ref = ref.serve([("a", P, mnt)])["a"]

    probe = _decoder(model, cache=False)
    budget_gib = (probe._weights_gib()
                  + 10 * probe.bytes_per_block() / 2.0 ** 30)
    obs.registry().reset()
    obs.enable()
    try:
        eng = _decoder(model, cache=True, kv_offload=True,
                       hbm_budget_gib=budget_gib)
        cold = eng.serve([("cold", P, mnt)])["cold"]
        reg = obs.registry()

        def ctr(name):
            return int(reg.counter(name, "").value())

        out_after_cold = ctr("paddle_tpu_kv_offload_out_bytes_total")
        # NaN-poison every freed device slot: the warm serve below must
        # source the offloaded prefix from HOST copies, never from the
        # slots page-out released
        free = [b for b in range(1, ENGINE_CFG["num_blocks"])
                if eng.allocator.refcount(b) == 0]
        eng.poison_blocks(free)
        warm = eng.serve([("warm", P, mnt)])["warm"]
        counters = {
            "out_bytes": ctr("paddle_tpu_kv_offload_out_bytes_total"),
            "in_bytes": ctr("paddle_tpu_kv_offload_in_bytes_total"),
        }
    finally:
        obs.disable()
    st = dict(eng.prefix_cache.stats)
    problems = gate_token_parity({"x": cold_ref},
                                 {"x": cold})
    problems += gate_token_parity({"poisoned_warm": cold},
                                  {"poisoned_warm": warm})
    problems += gate_paging_counters(counters, over_budget=True)
    if not out_after_cold > 0:
        problems.append("nothing paged out after the cold slot "
                        "retired — enforce_residency never ran")
    if not st.get("offloaded_blocks"):
        problems.append(f"cache stats report no offloaded blocks: {st}")
    if not st.get("faulted_blocks"):
        problems.append(f"cache stats report no faulted blocks: {st}")
    return {"pass": not problems, "problems": problems,
            "poisoned_slots": len(free), "counters": counters,
            "offloaded_blocks": st.get("offloaded_blocks"),
            "faulted_blocks": st.get("faulted_blocks"),
            "resident_blocks": eng.prefix_cache.resident_blocks}


def _run_train_lane(out, tag, refs="pure,dp,sep", teeth=""):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    if teeth:
        env["PT_LC_TEETH"] = teeth
    else:
        env.pop("PT_LC_TEETH", None)
    r = subprocess.run(
        [sys.executable, "tools/longcontext_drill.py", "--lane", "train",
         "--refs", refs], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=600)
    metrics = {}
    for line in r.stdout.strip().splitlines():
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if "metric" in doc:
            metrics[doc["metric"]] = doc
    with open(os.path.join(out, f"train_{tag}.log"), "w") as f:
        f.write(r.stdout + "\n--- stderr ---\n" + r.stderr)
    return r, metrics


def lane_seq_parallel_train(out):
    r, metrics = _run_train_lane(out, "main")
    problems = []
    if r.returncode != 0:
        problems.append(f"train lane rc={r.returncode}: "
                        f"{(r.stdout + r.stderr)[-400:]}")
    problems += gate_train_metrics(metrics)
    plan = metrics.get("longcontext_train_plan") or {}
    parity = metrics.get("longcontext_train_parity") or {}
    return {"pass": not problems, "problems": problems,
            "plan": {k: plan.get(k) for k in (
                "mesh", "sep_degree", "modeled_mfu", "mfu_floor")},
            "worst_rel_err": parity.get("worst_rel_err")}


def run_drill(out):
    gates = {}
    gates["sharded_attn_parity"] = lane_sharded_parity()
    gates["chunked_prefill_parity"] = lane_chunked_prefill()
    gates["offload_roundtrip"] = lane_offload_roundtrip()
    gates["seq_parallel_train"] = lane_seq_parallel_train(out)
    return gates


# -- the train lane itself (subprocess: 8-virtual-device CPU mesh) ----------
def _train_snapshot(model):
    import numpy as np
    return {n: np.asarray(p._data, dtype=np.float64)
            for n, p in sorted(model.named_parameters())}


def _train_delta_norms(model, w0):
    """||w_after - w_init|| per parameter. Init + AdamW are
    seed-identical across runs, so matching deltas REQUIRE matching
    gradients — the grad-parity gate without an eager backward."""
    import numpy as np
    out = {}
    for n, p in sorted(model.named_parameters()):
        out[n] = float(np.linalg.norm(
            np.asarray(p._data, dtype=np.float64) - w0[n]))
    return out


def _train_build(plan, cp, mesh_dims=None, devices=None):
    import paddle_tpu as pt
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   LlamaPretrainingCriterion)
    if mesh_dims is not None:
        mesh_mod._global_mesh[0] = None
        mesh_mod.build_mesh(("dp", "sep"), mesh_dims, devices=devices)
    pt.seed(3)
    kw = dict(TRAIN_DIMS)
    if cp:
        kw.update(context_parallel=True, context_parallel_mode="ring")
    cfg = LlamaConfig(**kw)
    model = LlamaForCausalLM(cfg)
    crit = LlamaPretrainingCriterion(cfg)
    opt = pt.optimizer.AdamW(learning_rate=1e-3,
                             parameters=model.parameters())
    step = pt.jit.TrainStep(model, lambda lg, lb: crit(lg, lb), opt,
                            plan=(plan if mesh_dims is None else None))
    return model, step


def _train_steps(step, ids, labels):
    import paddle_tpu as pt
    from paddle_tpu.distributed.shard_util import shard_constraint
    i = shard_constraint(pt.to_tensor(ids), ("dp", None))
    l = shard_constraint(pt.to_tensor(labels), ("dp", None))
    return [float(step((i,), (l,))) for _ in range(TRAIN_STEPS)]


def lane_train_main(refs_arg):
    """Runs in the subprocess. Prints JSON metric lines, returns rc."""
    teeth = os.environ.get("PT_LC_TEETH", "")
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import _bootstrap
    _bootstrap.force_virtual_cpu_mesh(2 * SEP_DEGREE)
    import jax
    import numpy as np
    import paddle_tpu.distributed as dist
    import paddle_tpu.observability as obs
    from paddle_tpu.analysis import hlo_lint
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.auto_tuner import best_plan
    from paddle_tpu.distributed.fleet.distributed_strategy import (
        DistributedStrategy)

    rc = 0
    model_cfg = dict(hidden_size=TRAIN_DIMS["hidden_size"],
                     num_hidden_layers=TRAIN_DIMS["num_hidden_layers"],
                     intermediate_size=TRAIN_DIMS["intermediate_size"],
                     vocab_size=TRAIN_DIMS["vocab_size"],
                     num_attention_heads=TRAIN_DIMS["num_attention_heads"],
                     seq_length=TRAIN_SEQ)
    candidates = {
        "schedule": [(2, 2)],
        "save_mode": ("scan",),      # pp==1: the only coherent mode
        "remat": ((False, None),),
        "grad_compress": (None,),
        "mp_overlap": ((False, None),),
        "dispatch_compress": (None,),
    }
    # the planner owns the dp factorization of its 2 chips; the
    # long-context scenario then stretches the SAME plan over a 4-wide
    # 'sep' axis through an explicit strategy override — 8 devices total
    plan = best_plan(model_cfg, 2, 15.75, candidates=candidates,
                     source="analytic", require_axes=("dp",))
    mfu = float(plan.predicted["modeled_mfu"])
    mfu_floor = 0.01
    print(json.dumps({
        "metric": "longcontext_train_plan",
        "mesh": {"dp": plan.dp, "mp": plan.mp, "pp": plan.pp,
                 "ep": plan.ep},
        "sep_degree": SEP_DEGREE,
        "modeled_mfu": round(mfu, 5), "mfu_floor": mfu_floor,
        "pass": bool(plan.dp == 2 and mfu >= mfu_floor),
    }))
    if not (plan.dp == 2 and mfu >= mfu_floor):
        rc = 1

    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"sep_degree": SEP_DEGREE}
    strategy = dist.fleet.apply_plan(plan, strategy=strategy)
    assert strategy._plan is plan
    mesh = mesh_mod.get_mesh()
    assert mesh.shape.get("sep") == SEP_DEGREE, mesh

    global_batch = plan.dp * plan.micro_bs * plan.microbatches
    rng = np.random.default_rng(9)
    ids = rng.integers(0, TRAIN_DIMS["vocab_size"],
                       (global_batch, TRAIN_SEQ))
    labels = rng.integers(0, TRAIN_DIMS["vocab_size"],
                          (global_batch, TRAIN_SEQ))

    obs.reset()
    obs.enable()             # telemetry keeps the analysed executable
    model, step = _train_build(plan, cp=True)
    if teeth == "break_parity":
        # CI mutation: perturb ONE weight so the parity gate must trip
        import jax.numpy as jnp
        name, p = sorted(model.named_parameters())[0]
        p._data = p._data + jnp.asarray(1e-2, p._data.dtype)
    w0 = _train_snapshot(model)
    losses_cp = _train_steps(step, ids, labels)
    obs.disable()
    deltas_cp = _train_delta_norms(model, w0)

    # compiled-HLO sharding gate: the attention operand must exist only
    # at its dp x sep per-chip shape — the sequence axis really lives
    # sharded on the mesh, not gathered
    nh = TRAIN_DIMS["num_attention_heads"]
    hd = TRAIN_DIMS["hidden_size"] // nh
    try:
        compiled = list(step.analysed_executables().values())
        assert compiled, "telemetry analysed no executable"
        text = compiled[-1].runtime_executable() \
            .hlo_modules()[0].to_string()
        hlo_lint.assert_sharding(
            text, global_shape=(global_batch, TRAIN_SEQ, nh, hd),
            spec=("dp", "sep", None, None), mesh=mesh,
            what="ring attention operand")
        print(json.dumps({"metric": "longcontext_train_sharding",
                          "operand": "dp/sep-sharded", "pass": True}))
    except Exception as e:  # noqa: BLE001 - LintError subclasses vary
        print(json.dumps({"metric": "longcontext_train_sharding",
                          "error": str(e)[:400], "pass": False}))
        rc = 1

    if teeth != "skip_parity":
        refs = {"pure": (1, 1), "dp": (2, 1), "sep": (1, SEP_DEGREE)}
        refs = {k: v for k, v in refs.items()
                if k in refs_arg.split(",")}
        devices = jax.devices()
        parity = {}
        worst = 0.0
        for name, dims in sorted(refs.items()):
            n = int(np.prod(dims))
            model_r, step_r = _train_build(
                plan, cp=(dims[1] > 1), mesh_dims=dims,
                devices=devices[:n])
            w0_r = _train_snapshot(model_r)
            losses_r = _train_steps(step_r, ids, labels)
            deltas_r = _train_delta_norms(model_r, w0_r)
            loss_err = max(abs(a - b) / max(abs(b), 1e-9)
                           for a, b in zip(losses_cp, losses_r))
            grad_err = max(abs(deltas_cp[k] - deltas_r[k])
                           / max(abs(deltas_r[k]), 1e-9)
                           for k in deltas_cp)
            parity[name] = {"loss_rel_err": round(loss_err, 6),
                            "grad_norm_rel_err": round(grad_err, 6)}
            worst = max(worst, loss_err, grad_err)
        mesh_mod._global_mesh[0] = None
        ok = worst < 5e-3 and losses_cp[-1] < losses_cp[0]
        print(json.dumps({
            "metric": "longcontext_train_parity",
            "losses": [round(v, 6) for v in losses_cp],
            "references": parity,
            "worst_rel_err": round(worst, 6),
            "descending": losses_cp[-1] < losses_cp[0],
            "pass": bool(ok),
        }))
        if not ok:
            rc = 1
    return rc


# -- teeth ------------------------------------------------------------------
def verify_teeth(out):
    """Every mutation must produce the failure it exists to catch."""
    teeth = {}
    import numpy as np
    model = _tiny_model()
    P = _prompt(24, seed=2)
    dec = _decoder(model, cache=False)
    base = dec.serve([("a", P, 6)])

    # 1. a mutated token stream trips the parity gate
    mutated = {"a": list(base["a"])}
    mutated["a"][-1] = (mutated["a"][-1] + 1) % 97
    tp = gate_token_parity(base, mutated)
    teeth["parity_gate_trips"] = {"pass": bool(tp), "problems": tp}

    # 2. and the healthy shape passes
    hp = gate_token_parity(base, base)
    teeth["healthy_parity_passes"] = {"pass": not hp, "problems": hp}

    # 3. zeroed paging counters at an over-budget context trip the gate
    zp = gate_paging_counters({"out_bytes": 0, "in_bytes": 0},
                              over_budget=True)
    hz = gate_paging_counters({"out_bytes": 4096, "in_bytes": 2048},
                              over_budget=True)
    teeth["paging_gate_trips"] = {"pass": bool(zp) and not hz,
                                  "problems": zp + hz}

    # 4. the NaN poison demonstrably lands in the pool (the stale-read
    # oracle is live, not a no-op on some detached copy)
    blocks = dec.allocator.alloc(2)
    dec.poison_blocks(blocks)
    kp, vp = dec.ensure_pools()
    payload = dec.export_blocks(kp, vp, blocks)
    import jax
    leaves = jax.tree_util.tree_leaves(payload)
    poisoned = any(bool(np.isnan(np.asarray(x, np.float64)).any())
                   for x in leaves if np.issubdtype(x.dtype, np.floating))
    dec.allocator.free(blocks)
    teeth["poison_lands_in_pool"] = {"pass": poisoned}

    # 5. a perturbed weight in the composed train run trips its parity
    # gate (rc != 0 and the metric itself reports the divergence)
    r, metrics = _run_train_lane(out, "break", refs="pure",
                                 teeth="break_parity")
    par = metrics.get("longcontext_train_parity") or {}
    teeth["train_break_parity_trips"] = {
        "pass": bool(r.returncode != 0 and par and not par.get("pass")),
        "rc": r.returncode, "worst_rel_err": par.get("worst_rel_err")}

    # 6. a run that silently omits the parity metric is rejected by the
    # tier harness even if its own rc is 0
    r2, metrics2 = _run_train_lane(out, "skip", refs="pure",
                                   teeth="skip_parity")
    harness = gate_train_metrics(metrics2)
    teeth["train_skip_parity_caught"] = {
        "pass": any("longcontext_train_parity" in p for p in harness),
        "problems": harness[:3]}
    return teeth


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--out", default="/tmp/paddle_tpu_longcontext_drill",
                   help="artifact directory (wiped per run)")
    p.add_argument("--verify-teeth", action="store_true",
                   help="prove the gates fail on mutated inputs")
    p.add_argument("--lane", default=None, choices=[None, "train"],
                   help="internal: run one lane in this process")
    p.add_argument("--refs", default="pure,dp,sep",
                   help="train lane: which references to train")
    args = p.parse_args(argv)
    if args.lane == "train":
        return lane_train_main(args.refs)
    out = os.path.abspath(args.out)
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out, exist_ok=True)

    if args.verify_teeth:
        gates = verify_teeth(out)
        metric = "longcontext_drill_teeth"
    else:
        gates = run_drill(out)
        metric = "longcontext_drill"
    ok = all(g.get("pass") for g in gates.values())
    print(json.dumps({"metric": metric, "out": out, "gates": gates,
                      "pass": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
