"""Composed Llama-MoE dp x mp x pp x ep benchmark lane (r17 planner
tentpole proof (b)).

Runs the auto-parallel planner END TO END on a forced 16-virtual-device
CPU mesh: `auto_tuner.best_plan` gets ONLY (model config, chip count,
HBM budget) plus the lane's scenario constraints, emits a Plan, the
Plan is applied through fleet (`fleet.apply_plan` — strategy degrees +
knobs + mesh) and `Plan.model_kwargs()` (pipeline/save-mode/remat
fields), and the composed Llama-MoE model (models/llama_moe_pipe.py:
llama attention + 'ep'-sharded expert stacks under the gspmd pipeline)
trains under it. `require_axes=("dp","mp","pp","ep")` expresses the
lane's scenario — a genuinely 4D-composed placement — which at 16
devices forces the 2x2x2x2 factorization; every other choice
(schedule, remat, save-mode-within-candidates) is the planner's.

Scenario knob restrictions (documented honesty, not hidden defaults):
save_mode is pinned to "buffer" (the lane's compiled-HLO assertion
targets the PR-3 save buffer, which only buffer mode materializes) and
the wire-compression candidates are disabled because THIS reference
model runs the exact einsum dispatch — the lane never prices a knob it
does not execute. grad_compress/mp_overlap pricing is exercised by the
mp4/mp2 profile scenarios (tools/planner_report.py).

Gates (all emitted as JSON metric lines, rc=1 on violation):
  zero-drop     live routing probe on the real router weights +
                embedding activations: dropped routes == 0 (capacity
                C = per-group tokens T makes overflow structurally
                impossible; the probe re-checks it on data)
  parity        loss trajectory (3 fused train steps) and grad norms
                vs the SINGLE-DIMENSION references — the same model,
                same seed, on pure (1-device), dp-only, mp-only,
                pp-only and ep-only meshes
  sharding      compiled-HLO assertions (analysis/hlo_lint
                .assert_sharding) on the pipeline save buffer
                [T,S,mb,seq,h] and the expert stacks [L,E,h,f] at
                their per-chip dp/pp/ep/mp-sharded shapes
  mfu floor     the plan's modeled MFU >= --mfu-floor (cost-model
                floor; the planner tier additionally re-prices the
                plan through `overlap_evidence --mode project --plan`
                with a <= 5% drift gate)

CI teeth (tools/run_ci.sh planner --teeth): PT_4D_TEETH=break_parity
perturbs one weight of the 4D run so the parity gate must trip (rc=1);
PT_4D_TEETH=skip_parity omits the parity metric entirely — the tier
harness requires it, proving a silently-disabled parity check cannot
pass CI.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import _bootstrap  # noqa: F401

N_DEVICES = 16
STEPS = 3
SEQ = 32
MODEL_DIMS = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
                  num_hidden_layers=4, num_attention_heads=4,
                  num_key_value_heads=4, max_position_embeddings=64,
                  use_flash_attention=False, num_experts=4, moe_top_k=2)


def model_cfg_dict():
    """The planner's view of the smoke model (cost_model keys)."""
    return dict(hidden_size=MODEL_DIMS["hidden_size"],
                num_hidden_layers=MODEL_DIMS["num_hidden_layers"],
                intermediate_size=MODEL_DIMS["intermediate_size"],
                vocab_size=MODEL_DIMS["vocab_size"],
                num_attention_heads=MODEL_DIMS["num_attention_heads"],
                seq_length=SEQ,
                num_experts=MODEL_DIMS["num_experts"],
                moe_top_k=MODEL_DIMS["moe_top_k"])


def lane_candidates():
    """The scenario's knob grid (see module docstring for why the wire
    codecs are off and save_mode is pinned here)."""
    return {
        "schedule": [(1, 2), (1, 4), (2, 2)],   # (micro_bs, microbatches)
        "save_mode": ("buffer",),
        "remat": ((False, None), (True, None), (True, "pp_attn_dots")),
        "grad_compress": (None,),
        "mp_overlap": ((False, None),),
        "dispatch_compress": (None,),
    }


def build_model(plan, mesh_dims=None, devices=None):
    """Build the composed model under `plan` (optionally overriding the
    mesh for a reference run) and return (model, crit, step, stack)."""
    import paddle_tpu as pt
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   LlamaPretrainingCriterion)
    if mesh_dims is not None:
        mesh_mod._global_mesh[0] = None
        mesh_mod.build_mesh(("dp", "pp", "sharding", "ep", "mp"),
                            mesh_dims,
                            devices=devices)
    pt.seed(0)
    kw = dict(MODEL_DIMS)
    kw.update(plan.model_kwargs())
    # references at degree 1 keep the SAME pipelined code path (S=1);
    # tensor/sequence parallel flags follow the mesh actually in use
    mesh = mesh_mod.get_mesh()
    kw["tensor_parallel"] = mesh.shape.get("mp", 1) > 1
    kw["sequence_parallel"] = mesh.shape.get("mp", 1) > 1
    kw["pipeline_parallel"] = True
    kw.setdefault("pp_microbatches", plan.microbatches)
    kw.setdefault("pipeline_save_mode", plan.save_mode)
    cfg = LlamaConfig(**kw)
    model = LlamaForCausalLM(cfg)
    crit = LlamaPretrainingCriterion(cfg)
    opt = pt.optimizer.AdamW(learning_rate=1e-3,
                             parameters=model.parameters())
    step = pt.jit.TrainStep(model, lambda lg, lb: crit(lg, lb), opt,
                            plan=plan)
    return model, crit, step, model.llama.decoder_stack


def run_steps(step, ids, labels, steps=STEPS):
    import paddle_tpu as pt
    from paddle_tpu.distributed.shard_util import shard_constraint
    i = shard_constraint(pt.to_tensor(ids), ("dp", None))
    l = shard_constraint(pt.to_tensor(labels), ("dp", None))
    losses, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = step((i,), (l,))
        losses.append(float(loss))
        times.append(time.perf_counter() - t0)
    return losses, times


def weight_delta_norms(stack, w_init):
    """||w_after_steps - w_init|| per weight family. The fused step's
    update is AdamW(grads), and init + optimizer are seed-identical
    across runs, so matching deltas REQUIRE matching gradients — the
    grad-parity gate without an eager backward through the pipelined
    primitive."""
    out = {}
    for fam, w0 in w_init.items():
        w1 = np.asarray(getattr(stack, fam)._data, dtype=np.float64)
        out[fam] = float(np.linalg.norm(w1 - w0))
    return out


def snapshot_weights(stack, fams=("wq", "we_g", "wgate")):
    return {f: np.asarray(getattr(stack, f)._data, dtype=np.float64)
            for f in fams}


def zero_drop_probe(model, ids):
    """Live-routing zero-drop probe THROUGH THE MODEL'S OWN DISPATCH
    CODE: route the first layer's router weights over the real
    embedding stream, then build the dispatch mask with the SAME
    `moe_dispatch_mask` + `dispatch_capacity` the traced block uses —
    dropped = one-hot routes minus mask entries. Because the capacity
    rule is shared (not re-derived here), shrinking it in
    llama_moe_pipe shows up as counted drops in this gate instead of a
    tautologically-green probe."""
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.framework.autograd import no_grad
    from paddle_tpu.models.llama_moe_pipe import (dispatch_capacity,
                                                  moe_dispatch_mask,
                                                  moe_route)
    stack = model.llama.decoder_stack
    k = int(model.config.moe_top_k)
    with no_grad():
        tok = model.llama.embed_tokens(pt.to_tensor(ids))
    x = jnp.asarray(np.asarray(tok._data, dtype=np.float32))  # [B,S,h]
    wg = jnp.asarray(np.asarray(stack.wgate._data,
                                dtype=np.float32)[0])         # layer 0
    B, S, H = x.shape
    E = wg.shape[-1]
    logits = jnp.einsum("bsh,he->bse", x, wg)
    _val, idx = moe_route(logits, k)
    idx = idx.reshape(B, S * k)                   # per-group routes
    dmask, r = moe_dispatch_mask(idx, E, dispatch_capacity(S))
    routed = int(np.asarray(r.sum()))
    dropped = routed - int(np.asarray(dmask.sum()))
    return routed, dropped


def sharding_assertions(step, plan, batch):
    """Compiled-HLO sharding gates on the fused train step: the save
    buffer only at its dp(+mp)-sharded per-chip shape, the expert
    stacks only at their pp x ep x mp-sharded shape."""
    from paddle_tpu.analysis import hlo_lint
    from paddle_tpu.distributed import mesh as mesh_mod
    compiled = list(step.analysed_executables().values())
    assert compiled, "telemetry analysed no executable"
    text = compiled[-1].runtime_executable().hlo_modules()[0].to_string()
    mesh = mesh_mod.get_mesh()
    M = plan.microbatches
    S = plan.pp
    T = M + S - 1
    mb = batch // M
    h = MODEL_DIMS["hidden_size"]
    sp = plan.sequence_parallel and plan.mp > 1
    hlo_lint.assert_sharding(
        text, global_shape=(T, S, mb, SEQ, h),
        spec=(None, "pp", "dp", "mp" if sp else None, None), mesh=mesh,
        what="4D pipeline save buffer")
    L = MODEL_DIMS["num_hidden_layers"]
    E = MODEL_DIMS["num_experts"]
    f = MODEL_DIMS["intermediate_size"]
    hlo_lint.assert_sharding(
        text, global_shape=(L, E, h, f),
        spec=("pp", "ep", None, "mp"), mesh=mesh,
        what="4D expert stack we_g")
    return True


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mfu-floor", type=float, default=0.05,
                    help="modeled-MFU floor for the chosen plan (CPU "
                         "analytic pricing at smoke shape)")
    ap.add_argument("--plan-out", default=None,
                    help="write the chosen Plan JSON here (the planner "
                         "tier re-prices it via overlap_evidence "
                         "--plan)")
    ap.add_argument("--steps", type=int, default=STEPS)
    args = ap.parse_args()
    teeth = os.environ.get("PT_4D_TEETH", "")

    _bootstrap.force_virtual_cpu_mesh(N_DEVICES)
    import jax
    import paddle_tpu as pt
    import paddle_tpu.distributed as dist
    import paddle_tpu.observability as obs
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.auto_tuner import best_plan

    rc = 0

    # -- 1. the planner, from (model config, chips, HBM budget) alone --
    plan = best_plan(model_cfg_dict(), N_DEVICES, 15.75,
                     candidates=lane_candidates(),
                     source="analytic",
                     require_axes=("dp", "mp", "pp", "ep"))
    if args.plan_out:
        plan.save(args.plan_out)
    composed_4d = all(d > 1 for d in (plan.dp, plan.mp, plan.pp,
                                      plan.ep))
    mfu = float(plan.predicted["modeled_mfu"])
    print(json.dumps({
        "metric": "llama_moe_4d_plan",
        "mesh": {"dp": plan.dp, "mp": plan.mp, "pp": plan.pp,
                 "ep": plan.ep},
        "micro_bs": plan.micro_bs, "microbatches": plan.microbatches,
        "save_mode": plan.save_mode,
        "recompute_policy": (plan.recompute_policy if plan.recompute
                             else None),
        "modeled_mfu": round(mfu, 4),
        "mfu_floor": args.mfu_floor,
        "memory_model_gib": plan.predicted["memory_model_gib"]["total"],
        "search_stats": plan.scenario.get("search_stats"),
        "composed_4d": composed_4d,
        "pass": bool(composed_4d and mfu >= args.mfu_floor),
    }))
    if not (composed_4d and mfu >= args.mfu_floor):
        rc = 1

    # -- 2. apply the plan end to end ---------------------------------
    strategy = dist.fleet.apply_plan(plan)
    assert strategy._plan is plan
    global_batch = plan.dp * plan.micro_bs * plan.microbatches
    rng = np.random.default_rng(7)
    ids = rng.integers(0, MODEL_DIMS["vocab_size"], (global_batch, SEQ))
    labels = rng.integers(0, MODEL_DIMS["vocab_size"],
                          (global_batch, SEQ))

    obs.reset()
    obs.enable()          # telemetry keeps the analysed executable
    model, crit, step, stack = build_model(plan)
    if teeth == "break_parity":
        # CI mutation: perturb ONE weight so the parity gate must trip
        import jax.numpy as jnp
        stack.wq._data = stack.wq._data + jnp.asarray(1e-2,
                                                      stack.wq._data.dtype)
    w_init_4d = snapshot_weights(stack)
    losses_4d, times_4d = run_steps(step, ids, labels, args.steps)
    obs.disable()
    gnorm_4d = weight_delta_norms(stack, w_init_4d)

    # -- 3. zero-drop routing probe -----------------------------------
    routed, dropped = zero_drop_probe(model, ids)
    drop_fraction = dropped / max(routed, 1)
    print(json.dumps({
        "metric": "llama_moe_4d_zero_drop",
        "routed": routed, "dropped": dropped,
        "drop_fraction": drop_fraction,
        "pass": dropped == 0,
    }))
    if dropped != 0:
        rc = 1

    # -- 4. compiled-HLO sharding assertions --------------------------
    try:
        sharding_assertions(step, plan, global_batch)
        print(json.dumps({"metric": "llama_moe_4d_sharding",
                          "save_buffer": "dp/pp/mp-sharded",
                          "expert_stack": "pp/ep/mp-sharded",
                          "pass": True}))
    except Exception as e:  # noqa: BLE001 - LintError subclasses vary
        print(json.dumps({"metric": "llama_moe_4d_sharding",
                          "error": str(e)[:400], "pass": False}))
        rc = 1

    # -- 5. grad/loss parity vs the single-dimension references -------
    if teeth != "skip_parity":
        refs = {
            "pure": (1, 1, 1, 1, 1),
            "dp2": (2, 1, 1, 1, 1),
            "pp2": (1, 2, 1, 1, 1),
            "ep2": (1, 1, 1, 2, 1),
            "mp2": (1, 1, 1, 1, 2),
        }
        devices = jax.devices()
        parity = {}
        worst = 0.0
        for name, dims in refs.items():
            n = int(np.prod(dims))
            model_r, crit_r, step_r, stack_r = build_model(
                plan, mesh_dims=dims, devices=devices[:n])
            w_init_r = snapshot_weights(stack_r)
            losses_r, _ = run_steps(step_r, ids, labels, args.steps)
            gnorm_r = weight_delta_norms(stack_r, w_init_r)
            loss_err = max(abs(a - b) / max(abs(b), 1e-9)
                           for a, b in zip(losses_4d, losses_r))
            grad_err = max(abs(gnorm_4d[k2] - gnorm_r[k2])
                           / max(abs(gnorm_r[k2]), 1e-9)
                           for k2 in gnorm_4d)
            parity[name] = {"loss_rel_err": round(loss_err, 6),
                            "grad_norm_rel_err": round(grad_err, 6),
                            "losses": [round(v, 6) for v in losses_r]}
            worst = max(worst, loss_err, grad_err)
        ok = worst < 5e-3 and losses_4d[-1] < losses_4d[0]
        print(json.dumps({
            "metric": "llama_moe_4d_parity",
            "losses_4d": [round(v, 6) for v in losses_4d],
            "references": parity,
            "worst_rel_err": round(worst, 6),
            "descending": losses_4d[-1] < losses_4d[0],
            "pass": bool(ok),
        }))
        if not ok:
            rc = 1
        # restore the composed mesh for any later consumers
        mesh_mod._global_mesh[0] = None

    tok_s = global_batch * SEQ / max(min(times_4d[1:] or times_4d),
                                     1e-9)
    print(json.dumps({
        "metric": "llama_moe_4d_tokens_per_sec",
        "value": round(tok_s, 1),
        "step_ms": [round(t * 1e3, 1) for t in times_4d],
        "unit": "tokens/s on the 16-virtual-device CPU mesh (smoke "
                "shape; correctness lane, not a speed claim)",
    }))
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
