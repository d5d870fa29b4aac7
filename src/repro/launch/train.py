"""End-to-end training driver.

Production posture on any device count: builds a mesh over the available
devices, shards params/optimizer with the framework rules (FSDP + TP),
streams the synthetic LM pipeline, applies the paper's accumulation policy
when requested, checkpoints atomically (with data cursor + scaler state) and
auto-resumes — including elastically onto a different device count.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \
      --steps 200 --global-batch 8 --seq-len 64 --policy predicted
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b \
      --steps 100 --mesh 16x16       # on a real pod
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, get_smoke_config
from repro.core.policy import AccumulationPolicy, plan_for_model
from repro.data.pipeline import DataConfig, SyntheticLM, with_extras
from repro.launch.mesh import make_mesh
from repro.models.api import get_model, param_count
from repro.models.layers import Dist
from repro.runtime import enable_compile_cache
from repro.sharding.specs import (
    ShardingRules,
    batch_spec,
    build_param_specs,
    named_shardings,
)
from repro.train import optimizer as O
from repro.train.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.train.loop import TrainConfig, init_train_state, make_train_step


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--policy", choices=["exact", "predicted", "perturbed"],
                    default="exact")
    ap.add_argument("--pp", type=int, default=0,
                    help="precision perturbation (bits) for --policy perturbed")
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--rounding", choices=["rne", "sr"], default="rne",
                    help="inter-chunk carry rounding for quantized GEMMs: "
                         "round-to-nearest-even (paper default) or seeded "
                         "stochastic rounding (the below-the-knee mode)")
    ap.add_argument("--sr-seed", type=int, default=0,
                    help="PRNG seed for --rounding sr (deterministic: the "
                         "same seed reproduces the run bitwise)")
    ap.add_argument("--a2q-reg", type=float, default=0.0,
                    help="A2Q accumulator-aware weight-norm regularizer "
                         "strength (0 = off).  When on, the per-output-"
                         "channel l1 caps derived from the planned "
                         "accumulator formats are soft-penalized in the "
                         "loss AND hard-projected after each optimizer "
                         "step, so reduced-e_acc carries provably never "
                         "overflow")
    ap.add_argument("--a2q-x-bound", type=float, default=16.0,
                    help="certified bound on the activation operand "
                         "magnitude for the --a2q-reg cap")
    ap.add_argument("--telemetry-cadence", type=int, default=0,
                    help="steps between swamping-telemetry probes (0 = off); "
                         "the closed-loop controller bumps/trims per-GEMM "
                         "m_acc from the measurements (repro.telemetry)")
    ap.add_argument("--telemetry-log", default="",
                    help="JSONL event-log path (default <ckpt-dir>/telemetry"
                         ".jsonl, or ./telemetry.jsonl without a ckpt dir)")
    ap.add_argument("--ingraph-telemetry", action="store_true",
                    help="measure swamping on TRUE training gradients from "
                         "inside the jitted step (repro.obs.ingraph) instead "
                         "of the synthetic-cotangent probe; the cadence tick "
                         "REPLACES the normal step (bit-identical numerics, "
                         "zero duplicated compute)")
    ap.add_argument("--obs-metrics", default="",
                    help="export the unified metrics registry as JSONL here "
                         "at exit (repro.obs.metrics)")
    ap.add_argument("--obs-prometheus", default="",
                    help="export the registry in Prometheus textfile-"
                         "collector format here at exit")
    ap.add_argument("--loss-scaling", action="store_true")
    ap.add_argument("--mesh", default="auto",
                    help="'auto' (all devices as data), 'DxM', or 'PxDxM'")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-out", default="")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--crash-at-step", type=int, default=-1,
                    help="fault injection: hard-exit at this step (supervisor test)")
    return ap.parse_args(argv)


def build_mesh(spec: str):
    n = len(jax.devices())
    if spec == "auto":
        if n == 1:
            return None
        return make_mesh((n, 1), ("data", "model"))
    dims = tuple(int(x) for x in spec.split("x"))
    axes = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
    return make_mesh(dims, axes)


def main(argv=None) -> dict:
    args = parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.rounding == "sr" and args.policy == "exact":
        raise SystemExit("--rounding sr needs a non-exact --policy (exact "
                         "mode has no emulated carries to dither)")
    policy = AccumulationPolicy(
        mode=args.policy, chunk=args.chunk,
        perturbation=args.pp if args.policy == "perturbed" else 0,
        rounding=args.rounding, sr_seed=args.sr_seed)
    cfg = plan_for_model(cfg, seq_len=args.seq_len,
                         global_batch=args.global_batch, policy=policy)
    model = get_model(cfg)

    a2q = None
    if args.a2q_reg > 0:
        # cap derived from the NARROWEST planned accumulator: a certificate
        # against that format covers every wider one in the plan
        from repro.telemetry.controller import PLAN_FIELDS, ROLES

        precs = [p for f in PLAN_FIELDS
                 for q in [getattr(cfg.quant, f, None)] if q is not None
                 for r in ROLES for p in [getattr(q, r)] if p is not None]
        if not precs:
            raise SystemExit("--a2q-reg needs a non-exact --policy "
                             "(nothing to certify in exact mode)")
        narrow = min(precs, key=lambda p: (p.e_acc, p.m_acc))
        a2q = O.A2QConfig(e_acc=narrow.e_acc, m_acc=narrow.m_acc,
                          x_bound=args.a2q_x_bound, strength=args.a2q_reg,
                          project=True)
        print(f"a2q: cap per-column l1 at {O.a2q_l1_cap(a2q):.4g} "
              f"(acc ({narrow.e_acc},{narrow.m_acc}), "
              f"x_bound {args.a2q_x_bound})")

    controller = None
    if args.telemetry_cadence > 0 and args.policy != "exact":
        from repro.telemetry.controller import (
            ControllerConfig,
            PrecisionController,
        )

        log_path = args.telemetry_log or os.path.join(
            args.ckpt_dir or ".", "telemetry.jsonl")
        controller = PrecisionController(
            policy, ControllerConfig(cadence=args.telemetry_cadence),
            log_path=log_path)

    mesh = build_mesh(args.mesh)
    dist = Dist(mesh=mesh, data_axes=("data",)) if mesh is not None else Dist()

    registry = None
    if args.obs_metrics or args.obs_prometheus or args.ingraph_telemetry:
        from repro.obs.metrics import get_registry

        registry = get_registry()

    tc = TrainConfig(
        opt=O.OptConfig(lr=args.lr, warmup_steps=args.warmup,
                        total_steps=args.steps),
        microbatches=args.microbatches,
        use_loss_scaling=args.loss_scaling,
        scaler=O.LossScaleConfig(init_scale=1000.0, dynamic=True),
        a2q=a2q,
    )

    ingraph = None
    if args.ingraph_telemetry:
        if controller is None:
            raise SystemExit("--ingraph-telemetry needs --telemetry-cadence "
                             "> 0 and a non-exact --policy")
        from repro.obs.ingraph import InGraphTelemetry

        ingraph = InGraphTelemetry(controller, tc, seq_len=args.seq_len,
                                   global_batch=args.global_batch, dist=dist,
                                   registry=registry)

    state = init_train_state(model, jax.random.PRNGKey(args.seed), tc)
    print(f"arch={cfg.name} params={param_count(state['params'])/1e6:.1f}M "
          f"policy={args.policy} pp={args.pp} devices={len(jax.devices())}")

    data = SyntheticLM(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len,
        global_batch=args.global_batch, seed=args.seed))

    # ---- shardings -------------------------------------------------------
    if mesh is not None:
        rules = ShardingRules(mesh)
        pspecs = build_param_specs(state["params"], rules)
        psh = named_shardings(pspecs, mesh)
        rep = NamedSharding(mesh, P())
        state_sh = {
            "params": psh,
            "opt": {"m": psh, "v": psh, "step": rep},
            "scaler": {"scale": rep, "good_steps": rep},
        }
        state = jax.device_put(state, state_sh)
        baxes = batch_spec(args.global_batch, mesh)
        tok_sh = NamedSharding(mesh, P(baxes if baxes else None, None))

        def jit_step(m):
            return jax.jit(make_train_step(m, tc, dist),
                           in_shardings=(state_sh, None),
                           out_shardings=(state_sh, None),
                           donate_argnums=(0,))
    else:
        state_sh = None

        def jit_step(m):
            return jax.jit(make_train_step(m, tc, dist), donate_argnums=(0,))

    step_fn = jit_step(model)

    # ---- resume ----------------------------------------------------------
    start = 0
    if args.ckpt_dir:
        last = latest_step(args.ckpt_dir)
        if last is not None:
            like = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
            state, meta = restore_checkpoint(args.ckpt_dir, last, like,
                                             shardings=state_sh)
            data.load_state_dict(meta["data"])
            start = int(meta["step"])
            print(f"resumed from step {start} "
                  f"(elastic onto {len(jax.devices())} devices)")
            if controller is not None and meta.get("precision_schedule"):
                # reproduce the realized precision trajectory: the restored
                # run must train under the widths the controller had reached
                from repro.telemetry.controller import apply_schedule

                controller.restore_meta(meta["precision_schedule"])
                cfg = apply_schedule(cfg, policy, controller.schedule(),
                                     seq_len=args.seq_len,
                                     global_batch=args.global_batch)
                model = get_model(cfg)
                step_fn = jit_step(model)
                print(f"restored precision schedule: "
                      f"{meta['precision_schedule']}")

    # ---- loop ------------------------------------------------------------
    metrics_f = open(args.metrics_out, "a") if args.metrics_out else None
    t0 = time.time()
    last_loss = float("nan")
    losses, batch = [], None
    for step in range(start, args.steps):
        if step == args.crash_at_step and start == 0:
            # one-shot transient-fault injection: only a FRESH incarnation
            # dies here; the supervisor's restart resumes from the latest
            # checkpoint and must run through
            print(f"FAULT INJECTION: dying at step {step}", flush=True)
            os._exit(42)
        batch = with_extras(next(data), cfg)
        due_ingraph = ingraph is not None and ingraph.due(step + 1)
        events, new_model = [], None
        with mesh or _null():
            if due_ingraph:
                # the stats-variant step REPLACES the normal step: same
                # numerics bit-for-bit, plus true-gradient swamping windows
                # shipped to the controller from inside the backward pass
                state, m, events, new_model = ingraph.tick(
                    model, state, batch, step=step + 1)
            else:
                state, m = step_fn(state, batch)
        if not due_ingraph and controller is not None \
                and controller.due(step + 1):
            from repro.train.loop import run_telemetry_tick

            events, new_model = run_telemetry_tick(
                controller, model, state, batch, dist, step=step + 1,
                key=jax.random.PRNGKey(args.seed * 1000003 + step + 1),
                seq_len=args.seq_len, global_batch=args.global_batch)
        for e in events:
            if e["event"] != "ok":
                print(json.dumps({"telemetry": e}), flush=True)
        if new_model is not None:
            # the controller changed some m_acc: re-plan, re-warm the
            # autotune entries the new widths key to, re-jit (rare —
            # hysteresis-gated)
            model, cfg = new_model, new_model.cfg
            step_fn = jit_step(model)
        if (step + 1) % args.log_every == 0 or step + 1 == args.steps:
            last_loss = float(m["loss"])
            losses.append(last_loss)
            rec = {"step": step + 1, "loss": last_loss,
                   "grad_norm": float(m["grad_norm"]),
                   "lr": float(m["lr"]),
                   "skipped": float(m["skipped"]),
                   "loss_scale": float(m["loss_scale"]),
                   "elapsed_s": round(time.time() - t0, 1)}
            print(json.dumps(rec), flush=True)
            if metrics_f:
                metrics_f.write(json.dumps(rec) + "\n")
                metrics_f.flush()
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, step + 1, state,
                            meta={"data": data.state_dict()},
                            precision_schedule=controller.to_meta()
                            if controller else None)
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, args.steps, state,
                        meta={"data": data.state_dict()},
                        precision_schedule=controller.to_meta()
                        if controller else None)
    if metrics_f:
        metrics_f.close()
    if registry is not None:
        from repro.obs.metrics import collect_process_metrics

        collect_process_metrics(registry)
        if args.obs_metrics:
            registry.export_jsonl(args.obs_metrics)
        if args.obs_prometheus:
            registry.export_prometheus(args.obs_prometheus)
    # the jitted step and its last operands let a caller inspect the
    # program that ran (``step_fn.lower(state, batch).compile()``)
    return {"final_loss": last_loss, "steps": args.steps, "losses": losses,
            "step_fn": step_fn, "state": state, "batch": batch}


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
