"""End-to-end training driver.

Wires together: model registry, logical sharding, host data pipe, optimizer,
fault-tolerant supervisor (checkpoint/resume/preemption), straggler
watchdog. Runs on whatever devices exist (CPU smoke -> TPU pods): pass
``--mesh host`` for a local mesh or ``--mesh pod`` for the production mesh.

Example (CPU, ~100M-param llama-style model, a few hundred steps):
  PYTHONPATH=src python -m repro.launch.train --arch llama3_2_1b --smoke \
      --steps 300 --batch 8 --seq 128
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ARCH_IDS, get_config, smoke_config
from repro.data import HostPipeline, SyntheticSpec, batch_at
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.optim import adamw
from repro.runtime import sharding as shlib
from repro.runtime.fault_tolerance import FTConfig, Supervisor
from repro.runtime.stragglers import (BatchRebalancer, StragglerConfig,
                                      StragglerWatchdog)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="llama3_2_1b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced per-arch config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--quantized-accum", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", choices=("host", "pod", "pod2"), default="host")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a failure at this step (tests)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--policy-mode", choices=("ff", "baseline", "autotune"),
                    default=None,
                    help="install a session PipePolicy of this mode (mesh-"
                         "tagged) around the train-step body, so stream-"
                         "kernel call sites inside the model plan under "
                         "the training mesh; default: no policy override")
    ap.add_argument("--record-profile", default=None, metavar="PATH",
                    help="record every plan resolution into a "
                         "TrafficProfile JSON at PATH (the input of "
                         "`python -m repro.plans sweep`)")
    ap.add_argument("--plan-db", default=None, metavar="PATH",
                    help="release PlanDB consulted after the per-host plan "
                         "cache and before measuring (pre-warmed at "
                         "startup; overrides $REPRO_PLAN_DB)")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="enable live telemetry and write "
                         "obs.metrics_snapshot() to PATH at exit")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    from repro.models import build_model
    model = build_model(cfg)

    mesh = (make_production_mesh(multi_pod=args.mesh == "pod2")
            if args.mesh.startswith("pod") else make_host_mesh())
    opt_cfg = adamw.AdamWConfig(lr_peak=args.lr, warmup_steps=20,
                                total_steps=args.steps)

    spec = SyntheticSpec(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
        n_frames=cfg.n_frames if cfg.family == "encdec" else 0,
        n_patches=cfg.n_patches if cfg.family == "vlm" else 0,
        d_model=cfg.d_model)

    policy = None
    if args.policy_mode is not None:
        from repro.core.program import PipePolicy
        policy = PipePolicy(mode=args.policy_mode)

    # plan-service hooks (same contract as launch/serve.py): --plan-db
    # feeds the autotune lookup chain, --record-profile captures the
    # training traffic for an offline sweep
    import contextlib

    stack = contextlib.ExitStack()
    if args.metrics_json:
        from repro import obs
        if not obs.enabled():
            prev_obs = obs.enable()     # in-memory ring, no JSONL sink
            stack.callback(obs.restore, prev_obs)

        def _dump_metrics(path=args.metrics_json):
            from repro import obs as _obs
            import json
            with open(path, "w") as f:
                json.dump(_obs.metrics_snapshot(), f, indent=2,
                          sort_keys=True)
            print(f"# wrote live metrics snapshot -> {path}")
        stack.callback(_dump_metrics)
    if args.plan_db:
        from repro.core import autotune
        from repro.plans import plandb as plandb_lib
        stack.enter_context(autotune.tuning_config(plan_db=args.plan_db))
        pre = plandb_lib.prewarm(args.plan_db)
        print(f"# plan-db {args.plan_db}: {pre['records_in_namespace']} "
              f"records for namespace {pre['namespace']}")
    if args.record_profile:
        from repro.plans import record_traffic
        profile = stack.enter_context(record_traffic(args.record_profile))

    overrides = dict(cfg.rule_overrides or {})
    with stack, shlib.use_sharding(mesh, overrides=overrides):
        params = model.init(jax.random.key(0))
        opt_init, _ = steps_lib.opt_init_and_update(cfg.optimizer, opt_cfg)
        opt_state = opt_init(params)
        train_step = jax.jit(
            steps_lib.make_train_step(
                model, optimizer=cfg.optimizer, opt_cfg=opt_cfg,
                accum_steps=args.accum,
                quantized_accum=args.quantized_accum, policy=policy),
            donate_argnums=(0, 1))

        sup = stack.enter_context(
            Supervisor(FTConfig(ckpt_dir=args.ckpt_dir,
                                ckpt_every=args.ckpt_every),
                       state_like={"params": params, "opt": opt_state,
                                   "data_step": np.zeros((), np.int64)},
                       fail_at_step=args.fail_at))
        state, start = sup.resume()
        if start:
            print(f"resumed from checkpoint at step {start}"
                  + (f" ({sup.resume_prewarmed} tuned plans pre-warmed)"
                     if sup.resume_prewarmed else ""))
        params, opt_state = state["params"], state["opt"]

        pipe = HostPipeline(lambda s: batch_at(spec, s), depth=2,
                            producers=2, start_step=start)

        # watchdog actions are real: "rebalance" shrinks this host's batch
        # share and re-plans the stream kernels at the shrunk local shape
        # (the next tuned resolution repopulates the caches); "replace" is
        # the elastic path — single-host smoke can only log it, a pod
        # driver wires elastic.replace_host here
        def replan(host, share):
            from repro.core import planner
            print(f"# straggler {host}: share -> {share}; re-planning "
                  f"local pipes ({planner.plan_cache_info().currsize} "
                  f"cached plans)", flush=True)
            return share

        rebalancer = BatchRebalancer({"host0": max(args.batch, 1)},
                                     replan=replan)
        watchdog = StragglerWatchdog(
            StragglerConfig(), hosts=["host0"], rebalancer=rebalancer,
            on_replace=lambda h: print(f"# straggler {h}: replace "
                                       f"requested (elastic.replace_host "
                                       f"on a pod driver)", flush=True))

        t_hist = []

        def step_fn(state, step):
            params, opt_state = state["params"], state["opt"]
            batch = {k: jnp.asarray(v) for k, v in pipe.get().items()}
            t0 = time.time()
            params, opt_state, metrics = train_step(params, opt_state, batch)
            metrics = jax.device_get(metrics)
            dt = time.time() - t0
            t_hist.append(dt)
            watchdog.step({"host0": dt})
            if step % args.log_every == 0:
                print(f"step {step:5d} loss={float(metrics['loss']):.4f} "
                      f"gnorm={float(metrics.get('grad_norm', 0)):.3f} "
                      f"lr={float(metrics.get('lr', 0)):.2e} {dt*1e3:.0f}ms",
                      flush=True)
            return {"params": params, "opt": opt_state,
                    "data_step": np.asarray(step + 1, np.int64)}

        try:
            state = sup.run({"params": params, "opt": opt_state,
                             "data_step": np.asarray(start, np.int64)},
                            start, args.steps, step_fn)
        finally:
            pipe.stop()
        print(f"done at step {args.steps}; median step "
              f"{np.median(t_hist)*1e3:.0f} ms")
        if args.record_profile:
            print(f"# recorded traffic profile: {len(profile)} buckets -> "
                  f"{args.record_profile}")
        return state


if __name__ == "__main__":
    main()
