"""Chaos harness: fault injection against the resilience + plan stack.

Each scenario is an orchestrated subprocess experiment (the injected
fault kills, signals, or degrades a *real* training process built on the
StreamProgram/autotune stack) with a machine-checkable outcome:

* ``kill-restart`` — SIGKILL mid-run (uncatchable, between checkpoints).
  The restart runs with a **cold plan cache** and must (a) resume from
  the newest checkpoint, (b) pre-warm the tuned-plan chain from the
  checkpoint's plan snapshot — zero re-measurements, every call site a
  memory hit — and (c) finish with a final state bitwise identical to an
  uninterrupted control run.
* ``sigterm-drain`` — preemption notice landing exactly on a
  ``ckpt_every`` boundary: the supervisor drains the step, saves exactly
  once (no double checkpoint), exits 0; resuming completes bitwise
  identically to the control run.
* ``evict-remesh`` — a 2-pod job loses a pod. ``replace_host`` (the
  watchdog's "replace" action, end to end) must restore shard-exact
  state onto the survivable mesh, drop every stale-mesh plan, and serve
  the first post-remesh call site from the swept PlanDB for the *new*
  topology — never the 2-pod plan, and without re-measuring.
* ``slow-host`` — an injected straggler trips the MAD outlier model;
  the watchdog's "rebalance" action shrinks the slow host's data share
  via :class:`~repro.runtime.stragglers.BatchRebalancer` and re-plans
  its local pipes through ``shard_streams`` at the shrunk shard shape.

``run_scenarios`` drives all four and returns the metrics dict that
``benchmarks/run.py --chaos`` writes to ``BENCH_chaos.json`` (recovery
seconds, bitwise flags, plan-stat breakdowns), gating CI on ``ok``.

Workers run as ``python -m repro.runtime.chaos <scenario> ...`` so the
orchestrator controls their device topology (``XLA_FLAGS``) and plan
caches per process — the restart legitimately starts cold. This is a
CPU-only harness: every worker is pinned to ``JAX_PLATFORMS=cpu`` with
forced host devices. It never runs on the chip, where one process holds
the TPU and a child that needs it would fail or hang.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

_SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))

# one matmul call site: (DIM, DIM) @ (DIM, DIM), a single 128^3 tile
DIM = 128

# generous wall bound for "restart -> first productive step" (includes
# process start + jax import + restore + prewarm; interpret-mode CPU)
RECOVERY_BOUND_S = 300.0


def _write_report(path: Optional[str], report: Dict[str, Any]) -> None:
    print("REPORT " + json.dumps(report, sort_keys=True), flush=True)
    if path:
        with open(path, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
            f.write("\n")


# ---------------------------------------------------------------------------
# Workers (run in subprocesses; heavy imports stay function-local)
# ---------------------------------------------------------------------------


def _worker_train(args) -> None:
    """Deterministic supervised loop on the autotuned matmul kernel.

    State evolves as ``w <- 0.99*w + 0.01*tanh(x_step @ w)`` with
    ``x_step`` derived from the step index — pure function of (step,
    state), so a killed-and-resumed run is bitwise identical to an
    uninterrupted one. ``--kill-at`` SIGKILLs after that step completes
    (before its boundary checkpoint); ``--sigterm-at`` delivers a real
    SIGTERM the supervisor must drain."""
    import hashlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro.ops
    from repro.core import PipePolicy, autotune
    from repro.runtime.fault_tolerance import FTConfig, Supervisor

    t_start = time.perf_counter()
    pol = PipePolicy(mode="autotune")
    with autotune.tuning_config(cache_path=args.plan_cache, warmup=0,
                                iters=1, top_k=2):
        cfg = FTConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                       keep_last=8)
        like = {"w": np.zeros((DIM, DIM), np.float32)}
        with Supervisor(cfg, like) as sup:
            t0 = time.perf_counter()
            state, start = sup.resume()
            resume_s = time.perf_counter() - t0
            autotune.plan_stats_clear()     # count post-resume resolutions

            def step_fn(state, step):
                x = jax.random.normal(jax.random.key(step), (DIM, DIM),
                                      jnp.float32)
                y = repro.ops.matmul(x, jnp.asarray(state["w"]), policy=pol)
                w = 0.99 * jnp.asarray(state["w"]) + 0.01 * jnp.tanh(y)
                return {"w": np.asarray(w)}

            progress = {"step": start, "first_step_s": None}

            def on_step(step, _state):
                if progress["first_step_s"] is None:
                    progress["first_step_s"] = time.perf_counter() - t_start
                progress["step"] = step
                print(f"step {step}", flush=True)
                if args.kill_at is not None and step == args.kill_at:
                    os.kill(os.getpid(), signal.SIGKILL)
                if args.sigterm_at is not None and step == args.sigterm_at:
                    os.kill(os.getpid(), signal.SIGTERM)

            state = sup.run(state, start, args.steps, step_fn,
                            on_step=on_step)
            report = {
                "scenario": "train",
                "resumed_from": start,
                "final_step": progress["step"],
                "preempted": sup.preempted,
                "save_count": sup.save_count,
                "prewarmed": sup.resume_prewarmed,
                "plan_stats": autotune.plan_stats_snapshot(),
                "resume_s": resume_s,
                "first_step_s": progress["first_step_s"],
                "total_s": time.perf_counter() - t_start,
                "state_sha256": hashlib.sha256(
                    np.ascontiguousarray(state["w"]).tobytes()).hexdigest(),
            }
    _write_report(args.report, report)


def _worker_remesh(args) -> None:
    """2-pod job loses a pod; replace_host must be plan-correct.

    A PlanDB is swept for the *surviving* topology up front (the release
    artifact a fleet would ship), the job tunes and checkpoints under
    the 2-pod mesh, then half the devices "fail". Asserts: shard-exact
    state on the new mesh, stale-mesh planner/autotune entries dropped,
    and the first post-remesh call site served from the PlanDB (not the
    stale plan, not a re-measurement)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro.ops
    from repro.checkpoint import save
    from repro.core import PipePolicy, autotune, planner
    from repro.core.meshspec import MeshSpec
    from repro.plans import PlanDB
    from repro.plans.registry import plan_namespace
    from repro.runtime import sharding as shlib
    from repro.runtime.elastic import last_remesh, replace_host, \
        survivable_mesh

    base = args.dir
    host_cache = os.path.join(base, "host_cache.json")
    sweep_cache = os.path.join(base, "sweep_cache.json")
    db_path = os.path.join(base, "plandb.json")
    ckpt = os.path.join(base, "ckpt")

    old_spec = MeshSpec((("pod", 2), ("data", 2), ("model", 2)))
    new_spec = MeshSpec((("data", 2), ("model", 2)))
    a = jax.random.normal(jax.random.key(1), (DIM, DIM), jnp.float32)
    b = jax.random.normal(jax.random.key(2), (DIM, DIM), jnp.float32)

    def pol(spec):
        return PipePolicy(mode="autotune", mesh=spec)

    # offline sweep for the topology we will *fail over to* -> PlanDB
    with autotune.tuning_config(cache_path=sweep_cache, warmup=0, iters=1,
                                top_k=2):
        repro.ops.matmul(a, b, policy=pol(new_spec))
        db = PlanDB()
        ns = plan_namespace()
        for key, rec in autotune.load_plans(sweep_cache).items():
            db.put(ns, key, rec)
        db.save(db_path)
    autotune.tuned_cache_clear()

    with autotune.tuning_config(cache_path=host_cache, warmup=0, iters=1,
                                top_k=2, plan_db=db_path):
        # phase 1: healthy 2-pod job — tune + checkpoint
        old_mesh = survivable_mesh(jax.devices(), model_axis=2, pod_axis=2)
        params = {"w": np.asarray(jax.random.normal(
            jax.random.key(0), (2 * DIM, DIM), jnp.float32))}
        with shlib.use_sharding(old_mesh):
            save(ckpt, 3, params)
            repro.ops.matmul(a, b, policy=pol(old_spec))
        assert planner.last_plan("ff_matmul").mesh == old_spec

        # pod loss -> the watchdog's "replace" action, end to end
        autotune.plan_stats_clear()
        t_fail = time.perf_counter()
        like = {"w": jax.ShapeDtypeStruct((2 * DIM, DIM), jnp.float32)}
        axes = {"w": ("batch", None)}
        state, step, new_mesh = replace_host(
            ckpt, like, axes, jax.devices()[:4], model_axis=2,
            plan_db=db_path)
        rep = last_remesh()
        assert step == 3, step
        assert rep.mesh == new_spec, rep
        assert rep.planner_dropped >= 1, rep
        assert rep.autotune_dropped >= 1, rep
        assert rep.plan_db_records >= 1, rep
        np.testing.assert_array_equal(np.asarray(state["w"]), params["w"])

        # first call site under the new topology: swept plan, never the
        # stale 2-pod plan, no measurement
        with shlib.use_sharding(new_mesh):
            repro.ops.matmul(a, b, policy=pol(new_spec))
        recovery_s = time.perf_counter() - t_fail
        rec = autotune.last_record("ff_matmul")
        assert rec is not None and rec.get("mesh") == new_spec.token, rec
        assert rec.get("source") == "plandb", rec
        # the stale 2-pod plan is gone from the planner cache entirely
        stale = planner.last_plan("ff_matmul")
        assert stale is None or stale.mesh != old_spec, stale
        stats = autotune.plan_stats_snapshot()
        assert stats.get("plandb", 0) >= 1, stats
        assert stats.get("measured", 0) == 0, stats

    _write_report(args.report, {
        "scenario": "remesh",
        "ok": True,
        "old_mesh": old_spec.token,
        "new_mesh": rep.mesh.token,
        "planner_dropped": rep.planner_dropped,
        "autotune_dropped": rep.autotune_dropped,
        "plan_db_records": rep.plan_db_records,
        "post_remesh_source": rec.get("source"),
        "post_remesh_mesh": rec.get("mesh"),
        "post_remesh_stats": stats,
        "recovery_s": recovery_s,
    })


def _worker_slowhost(args) -> None:
    """Injected straggler -> MAD detection -> rebalance -> re-plan.

    Two hosts share a data batch; host h1 turns 2x slow with realistic
    per-step jitter (so the MAD path, not the degenerate slow_factor
    fallback, does the detecting). The watchdog's rebalance must shrink
    h1's share and the hook re-plans the local pipes through
    ``shard_streams`` — asserted via the planner's last_plan workload
    shrinking under the mesh-tagged key."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    import repro.ops
    from repro.core import planner
    from repro.runtime import sharding as shlib
    from repro.runtime.streams import shard_streams
    from repro.runtime.stragglers import (BatchRebalancer, StragglerConfig,
                                          StragglerWatchdog)

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))
    b = jax.random.normal(jax.random.key(2), (DIM, DIM), jnp.float32)

    def plan_local():
        # re-plan the local pipes at the current global share total:
        # shard_streams plans inside shard_map, i.e. at shard-local shape
        m_global = rb.total() * DIM
        a = jnp.zeros((m_global, DIM), jnp.float32)
        with shlib.use_sharding(mesh):
            f = shard_streams(repro.ops.matmul,
                              in_specs=(P("data"), P(None, None)),
                              out_specs=P("data"))
            f(a, b)
        plan = planner.last_plan("ff_matmul")
        return {"mesh": plan.mesh.token, "n_words": plan.workload.n_words}

    def replan(host, share):
        out = plan_local()
        out.update(host=host, share=share)
        return out

    rb = BatchRebalancer({"h0": 4, "h1": 4}, replan=replan)
    before = plan_local()
    cfg = StragglerConfig(window=16, tolerate=3, evict_after=64,
                          slow_factor=1.5, mad_factor=5.0)
    wd = StragglerWatchdog(cfg, hosts=["h0", "h1"], rebalancer=rb)

    slow_from, strikes_seen = 3, 0
    for i in range(10):
        jitter = 0.005 * ((i * 7) % 5 - 2)      # MAD > 0: realistic noise
        t0 = 1.0 + jitter
        t1 = 2.0 + jitter if i >= slow_from else t0
        acts = wd.observe_step({"h0": t0, "h1": t1})
        strikes_seen += int(acts.get("h1") != "none")
        wd.mitigate(acts)

    thr = wd._threshold()
    med = 1.0
    assert thr < cfg.slow_factor * med, (thr, "MAD path not taken")
    assert any(m["action"] == "rebalance" for m in wd.mitigations), \
        wd.mitigations
    after = rb.last_replan["h1"]
    assert rb.shares["h1"] < 4, rb.shares
    assert after["mesh"] == "data2", after
    assert after["n_words"] < before["n_words"], (before, after)

    _write_report(args.report, {
        "scenario": "slowhost",
        "ok": True,
        "threshold": thr,
        "mad_path": thr < cfg.slow_factor * med,
        "share_before": 4,
        "share_after": rb.shares["h1"],
        "n_words_before": before["n_words"],
        "n_words_after": after["n_words"],
        "replan_mesh": after["mesh"],
        "mitigations": wd.mitigations,
    })


# ---------------------------------------------------------------------------
# Orchestration (runs in the parent process; jax-free)
# ---------------------------------------------------------------------------


def _worker_env(n_dev: Optional[int] = None) -> Dict[str, str]:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env["PYTHONPATH"] = _SRC + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    if n_dev:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={n_dev}"
    return env


def _run_worker(cmd_args: List[str], *, n_dev: Optional[int] = None,
                timeout: int = 600):
    cmd = [sys.executable, "-m", "repro.runtime.chaos"] + cmd_args
    t0 = time.perf_counter()
    r = subprocess.run(cmd, env=_worker_env(n_dev), capture_output=True,
                       text=True, timeout=timeout)
    return r, time.perf_counter() - t0


def _load_report(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _train_args(ckpt: str, cache: str, report: str, *, steps: int,
                ckpt_every: int, kill_at: Optional[int] = None,
                sigterm_at: Optional[int] = None) -> List[str]:
    out = ["train", "--ckpt-dir", ckpt, "--plan-cache", cache,
           "--report", report, "--steps", str(steps),
           "--ckpt-every", str(ckpt_every)]
    if kill_at is not None:
        out += ["--kill-at", str(kill_at)]
    if sigterm_at is not None:
        out += ["--sigterm-at", str(sigterm_at)]
    return out


def scenario_kill_restart(workdir: str, *, steps: int = 10, kill_at: int = 7,
                          ckpt_every: int = 3,
                          timeout: int = 600) -> Dict[str, Any]:
    """SIGKILL mid-run; cold-cache restart must be bitwise + pre-warmed."""
    base = os.path.join(workdir, "kill")
    os.makedirs(base, exist_ok=True)
    ckpt = os.path.join(base, "ckpt")
    reports = {k: os.path.join(base, f"report_{k}.json") for k in "abc"}

    rA, _ = _run_worker(_train_args(
        ckpt, os.path.join(base, "cache_a.json"), reports["a"],
        steps=steps, ckpt_every=ckpt_every, kill_at=kill_at),
        timeout=timeout)
    killed = rA.returncode == -signal.SIGKILL

    # restart with a COLD plan cache: the checkpoint snapshot is the only
    # warm source — measured must stay 0
    rB, wall_b = _run_worker(_train_args(
        ckpt, os.path.join(base, "cache_b.json"), reports["b"],
        steps=steps, ckpt_every=ckpt_every), timeout=timeout)
    # uninterrupted control run (own checkpoint dir + cache)
    rC, _ = _run_worker(_train_args(
        os.path.join(base, "ckpt_control"),
        os.path.join(base, "cache_c.json"), reports["c"],
        steps=steps, ckpt_every=ckpt_every), timeout=timeout)

    out: Dict[str, Any] = {"killed": killed, "kill_rc": rA.returncode,
                           "restart_rc": rB.returncode,
                           "control_rc": rC.returncode}
    if rB.returncode != 0 or rC.returncode != 0:
        out.update(ok=False, stderr=(rB.stderr + rC.stderr)[-2000:])
        return out
    rb, rc = _load_report(reports["b"]), _load_report(reports["c"])
    expect_resume = kill_at - (kill_at % ckpt_every)
    recovery_s = rb["first_step_s"]
    stats = rb["plan_stats"]
    out.update(
        ok=(killed
            and rb["resumed_from"] == expect_resume
            and rb["prewarmed"] >= 1
            and stats.get("measured", 0) == 0
            and stats.get("hits", 0) >= steps - expect_resume
            and rb["state_sha256"] == rc["state_sha256"]
            and recovery_s <= RECOVERY_BOUND_S),
        bitwise_identical=rb["state_sha256"] == rc["state_sha256"],
        resume_step=rb["resumed_from"], expect_resume=expect_resume,
        prewarmed=rb["prewarmed"], restart_plan_stats=stats,
        recovery_s=recovery_s, recovery_bound_s=RECOVERY_BOUND_S,
        restart_wall_s=wall_b)
    return out


def scenario_sigterm_drain(workdir: str, *, steps: int = 12,
                           sigterm_at: int = 6, ckpt_every: int = 3,
                           timeout: int = 600) -> Dict[str, Any]:
    """Preemption on a ckpt boundary: drain, save once, resume bitwise."""
    base = os.path.join(workdir, "sigterm")
    os.makedirs(base, exist_ok=True)
    ckpt = os.path.join(base, "ckpt")
    reports = {k: os.path.join(base, f"report_{k}.json") for k in "abc"}
    assert sigterm_at % ckpt_every == 0, \
        "scenario targets the boundary-coincident preemption"

    rA, _ = _run_worker(_train_args(
        ckpt, os.path.join(base, "cache_a.json"), reports["a"],
        steps=steps, ckpt_every=ckpt_every, sigterm_at=sigterm_at),
        timeout=timeout)
    rB, _ = _run_worker(_train_args(
        ckpt, os.path.join(base, "cache_b.json"), reports["b"],
        steps=steps, ckpt_every=ckpt_every), timeout=timeout)
    rC, _ = _run_worker(_train_args(
        os.path.join(base, "ckpt_control"),
        os.path.join(base, "cache_c.json"), reports["c"],
        steps=steps, ckpt_every=ckpt_every), timeout=timeout)

    out: Dict[str, Any] = {"drain_rc": rA.returncode,
                           "resume_rc": rB.returncode,
                           "control_rc": rC.returncode}
    if rA.returncode != 0 or rB.returncode != 0 or rC.returncode != 0:
        out.update(ok=False,
                   stderr=(rA.stderr + rB.stderr + rC.stderr)[-2000:])
        return out
    ra, rb, rc = (_load_report(reports[k]) for k in "abc")
    expected_saves = sigterm_at // ckpt_every   # drain save deduplicated
    out.update(
        ok=(ra["preempted"]
            and ra["final_step"] == sigterm_at
            and ra["save_count"] == expected_saves
            and rb["resumed_from"] == sigterm_at
            and rb["state_sha256"] == rc["state_sha256"]),
        preempted=ra["preempted"], drained_at=ra["final_step"],
        save_count=ra["save_count"], expected_saves=expected_saves,
        resume_step=rb["resumed_from"],
        bitwise_identical=rb["state_sha256"] == rc["state_sha256"])
    return out


def scenario_evict_remesh(workdir: str, *,
                          timeout: int = 600) -> Dict[str, Any]:
    """Pod loss: replace_host keeps plans correct for the new topology."""
    base = os.path.join(workdir, "remesh")
    os.makedirs(base, exist_ok=True)
    report = os.path.join(base, "report.json")
    r, wall = _run_worker(["remesh", "--dir", base, "--report", report],
                          n_dev=8, timeout=timeout)
    if r.returncode != 0:
        return {"ok": False, "rc": r.returncode, "stderr": r.stderr[-2000:]}
    out = _load_report(report)
    out["ok"] = bool(out.get("ok")) and out["recovery_s"] <= RECOVERY_BOUND_S
    out["recovery_bound_s"] = RECOVERY_BOUND_S
    out["wall_s"] = wall
    return out


def scenario_slow_host(workdir: str, *,
                       timeout: int = 600) -> Dict[str, Any]:
    """Straggler: MAD detection -> rebalance -> shrunk-shard re-plan."""
    base = os.path.join(workdir, "slowhost")
    os.makedirs(base, exist_ok=True)
    report = os.path.join(base, "report.json")
    r, wall = _run_worker(["slowhost", "--report", report], n_dev=2,
                          timeout=timeout)
    if r.returncode != 0:
        return {"ok": False, "rc": r.returncode, "stderr": r.stderr[-2000:]}
    out = _load_report(report)
    out["wall_s"] = wall
    return out


def run_scenarios(workdir: Optional[str] = None, *, smoke: bool = True,
                  timeout: int = 600) -> Dict[str, Any]:
    """Run the full chaos suite; the BENCH_chaos.json payload."""
    workdir = workdir or tempfile.mkdtemp(prefix="repro_chaos_")
    steps = 10 if smoke else 24
    t0 = time.perf_counter()
    scenarios = {
        "kill_restart": scenario_kill_restart(
            workdir, steps=steps, kill_at=7, ckpt_every=3, timeout=timeout),
        "sigterm_drain": scenario_sigterm_drain(
            workdir, steps=steps + 2, sigterm_at=6, ckpt_every=3,
            timeout=timeout),
        "evict_remesh": scenario_evict_remesh(workdir, timeout=timeout),
        "slow_host": scenario_slow_host(workdir, timeout=timeout),
    }
    return {"suite": "chaos", "smoke": smoke, "workdir": workdir,
            "wall_s": time.perf_counter() - t0,
            "scenarios": scenarios,
            "ok": all(s.get("ok") for s in scenarios.values())}


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    sub = parser.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("train", help="deterministic supervised worker")
    p.add_argument("--ckpt-dir", required=True)
    p.add_argument("--plan-cache", required=True)
    p.add_argument("--report", default="")
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--ckpt-every", type=int, default=3)
    p.add_argument("--kill-at", type=int, default=None)
    p.add_argument("--sigterm-at", type=int, default=None)

    p = sub.add_parser("remesh", help="pod-loss replace_host worker")
    p.add_argument("--dir", required=True)
    p.add_argument("--report", default="")

    p = sub.add_parser("slowhost", help="straggler rebalance worker")
    p.add_argument("--report", default="")

    p = sub.add_parser("suite", help="orchestrate all scenarios")
    p.add_argument("--workdir", default=None)
    p.add_argument("--full", action="store_true")
    p.add_argument("--json", default="")

    args = parser.parse_args(argv)
    if args.cmd == "train":
        _worker_train(args)
    elif args.cmd == "remesh":
        _worker_remesh(args)
    elif args.cmd == "slowhost":
        _worker_slowhost(args)
    else:
        result = run_scenarios(args.workdir, smoke=not args.full)
        _write_report(args.json, result)
        return 0 if result["ok"] else 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
