"""Mesh-aware streams: sharded-stream parity and local-shape planning.

Runs in subprocesses with 8 forced host devices (the main test process
keeps the single real CPU device), like tests/test_distributed.py:

* every registry kernel that declares ``shard_dims`` runs under
  ``shard_map`` and must match the unsharded op and the XLA oracle;
* a kernel compiled inside ``shard_map`` plans against *local* shard
  shapes (asserted via the planner's ``last_plan`` workload) with the
  plan cache keyed by the mesh topology;
* the collective-overlap helpers route their local dot through the
  ``repro.ops.matmul`` stream kernel when given a policy;
* ``pipeline_apply`` keeps GPipe parity with a policy installed;
* ``launch/serve.py --smoke`` runs end-to-end through ``repro.ops``
  under the host mesh.
"""

import os
import subprocess
import sys
import textwrap

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_sub(body: str, n_dev: int = 8, timeout: int = 560) -> str:
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_dev}"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
    """) + textwrap.dedent(body)
    env = {**os.environ, "PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", prog], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


def test_registry_kernels_sharded_parity():
    """Per registry kernel: sharded == unsharded == XLA reference."""
    out = run_sub("""
        from repro.kernels.registry import all_kernels, run_sharded_smoke
        from repro.runtime import sharding as shlib

        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
        covered = 0
        with shlib.use_sharding(mesh):
            for spec in all_kernels():
                if spec.shard_dims is None:     # documented opt-out
                    print(f"parity {spec.name} skipped (no shard_dims)")
                    continue
                _, _, _, err_un, err_ref = run_sharded_smoke(spec, mesh)
                tol = max(spec.tol, 1e-6)
                assert err_un <= tol, (spec.name, "vs unsharded", err_un)
                assert err_ref <= tol, (spec.name, "vs ref", err_ref)
                print(f"parity {spec.name} {err_un:.1e} {err_ref:.1e}")
                covered += 1
        assert covered >= 5, f"only {covered} kernels ran sharded parity"
        print("sharded parity ok")
    """)
    assert "sharded parity ok" in out


def test_shard_map_plans_local_workload_with_mesh_key():
    """Inside shard_map the planner sees the per-shard word schedule, and
    the plan is keyed by the mesh topology (acceptance: Plan workload)."""
    out = run_sub("""
        import repro
        from repro.core import planner
        from repro.kernels.ff_matmul.ops import matmul_workload
        from repro.runtime import sharding as shlib
        from repro.runtime.streams import shard_streams

        mesh = make_mesh((8,), ("data",))
        m_global, n, k = 8 * 192, 160, 136
        a = jax.random.normal(jax.random.key(0), (m_global, k), jnp.float32)
        b = jax.random.normal(jax.random.key(1), (k, n), jnp.float32)

        planner.plan_cache_clear()
        with shlib.use_sharding(mesh):
            f = shard_streams(repro.ops.matmul,
                              in_specs=(P("data"), P(None, None)),
                              out_specs=P("data"))
            out = f(a, b)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(a) @ np.asarray(b), atol=1e-4)

        plan = planner.last_plan("ff_matmul")
        w_local, _ = matmul_workload(m_global // 8, n, k,
                                     (128, 128, 128), jnp.float32)
        w_global, _ = matmul_workload(m_global, n, k,
                                      (128, 128, 128), jnp.float32)
        assert plan.workload == w_local, (plan.workload, w_local)
        assert plan.workload.n_words < w_global.n_words
        assert plan.mesh.token == "data8", plan.mesh
        assert plan.mesh.device_count == 8

        # repeat call: served from the mesh-keyed plan cache, no new miss
        misses = planner.plan_cache_info().misses
        _ = f(a, b)
        info = planner.plan_cache_info()
        assert info.misses == misses and info.hits >= 1, info
        print("local planning ok", plan.workload.n_words, plan.mesh.token)
    """)
    assert "local planning ok" in out


def test_collectives_policy_routes_stream_matmul():
    """allgather_matmul / matmul_reducescatter with a PipePolicy run their
    per-hop dot through repro.ops.matmul and keep exact-shape parity."""
    out = run_sub("""
        from repro.core import PipePolicy, planner
        from repro.runtime import sharding as shlib
        from repro.runtime.collectives import allgather_matmul, \\
            matmul_reducescatter

        mesh = make_mesh((8,), ("d",))
        pol = PipePolicy(interpret=True)
        x = jax.random.normal(jax.random.key(0), (64, 32))
        w = jax.random.normal(jax.random.key(1), (32, 16))
        with shlib.use_sharding(mesh):
            f = jax.shard_map(
                lambda xs, ws: allgather_matmul(xs, ws, "d", policy=pol),
                mesh=mesh, in_specs=(P("d", None), P(None, None)),
                out_specs=P(None, None), check_vma=False)
            got = f(x, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                                   rtol=1e-4, atol=1e-4)
        plan = planner.last_plan("ff_matmul")
        assert plan is not None and plan.mesh.token == "d8", plan

        x2 = jax.random.normal(jax.random.key(2), (64, 128))
        w2 = jax.random.normal(jax.random.key(3), (128, 16))
        with shlib.use_sharding(mesh):
            g = jax.shard_map(
                lambda xs, ws: matmul_reducescatter(xs, ws, "d", policy=pol),
                mesh=mesh, in_specs=(P(None, "d"), P("d", None)),
                out_specs=P("d", None), check_vma=False)
            got2 = g(x2, w2)
        np.testing.assert_allclose(np.asarray(got2), np.asarray(x2 @ w2),
                                   rtol=1e-4, atol=1e-4)
        print("collective stream matmul ok")
    """)
    assert "collective stream matmul ok" in out


def test_pipeline_apply_with_policy_matches_sequential():
    """The stream-schedule rewrite of pipeline_apply keeps GPipe parity,
    with a session policy installed around the stage body."""
    out = run_sub("""
        from repro.core import PipePolicy
        from repro.runtime.pipeline_parallel import pipeline_apply
        from repro.runtime import sharding as shlib

        n_stage, m, mb, d = 4, 8, 4, 16
        mesh = make_mesh((n_stage,), ("pod",))
        ws = jax.random.normal(jax.random.key(0), (n_stage, d, d)) / (d ** 0.5)
        x = jax.random.normal(jax.random.key(1), (m, mb, d))

        def stage(w, h):
            return jnp.tanh(h @ w)

        pol = PipePolicy(interpret=True)
        with shlib.use_sharding(mesh):
            f = jax.shard_map(
                lambda w, x: pipeline_apply(stage, w[0], x, "pod",
                                            policy=pol),
                mesh=mesh, in_specs=(P("pod"), P(None)),
                out_specs=P("pod"), check_vma=False)
            got = f(ws, x)

        want = x
        for s in range(n_stage):
            want = stage(ws[s], want)
        np.testing.assert_allclose(np.asarray(got)[-m:], np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
        print("pipeline policy ok")
    """)
    assert "pipeline policy ok" in out


def test_compile_graph_localizes_node_workloads():
    """compile_graph(sharding=...) plans each node against the per-shard
    word schedule, keyed by the mesh (single-process: synthetic MeshSpec)."""
    out = run_sub("""
        from repro.core import MeshSpec, PipePolicy, planner
        from repro.core.graph import compile_graph
        from repro.models.layers import build_attention_proj_graph

        g = build_attention_proj_graph()
        planner.plan_cache_clear()
        cg_single = compile_graph(g, policy=PipePolicy())
        single = {op: p.workload.n_words
                  for op, p in planner._LAST_PLAN.items()}

        planner.plan_cache_clear()
        mesh = MeshSpec(axes=(("data", 4),))
        cg_mesh = compile_graph(g, policy=PipePolicy(), sharding=mesh)
        for op, plan in planner._LAST_PLAN.items():
            assert plan.mesh.token == "data4", (op, plan.mesh)
            assert plan.workload.n_words <= -(-single[op] // 4) or \\
                plan.workload.n_words == 1, (op, plan.workload.n_words,
                                             single[op])
        print("graph localization ok")
    """, n_dev=1)
    assert "graph localization ok" in out


def test_serve_smoke_runs_through_repro_ops_under_mesh():
    """launch/serve.py --smoke end to end: repro.ops kernels, host mesh."""
    env = {**os.environ, "PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "qwen1_5_0p5b", "--smoke", "--impl", "ff", "--requests", "2",
         "--prompt-len", "12", "--max-new", "4"],
        env=env, capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    assert "impl=ff" in r.stdout
    assert "'data': 4" in r.stdout and "'model': 2" in r.stdout
    assert "decode" in r.stdout
