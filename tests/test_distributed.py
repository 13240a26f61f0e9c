"""Multi-device behaviours, run in subprocesses with 8 forced host devices
(the main test process keeps the single real CPU device).

Covers: sharded train step == single-device step, int8 compressed
all-reduce error bound, collective-matmul overlap helpers == plain matmul,
GPipe pipeline == sequential stage application, elastic remesh restore."""

import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_sub(body: str, n_dev: int = 8, timeout: int = 560) -> str:
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_dev}"
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
    """) + textwrap.dedent(body)
    env = {**os.environ, "PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", prog], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    out = run_sub("""
        from repro.configs.base import smoke_config
        from repro.models import build_model
        from repro.launch import steps as steps_lib
        from repro.runtime import sharding as shlib
        from repro.optim import adamw

        cfg = smoke_config("llama3_2_1b")
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        opt_cfg = adamw.AdamWConfig(lr_peak=1e-3, warmup_steps=1, total_steps=10)
        opt_init, _ = steps_lib.opt_init_and_update("adamw", opt_cfg)
        batch = {"tokens": jax.random.randint(jax.random.key(1), (8, 32), 0, cfg.vocab),
                 "labels": jax.random.randint(jax.random.key(2), (8, 32), 0, cfg.vocab)}

        # single-device reference
        ts = steps_lib.make_train_step(model, opt_cfg=opt_cfg)
        p1, _, m1 = jax.jit(ts)(params, opt_init(params), batch)

        # sharded over (data=4, model=2)
        mesh = make_mesh((4, 2), ("data", "model"))
        with shlib.use_sharding(mesh):
            p2, _, m2 = jax.jit(ts)(params, opt_init(params), batch)
        d = max(float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
        print("maxdiff", d, "loss", float(m1["loss"]), float(m2["loss"]))
        # sharded reductions reorder float sums; AdamW's rsqrt amplifies the
        # epsilon-scale grad differences into ~1e-4 param deltas after one step
        assert d < 1e-3, d
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
    """)
    assert "maxdiff" in out


def test_compressed_allreduce_error_bound():
    out = run_sub("""
        from repro.optim.compression import compressed_allreduce, quantize
        mesh = make_mesh((8,), ("d",))
        x = jax.random.normal(jax.random.key(0), (8, 64, 128)) * 3.0
        f = shard_map(lambda s: compressed_allreduce(s, "d"),
                      mesh=mesh, in_specs=P("d"), out_specs=P("d"))
        got = f(x)                       # mean over devices, each row = mean
        want = jnp.mean(x, axis=0, keepdims=True)
        err = float(jnp.max(jnp.abs(got[0] - want[0])))
        # per-device quantization error <= max|x|/127; mean preserves bound
        bound = float(jnp.max(jnp.abs(x))) / 127.0 + 1e-6
        print("err", err, "bound", bound)
        assert err <= bound, (err, bound)
    """)
    assert "err" in out


def test_allgather_matmul_overlap_equals_plain():
    out = run_sub("""
        from repro.runtime.collectives import allgather_matmul, matmul_reducescatter
        mesh = make_mesh((8,), ("d",))
        x = jax.random.normal(jax.random.key(0), (64, 32))
        w = jax.random.normal(jax.random.key(1), (32, 16))
        f = shard_map(lambda xs, w: allgather_matmul(xs, w, "d"),
                      mesh=mesh, in_specs=(P("d", None), P(None, None)),
                      out_specs=P(None, None), check_vma=False)
        got = f(x, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                                   rtol=1e-5, atol=1e-5)

        # matmul + reduce-scatter: x [m, k] sharded on k
        x2 = jax.random.normal(jax.random.key(2), (64, 128))
        w2 = jax.random.normal(jax.random.key(3), (128, 16))
        g = shard_map(lambda xs, ws: matmul_reducescatter(xs, ws, "d"),
                      mesh=mesh, in_specs=(P(None, "d"), P("d", None)),
                      out_specs=P("d", None), check_vma=False)
        got2 = g(x2, w2)
        np.testing.assert_allclose(np.asarray(got2), np.asarray(x2 @ w2),
                                   rtol=1e-4, atol=1e-4)
        print("overlap ok")
    """)
    assert "overlap ok" in out


def test_pipeline_parallel_matches_sequential():
    out = run_sub("""
        from repro.runtime.pipeline_parallel import pipeline_apply
        n_stage, m, mb, d = 4, 8, 4, 16
        mesh = make_mesh((n_stage,), ("pod",))
        ws = jax.random.normal(jax.random.key(0), (n_stage, d, d)) / (d ** 0.5)
        x = jax.random.normal(jax.random.key(1), (m, mb, d))

        def stage(w, h):
            return jnp.tanh(h @ w)

        f = shard_map(lambda w, x: pipeline_apply(stage, w[0], x, "pod"),
                      mesh=mesh, in_specs=(P("pod"), P(None)),
                      out_specs=P("pod"), check_vma=False)
        got = f(ws, x)            # [n_stage * M, mb, d]; last stage banks outs

        want = x
        for s in range(n_stage):
            want = stage(ws[s], want)
        np.testing.assert_allclose(np.asarray(got)[-m:],
                                   np.asarray(want), rtol=1e-4, atol=1e-4)
        print("pipeline ok")
    """)
    assert "pipeline ok" in out


@pytest.mark.slow
def test_elastic_remesh_restore(tmp_path):
    out = run_sub(f"""
        from repro.configs.base import smoke_config
        from repro.models import build_model
        from repro.runtime import sharding as shlib
        from repro.runtime.elastic import remesh_restore, survivable_mesh
        from repro.checkpoint import save

        cfg = smoke_config("qwen1_5_0p5b")
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        save(r"{tmp_path}", 5, params)

        # "pod loss": restore onto a 4-device mesh (model axis kept at 2)
        devs = jax.devices()[:4]
        mesh = survivable_mesh(devs, model_axis=2)
        state, step = remesh_restore(r"{tmp_path}", model.abstract_params(),
                                     model.param_axes(), mesh)
        assert step == 5
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        shs = {{str(l.sharding) for l in jax.tree.leaves(state)}}
        print("remesh ok", len(shs))
    """)
    assert "remesh ok" in out


def test_production_mesh_shapes():
    out = run_sub("""
        from repro.launch.mesh import make_production_mesh
        m1 = make_production_mesh()
        assert m1.shape == {"data": 16, "model": 16}, m1.shape
        m2 = make_production_mesh(multi_pod=True)
        assert m2.shape == {"pod": 2, "data": 16, "model": 16}, m2.shape
        print("mesh ok")
    """, n_dev=512)
    assert "mesh ok" in out
