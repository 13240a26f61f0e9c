"""StreamProgram / PipePolicy API tests.

Covers the declarative redesign end to end: the policy context manager and
deprecation shims, registry-enumerated old-API/new-API/ref equivalence for
every kernel, compile_program correctness on a from-scratch "sixth kernel",
the planner cache keyed by policy (hardware model), and the ff_gather
streams wiring.
"""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import (
    ARRIA_CX,
    TPU_V5E,
    BlockIn,
    Pipe,
    PipePolicy,
    ScalarIn,
    ScratchSpec,
    Stream,
    StreamProgram,
    compile_program,
    current_policy,
    plan_cache_clear,
    plan_cache_info,
    policy,
)
from repro.core import program as program_mod
from repro.kernels.registry import all_kernels

KEY = jax.random.key(7)


def _smoke_call(spec, **op_kwargs):
    args, kw = spec.make_inputs(KEY)
    return np.float32(spec.op(*args, **kw, **op_kwargs))


# ---------------------------------------------------------------------------
# PipePolicy + policy() context manager
# ---------------------------------------------------------------------------

def test_default_policy_is_auto():
    pol = current_policy()
    assert pol == PipePolicy()
    assert pol.mode == "ff" and pol.depth == "auto" and pol.streams == "auto"
    assert pol.hw is TPU_V5E


def test_policy_context_nests_and_restores():
    base = current_policy()
    with policy(mode="baseline") as p1:
        assert current_policy() is p1
        assert p1.mode == "baseline"
        # untouched fields inherit from the enclosing policy
        assert p1.depth == base.depth and p1.hw is base.hw
        with policy(hw=ARRIA_CX, depth=3) as p2:
            assert current_policy().mode == "baseline"
            assert current_policy().hw is ARRIA_CX
            assert current_policy().depth == 3
        assert current_policy() is p1
    assert current_policy() == base


def test_policy_context_accepts_whole_policy():
    pol = PipePolicy(mode="ref", interpret=False)
    with policy(pol):
        assert current_policy() is pol
    with policy(pol, mode="ff"):
        assert current_policy().mode == "ff"
        assert current_policy().interpret is False


def test_pipe_policy_validation():
    with pytest.raises(ValueError, match="depth"):
        PipePolicy(depth="bogus")
    with pytest.raises(ValueError, match="streams"):
        PipePolicy(streams=0)
    with pytest.raises(TypeError, match="mode"):
        PipePolicy(mode=3)


def test_policy_and_legacy_kwargs_conflict():
    from repro.kernels.ff_matmul import matmul
    a = jnp.zeros((8, 8), jnp.float32)
    with pytest.raises(TypeError, match="not both"):
        matmul(a, a, policy=PipePolicy(), depth=2)


def test_legacy_kwargs_warn_once_per_op():
    from repro.kernels.ff_matmul import matmul
    a = jax.random.normal(KEY, (64, 64), jnp.float32)
    program_mod._warned_ops.discard("ff_matmul")
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        matmul(a, a, depth=2, streams=1)
        first = [w for w in rec if issubclass(w.category, DeprecationWarning)]
        matmul(a, a, depth=2, streams=1)
        second = [w for w in rec if issubclass(w.category, DeprecationWarning)]
    assert len(first) == 1 and "deprecated" in str(first[0].message)
    assert len(second) == 1       # no second warning for the same op


# ---------------------------------------------------------------------------
# Registry-enumerated equivalence: old API == new API == ref (satellite)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", all_kernels(), ids=lambda s: s.name)
def test_shim_and_policy_api_equivalent(spec):
    """The deprecated keyword plumbing and PipePolicy must hit the exact
    same compiled program, and both must match the oracle."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        old = _smoke_call(spec, mode="ff", depth=2, streams=1)
    new = _smoke_call(spec, policy=PipePolicy(mode="ff", depth=2, streams=1))
    ref = _smoke_call(spec, policy=PipePolicy(mode="ref"))
    np.testing.assert_array_equal(old, new)
    assert np.max(np.abs(new - ref)) <= spec.tol, spec.name


@pytest.mark.parametrize("spec", all_kernels(), ids=lambda s: s.name)
@pytest.mark.parametrize("mode", ["ff", "baseline"])
def test_every_program_matches_ref_under_auto(spec, mode):
    """compile_program property check: every registered program, planner-
    sized ("auto") pipes, both pipelined and synchronous-baseline modes."""
    out = _smoke_call(
        spec, policy=PipePolicy(mode=mode, depth="auto", streams="auto"))
    ref = _smoke_call(spec, policy=PipePolicy(mode="ref"))
    assert np.max(np.abs(out - ref)) <= spec.tol, (spec.name, mode)


def test_session_policy_reaches_kernels():
    spec = next(s for s in all_kernels() if s.name == "ff_matmul")
    ref = _smoke_call(spec, policy=PipePolicy(mode="ref"))
    with policy(mode="baseline", depth=5):     # depth ignored by baseline
        out = _smoke_call(spec)
    assert np.max(np.abs(out - ref)) <= spec.tol


def test_session_policy_reaches_model_layers():
    """Model layers must derive their policy from the session context, so
    `with repro.policy(mode="baseline")` A/B runs reach model code."""
    from repro.models import layers as L
    q = jax.random.normal(KEY, (1, 32, 2, 64), jnp.float32)
    kv = jax.random.normal(jax.random.fold_in(KEY, 9), (1, 32, 2, 64),
                           jnp.float32)
    ref = L.attention_op(q, kv, kv, causal=True, impl="xla")
    with policy(mode="baseline", depth=4, streams=1):
        out = L.attention_op(q, kv, kv, causal=True, impl="ff")
    assert np.max(np.abs(np.float32(out) - np.float32(ref))) < 2e-4


# ---------------------------------------------------------------------------
# Registered programs are StreamPrograms; repro.ops is registry-generated
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", all_kernels(), ids=lambda s: s.name)
def test_registered_program_declaration(spec):
    prog = spec.program(depth=2, streams=1)
    assert isinstance(prog, StreamProgram)
    assert prog.name == spec.name
    assert prog.n_words >= 1
    assert len(prog.streams) >= 1
    assert prog.vmem_bytes > 0
    for edge in prog.streams:
        assert edge.spec.depth == 2
    if spec.name == "ff_gather":
        assert prog.streams[0].gather
        assert prog.num_scalar_prefetch == 1


def test_ops_namespace_enumerates_registry():
    assert set(repro.ops.names()) == {
        "matmul", "attention", "decode_attention", "chunk_scan", "gather"}
    for spec in all_kernels():
        assert getattr(repro.ops, spec.alias) is spec.op
        assert getattr(repro.ops, spec.name) is spec.op
    with pytest.raises(AttributeError, match="registered"):
        repro.ops.nonexistent_op


# ---------------------------------------------------------------------------
# Plan cache keyed by policy (hardware model rides the cache key)
# ---------------------------------------------------------------------------

def test_plan_cache_hits_keyed_by_policy():
    from repro.kernels.ff_matmul import matmul
    a = jax.random.normal(KEY, (256, 256), jnp.float32)
    plan_cache_clear()
    pol = PipePolicy(depth="auto", streams="auto")
    matmul(a, a, policy=pol)
    info1 = plan_cache_info()
    assert info1.misses == 1
    matmul(a, a, policy=pol)
    info2 = plan_cache_info()
    assert info2.hits == info1.hits + 1 and info2.misses == info1.misses
    # a different hardware model is a different policy -> different plan key
    with policy(hw=ARRIA_CX):
        matmul(a, a)
    info3 = plan_cache_info()
    assert info3.misses == info2.misses + 1


# ---------------------------------------------------------------------------
# ff_gather streams wiring (satellite): planned streams widen the bundle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("streams", [1, 2, 4])
def test_gather_streams_wired_into_row_bundle(streams):
    from repro.kernels.ff_gather import gather, gather_ref
    from repro.kernels.ff_gather.kernel import build_program
    prog = build_program(32, 128, streams=streams)
    assert prog.streams[0].spec.tile[0] == 8 * streams
    assert prog.n_words == 32 // (8 * streams)

    tab = jax.random.normal(KEY, (64, 128), jnp.float32)
    idx = jax.random.randint(jax.random.fold_in(KEY, 1), (29,), 0, 64)
    out = gather(tab, idx, policy=PipePolicy(depth=2, streams=streams))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(gather_ref(tab, idx)))


# ---------------------------------------------------------------------------
# Mixed-precision operands: each Stream edge keeps its own pipe dtype
# ---------------------------------------------------------------------------

def test_mixed_dtype_operands_stream_through_own_pipes():
    pol = PipePolicy(depth=2, streams=1)
    a = jax.random.normal(KEY, (128, 128), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(KEY, 1), (128, 128), jnp.bfloat16)
    out = repro.ops.matmul(a, b, policy=pol)
    ref = repro.ops.matmul(a, b, policy=PipePolicy(mode="ref"))
    assert np.max(np.abs(np.float32(out) - np.float32(ref))) < 2e-1

    q = jax.random.normal(KEY, (2, 128, 64), jnp.float32)
    kv = jax.random.normal(jax.random.fold_in(KEY, 2), (2, 128, 64),
                           jnp.bfloat16)
    out = repro.ops.attention(q, kv, kv, block_q=64, block_kv=64, policy=pol)
    ref = repro.ops.attention(q, kv, kv, policy=PipePolicy(mode="ref"))
    assert np.max(np.abs(np.float32(out) - np.float32(ref))) < 5e-2


# ---------------------------------------------------------------------------
# compile_program on a from-scratch "sixth kernel" (the ~50-line claim)
# ---------------------------------------------------------------------------

def _prefix_sum_program(n_tiles, cols, depth):
    """Running sum of 8-row tiles: one stream edge, one scratch carry."""

    def slicer(ctx, word):
        return ctx.ref("x").at[jax.experimental.pallas.ds(word * 8, 8), :]

    def consumer(ctx):
        carry = ctx.scratch("carry")

        @jax.experimental.pallas.when(ctx.g == 0)
        def _():
            carry[...] = jnp.zeros_like(carry)

        carry[...] += ctx.word("x")[...]
        ctx.out[...] = carry[...]

    return StreamProgram(
        name="tile_prefix_sum",
        n_words=n_tiles,
        inputs=(Stream("x", Pipe(tile=(8, cols), depth=depth), slicer),),
        consumer=consumer,
        out_shape=(n_tiles * 8, cols),
        out_dtype=jnp.float32,
        out_block=(8, cols),
        out_index_map=lambda g: (g, 0),
        scratch=(ScratchSpec("carry", (8, cols), jnp.float32),),
    )


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_compile_program_sixth_kernel(depth):
    import jax.experimental.pallas  # noqa: F401  (used inside the program)
    n_tiles, cols = 6, 128
    x = jax.random.normal(KEY, (n_tiles * 8, cols), jnp.float32)
    out = compile_program(_prefix_sum_program(n_tiles, cols, depth))(x)
    ref = jnp.cumsum(x.reshape(n_tiles, 8, cols), axis=0).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# StreamProgram declaration validation
# ---------------------------------------------------------------------------

def _dummy_stream(name="x"):
    return Stream(name, Pipe(tile=(8, 128)), lambda ctx, w: None)


def test_stream_program_validation():
    kwargs = dict(consumer=lambda ctx: None, out_shape=(8, 128),
                  out_dtype=jnp.float32, out_block=(8, 128),
                  out_index_map=lambda g: (0, 0))
    with pytest.raises(ValueError, match="duplicate"):
        StreamProgram(name="p", n_words=1,
                      inputs=(_dummy_stream("x"), _dummy_stream("x")), **kwargs)
    with pytest.raises(ValueError, match="ScalarIn"):
        StreamProgram(name="p", n_words=1,
                      inputs=(_dummy_stream("x"), ScalarIn("idx")), **kwargs)
    with pytest.raises(ValueError, match="Stream"):
        StreamProgram(name="p", n_words=1,
                      inputs=(BlockIn("b", (8, 128), lambda g: (0, 0)),),
                      **kwargs)
    with pytest.raises(ValueError, match="n_words"):
        StreamProgram(name="p", n_words=0, inputs=(_dummy_stream(),), **kwargs)


def test_policy_is_frozen_and_replaceable():
    pol = PipePolicy()
    with pytest.raises(dataclasses.FrozenInstanceError):
        pol.mode = "baseline"
    assert pol.replace(mode="baseline").mode == "baseline"
    assert pol.mode == "ff"


# ---------------------------------------------------------------------------
# Backend-derived defaults: interpret mode, hardware model, row tiling
# ---------------------------------------------------------------------------

def test_policy_derives_interpret_from_backend():
    on_tpu = jax.default_backend() == "tpu"
    assert PipePolicy().interpret is (not on_tpu)
    assert PipePolicy(interpret=True).interpret is True
    assert PipePolicy(interpret=False).interpret is False
    # the resolved flag is what the plan key carries
    from repro.core import autotune
    sig = autotune._policy_constraints(PipePolicy())
    assert f"|interp{int(not on_tpu)}" in sig


def test_import_leaves_backends_uninitialized():
    """Importing the package must not claim a device: on a TPU host only
    one process may hold the chip, and a parent that merely imports repro
    before starting a child would take it."""
    import subprocess
    import sys
    code = ("import repro, repro.core.program, repro.launch.serve, "
            "repro.launch.train, repro.models, repro.kernels\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n"
            "repro.core.program.current_policy()\n"
            "assert xla_bridge.backends_are_initialized()\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_policy_hw_from_device_kind():
    from repro.core.pipeline_model import TPU_V5E, device_hardware

    class Dev:
        def __init__(self, platform, kind):
            self.platform, self.device_kind = platform, kind

    assert device_hardware(Dev("tpu", "TPU v5 lite")) is TPU_V5E
    assert device_hardware(Dev("cpu", "cpu")) is TPU_V5E
    with pytest.raises(ValueError, match="TPU v9"):
        device_hardware(Dev("tpu", "TPU v9"))
    # an explicit hw wins, and the default on the CPU plans for TPU v5e
    assert PipePolicy(hw=ARRIA_CX).hw is ARRIA_CX
    assert PipePolicy().hw is device_hardware(jax.devices()[0])


@pytest.mark.parametrize("dtype,rows,allowed", [
    (jnp.float32, 16, (1, 2)),
    (jnp.bfloat16, 16, (1,)),
    (jnp.bfloat16, 64, (1, 2, 4)),
    (jnp.int8, 64, (1, 2)),
])
def test_planner_never_splits_below_row_tiling(dtype, rows, allowed):
    """A stream split must leave each DMA whole native row tiles (8 rows
    for 32-bit, 16 for bf16, 32 for int8): the TPU compiler refuses less."""
    from repro.core.pipe import clamp_streams
    from repro.core.pipeline_model import Workload
    from repro.core.planner import plan_pipe
    w = Workload(n_words=64, word_bytes=float(rows * 128 * 4),
                 flops_per_word=0.0)
    plan = plan_pipe(w, (rows, 128), dtype, stream_options=(1, 2, 4))
    assert plan.pipe.streams in allowed
    assert clamp_streams(rows, 4, dtype) == max(allowed)


def test_compile_cache_location(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins untouched; without it the cache goes
    to the fixed in-checkout path on a TPU, and stays off elsewhere."""
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.CHECKOUT_CACHE_DIR.name == ".jax_cache"
    if jax.default_backend() != "tpu":
        assert compile_cache.enable_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == before
