"""The served path's Pallas kernels compile for a TPU v5e at Qwen1.5-0.5B
widths (hd 64, 16 KV heads, bf16) with ``interpret=False``.

Nothing runs: each case lowers a jitted call against a *described*
``v5e:2x2`` topology (no chip attached) and asserts that the TPU compiler
accepted the kernel (``tpu_custom_call`` in the compiled HLO). This is what
the Pallas interpreter cannot check: DMA slices aligned to the chip's
(8, 128) tiling, narrow dtypes' row packing, and the VMEM limit.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and a test worker
that loads it at import would take it from the others.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.program import PipePolicy

# Qwen1.5-0.5B: d_model 1024, 16 heads = 16 KV heads of hd 64, d_ff 2816
B, H, KVH, HD, D_MODEL, D_FF = 8, 16, 16, 64, 1024, 2816
S_CACHE, PAGE = 2048, 16
BF16 = jnp.bfloat16


def compiled():
    """Kernels compiled for the chip, whatever backend this process has."""
    return PipePolicy(mode="ff", interpret=False)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("block_kv", [PAGE, 128])
def test_decode_attention_compiles(one_chip, block_kv):
    import repro

    def f(q, k, v, lens):
        return repro.ops.decode_attention(q, k, v, lens, block_kv=block_kv,
                                          policy=compiled())

    text = _compile_text(f, one_chip, ((B, H, HD), BF16),
                         ((B, KVH, S_CACHE, HD), BF16),
                         ((B, KVH, S_CACHE, HD), BF16), ((B,), jnp.int32))
    assert "tpu_custom_call" in text


def test_prefill_attention_compiles(one_chip):
    import repro
    s = 256        # one prompt bucket

    def f(q, k, v):
        return repro.ops.attention(q, k, v, kv_groups=H // KVH, causal=True,
                                   block_q=128, block_kv=128,
                                   policy=compiled())

    text = _compile_text(f, one_chip, ((H, s, HD), BF16),
                         ((KVH, s, HD), BF16), ((KVH, s, HD), BF16))
    assert "tpu_custom_call" in text


def test_paged_decode_graph_compiles(one_chip):
    from repro.runtime.paged_kv import paged_decode_attention, pool_lanes
    n_pages = S_CACHE // PAGE
    nb = B * n_pages
    j, lanes = pool_lanes(KVH, HD)
    assert lanes == 128

    def f(q, pool, bt, lens):
        return paged_decode_attention(q, pool, bt, lens, policy=compiled())

    text = _compile_text(f, one_chip, ((B, H, HD), BF16),
                         ((nb, j, 2, PAGE, lanes), BF16),
                         ((B, n_pages), jnp.int32), ((B,), jnp.int32))
    assert "tpu_custom_call" in text


def test_gather_compiles(one_chip):
    import repro

    def f(table, idx):
        return repro.ops.gather(table, idx, policy=compiled())

    text = _compile_text(f, one_chip, ((4096, D_MODEL), BF16),
                         ((64,), jnp.int32))
    assert "tpu_custom_call" in text


def test_matmul_compiles(one_chip):
    import repro

    def f(a, b):
        return repro.ops.matmul(a, b, policy=compiled())

    text = _compile_text(f, one_chip, ((D_MODEL, D_MODEL), BF16),
                         ((D_MODEL, D_FF), BF16))
    assert "tpu_custom_call" in text
