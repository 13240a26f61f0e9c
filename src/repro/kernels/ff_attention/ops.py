"""Public op wrapper + cost model for ff_attention (prefill)."""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import autotune
from repro.core.emitter import cdiv, gather_lanes, lane_pack, pack_heads, \
    pad_to, spread_lanes
from repro.core.pipeline_model import Workload
from repro.core.program import PipePolicy, make_entrypoint
from repro.kernels.ff_attention.kernel import build_program, \
    flash_attention_ff
from repro.kernels.ff_attention.ref import attention_ref
from repro.kernels.registry import KernelCost, register_kernel


def attention_cost(bh: int, s: int, d: int, *, causal: bool = True,
                   block_kv: int = 128, depth: int = 2,
                   dtype=jnp.bfloat16) -> KernelCost:
    """Exact stream costs for one prefill attention call (per the kernel's
    tile schedule). Causal halves the live score blocks."""
    frac = 0.5 if causal else 1.0
    flops = 4.0 * bh * s * s * d * frac            # qk^T and pv matmuls
    itemsize = jnp.dtype(dtype).itemsize
    nq = cdiv(s, 128)
    # K and V are re-streamed once per live q block; q,o move once.
    kv_stream = 2 * s * d * itemsize * nq * frac
    hbm = bh * (kv_stream + 2 * s * d * itemsize)
    vmem = 2 * depth * block_kv * d * itemsize + 128 * d * 4 * 3
    return KernelCost(flops=flops, hbm_bytes=float(hbm), vmem_bytes=vmem)


def attention_workload(bh: int, s: int, d: int, *, causal: bool = True,
                       block_q: int = 128, block_kv: int = 128,
                       dtype=jnp.bfloat16) -> Tuple[Workload, Tuple[int, int]]:
    """One pipe word per (bh, qi, kj) grid step: a K and a V tile. Causal
    predication idles the consumer on dead blocks, not the stream."""
    itemsize = jnp.dtype(dtype).itemsize
    nq, nkv = cdiv(s, block_q), cdiv(s, block_kv)
    frac = 0.5 if causal else 1.0
    w = Workload(
        n_words=bh * nq * nkv,
        word_bytes=float(2 * block_kv * d * itemsize),
        flops_per_word=4.0 * block_q * block_kv * d * frac,
        regular=True,
        store_bytes_per_word=float(block_q * d * itemsize) / nkv,
    )
    return w, (block_kv, d)


# tile candidates for mode="autotune": both KV ring word sizes and the
# q-block revisit factor move the modeled (and measured) word schedule
_TILE_OPTIONS = (
    {"block_q": 64, "block_kv": 64},
    {"block_q": 64, "block_kv": 128},
    {"block_q": 128, "block_kv": 256},
    {"block_q": 256, "block_kv": 128},
)


def _apply(q, k, v, *, kv_groups: int = 1, causal: bool = True,
           block_q: int = 128, block_kv: int = 128,
           policy: PipePolicy):
    """Flash attention over [BH, S, D] tensors (wrapper pads S to blocks).

    policy.mode="ff"|"autotune"(measured plan)|"baseline"(depth=1)|"ref";
    the policy's depth/streams "auto" are planner-sized per call-site shape
    against policy.hw, "measured" resolves through the autotuner's plan
    cache.
    """
    if policy.mode == "ref":
        return attention_ref(q, k, v, kv_groups=kv_groups, causal=causal)
    bh, s, d = q.shape
    skv = k.shape[1]
    # p KV rows side by side in each lane word; a q row reads only its
    # own KV row's lane block (any p dividing the KV row count will do)
    p = lane_pack(k.shape[0], d)
    qw = spread_lanes(q, p, kv_groups)
    kw = pack_heads(k, p)
    vw = pack_heads(v, p)

    def _run(bq, bkv, depth, streams):
        qp = pad_to(qw, bq, 1)
        kp = pad_to(kw, bkv, 1)
        vp = pad_to(vw, bkv, 1)
        if kp.shape[1] > skv and not causal:
            raise ValueError(
                "non-causal attention requires Skv to be a block multiple "
                "(padded keys would receive softmax mass)")
        out = flash_attention_ff(
            qp, kp, vp, kv_groups=kv_groups * p, block_q=bq, block_kv=bkv,
            depth=depth, streams=streams, causal=causal,
            scale=1.0 / (d ** 0.5), interpret=policy.interpret)
        return gather_lanes(out, p, kv_groups)

    w, tile = attention_workload(bh, s, d, causal=causal, block_q=block_q,
                                 block_kv=block_kv, dtype=q.dtype)
    choice = autotune.resolve_call(
        "ff_attention", policy, workload=w, tile=tile, dtype=q.dtype,
        workload_fn=lambda tk: attention_workload(
            bh, s, d, causal=causal, block_q=tk.get("block_q", block_q),
            block_kv=tk.get("block_kv", block_kv), dtype=q.dtype),
        runner=None if autotune.has_tracers(q, k, v) else
        lambda tk, dep, st: lambda: _run(
            tk.get("block_q", block_q), tk.get("block_kv", block_kv),
            dep, st),
        tile_options=_TILE_OPTIONS,
        # the workload is built from the q shape only; skv/kv_groups
        # change the measured kernel
        extra_key=f"skv={skv}|groups={kv_groups}",
        site={"bh": bh, "s": s, "d": d, "skv": skv,
              "kv_groups": kv_groups, "causal": causal,
              "block_q": block_q, "block_kv": block_kv},
        site_dynamic=("bh", "s", "skv"))
    out = _run(choice.tile_kwargs.get("block_q", block_q),
               choice.tile_kwargs.get("block_kv", block_kv),
               choice.depth, choice.streams)
    return out[:, :s, :]


attention = make_entrypoint("ff_attention", _apply)


def _make_inputs(key):
    q = jax.random.normal(key, (2, 192, 64), jnp.float32)
    kv = jax.random.normal(jax.random.fold_in(key, 1), (1, 192, 64),
                           jnp.float32)
    return (q, kv, kv), {"kv_groups": 2, "causal": True, "block_q": 64,
                         "block_kv": 64}


def _sweep_inputs(key, site):
    # rebuild concrete operands at a recorded call-site shape (plan sweep).
    # The KV batch is bh/kv_groups, so bh snaps to the nearest multiple of
    # the recorded group count; causal self-attention keeps s == skv.
    groups = int(site.get("kv_groups", 1))
    kvb = max(1, int(site["bh"]) // groups)
    bh, s, d = kvb * groups, int(site["s"]), int(site["d"])
    skv = s if site.get("causal", True) else int(site.get("skv", s))
    dt = jnp.dtype(site.get("dtype", "float32"))
    q = jax.random.normal(key, (bh, s, d), dt)
    kv = jax.random.normal(jax.random.fold_in(key, 1), (kvb, skv, d), dt)
    return (q, kv, kv), {"kv_groups": groups,
                         "causal": bool(site.get("causal", True)),
                         "block_q": int(site.get("block_q", 128)),
                         "block_kv": int(site.get("block_kv", 128))}


def _smoke_program(*, depth: int = 2, streams: int = 1, tile=None):
    # the smoke shape point of _make_inputs (already block-aligned)
    tile = tile or {}
    return build_program(2, 192, 192, 64, kv_groups=2,
                         block_q=tile.get("block_q", 64),
                         block_kv=tile.get("block_kv", 64), causal=True,
                         dtype=jnp.float32, depth=depth, streams=streams)


register_kernel(
    name="ff_attention",
    alias="attention",
    op=attention,
    ref=attention_ref,
    cost=attention_cost,
    workload=attention_workload,
    program=_smoke_program,
    make_inputs=_make_inputs,
    bench_kwargs={"bh": 32, "s": 8192, "d": 128, "dtype": jnp.bfloat16},
    tile_options=_TILE_OPTIONS,
    regular=True,
    tol=2e-4,
    doc="flash attention prefill, GQA, KV ring pipes",
    shard_dims=(0, 0, 0),        # head-batch dim data-parallel (q and kv
    shard_out_dim=0,             # shard together, preserving kv_groups)
    sweep_inputs=_sweep_inputs,
)
