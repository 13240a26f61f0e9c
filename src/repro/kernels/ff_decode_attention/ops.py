"""Public op wrapper + cost model for ff_decode_attention."""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import autotune
from repro.core.emitter import cdiv, lane_pack, pack_blockdiag, \
    pack_heads, unpack_blockdiag
from repro.core.pipeline_model import Workload
from repro.core.program import PipePolicy, make_entrypoint
from repro.kernels.ff_decode_attention.kernel import build_program, \
    decode_attention_ff
from repro.kernels.ff_decode_attention.ref import decode_attention_ref
from repro.kernels.registry import KernelCost, register_kernel


def decode_attention_cost(b: int, h: int, kvh: int, s: int, d: int,
                          *, block_kv: int = 128, depth: int = 2,
                          dtype=jnp.bfloat16) -> KernelCost:
    itemsize = jnp.dtype(dtype).itemsize
    flops = 4.0 * b * h * s * d
    hbm = b * kvh * 2 * s * d * itemsize + 2 * b * h * d * itemsize
    g_pad = max(8, -(-(h // kvh) // 8) * 8)
    vmem = 2 * depth * block_kv * d * itemsize + g_pad * d * 4 * 3
    return KernelCost(flops=flops, hbm_bytes=float(hbm), vmem_bytes=vmem)


def decode_attention_workload(b: int, h: int, kvh: int, s: int, d: int,
                              *, block_kv: int = 128, dtype=jnp.bfloat16
                              ) -> Tuple[Workload, Tuple[int, int]]:
    """One word per (b, lane word, kj): a K and a V cache tile of ``p``
    lane-packed heads (:func:`~repro.core.emitter.lane_pack`). The whole
    KV cache streams once — the paper's regular, DLCD-free favourable
    case."""
    itemsize = jnp.dtype(dtype).itemsize
    nkv = cdiv(s, block_kv)
    group = max(h // kvh, 1)
    p = lane_pack(kvh, d)
    w = Workload(
        n_words=b * (kvh // p) * nkv,
        word_bytes=float(2 * block_kv * p * d * itemsize),
        flops_per_word=4.0 * p * group * block_kv * d,
        regular=True,
    )
    return w, (block_kv, p * d)


def paged_decode_attention_workload(b: int, h: int, kvh: int, n_pages: int,
                                    page: int, d: int, *, dtype=jnp.bfloat16
                                    ) -> Tuple[Workload, Tuple[int, int]]:
    """One word per (b, lane word, page): a merged K+V page tile of ``p``
    lane-packed heads gathered through the block table. Same math as
    :func:`decode_attention_workload` at ``block_kv == page``, but the
    stream arrives via an irregular gather."""
    itemsize = jnp.dtype(dtype).itemsize
    group = max(h // kvh, 1)
    p = lane_pack(kvh, d)
    w = Workload(
        n_words=b * (kvh // p) * n_pages,
        word_bytes=float(2 * page * p * d * itemsize),
        flops_per_word=4.0 * p * group * page * d,
        regular=True,
    )
    return w, (2 * page, p * d)


# KV-cache tile candidates for mode="autotune" (the cache stream's word
# size; candidates not dividing the call site's S are skipped at measure)
_TILE_OPTIONS = (
    {"block_kv": 64},
    {"block_kv": 256},
    {"block_kv": 512},
)


def _apply(q, k, v, lengths=None, *, kv_heads: int = None,
           block_kv: int = 128, policy: PipePolicy):
    """Decode attention for one new token.

    q: [B, H, D]; k, v: [B, KVH, S, D]; lengths: [B] int32 (defaults to S).
    Returns [B, H, D]. The wrapper regroups q heads per KV head, pads the
    group to the 8-sublane granule, and lane-packs ``p`` heads per KV word
    (:func:`~repro.core.emitter.lane_pack`; q block-diagonal).
    policy.mode="ff"|"autotune"(measured plan)|"baseline"|"ref".
    """
    del kv_heads    # accepted for legacy signature compatibility
    b, h, d = q.shape
    _, kvh, s, _ = k.shape
    assert h % kvh == 0
    group = h // kvh
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    if policy.mode == "ref":
        qg = q.reshape(b, kvh, group, d)
        return decode_attention_ref(qg, k, v, lengths).reshape(b, h, d)
    g_pad = -(-group // 8) * 8
    p = lane_pack(kvh, d)
    qg = q.reshape(b, kvh, group, d)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_pad - group), (0, 0)))
    qp = pack_blockdiag(qg, p)
    kp, vp = pack_heads(k, p), pack_heads(v, p)
    lens = lengths.astype(jnp.int32)

    def _run(bkv, depth, streams):
        if s % bkv != 0:
            raise ValueError(f"block_kv={bkv} does not divide S={s}")
        out = decode_attention_ff(
            qp, kp, vp, lens, block_kv=bkv, depth=depth, streams=streams,
            scale=1.0 / (d ** 0.5), lane_heads=p,
            interpret=policy.interpret)
        return unpack_blockdiag(out, p)

    w, tile = decode_attention_workload(b, h, kvh, s, d, block_kv=block_kv,
                                        dtype=k.dtype)
    choice = autotune.resolve_call(
        "ff_decode_attention", policy, workload=w, tile=tile, dtype=k.dtype,
        workload_fn=lambda tk: decode_attention_workload(
            b, h, kvh, s, d, block_kv=tk.get("block_kv", block_kv),
            dtype=k.dtype),
        runner=None if autotune.has_tracers(q, k, v, lens) else
        lambda tk, dep, st: lambda: _run(
            tk.get("block_kv", block_kv), dep, st),
        tile_options=_TILE_OPTIONS,
        site={"b": b, "h": h, "kvh": kvh, "s": s, "d": d,
              "block_kv": block_kv},
        site_dynamic=("b", "s"))
    out = _run(choice.tile_kwargs.get("block_kv", block_kv), choice.depth,
               choice.streams)
    return out[:, :, :group, :].reshape(b, h, d)


decode_attention = make_entrypoint("ff_decode_attention", _apply)


def _make_inputs(key):
    q = jax.random.normal(key, (2, 4, 64), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 2, 128, 64),
                          jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 2, 128, 64),
                          jnp.float32)
    lens = jnp.array([70, 128], jnp.int32)
    return (q, k, v, lens), {"block_kv": 64}


def _sweep_inputs(key, site):
    # rebuild concrete operands at a recorded call-site shape (plan sweep);
    # h snaps to a multiple of the recorded KV-head count
    kvh = int(site["kvh"])
    h = max(1, int(site["h"]) // kvh) * kvh
    b, s, d = int(site["b"]), int(site["s"]), int(site["d"])
    dt = jnp.dtype(site.get("dtype", "float32"))
    q = jax.random.normal(key, (b, h, d), dt)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, kvh, s, d), dt)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, kvh, s, d), dt)
    lens = jnp.full((b,), s, jnp.int32)
    return (q, k, v, lens), {"block_kv": int(site.get("block_kv", 128))}


def _smoke_program(*, depth: int = 2, streams: int = 1, tile=None):
    # the smoke shape point of _make_inputs (group 2 -> g_pad 8)
    return build_program(2, 2, 8, 128, 64,
                         block_kv=(tile or {}).get("block_kv", 64),
                         dtype=jnp.float32, depth=depth, streams=streams)


register_kernel(
    name="ff_decode_attention",
    alias="decode_attention",
    op=decode_attention,
    ref=decode_attention_ref,
    cost=decode_attention_cost,
    workload=decode_attention_workload,
    program=_smoke_program,
    make_inputs=_make_inputs,
    bench_kwargs={"b": 8, "h": 64, "kvh": 8, "s": 32768, "d": 128,
                  "dtype": jnp.bfloat16},
    tile_options=_TILE_OPTIONS,
    regular=True,
    tol=2e-4,
    doc="flash-decode vs. long KV caches",
    shard_dims=(0, 0, 0, 0),     # request batch data-parallel
    shard_out_dim=0,
    sweep_inputs=_sweep_inputs,
)
