"""Shared building blocks for all model families.

Params are plain nested dicts of arrays. Each model module declares its
parameters as :class:`ParamSpec` trees, which give us three views for free:

  * ``init``      — materialized random params (smoke tests / real training)
  * ``abstract``  — ShapeDtypeStruct stand-ins (dry-run lowering, no alloc)
  * ``axes``      — logical sharding axes per leaf (runtime.sharding rules)

Attention/scan/matmul call sites go through ``repro.kernels`` wrappers with
an ``impl`` switch: "xla" (HLO-visible reference path — used when lowering
for the dry-run and on CPU) or "ff" (the feed-forward Pallas kernels — the
TPU fast path; compiled on a TPU, interpreted elsewhere).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.sharding import constrain

# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    dtype: Any = jnp.float32
    init: str = "normal"          # "normal" | "zeros" | "ones" | "small"
    scale: Optional[float] = None  # override fan-in scale

    def initializer(self, key: jax.Array) -> jnp.ndarray:
        if self.init == "zeros":
            return jnp.zeros(self.shape, self.dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, self.dtype)
        if self.init == "small":
            return 0.01 * jax.random.normal(key, self.shape, self.dtype)
        # fan-in = product of all non-output dims, skipping the stacked layer
        # dim (a [d, heads, hd] projection must scale by 1/sqrt(d), not
        # 1/sqrt(heads) — the old shape[-2] rule exploded wide attention)
        dims = self.shape
        if self.axes and self.axes[0] == "layers":
            dims = dims[1:]
        fan_in = max(int(np.prod(dims[:-1])), 1) if len(dims) >= 2 \
            else dims[-1]
        scale = self.scale if self.scale is not None else 1.0 / math.sqrt(fan_in)
        return scale * jax.random.normal(key, self.shape, self.dtype)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def init_params(specs, key: jax.Array):
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(
        treedef, [s.initializer(k) for s, k in zip(leaves, keys)])


def abstract_params(specs):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), specs, is_leaf=is_spec)


def param_axes(specs):
    return jax.tree.map(lambda s: s.axes, specs, is_leaf=is_spec)


def param_count(specs) -> int:
    return sum(int(np.prod(s.shape))
               for s in jax.tree.leaves(specs, is_leaf=is_spec))


# ---------------------------------------------------------------------------
# Norms / activations
# ---------------------------------------------------------------------------


# NOTE (§Perf it5, refuted): applying the norm scale in bf16 (f32 stats
# only) was tried to shrink boundary collectives; collective bytes did not
# move and HBM bytes **rose** 18% (lost fusion in the backward). Reverted to
# f32-internal norms.
def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dt)


def layernorm(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
              eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


def norm_apply(kind: str, x, p):
    if kind == "rmsnorm":
        return rmsnorm(x, p["w"])
    return layernorm(x, p["w"], p["b"])


def norm_specs(kind: str, d: int) -> Dict[str, ParamSpec]:
    s = {"w": ParamSpec((d,), ("embed",), init="ones")}
    if kind == "layernorm":
        s["b"] = ParamSpec((d,), ("embed",), init="zeros")
    return s


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
         dim: Optional[int] = None) -> jnp.ndarray:
    """Rotary embedding. x: [..., S, H, D]; positions: [..., S]."""
    d = dim or x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., :, None].astype(jnp.float32) * freqs   # [..., S, half]
    cos = jnp.cos(ang)[..., None, :]                            # [..., S, 1, half]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:d]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    if d < x.shape[-1]:
        rot = jnp.concatenate([rot, x[..., d:]], axis=-1)
    return rot.astype(x.dtype)


def sinusoidal_positions(s: int, d: int) -> jnp.ndarray:
    pos = np.arange(s)[:, None]
    dim = np.arange(d // 2)[None, :]
    ang = pos / np.power(10000.0, 2 * dim / d)
    return jnp.asarray(
        np.concatenate([np.sin(ang), np.cos(ang)], axis=-1), jnp.float32)


# ---------------------------------------------------------------------------
# Attention (GQA) — XLA reference path + kernel fast path
# ---------------------------------------------------------------------------


_Q_CHUNK = 1024


def _attention_xla_block(q, k, v, *, causal, q_offset, positions_q=None,
                         lengths=None) -> jnp.ndarray:
    b, s, h, d = q.shape
    kvh = k.shape[2]
    group = h // kvh
    qg = q.reshape(b, s, kvh, group, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(d)
    skv = k.shape[1]
    if causal:
        qpos = (positions_q if positions_q is not None
                else q_offset + jnp.arange(s))
        mask = qpos[:, None] >= jnp.arange(skv)[None, :]
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    if lengths is not None:
        mask = jnp.arange(skv)[None, :] < lengths[:, None]      # [B, Skv]
        scores = jnp.where(mask[:, None, None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32))
    return out.reshape(b, s, h, v.shape[-1]).astype(q.dtype)


def attention_xla(q, k, v, *, causal: bool, positions_q=None,
                  lengths=None) -> jnp.ndarray:
    """q: [B,S,H,D]; k,v: [B,Skv,KVH,D] -> [B,S,H,D]. HLO-visible path.

    This is the roofline *baseline*: scores materialize through HBM exactly
    like the paper's baseline round-trips global memory. Long sequences are
    processed in q-chunks (scan) so the live score block stays bounded at
    [B, H, _Q_CHUNK, Skv] — the un-fused-but-not-insane baseline a careful
    XLA user would write.
    """
    b, s, h, d = q.shape
    if s <= _Q_CHUNK or s % _Q_CHUNK != 0 or positions_q is not None:
        return _attention_xla_block(q, k, v, causal=causal, q_offset=0,
                                    positions_q=positions_q, lengths=lengths)
    # statically unrolled q-chunks: a lax.map here would hide the chunk body
    # from cost_analysis (loop bodies are counted once — DESIGN.md §4)
    outs = []
    for i in range(s // _Q_CHUNK):
        qc = jax.lax.slice_in_dim(q, i * _Q_CHUNK, (i + 1) * _Q_CHUNK, axis=1)
        outs.append(_attention_xla_block(qc, k, v, causal=causal,
                                         q_offset=i * _Q_CHUNK,
                                         lengths=lengths))
    return jnp.concatenate(outs, axis=1)


def _session_kernel_policy():
    """Derive the kernel policy from the session `repro.policy` context (so
    no-touch A/B runs reach model code); modes the attention kernels don't
    speak (e.g. chunk_scan's "xla") fall back to "ff". "autotune" passes
    through — the serve/train ``--policy-mode autotune`` path and the plan
    service (record/replay through the PlanDB lookup chain) depend on it.
    Interpret mode is the policy's own (derived from the backend)."""
    from repro.core.program import current_policy
    pol = current_policy()
    if pol.mode not in ("ff", "baseline", "ref", "autotune"):
        pol = pol.replace(mode="ff")
    return pol


def _session_scan_policy(cfg_impl: str):
    """Scan-kernel policy: the model config pins the default impl, but an
    explicit session mode override (anything but the "ff" session default)
    wins — so `with repro.policy(mode="baseline")` A/B runs reach the
    chunk_scan call sites too. To force pipelined scans by default, set
    cfg.scan_impl="ff" rather than a session policy."""
    from repro.core.program import current_policy
    pol = current_policy()
    return pol.replace(mode=pol.mode if pol.mode != "ff" else cfg_impl)


def attention_op(q, k, v, *, causal: bool, impl: str = "xla",
                 lengths=None) -> jnp.ndarray:
    """Dispatch between the XLA path and the ff_attention Pallas kernel."""
    if impl == "xla":
        return attention_xla(q, k, v, causal=causal, lengths=lengths)
    from repro.kernels.ff_attention import attention as ff_attn
    b, s, h, d = q.shape
    kvh = k.shape[2]
    qh = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kh = k.transpose(0, 2, 1, 3).reshape(b * kvh, k.shape[1], d)
    vh = v.transpose(0, 2, 1, 3).reshape(b * kvh, v.shape[1], d)
    block_q = min(128, max(8, s))
    out = ff_attn(qh, kh, vh, kv_groups=h // kvh, causal=causal,
                  block_q=block_q, block_kv=128,
                  policy=_session_kernel_policy())
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def decode_attention_op(q, k, v, lengths, *, impl: str = "xla",
                        block_kv: Optional[int] = None) -> jnp.ndarray:
    """q: [B,H,D] one token; k,v: [B,Skv,KVH,D] cache; lengths: [B].
    ``block_kv`` pins the ff KV tile (serving pins it to the paged cache's
    page size for bitwise parity); None picks the traffic heuristic."""
    if impl == "xla":
        out = attention_xla(q[:, None], k, v, causal=False, lengths=lengths)
        return out[:, 0]
    from repro.kernels.ff_decode_attention import decode_attention as ff_dec
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    # the kernel streams whole KV tiles: round the cache up to the block
    # (rows past `lengths` are masked inside the kernel, so zero-padding
    # is free of numerics). For unpinned block_kv pick the tile that
    # minimizes padded traffic (skv=130 streams 160 rows at block 32, not
    # 256 at block 128), preferring larger tiles on ties (fewer DMAs).
    skv = k.shape[1]
    if block_kv is None:
        if skv <= 128:
            block_kv = -(-skv // 8) * 8
        else:
            block_kv = min((128, 64, 32),
                           key=lambda blk: (-(-skv // blk) * blk, -blk))
    pad = -skv % block_kv
    if pad:
        kh = jnp.pad(kh, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return ff_dec(q, kh, vh, lengths, block_kv=block_kv,
                  policy=_session_kernel_policy())


def paged_decode_attention_op(q, kv_pool, block_tables, lengths, *,
                              impl: str = "xla") -> jnp.ndarray:
    """Decode attention through a paged KV pool (continuous batching).

    q: [B,H,D] one token; kv_pool: [nb, J, 2, page, lanes] (one layer's
    block pool, lane-packed heads — ``runtime/paged_kv.py``);
    block_tables: [B, n_pages] (entries >= nb are sentinels); lengths: [B]
    (0 = inactive slot). "xla" dereferences the table densely; "ff" runs
    the fused gather->attention StreamGraph.
    """
    from repro.runtime import paged_kv
    if impl == "xla":
        _, j, _, _, lanes = kv_pool.shape
        k, v = paged_kv.dense_kv(kv_pool, block_tables,
                                 j * lanes // q.shape[-1])
        out = attention_xla(q[:, None], k, v, causal=False, lengths=lengths)
        return out[:, 0]
    return paged_kv.paged_decode_attention(q, kv_pool, block_tables, lengths,
                                           policy=_session_kernel_policy())


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_specs(d: int, f: int, act: str) -> Dict[str, ParamSpec]:
    s = {"wo": ParamSpec((f, d), ("mlp", "embed"))}
    if act == "swiglu":
        s["wi"] = ParamSpec((d, 2 * f), ("embed", "mlp"))
    else:
        s["wi"] = ParamSpec((d, f), ("embed", "mlp"))
        s["bi"] = ParamSpec((f,), ("mlp",), init="zeros")
        s["bo"] = ParamSpec((d,), ("embed",), init="zeros")
    return s


def mlp_apply(p, x, act: str) -> jnp.ndarray:
    dt = x.dtype
    if act == "swiglu":
        gate_up = x @ p["wi"].astype(dt)
        gate, up = jnp.split(gate_up, 2, axis=-1)
        h = jax.nn.silu(gate) * up
        return h @ p["wo"].astype(dt)
    h = x @ p["wi"].astype(dt) + p["bi"].astype(dt)
    h = jax.nn.gelu(h)
    return h @ p["wo"].astype(dt) + p["bo"].astype(dt)


# ---------------------------------------------------------------------------
# Embedding / unembedding / loss
# ---------------------------------------------------------------------------


@jax.custom_vjp
def bf16_grad_barrier(x):
    """Identity whose cotangent is cast to bf16: placed between the (f32)
    loss and the decoder stack so every backward all-reduce below runs in
    bf16 — halves TP-boundary collective bytes (§Perf 'bf16 grads')."""
    return x


def _bgb_fwd(x):
    return x, None


def _bgb_bwd(_, ct):
    return (ct.astype(jnp.bfloat16).astype(ct.dtype)
            if ct.dtype == jnp.float32 else ct,)


# NOTE: casting f32->bf16->f32 keeps dtypes consistent for jax while
# quantizing the cotangent mantissa; XLA then propagates the cheap form.
bf16_grad_barrier.defvjp(_bgb_fwd, _bgb_bwd)


@jax.custom_vjp
def bf16_grad_cast(x):
    """Identity fwd; bwd converts the cotangent to true bf16 (dtype change).
    Valid where the primal is bf16 (cotangent dtype must match primal)."""
    return x


def _bgc_fwd(x):
    return x, jnp.zeros((0,), x.dtype)    # dtype token (valid JAX residual)


def _bgc_bwd(tok, ct):
    return (ct.astype(tok.dtype),)


bf16_grad_cast.defvjp(_bgc_fwd, _bgc_bwd)


def embed_specs(vocab: int, d: int) -> ParamSpec:
    return ParamSpec((vocab, d), ("vocab", "embed"), scale=0.02)


def embed_lookup(table: jnp.ndarray, tokens: jnp.ndarray,
                 compute_dtype) -> jnp.ndarray:
    out = jnp.take(table, tokens, axis=0).astype(compute_dtype)
    return constrain(out, ("batch", "seq", "embed"))


def unembed_logits(x: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """x: [B,S,D] -> logits [B,S,V] (bf16, sharded batch x vocab)."""
    logits = x @ table.T.astype(x.dtype)
    return constrain(logits, ("batch", "seq", "vocab"))


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  z_loss: float = 1e-4) -> jnp.ndarray:
    """Mean token CE in f32, with a z-loss regularizer (stabilizes bf16)."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = lse - gold
    if z_loss:
        loss = loss + z_loss * lse ** 2
    return jnp.mean(loss)


def chunked_unembed_loss(x: jnp.ndarray, table: jnp.ndarray,
                         labels: jnp.ndarray, n_chunks: int,
                         z_loss: float = 1e-4) -> jnp.ndarray:
    """CE without materializing the full [B,S,V] logits: the unembed matmul
    + softmax run per sequence chunk (statically unrolled so cost_analysis
    sees every chunk). Cuts the dominant train-step temp (f32 logits) by
    ``n_chunks`` — §Perf iteration 'chunked-vocab loss'."""
    b, s, d = x.shape
    assert s % n_chunks == 0, (s, n_chunks)
    cs = s // n_chunks
    total = jnp.zeros((), jnp.float32)
    wt = table.T.astype(x.dtype)
    for i in range(n_chunks):
        xc = jax.lax.slice_in_dim(x, i * cs, (i + 1) * cs, axis=1)
        lc = jax.lax.slice_in_dim(labels, i * cs, (i + 1) * cs, axis=1)
        logits = constrain(xc @ wt, ("batch", "seq", "vocab"))
        logits = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        piece = lse - gold
        if z_loss:
            piece = piece + z_loss * lse ** 2
        total = total + jnp.sum(piece)
    return total / (b * s)


# ---------------------------------------------------------------------------
# StreamGraph workload: attention -> out-projection
# ---------------------------------------------------------------------------
#
# The transformer block's hottest fusion opportunity above single kernels:
# flash attention writes [BH, S, D] q-blocks in q-major order, and the out-
# projection matmul streams exactly those (block_q, d) tiles as its A
# operand — so the attention output can live in a VMEM ring inside one
# fused pallas_call instead of round-tripping HBM between two kernels
# (repro.core.graph decides per edge; a mismatched block_q stages instead).


def build_attention_proj_graph(*, bh: int = 2, s: int = 256, d: int = 64,
                               d_out: int = 256, causal: bool = True,
                               dtype=jnp.float32, depth: int = 2,
                               streams: int = 1, block_q: int = 128):
    """Declare the attention→out-projection StreamGraph at one shape point.

    The projection's M tile is pinned to ``block_q`` so the edge is fusable
    when the attention output schedule lines up; ``block_q`` is the joint
    tuner's shared-tile axis.
    """
    from repro.core.graph import GraphEdge, GraphNode, StreamGraph
    from repro.kernels.ff_attention.kernel import build_program as attn_prog
    from repro.kernels.ff_attention.ops import attention_workload
    from repro.kernels.ff_matmul.kernel import build_program as matmul_prog
    from repro.kernels.ff_matmul.ops import matmul_workload

    block = (block_q, min(128, d_out), d)
    attn = attn_prog(bh, s, s, d, block_q=block_q, block_kv=128,
                     causal=causal, dtype=dtype, depth=depth, streams=streams)
    proj = matmul_prog(bh * s, d_out, d, block=block, dtype=dtype,
                       depth=depth, streams=streams)
    w_a, t_a = attention_workload(bh, s, d, causal=causal, block_q=block_q,
                                  dtype=dtype)
    w_p, t_p = matmul_workload(bh * s, d_out, d, block, dtype)
    return StreamGraph(
        name="attention_proj",
        nodes=(
            GraphNode("attn", attn, workload=w_a, plan_tile=t_a),
            GraphNode("proj", proj, workload=w_p, plan_tile=t_p),
        ),
        edges=(
            GraphEdge("attn", "proj", "a", reshape=(bh * s, d)),
        ),
    )


def _attention_proj_inputs(key):
    """Operands in CompiledGraph.arg_names order:
    (attn.q, attn.k, attn.v, proj.b)."""
    # d_out = 2 N tiles: the projection re-reads each attention block
    # once per N tile, so the fused ring saves the re-streams too
    bh, s, d, d_out = 2, 256, 64, 256
    q = 0.3 * jax.random.normal(key, (bh, s, d), jnp.float32)
    k = 0.3 * jax.random.normal(jax.random.fold_in(key, 1), (bh, s, d),
                                jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (bh, s, d),
                          jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 3), (d, d_out),
                          jnp.float32) / jnp.sqrt(d)
    return (q, k, v, w)


def _attention_proj_ref(q, k, v, w):
    bh, s, d = q.shape
    scores = jnp.einsum("bsd,btd->bst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(d)
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None], scores, -1e30)
    attn = jnp.einsum("bst,btd->bsd", jax.nn.softmax(scores, axis=-1),
                      v.astype(jnp.float32))
    return (attn.reshape(bh * s, d) @ w.astype(jnp.float32)).astype(q.dtype)


def _attention_proj_unfused(q, k, v, w):
    """Attention then projection as two separate repro.ops calls — the
    [BH, S, D] intermediate round-trips HBM (the BENCH_graph baseline).
    The projection is pinned to the graph's tile so the comparison
    isolates the lowering, not the tiling."""
    import repro

    bh, s, d = q.shape
    attn = repro.ops.attention(q, k, v, causal=True)
    return repro.ops.matmul(attn.reshape(bh * s, d), w,
                            block=(128, 128, d))


def attention_proj(q, k, v, w, *, causal: bool = True,
                   policy=None) -> jnp.ndarray:
    """Causal attention → out-projection through the fused StreamGraph, at
    the caller's shapes.

    q/k/v: [BH, S, D]; w: [D, D_out]. Returns [BH*S, D_out].

    Unlike ``run_graph`` (fixed smoke shapes), this entrypoint resolves the
    joint graph plan at the call site's shapes and records the site for the
    plan-service sweep — mirroring ``paged_decode_attention``.
    """
    from repro.core import autotune
    from repro.core import graph as graphlib
    from repro.core.program import current_policy

    policy = current_policy() if policy is None else policy
    if policy.mode == "ref":
        return _attention_proj_ref(q, k, v, w)
    bh, s, d = q.shape
    d_out = w.shape[1]

    def build(depth=2, streams=1, **tk):
        return build_attention_proj_graph(
            bh=bh, s=s, d=d, d_out=d_out, causal=causal, dtype=q.dtype,
            depth=depth, streams=streams, **tk)

    g0 = build()
    wl, tile = graphlib.graph_workload(g0)
    sig = graphlib.graph_signature(g0)

    def runner(tk, depth, streams):
        cg = graphlib.compile_graph(
            build(depth=depth, streams=streams, **dict(tk)),
            policy=policy.replace(mode="ff", depth=depth, streams=streams))
        return lambda: cg(q, k, v, w)

    choice = autotune.resolve_graph(
        "attention_proj", policy, workload=wl, tile=tile,
        dtype=q.dtype, signature=sig,
        workload_fn=lambda tk: graphlib.graph_workload(build(**dict(tk))),
        runner=None if autotune.has_tracers(q, k, v, w) else runner,
        site={"bh": bh, "s": s, "d": d, "d_out": d_out,
              "causal": bool(causal)},
        site_dynamic=("bh", "s"),
        tile_options=({"block_q": 64},))
    # compiled fresh per call (trace-scoped closures must not be reused)
    mode = "ff" if policy.mode == "autotune" else policy.mode
    cg = graphlib.compile_graph(
        build(depth=choice.depth, streams=choice.streams,
              **dict(choice.tile_kwargs)),
        policy=policy.replace(mode=mode, depth=choice.depth,
                              streams=choice.streams))
    return cg(q, k, v, w)


def _attention_proj_sweep_inputs(key, site):
    """Rebuild attention_proj operands at a recorded call-site shape
    (plan sweep)."""
    bh, s = int(site["bh"]), int(site["s"])
    d, d_out = int(site["d"]), int(site["d_out"])
    dt = jnp.dtype(site.get("dtype", "float32"))
    q = 0.3 * jax.random.normal(key, (bh, s, d), dt)
    k = 0.3 * jax.random.normal(jax.random.fold_in(key, 1), (bh, s, d), dt)
    v = jax.random.normal(jax.random.fold_in(key, 2), (bh, s, d), dt)
    w = jax.random.normal(jax.random.fold_in(key, 3), (d, d_out),
                          dt) / jnp.sqrt(d)
    kwargs = {"causal": bool(site.get("causal", True))}
    return (q, k, v, w), kwargs


def _register_attention_proj_graph():
    from repro.kernels.registry import register_graph

    register_graph(
        name="attention_proj",
        build=build_attention_proj_graph,
        make_inputs=_attention_proj_inputs,
        ref=_attention_proj_ref,
        unfused=_attention_proj_unfused,
        tile_options=({"block_q": 64},),
        tol=5e-4,
        doc="flash attention -> out-projection matmul; the [BH,S,D] "
            "intermediate stays in a VMEM ring when block_q tiles match",
        # plan-service sweep: resolve at call-site shapes through the real
        # entrypoint, not run_graph's fixed smoke point
        op=attention_proj,
        sweep_inputs=_attention_proj_sweep_inputs,
    )


_register_attention_proj_graph()


# ---------------------------------------------------------------------------
# StreamGraph workload: the whole transformer decode layer
# ---------------------------------------------------------------------------
#
# ROADMAP item 2: QKV projection -> decode attention -> out-projection ->
# gate/up MLP -> down-projection as ONE StreamGraph. RMSNorms ride as fused
# prologues inside the matmul consumers, the residual adds and RoPE as
# consumer *epilogues* (GraphNode.epilogue), and the out-projection output
# is a multi-consumer edge: it feeds the MLP gate/up node AND the final
# residual epilogue. compile_graph fuses oproj->gateup->down into one
# chain kernel (the residual rides the chain's intermediate VMEM ring, so
# the post-attention hidden state never round-trips HBM) and stages the two
# attention-adjacent edges with per-edge rationales (the q handoff is a
# block-delivered operand; the attention output's (g_pad, hd) blocks don't
# match the out-projection's (block_m, hpad) row tiles).


def build_decode_layer_graph(*, b: int = 16, d_model: int = 64,
                             kvh: int = 1, g_pad: int = 8, hd: int = 16,
                             d_ff: int = 128, s: int = 128,
                             eps: float = 1e-6, dtype=jnp.float32,
                             depth: int = 2, streams: int = 1,
                             block_m: int = 8, block_kv: int = 128):
    """Declare the whole-decode-layer StreamGraph at one shape point.

    Row-space: ``b`` decode tokens (one per sequence), padded to a multiple
    of ``block_m``. Head-space: ``kvh`` KV heads of ``g_pad`` (8-padded)
    query heads each, ``hpad = kvh * g_pad * hd`` flattened q columns —
    the entrypoint zero-pads the flattened projections so padded head rows
    contribute exactly zero. ``block_kv`` is the joint tuner's shared tile
    axis (``block_m`` is pinned: epilogue operands are blocked on it).
    """
    from repro.core.graph import Epilogue, GraphEdge, GraphNode, StreamGraph
    from repro.core.program import BlockIn
    from repro.kernels.ff_decode_attention.kernel import \
        build_program as attn_prog
    from repro.kernels.ff_decode_attention.ops import \
        decode_attention_workload
    from repro.kernels.ff_layer.kernel import build_matmul_program, \
        build_swiglu_program
    from repro.kernels.ff_matmul.ops import matmul_workload

    hpad = kvh * g_pad * hd
    half = hd // 2

    qprog = build_matmul_program(b, hpad, d_model, block_m=block_m,
                                 norm=True, eps=eps, dtype=dtype,
                                 depth=depth, streams=streams,
                                 name="ff_layer_qproj")
    attn = attn_prog(b, kvh, g_pad, s, hd, block_kv=block_kv, dtype=dtype,
                     depth=depth, streams=streams)
    oprog = build_matmul_program(b, d_model, hpad, block_m=block_m,
                                 dtype=dtype, depth=depth, streams=streams,
                                 name="ff_layer_oproj")
    gprog = build_swiglu_program(b, d_ff, d_model, block_m=block_m,
                                 norm=True, eps=eps, dtype=dtype,
                                 depth=depth, streams=streams)
    dprog = build_matmul_program(b, d_model, d_ff, block_m=block_m,
                                 dtype=dtype, depth=depth, streams=streams,
                                 name="ff_layer_down")

    def _rope_bias_ep(ctx, idx, value):
        # q = (rmsnorm(x) @ wq + bq) rotated by the per-row cos/sin tables
        # (rope over the trailing hd dim of each padded head), all in f32 —
        # mirrors L.rope numerics exactly; rope(0) = 0 keeps padded head
        # columns zero
        v = value.astype(jnp.float32) + ctx.ref("bq")[...].astype(jnp.float32)
        c = ctx.ref("cos")[...][:, None, :].astype(jnp.float32)
        s_ = ctx.ref("sin")[...][:, None, :].astype(jnp.float32)
        vh = v.reshape(v.shape[0], kvh * g_pad, hd)
        x1, x2 = vh[..., :half], vh[..., half:]
        vh = jnp.concatenate([x1 * c - x2 * s_, x1 * s_ + x2 * c], axis=-1)
        return vh.reshape(v.shape).astype(value.dtype)

    def _residual_ep(name):
        def ep(ctx, idx, value):
            return value + ctx.ref(name)[...].astype(value.dtype)
        return ep

    w_q, t_q = matmul_workload(b, hpad, d_model, (block_m, hpad, d_model),
                               dtype)
    w_a, t_a = decode_attention_workload(b, kvh * g_pad, kvh, s, hd,
                                         block_kv=block_kv, dtype=dtype)
    w_o, t_o = matmul_workload(b, d_model, hpad, (block_m, d_model, hpad),
                               dtype)
    w_d, t_d = matmul_workload(b, d_model, d_ff, (block_m, d_model, d_ff),
                               dtype)
    return StreamGraph(
        name="decode_layer",
        nodes=(
            GraphNode("qproj", qprog, workload=w_q, plan_tile=t_q,
                      epilogue=Epilogue(_rope_bias_ep, inputs=(
                          BlockIn("bq", (block_m, hpad), lambda g: (0, 0)),
                          BlockIn("cos", (block_m, half), lambda g: (g, 0)),
                          BlockIn("sin", (block_m, half), lambda g: (g, 0)),
                      ))),
            GraphNode("attn", attn, workload=w_a, plan_tile=t_a),
            GraphNode("oproj", oprog, workload=w_o, plan_tile=t_o,
                      epilogue=Epilogue(_residual_ep("res1"), inputs=(
                          BlockIn("res1", (block_m, d_model),
                                  lambda g: (g, 0), dtype=dtype),))),
            # gateup's workload is synthesized from its streams (exact:
            # one x row-block + both weight blocks per word)
            GraphNode("gateup", gprog),
            GraphNode("down", dprog, workload=w_d, plan_tile=t_d,
                      epilogue=Epilogue(_residual_ep("res"), inputs=(
                          BlockIn("res", (block_m, d_model),
                                  lambda g: (g, 0), dtype=dtype),))),
        ),
        edges=(
            # staged: attn's q is a block-delivered BlockIn operand
            GraphEdge("qproj", "attn", "q", reshape=(b, kvh, g_pad, hd)),
            # staged: (1,1,g_pad,hd) attention blocks vs (block_m, hpad)
            # row tiles — mismatched schedules
            GraphEdge("attn", "oproj", "a", reshape=(b, hpad)),
            # fused chain: oproj -> gateup -> down, one pallas_call
            GraphEdge("oproj", "gateup", "x"),
            # multi-consumer: the post-attention hidden state also feeds
            # the final residual epilogue — ring-served from the chain's
            # intermediate VMEM ring, no HBM materialization
            GraphEdge("oproj", "down", "res"),
            GraphEdge("gateup", "down", "a"),
        ),
    )


def _decode_layer_inputs(key):
    """Operands in CompiledGraph.arg_names order: (qproj.a, qproj.b,
    qproj.nw, qproj.bq, qproj.cos, qproj.sin, attn.lengths, attn.k,
    attn.v, oproj.b, oproj.res1, gateup.wg, gateup.wu, gateup.nw,
    down.b). Norm weights and the q bias arrive broadcast to ``block_m``
    rows (ring-promotable blocks need 8-aligned sublanes)."""
    b, d, kvh, g_pad, hd, f, s = 16, 64, 1, 8, 16, 128, 128
    hpad, half, bm = kvh * g_pad * hd, hd // 2, 8
    ks = [jax.random.fold_in(key, i) for i in range(12)]
    x = 0.3 * jax.random.normal(key, (b, d), jnp.float32)
    wq = jax.random.normal(ks[1], (d, hpad), jnp.float32) / math.sqrt(d)
    nw1 = jnp.broadcast_to(
        1.0 + 0.1 * jax.random.normal(ks[2], (d,), jnp.float32), (bm, d))
    bq = jnp.broadcast_to(
        0.1 * jax.random.normal(ks[3], (hpad,), jnp.float32), (bm, hpad))
    lengths = jax.random.randint(ks[4], (b,), 1, s + 1, dtype=jnp.int32)
    ang = (lengths - 1).astype(jnp.float32)[:, None] \
        * (1e4 ** (-jnp.arange(half, dtype=jnp.float32) / half))
    k = 0.3 * jax.random.normal(ks[5], (b, kvh, s, hd), jnp.float32)
    v = jax.random.normal(ks[6], (b, kvh, s, hd), jnp.float32)
    wo = jax.random.normal(ks[7], (hpad, d), jnp.float32) / math.sqrt(hpad)
    wg = jax.random.normal(ks[8], (d, f), jnp.float32) / math.sqrt(d)
    wu = jax.random.normal(ks[9], (d, f), jnp.float32) / math.sqrt(d)
    nw2 = jnp.broadcast_to(
        1.0 + 0.1 * jax.random.normal(ks[10], (d,), jnp.float32), (bm, d))
    wo2 = jax.random.normal(ks[11], (f, d), jnp.float32) / math.sqrt(f)
    return (x, wq, nw1, bq, jnp.cos(ang), jnp.sin(ang), lengths, k, v,
            wo, x, wg, wu, nw2, wo2)


def _decode_layer_ref(x, wq, nw1, bq, cos, sin, lengths, k, v, wo, res1,
                      wg, wu, nw2, wo2, eps: float = 1e-6):
    """Pure-XLA decode layer at the graph's operand layout (flattened
    zero-padded projections, broadcast norm rows, precomputed rope
    tables). Mirrors the kernel convention that a fully-masked row
    (length 0) attends to nothing and outputs zeros."""
    b, d = x.shape
    _, kvh, s, hd = k.shape
    hpad, half = wq.shape[1], hd // 2
    dt = x.dtype
    xn = rmsnorm(x, nw1[0], eps)
    q = jnp.dot(xn, wq, preferred_element_type=jnp.float32).astype(dt)
    q = q.astype(jnp.float32) + bq[0].astype(jnp.float32)
    qh = q.reshape(b, hpad // hd, hd)
    c = cos[:, None, :].astype(jnp.float32)
    s_ = sin[:, None, :].astype(jnp.float32)
    x1, x2 = qh[..., :half], qh[..., half:]
    qh = jnp.concatenate([x1 * c - x2 * s_, x1 * s_ + x2 * c], axis=-1)
    q4 = qh.reshape(b, kvh, hpad // (kvh * hd), hd).astype(dt)
    scores = jnp.einsum("bkgd,bksd->bkgs", q4.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    valid = jnp.arange(s)[None, None, None, :] \
        < lengths[:, None, None, None]
    scores = jnp.where(valid, scores, -1e30)
    attn = jnp.einsum("bkgs,bksd->bkgd", jax.nn.softmax(scores, axis=-1),
                      v.astype(jnp.float32))
    attn = jnp.where(lengths[:, None, None, None] > 0, attn, 0.0)
    a = attn.astype(dt).reshape(b, hpad)
    h = jnp.dot(a, wo, preferred_element_type=jnp.float32).astype(dt) + res1
    hn = rmsnorm(h, nw2[0], eps)
    g32 = jnp.dot(hn, wg, preferred_element_type=jnp.float32)
    u32 = jnp.dot(hn, wu, preferred_element_type=jnp.float32)
    m = (jax.nn.silu(g32) * u32).astype(dt)
    return jnp.dot(m, wo2, preferred_element_type=jnp.float32).astype(dt) + h


@functools.lru_cache(maxsize=8)
def _unfused_decode_layer_fn(b, d, kvh, g_pad, hd, d_ff, s, dtype):
    """The chained-ops baseline: the same five planned kernels as the
    graph (identical per-node depth/streams sizing, via a one-time staged
    compile), but each node is its own jitted dispatch — intermediates
    cross the dispatch boundary instead of staying device-resident inside
    one program. Compiled once per shape so the bench measures execution,
    not per-call re-tracing."""
    from repro.core.graph import compile_graph

    g = build_decode_layer_graph(b=b, d_model=d, kvh=kvh, g_pad=g_pad,
                                 hd=hd, d_ff=d_ff, s=s, dtype=dtype)
    cg = compile_graph(g, prefer="staged")
    run = {u.out_node: jax.jit(u.fn) for u in cg.units}
    hpad = kvh * g_pad * hd

    def fn(x, wq, nw1, bq, cos, sin, lengths, k, v, wo, res1, wg, wu,
           nw2, wo2):
        q = run["qproj"](x, wq, nw1, bq, cos, sin)
        a = run["attn"](lengths, q.reshape(b, kvh, -1, hd), k, v)
        h = run["oproj"](a.reshape(b, hpad), wo, res1)
        m = run["gateup"](h, wg, wu, nw2)
        return run["down"](m, wo2, h)

    return fn


def _decode_layer_unfused(x, wq, nw1, bq, cos, sin, lengths, k, v, wo,
                          res1, wg, wu, nw2, wo2):
    """The same five node programs as five separate pallas_calls — every
    intermediate round-trips HBM (the BENCH_graph whole-layer baseline).
    Same lowering and sizing, no graph: the comparison isolates the
    fusion."""
    b, d = x.shape
    _, kvh, s, hd = k.shape
    hpad = wq.shape[1]
    fn = _unfused_decode_layer_fn(b, d, kvh, hpad // (kvh * hd), hd,
                                  wg.shape[1], s, jnp.dtype(x.dtype))
    return fn(x, wq, nw1, bq, cos, sin, lengths, k, v, wo, res1, wg, wu,
              nw2, wo2)


def decode_layer(x, nw1, wq, bq, positions, k_cache, v_cache, lengths,
                 wo, nw2, wg, wu, wo2, *, rope_theta: float = 10000.0,
                 eps: float = 1e-6, block_kv: Optional[int] = None,
                 policy=None) -> jnp.ndarray:
    """One transformer decode step (post cache-update) through the
    whole-layer ``decode_layer`` StreamGraph, at the caller's shapes.

    x: [B, D] current-token hidden states; nw1/nw2: [D] RMSNorm weights;
    wq: [D, H*hd] (bq: [H*hd] or None); positions: [B] rope positions of
    the current token; k_cache/v_cache: [B, KVH, S, hd] post-update;
    lengths: [B] live prefix length *including* the current token;
    wo: [H*hd, D]; wg/wu: [D, F]; wo2: [F, D]. Returns [B, D] =
    ``x + attn(...) @ wo + mlp(...)`` — the full pre-norm layer body.

    Marshals to the graph's padded operand layout (rows to ``block_m``,
    query-head group to ``g_pad``, cache length to ``block_kv``; the
    zero-padded flattened projections make every padded lane contribute
    exactly zero), resolves the joint plan, and records the call site for
    the plan-service sweep — mirroring ``attention_proj``.
    """
    from repro.core import autotune
    from repro.core import graph as graphlib
    from repro.core.program import current_policy

    policy = current_policy() if policy is None else policy
    dt = x.dtype
    b, d_model = x.shape
    _, kvh, s_len, hd = k_cache.shape
    half = hd // 2
    n_q = wq.shape[1] // hd
    group = max(n_q // kvh, 1)
    g_pad = max(8, -(-group // 8) * 8)
    hpad = kvh * g_pad * hd
    d_ff = wg.shape[1]
    block_m = 8
    bkv = int(block_kv or 128)
    bp = -(-b // block_m) * block_m
    spad = -(-s_len // bkv) * bkv

    def pad_rows(a):
        if a.shape[0] == bp:
            return a
        return jnp.pad(a, ((0, bp - b),) + ((0, 0),) * (a.ndim - 1))

    def pad_seq(c):
        c = c.astype(dt)
        if c.shape[2] != spad:
            c = jnp.pad(c, ((0, 0), (0, 0), (0, spad - s_len), (0, 0)))
        return pad_rows(c)

    # zero-pad the flattened projections over the padded head group:
    # padded q columns are 0 (rope keeps them 0), padded attention rows
    # are killed by zero wo rows
    wq4 = wq.reshape(d_model, kvh, group, hd)
    wqf = jnp.zeros((d_model, kvh, g_pad, hd), wq.dtype) \
        .at[:, :, :group].set(wq4).reshape(d_model, hpad)
    bqv = jnp.zeros((n_q * hd,), dt) if bq is None else bq
    bqf = jnp.zeros((kvh, g_pad, hd), bqv.dtype) \
        .at[:, :group].set(bqv.reshape(kvh, group, hd)).reshape(hpad)
    wo4 = wo.reshape(kvh, group, hd, d_model)
    wof = jnp.zeros((kvh, g_pad, hd, d_model), wo.dtype) \
        .at[:, :group].set(wo4).reshape(hpad, d_model)
    freqs = rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    xp = pad_rows(x)
    ops = (xp, wqf.astype(dt),
           jnp.broadcast_to(nw1.astype(jnp.float32)[None],
                            (block_m, d_model)),
           jnp.broadcast_to(bqf.astype(jnp.float32)[None],
                            (block_m, hpad)),
           pad_rows(jnp.cos(ang)), pad_rows(jnp.sin(ang)),
           pad_rows(lengths.astype(jnp.int32)),
           pad_seq(k_cache), pad_seq(v_cache), wof.astype(dt), xp,
           wg.astype(dt), wu.astype(dt),
           jnp.broadcast_to(nw2.astype(jnp.float32)[None],
                            (block_m, d_model)),
           wo2.astype(dt))
    if policy.mode == "ref":
        return _decode_layer_ref(*ops, eps=eps)[:b]

    def build(depth=2, streams=1, **tk):
        return build_decode_layer_graph(
            b=bp, d_model=d_model, kvh=kvh, g_pad=g_pad, hd=hd, d_ff=d_ff,
            s=spad, eps=eps, dtype=dt, depth=depth, streams=streams,
            block_kv=tk.pop("block_kv", bkv), **tk)

    g0 = build()
    wl, tile = graphlib.graph_workload(g0)
    sig = graphlib.graph_signature(g0)

    def runner(tk, depth, streams):
        cg = graphlib.compile_graph(
            build(depth=depth, streams=streams, **dict(tk)),
            policy=policy.replace(mode="ff", depth=depth, streams=streams))
        return lambda: cg(*ops)

    choice = autotune.resolve_graph(
        "decode_layer", policy, workload=wl, tile=tile, dtype=dt,
        signature=sig,
        workload_fn=lambda tk: graphlib.graph_workload(build(**dict(tk))),
        runner=None if autotune.has_tracers(*ops) else runner,
        site={"b": b, "d_model": d_model, "h": n_q, "kvh": kvh, "hd": hd,
              "d_ff": d_ff, "s": s_len},
        site_dynamic=("b", "s"),
        tile_options=({"block_kv": 64},))
    # compiled fresh per call (trace-scoped closures must not be reused)
    mode = "ff" if policy.mode == "autotune" else policy.mode
    cg = graphlib.compile_graph(
        build(depth=choice.depth, streams=choice.streams,
              **dict(choice.tile_kwargs)),
        policy=policy.replace(mode=mode, depth=choice.depth,
                              streams=choice.streams))
    return cg(*ops)[:b]


def _decode_layer_sweep_inputs(key, site):
    """Rebuild decode_layer operands at a recorded call-site shape
    (plan sweep)."""
    b, d = int(site["b"]), int(site["d_model"])
    h, kvh, hd = int(site["h"]), int(site["kvh"]), int(site["hd"])
    f, s = int(site["d_ff"]), int(site["s"])
    dt = jnp.dtype(site.get("dtype", "float32"))
    ks = [jax.random.fold_in(key, i) for i in range(12)]
    x = 0.3 * jax.random.normal(key, (b, d), dt)
    nw1 = 1.0 + 0.1 * jax.random.normal(ks[1], (d,), dt)
    wq = jax.random.normal(ks[2], (d, h * hd), dt) / math.sqrt(d)
    bq = 0.1 * jax.random.normal(ks[3], (h * hd,), dt)
    lengths = jax.random.randint(ks[4], (b,), 1, s + 1, dtype=jnp.int32)
    positions = lengths - 1
    k = 0.3 * jax.random.normal(ks[5], (b, kvh, s, hd), dt)
    v = jax.random.normal(ks[6], (b, kvh, s, hd), dt)
    wo = jax.random.normal(ks[7], (h * hd, d), dt) / math.sqrt(h * hd)
    nw2 = 1.0 + 0.1 * jax.random.normal(ks[8], (d,), dt)
    wg = jax.random.normal(ks[9], (d, f), dt) / math.sqrt(d)
    wu = jax.random.normal(ks[10], (d, f), dt) / math.sqrt(d)
    wo2 = jax.random.normal(ks[11], (f, d), dt) / math.sqrt(f)
    return (x, nw1, wq, bq, positions, k, v, lengths, wo, nw2, wg, wu,
            wo2), {}


def _register_decode_layer_graph():
    from repro.kernels.registry import register_graph

    register_graph(
        name="decode_layer",
        build=build_decode_layer_graph,
        make_inputs=_decode_layer_inputs,
        ref=_decode_layer_ref,
        unfused=_decode_layer_unfused,
        tile_options=({"block_kv": 64},),
        tol=5e-4,
        doc="whole transformer decode layer: q-projection (+RMSNorm "
            "prologue, +bias/RoPE epilogue) -> decode attention -> "
            "out-projection (+residual) -> SwiGLU gate/up -> "
            "down-projection (+residual); oproj->gateup->down fuse into "
            "one chain kernel with the residual ring-served in VMEM",
        # plan-service sweep: resolve at call-site shapes through the real
        # entrypoint, not run_graph's fixed smoke point
        op=decode_layer,
        sweep_inputs=_decode_layer_sweep_inputs,
    )


_register_decode_layer_graph()
