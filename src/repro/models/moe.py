"""Mixture-of-experts FFN (grok-1, deepseek-v2-lite).

Two routed layers behind one entry point (:func:`moe_ffn_apply`):

* capacity-based top-k routing (``capacity_factor > 0``, grok): tokens are
  placed into a ``[E, C, d]`` dispatch buffer (expert-sharded under the
  "expert" rule — EP over the model axis), experts run as one batched
  einsum, and results gather back weighted by router probs. Overflow
  beyond capacity ``C = ceil(T/E * k * capacity_factor)`` is dropped
  (standard token-dropping MoE).
* no-drop routing over held experts (``capacity_factor == 0``, deepseek):
  the router scores all ``n_experts`` in f32; the layer holds
  ``experts_held`` of them (from ``expert_offset`` on: one chip's share of
  an expert-parallel deployment) and computes only their part of the
  result, for every token routed to them, plus the shared experts. Its
  third output counts each token's assignments to every expert.

Paper mapping: the dispatch/combine *is* the irregular-gather microbenchmark
at system scale — under EP sharding XLA materializes it as all-to-alls, which
the roofline's collective term picks up (deepseek/grok are the most
collective-bound cells in EXPERIMENTS.md).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.runtime.sharding import constrain, current


def moe_ffn_specs(cfg: ArchConfig) -> Dict[str, Any]:
    """The router over all ``n_experts``; weights of the held ones."""
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    held = cfg.experts_held
    s = {
        "router": L.ParamSpec((d, e), ("embed", None), scale=0.02),
        "w1": L.ParamSpec((held, d, 2 * f), ("expert", "embed", "mlp")),
        "w2": L.ParamSpec((held, f, d), ("expert", "mlp", "embed")),
    }
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * cfg.moe_d_ff
        s["shared"] = L.mlp_specs(d, fs, "swiglu")
    return s


def _dispatch_indices(gates: jnp.ndarray, top_k: int, capacity: int):
    """gates: [T, E] router probs. Returns (expert_idx [T,k], probs [T,k],
    slot [T,k], keep [T,k]) with capacity-ranked slots per expert."""
    t, e = gates.shape
    probs, idx = jax.lax.top_k(gates, top_k)                    # [T,k]
    probs = probs / (jnp.sum(probs, axis=-1, keepdims=True) + 1e-9)
    count = jnp.zeros((e,), jnp.int32)
    slots = []
    for k in range(top_k):
        oh = jax.nn.one_hot(idx[:, k], e, dtype=jnp.int32)       # [T,E]
        rank = jnp.cumsum(oh, axis=0) - 1                        # [T,E]
        r = jnp.take_along_axis(rank, idx[:, k:k + 1], axis=1)[:, 0]
        slots.append(r + count[idx[:, k]])
        count = count + jnp.sum(oh, axis=0)
    slot = jnp.stack(slots, axis=1)                              # [T,k]
    keep = slot < capacity
    return idx, probs, slot, keep


def _batch_shards() -> int:
    """How many ways the token (batch) dim is sharded under current rules."""
    ctx = current()
    if ctx is None:
        return 1
    target = ctx.rules.get("batch")
    if target is None:
        return 1
    tgt = (target,) if isinstance(target, str) else target
    n = 1
    for a in tgt:
        n *= ctx.axis_size(a)
    return n


def _local_dispatch_apply(cfg: ArchConfig, p, x
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Hierarchical dispatch (§Perf, 'MoE local dispatch'): slot ranks and
    capacity are computed *per data shard*, and the dispatch buffer's
    capacity dim is laid out [E, shards, C_local] with the shard dim aligned
    to the token sharding — the scatter/gather becomes shard-local and the
    only cross-device movement is the expert-parallel all-to-all, instead of
    the global-buffer all-gathers of the naive path."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    shards = _batch_shards()
    if t % shards:
        shards = 1
    tl = t // shards
    xf = x.reshape(t, d)

    gates = jax.nn.softmax(
        (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32)), axis=-1)
    probs_k, idx = jax.lax.top_k(gates, k)
    probs_k = probs_k / (jnp.sum(probs_k, axis=-1, keepdims=True) + 1e-9)

    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=1), axis=0)
    aux = e * jnp.sum(me * ce)

    cap_l = int(tl // e * k * cfg.capacity_factor) + 1
    cap_l = -(-cap_l // 8) * 8
    idx_s = idx.reshape(shards, tl, k)
    count = jnp.zeros((shards, e), jnp.int32)
    slots = []
    for kk in range(k):
        oh = jax.nn.one_hot(idx_s[:, :, kk], e, dtype=jnp.int32)  # [D,tl,E]
        rank = jnp.cumsum(oh, axis=1) - 1
        r = jnp.take_along_axis(rank, idx_s[:, :, kk:kk + 1], axis=2)[..., 0]
        base = jnp.take_along_axis(count, idx_s[:, :, kk], axis=1)
        slots.append(r + base)
        count = count + jnp.sum(oh, axis=1)
    slot = jnp.stack(slots, axis=2)                               # [D,tl,k]
    keep = slot < cap_l

    # vmapped shard-local scatter: the buffer is *born* sharded on its
    # leading (data) dim, so the partitioner never materializes a global
    # buffer (the naive path all-gathers the whole [E,C,d] buffer — the
    # 181 GiB/layer pathology in the baseline grok HLO)
    flat_local = idx_s * cap_l + slot                             # [D,tl,k]
    contrib = xf.reshape(shards, tl, 1, d) * keep[..., None].astype(x.dtype)
    contrib = jnp.broadcast_to(contrib, (shards, tl, k, d))
    buf_s = jnp.zeros((shards, e * cap_l, d), x.dtype)
    buf_s = constrain(buf_s, ("batch", None, "embed"))
    buf_s = jax.vmap(
        lambda bb, ix, cc: bb.at[ix.reshape(-1)].add(
            cc.reshape(-1, d), mode="drop"))(buf_s, flat_local, contrib)
    buf = buf_s.reshape(shards, e, cap_l, d).transpose(1, 0, 2, 3) \
        .reshape(e, shards * cap_l, d)
    buf = constrain(buf, ("expert", "exp_cap", "embed"))

    dt = x.dtype
    h = jnp.einsum("ecd,edf->ecf", buf, p["w1"].astype(dt))
    gate, up = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(gate) * up
    y = jnp.einsum("ecf,efd->ecd", h, p["w2"].astype(dt))
    y = constrain(y, ("expert", "exp_cap", "embed"))

    y_s = y.reshape(e, shards, cap_l, d).transpose(1, 0, 2, 3) \
        .reshape(shards, e * cap_l, d)
    y_s = constrain(y_s, ("batch", None, "embed"))
    picked = jax.vmap(lambda yy, ix: yy[ix.reshape(-1)])(
        y_s, flat_local).reshape(t, k, d)
    w = (probs_k.reshape(t, k) *
         keep.reshape(t, k).astype(jnp.float32)).astype(x.dtype)
    out = jnp.einsum("tkd,tk->td", picked, w).reshape(b, s, d)
    if cfg.n_shared_experts:
        out = out + L.mlp_apply(p["shared"], x, "swiglu")
    return out, aux.astype(jnp.float32)


# rows of the grouped expert matmul's tile (``jax.lax.ragged_dot`` on a
# TPU): at most this many tokens, every held expert's group would be
# padded to a tile anyway, so one dense pass over the held experts does no
# more work and launches no kernel
_GROUP_ROWS = 512


def _held_experts_dense(cfg: ArchConfig, p, xf, local, probs, mine):
    """Every held expert on every token, each weighted by the token's
    routing weight for it (0 where the token did not pick it)."""
    dt = xf.dtype
    held = cfg.experts_held
    wte = jnp.sum(jnp.where(mine[..., None],
                            probs[..., None] * jax.nn.one_hot(
                                local, held, dtype=jnp.float32), 0.0),
                  axis=1)                                      # [T, held]
    h = jnp.einsum("td,edf->tef", xf, p["w1"].astype(dt))
    gate, up = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(gate) * up * wte[..., None].astype(dt)
    return jnp.einsum("tef,efd->td", h, p["w2"].astype(dt))


def _held_experts_grouped(cfg: ArchConfig, p, xf, local, probs, mine):
    """The routed rows sorted by held expert and multiplied in groups
    (``jax.lax.ragged_dot``); assignments to experts held elsewhere sort
    last, outside every group, and add nothing."""
    t, d = xf.shape
    k, held = local.shape[1], cfg.experts_held
    dt = xf.dtype
    e_flat = jnp.where(mine, local, held).reshape(-1)          # [T*k]
    order = jnp.argsort(e_flat, stable=True)
    tok = order // k
    sizes = jnp.bincount(e_flat, length=held + 1)[:held].astype(jnp.int32)
    inside = (jnp.arange(t * k) < jnp.sum(sizes))[:, None]
    h = jax.lax.ragged_dot(xf[tok], p["w1"].astype(dt), sizes)
    gate, up = jnp.split(h, 2, axis=-1)
    h = jnp.where(inside, jax.nn.silu(gate) * up, 0.0).astype(dt)
    y = jax.lax.ragged_dot(h, p["w2"].astype(dt), sizes)
    wt = jnp.where(mine, probs, 0.0).reshape(-1)[order]
    y = jnp.where(inside, y * wt[:, None].astype(dt), 0.0).astype(dt)
    return jnp.zeros((t, d), dt).at[tok].add(y)


def _dropless_apply(cfg: ArchConfig, p, x, *, grouped=None):
    """No-drop routing over the held experts: (out, aux, per-token
    assignment counts over all experts [B*S, n_experts]). ``grouped``
    forces the grouped (True) or dense (False) expert matmuls; by default
    a batch of more than :data:`_GROUP_ROWS` tokens is grouped."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    xf = x.reshape(t, d)
    gates = jax.nn.softmax(
        (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32)), axis=-1)
    probs, idx = jax.lax.top_k(gates, k)                        # [T,k]
    if cfg.norm_topk_prob:
        probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    probs = probs * cfg.routed_scaling_factor
    assign = jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.int32), axis=1)
    aux = e * jnp.sum(jnp.mean(gates, axis=0)
                      * jnp.mean(assign.astype(jnp.float32), axis=0))
    local = idx - cfg.expert_offset
    mine = (local >= 0) & (local < cfg.experts_held)
    if grouped is None:
        grouped = t > _GROUP_ROWS
    experts = _held_experts_grouped if grouped else _held_experts_dense
    out = experts(cfg, p, xf, local, probs, mine).reshape(b, s, d)
    if cfg.n_shared_experts:
        out = out + L.mlp_apply(p["shared"], x, "swiglu")
    return out, aux.astype(jnp.float32), assign


def moe_ffn_apply(cfg: ArchConfig, p, x):
    """x: [B,S,D] -> (out [B,S,D], aux load-balance loss); the no-drop
    layer (``capacity_factor == 0``) adds each token's assignment counts
    over all experts, [B*S, n_experts]."""
    if not cfg.capacity_factor:
        return _dropless_apply(cfg, p, x)
    if cfg.moe_local_dispatch:
        return _local_dispatch_apply(cfg, p, x)
    b, s, d = x.shape
    e, k, f = cfg.n_experts, cfg.top_k, cfg.moe_d_ff
    t = b * s
    xf = x.reshape(t, d)
    capacity = int(t // e * k * cfg.capacity_factor) + 1
    # round capacity so the buffer's capacity dim stays mesh-divisible
    gran = 2048 if t >= (1 << 17) else 8
    capacity = -(-capacity // gran) * gran

    gates = jax.nn.softmax(
        (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32)), axis=-1)
    idx, probs, slot, keep = _dispatch_indices(gates, k, capacity)

    # load-balance aux loss (Switch-style)
    me = jnp.mean(gates, axis=0)                                  # [E]
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=1), axis=0)
    aux = e * jnp.sum(me * ce)

    # scatter tokens into the expert-sharded dispatch buffer
    flat_idx = (idx * capacity + slot)                            # [T,k]
    buf = jnp.zeros((e * capacity, d), x.dtype)
    contrib = xf[:, None, :] * keep[:, :, None].astype(x.dtype)   # [T,k,D]
    buf = buf.at[flat_idx.reshape(-1)].add(
        contrib.reshape(t * k, d), mode="drop")
    # "exp_cap" shards the capacity dim when experts themselves cannot be
    # sharded (grok: 8 experts vs 16-way model axis)
    buf = constrain(buf.reshape(e, capacity, d), ("expert", "exp_cap", "embed"))

    # batched expert FFN (swiglu)
    dt = x.dtype
    h = jnp.einsum("ecd,edf->ecf", buf, p["w1"].astype(dt))
    gate, up = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(gate) * up
    y = jnp.einsum("ecf,efd->ecd", h, p["w2"].astype(dt))
    y = constrain(y, ("expert", "exp_cap", "embed"))

    # gather/combine
    flat_y = y.reshape(e * capacity, d)
    picked = flat_y[flat_idx.reshape(-1)].reshape(t, k, d)
    w = (probs * keep.astype(jnp.float32)).astype(x.dtype)        # [T,k]
    out = jnp.einsum("tkd,tk->td", picked, w).reshape(b, s, d)

    if cfg.n_shared_experts:
        out = out + L.mlp_apply(p["shared"], x, "swiglu")
    return out, aux.astype(jnp.float32)


# ---------------------------------------------------------------------------
# StreamGraph workload: dispatch → expert-matmul → combine
# ---------------------------------------------------------------------------
#
# The kernel-level core of the MoE layer above, as a registered multi-kernel
# pipe graph (repro.core.graph): `dispatch` gathers the routed token rows
# (the paper's irregular-access pattern, ff_gather), `expert` is the regular
# expert FFN matmul over the dispatched buffer, `combine` gathers the expert
# outputs back into token order (the un-permute; routing-prob weighting
# stays in XLA where the layer applies it). The dispatch→expert edge is the
# showcase fusion: the gather's 8·streams-row bundles are exactly the
# matmul's A tiles, so the dispatched buffer never touches HBM — while
# expert→combine ends at an irregular gather stream (data-dependent
# addresses) and stages through HBM by construction, demonstrating the
# per-edge decision.


def build_moe_graph(*, t_tokens: int = 96, n_dispatch: int = 64,
                    d_model: int = 128, d_ff: int = 256, t_out: int = 64,
                    dtype=jnp.float32, depth: int = 2, streams: int = 1,
                    bn: int = 128):
    """Declare the MoE dispatch→expert-matmul→combine StreamGraph.

    ``n_dispatch`` (dispatched rows) and ``t_out`` (combined rows) must be
    multiples of the gather row bundle ``8 * streams``; the expert matmul's
    M tile is pinned to that bundle so the dispatch→expert edge is fusable
    by construction. ``bn`` is the expert matmul's N tile (the joint
    tuner's shared-tile axis).
    """
    from repro.core.graph import GraphEdge, GraphNode, StreamGraph
    from repro.kernels.ff_gather.kernel import _ROWS
    from repro.kernels.ff_gather.kernel import build_program as gather_prog
    from repro.kernels.ff_gather.ops import gather_workload
    from repro.kernels.ff_matmul.kernel import build_program as matmul_prog
    from repro.kernels.ff_matmul.ops import matmul_workload

    rpw = _ROWS * streams
    if n_dispatch % rpw or t_out % rpw:
        raise ValueError(f"n_dispatch={n_dispatch} / t_out={t_out} must be "
                         f"multiples of the {rpw}-row gather bundle")
    block = (rpw, min(bn, d_ff), d_model)
    dispatch = gather_prog(n_dispatch, d_model, dtype=dtype, depth=depth,
                           streams=streams)
    expert = matmul_prog(n_dispatch, d_ff, d_model, block=block, dtype=dtype,
                         depth=depth, streams=streams)
    combine = gather_prog(t_out, d_ff, dtype=dtype, depth=depth,
                          streams=streams)
    w_d, t_d = gather_workload(n_dispatch, d_model, dtype=dtype)
    w_e, t_e = matmul_workload(n_dispatch, d_ff, d_model, block, dtype)
    w_c, t_c = gather_workload(t_out, d_ff, dtype=dtype)
    return StreamGraph(
        name="moe_dispatch_ffn",
        nodes=(
            GraphNode("dispatch", dispatch, workload=w_d, plan_tile=t_d),
            GraphNode("expert", expert, workload=w_e, plan_tile=t_e),
            GraphNode("combine", combine, workload=w_c, plan_tile=t_c),
        ),
        edges=(
            GraphEdge("dispatch", "expert", "a"),
            GraphEdge("expert", "combine", "table"),
        ),
    )


def _moe_graph_inputs(key):
    """Operands in CompiledGraph.arg_names order:
    (dispatch.idx, dispatch.table, expert.b, combine.idx)."""
    # d_ff = 2 N tiles: the expert matmul re-reads each dispatched A tile
    # once per N tile, so the fused ring saves the re-streams too
    t, n, d, f, t_out = 96, 64, 128, 256, 64
    tokens = jax.random.normal(key, (t, d), jnp.float32)
    idx = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, t,
                             dtype=jnp.int32)
    w1 = jax.random.normal(jax.random.fold_in(key, 2), (d, f),
                           jnp.float32) / jnp.sqrt(d)
    comb = jax.random.randint(jax.random.fold_in(key, 3), (t_out,), 0, n,
                              dtype=jnp.int32)
    return (idx, tokens, w1, comb)


def _moe_graph_ref(idx, tokens, w1, comb):
    return (tokens[idx] @ w1)[comb]


def _moe_graph_unfused(idx, tokens, w1, comb):
    """The same computation as three separate repro.ops calls — every
    intermediate round-trips HBM (the BENCH_graph baseline). The expert
    matmul is pinned to the graph's 8-row tile so the comparison isolates
    the lowering (calls + HBM handoffs), not the tiling."""
    import repro

    h = repro.ops.gather(tokens, idx)
    y = repro.ops.matmul(h, w1, block=(8, 128, 128))
    return repro.ops.gather(y, comb)


def moe_dispatch_ffn(idx, tokens, w1, comb, *, policy=None) -> jnp.ndarray:
    """Dispatch→expert-matmul→combine through the fused StreamGraph, at the
    caller's shapes.

    idx: [n_dispatch] int32 rows into ``tokens``; tokens: [T, d_model];
    w1: [d_model, d_ff]; comb: [t_out] int32 rows into the expert output.
    Returns [t_out, d_ff] = ``(tokens[idx] @ w1)[comb]``.

    Unlike ``run_graph`` (fixed smoke shapes), this entrypoint resolves the
    joint graph plan at the call site's shapes and records the site for the
    plan-service sweep — mirroring ``paged_decode_attention``.
    """
    from repro.core import autotune
    from repro.core import graph as graphlib
    from repro.core.program import current_policy

    policy = current_policy() if policy is None else policy
    if policy.mode == "ref":
        return _moe_graph_ref(idx, tokens, w1, comb)
    n = idx.shape[0]
    t_tokens, d_model = tokens.shape
    d_ff = w1.shape[1]
    t_out = comb.shape[0]

    def build(depth=2, streams=1, **tk):
        return build_moe_graph(
            t_tokens=t_tokens, n_dispatch=n, d_model=d_model, d_ff=d_ff,
            t_out=t_out, dtype=tokens.dtype, depth=depth, streams=streams,
            **tk)

    g0 = build()
    w, tile = graphlib.graph_workload(g0)
    sig = graphlib.graph_signature(g0)

    def runner(tk, depth, streams):
        cg = graphlib.compile_graph(
            build(depth=depth, streams=streams, **dict(tk)),
            policy=policy.replace(mode="ff", depth=depth, streams=streams))
        return lambda: cg(idx, tokens, w1, comb)

    choice = autotune.resolve_graph(
        "moe_dispatch_ffn", policy, workload=w, tile=tile,
        dtype=tokens.dtype, signature=sig,
        workload_fn=lambda tk: graphlib.graph_workload(build(**dict(tk))),
        runner=None if autotune.has_tracers(idx, tokens, w1, comb)
        else runner,
        site={"t_tokens": t_tokens, "n_dispatch": n, "d_model": d_model,
              "d_ff": d_ff, "t_out": t_out},
        site_dynamic=("t_tokens", "n_dispatch", "t_out"),
        tile_options=({"bn": 64},))
    # compiled fresh per call (trace-scoped closures must not be reused)
    mode = "ff" if policy.mode == "autotune" else policy.mode
    cg = graphlib.compile_graph(
        build(depth=choice.depth, streams=choice.streams,
              **dict(choice.tile_kwargs)),
        policy=policy.replace(mode=mode, depth=choice.depth,
                              streams=choice.streams))
    return cg(idx, tokens, w1, comb)


def _moe_sweep_inputs(key, site):
    """Rebuild moe_dispatch_ffn operands at a recorded call-site shape
    (plan sweep)."""
    t = int(site.get("t_tokens", 96))
    n, d = int(site["n_dispatch"]), int(site["d_model"])
    f, t_out = int(site["d_ff"]), int(site["t_out"])
    dt = jnp.dtype(site.get("dtype", "float32"))
    tokens = jax.random.normal(key, (t, d), dt)
    idx = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, t,
                             dtype=jnp.int32)
    w1 = jax.random.normal(jax.random.fold_in(key, 2), (d, f),
                           dt) / jnp.sqrt(d).astype(dt)
    comb = jax.random.randint(jax.random.fold_in(key, 3), (t_out,), 0, n,
                              dtype=jnp.int32)
    return (idx, tokens, w1, comb), {}


def _register_moe_graph():
    from repro.kernels.registry import register_graph

    register_graph(
        name="moe_dispatch_ffn",
        build=build_moe_graph,
        make_inputs=_moe_graph_inputs,
        ref=_moe_graph_ref,
        unfused=_moe_graph_unfused,
        tile_options=({"bn": 64},),
        tol=5e-4,
        doc="MoE dispatch (irregular gather) -> expert matmul -> combine; "
            "dispatch->expert fuses, expert->combine stages (gather edge)",
        # plan-service sweep: resolve at call-site shapes through the real
        # entrypoint, not run_graph's fixed smoke point
        op=moe_dispatch_ffn,
        sweep_inputs=_moe_sweep_inputs,
    )


_register_moe_graph()
