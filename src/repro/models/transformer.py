"""Dense decoder-only transformer (GQA + RoPE), the backbone for the
dense/moe/vlm families.

Layer math is injectable (``mixer_specs`` / ``mixer_apply`` for attention or
MLA, ``ffn_specs`` / ``ffn_apply`` for dense or MoE FFNs), so MoE and MLA
variants reuse the same stacked-scan machinery. Layers are stacked along a
leading L dim and iterated with ``lax.scan`` (HLO-compact: one compiled
body), with optional unrolled mode (``cfg.scan_layers=False``) used by the
roofline's per-layer cost extraction.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.runtime.sharding import constrain


# ---------------------------------------------------------------------------
# GQA attention mixer (the default)
# ---------------------------------------------------------------------------


def attn_specs(cfg: ArchConfig) -> Dict[str, Any]:
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    s = {
        "wq": L.ParamSpec((d, h, hd), ("embed", "heads", None)),
        "wk": L.ParamSpec((d, kvh, hd), ("embed", "kv_heads", None)),
        "wv": L.ParamSpec((d, kvh, hd), ("embed", "kv_heads", None)),
        "wo": L.ParamSpec((h, hd, d), ("heads", None, "embed")),
    }
    if cfg.qkv_bias:
        s["bq"] = L.ParamSpec((h, hd), ("heads", None), init="zeros")
        s["bk"] = L.ParamSpec((kvh, hd), ("kv_heads", None), init="zeros")
        s["bv"] = L.ParamSpec((kvh, hd), ("kv_heads", None), init="zeros")
    return s


def _project_qkv(cfg: ArchConfig, p, x):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    return q, k, v


def attn_apply(cfg: ArchConfig, p, x, *, positions, cache=None,
               lengths=None) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """x: [B,S,D]. cache (decode): {"k","v": [B,Smax,KVH,hd]}; returns
    (out [B,S,D], new_cache)."""
    q, k, v = _project_qkv(cfg, p, x)
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)
    if cache is None:
        q = constrain(q, ("batch", "seq", "heads", None))
        out = L.attention_op(q, k, v, causal=True, impl=cfg.attn_impl)
        # cache layout: seq dim re-sharded per the "kv" rule (decode shards
        # the cache sequence over the model axis)
        new_cache = {"k": constrain(k, ("batch", "kv", "kv_heads", None)),
                     "v": constrain(v, ("batch", "kv", "kv_heads", None))}
    elif "kv_pool" in cache:
        # paged decode: append this token's K/V through the block table,
        # then attend via the gather->attention stream graph (sentinel
        # table entries drop the write / mask the read, so inactive
        # continuous-batching slots are inert)
        from repro.runtime.paged_kv import scatter_token
        pool = scatter_token(cache["kv_pool"], cache["block_tables"],
                             lengths, k[:, 0], v[:, 0],
                             n_blocks=cache["kv_pool"].shape[0])
        out = L.paged_decode_attention_op(
            q[:, 0], pool, cache["block_tables"], lengths + 1,
            impl=cfg.attn_impl)[:, None]
        new_cache = {"kv_pool": pool,
                     "block_tables": cache["block_tables"]}
    else:
        b = x.shape[0]
        ck = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(
            c, u, i, axis=0))(cache["k"], k, lengths)
        cv = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(
            c, u, i, axis=0))(cache["v"], v, lengths)
        out = L.decode_attention_op(q[:, 0], ck, cv, lengths + 1,
                                    impl=cfg.attn_impl,
                                    block_kv=cfg.decode_block_kv)[:, None]
        new_cache = {"k": ck, "v": cv}
    dt = x.dtype
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt)), new_cache


def attn_cache_spec(cfg: ArchConfig, batch: int, s_max: int):
    shape = (batch, s_max, cfg.n_kv_heads, cfg.hd)
    spec = {"k": jax.ShapeDtypeStruct(shape, cfg.cdtype),
            "v": jax.ShapeDtypeStruct(shape, cfg.cdtype)}
    axes = {"k": ("batch", "kv", "kv_heads", None),
            "v": ("batch", "kv", "kv_heads", None)}
    return spec, axes


# ---------------------------------------------------------------------------
# Dense FFN (default ffn hook)
# ---------------------------------------------------------------------------


def ffn_specs(cfg: ArchConfig) -> Dict[str, Any]:
    return L.mlp_specs(cfg.d_model, cfg.d_ff, cfg.act)


def ffn_apply(cfg: ArchConfig, p, x):
    y = L.mlp_apply(p, x, cfg.act)
    return y, jnp.zeros((), jnp.float32)     # (out, aux_loss)


# ---------------------------------------------------------------------------
# Decoder stack
# ---------------------------------------------------------------------------


class DecoderStack:
    """Stacked pre-norm decoder with injectable mixer/ffn."""

    def __init__(self, cfg: ArchConfig,
                 mixer_specs=attn_specs, mixer_apply=attn_apply,
                 mixer_cache_spec=attn_cache_spec,
                 ffn_specs=ffn_specs, ffn_apply=ffn_apply):
        self.cfg = cfg
        self._mixer_specs = mixer_specs
        self._mixer_apply = mixer_apply
        self._mixer_cache_spec = mixer_cache_spec
        self._ffn_specs = ffn_specs
        self._ffn_apply = ffn_apply

    # -- specs ---------------------------------------------------------------

    def layer_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        return {
            "norm1": L.norm_specs(cfg.norm, cfg.d_model),
            "mixer": self._mixer_specs(cfg),
            "norm2": L.norm_specs(cfg.norm, cfg.d_model),
            "ffn": self._ffn_specs(cfg),
        }

    def specs(self) -> Dict[str, Any]:
        """Params are always stacked [L, ...]; ``cfg.scan_layers`` only
        selects scan vs. indexed-unroll iteration (same param structure, so
        cost-extraction variants restore nothing)."""
        cfg = self.cfg
        one = self.layer_specs()
        stacked = jax.tree.map(
            lambda s: L.ParamSpec((cfg.n_layers, *s.shape),
                                  ("layers", *s.axes), s.dtype, s.init,
                                  s.scale),
            one, is_leaf=L.is_spec)
        return {"layers": stacked}

    def cache_spec(self, batch: int, s_max: int):
        cfg = self.cfg
        one, one_axes = self._mixer_cache_spec(cfg, batch, s_max)
        spec = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((cfg.n_layers, *s.shape), s.dtype),
            one)
        axes = jax.tree.map(lambda a: ("layers", *a), one_axes,
                            is_leaf=lambda x: isinstance(x, tuple))
        return spec, axes

    # -- forward ---------------------------------------------------------------

    def _layer(self, p, x, positions, cache, lengths, want_cache: bool):
        cfg = self.cfg
        if (cfg.layer_graph and cache is not None and "kv_pool" not in cache
                and x.shape[1] == 1 and cfg.norm == "rmsnorm"
                and cfg.act == "swiglu"
                and self._mixer_apply is attn_apply
                and self._ffn_apply is ffn_apply):
            return self._decode_layer_graph(p, x, positions, cache, lengths)
        # NOTE (§Perf it4a, refuted): inserting explicit Megatron-SP
        # all-gather / reduce-scatter constraints around the norms tripled
        # compiled FLOPs — XLA SPMD fell back to replicate-and-repartition
        # ("involuntary full remat"). The single residual-boundary constraint
        # below lets the partitioner place the boundary collectives itself.
        h = L.norm_apply(cfg.norm, x, p["norm1"])
        attn_out, new_cache = self._mixer_apply(
            cfg, p["mixer"], h, positions=positions, cache=cache,
            lengths=lengths)
        # named so remat="collectives" can save the post-all-reduce tensors
        # (backward then re-runs only device-local math, not the TP psums)
        attn_out = checkpoint_name(attn_out, "attn_out")
        x = x + attn_out
        h = L.norm_apply(cfg.norm, x, p["norm2"])
        ffn_out, aux, *routed = self._ffn_apply(cfg, p["ffn"], h)
        if routed and cache is not None:
            # a decode step hands out each row's expert assignments
            new_cache = {**new_cache,
                         "expert_counts": routed[0].reshape(x.shape[0], -1)}
        ffn_out = checkpoint_name(ffn_out, "ffn_out")
        x = x + ffn_out
        # residual saves use the SP axis (None by default; "model" enables
        # Megatron sequence parallelism for layer-boundary activations)
        x = constrain(x, ("batch", "seq_sp", "embed"))
        if cfg.bf16_grads:
            x = L.bf16_grad_cast(x)   # bwd: boundary cotangent in bf16
        if not want_cache and cache is None:
            new_cache = None    # train mode: never stack per-layer caches
        return x, new_cache, aux

    def _decode_layer_graph(self, p, x, positions, cache, lengths):
        """One dense-cache decode step through the whole-layer
        ``decode_layer`` StreamGraph (ROADMAP item 2): q-projection +
        RoPE + attention + out-projection + SwiGLU MLP as one planned
        multi-kernel program, residual adds and RMSNorms folded into the
        consumer bodies. The K/V projection and cache update stay outside
        the graph — the cache write must materialize in HBM regardless."""
        cfg = self.cfg
        dt = x.dtype
        mp = p["mixer"]
        h1 = L.norm_apply(cfg.norm, x, p["norm1"])
        k = jnp.einsum("bsd,dhk->bshk", h1, mp["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bshk", h1, mp["wv"].astype(dt))
        if cfg.qkv_bias:
            k = k + mp["bk"].astype(dt)
            v = v + mp["bv"].astype(dt)
        k = L.rope(k, positions, cfg.rope_theta)
        ck = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(
            c, u, i, axis=0))(cache["k"], k, lengths)
        cv = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(
            c, u, i, axis=0))(cache["v"], v, lengths)
        d, h_q, hd = cfg.d_model, cfg.n_heads, cfg.hd
        fp = p["ffn"]
        wi = fp["wi"].astype(dt)
        f = wi.shape[1] // 2
        out = L.decode_layer(
            x[:, 0], p["norm1"]["w"],
            mp["wq"].astype(dt).reshape(d, h_q * hd),
            mp["bq"].astype(dt).reshape(h_q * hd) if cfg.qkv_bias else None,
            positions[:, -1],
            ck.transpose(0, 2, 1, 3), cv.transpose(0, 2, 1, 3),
            lengths + 1,
            mp["wo"].astype(dt).reshape(h_q * hd, d), p["norm2"]["w"],
            wi[:, :f], wi[:, f:], fp["wo"].astype(dt),
            rope_theta=cfg.rope_theta, block_kv=cfg.decode_block_kv)
        return out[:, None], {"k": ck, "v": cv}, jnp.zeros((), jnp.float32)

    def _remat_layer(self):
        cfg = self.cfg
        fn = self._layer
        if cfg.remat == "none":
            return fn
        if cfg.remat == "dots":
            policy = jax.checkpoint_policies.checkpoint_dots
        elif cfg.remat == "collectives":
            policy = jax.checkpoint_policies.save_only_these_names(
                "attn_out", "ffn_out")
        else:
            policy = jax.checkpoint_policies.nothing_saveable
        return jax.checkpoint(fn, policy=policy, static_argnums=(5,))

    def decode_pool(self, params, x, *, positions, pool, block_tables,
                    lengths, first: int):
        """Decode one token through a paged pool that spans more layers
        than this stack: layer ``i`` reads and writes pool layer ``first +
        i``, in place on the scan's carry, so the pool is never sliced
        apart and stacked again. Returns (x, pool, per-layer
        ``expert_counts`` or None, aux)."""
        layer = self._remat_layer()

        def body(carry, xs):
            xx, pool, aux = carry
            p, j = xs
            cache = {"kv_pool": jax.lax.dynamic_index_in_dim(
                         pool, j, keepdims=False),
                     "block_tables": jax.lax.dynamic_index_in_dim(
                         block_tables, j, keepdims=False)}
            xx, nc, a = layer(p, xx, positions, cache, lengths, True)
            pool = jax.lax.dynamic_update_index_in_dim(pool, nc["kv_pool"],
                                                       j, 0)
            return (xx, pool, aux + a), nc.get("expert_counts")

        (x, pool, aux), assign = jax.lax.scan(
            body, (x, pool, jnp.zeros((), jnp.float32)),
            (params["layers"], first + jnp.arange(self.cfg.n_layers)))
        return x, pool, assign, aux

    def __call__(self, params, x, *, positions, caches=None, lengths=None,
                 want_cache: bool = False):
        """x: [B,S,D]. caches: stacked (scan) or list (unrolled) or None.
        Returns (x, new_caches, aux_loss_sum)."""
        cfg = self.cfg
        layer = self._remat_layer()
        if cfg.scan_layers:
            if caches is None:
                def body_nocache(carry, p):
                    xx, aux = carry
                    xx, new_cache, a = layer(p, xx, positions, None, lengths,
                                             want_cache)
                    return (xx, aux + a), new_cache
                (x, aux), new_caches = jax.lax.scan(
                    body_nocache, (x, jnp.zeros((), jnp.float32)),
                    params["layers"])
                return x, new_caches, aux

            def body(carry, xs):
                xx, aux = carry
                p, cache = xs
                xx, new_cache, a = layer(p, xx, positions, cache, lengths,
                                         want_cache)
                return (xx, aux + a), new_cache
            (x, aux), new_caches = jax.lax.scan(
                body, (x, jnp.zeros((), jnp.float32)),
                (params["layers"], caches))
            return x, new_caches, aux
        # unrolled: index the stacked params (same structure as scan mode)
        aux = jnp.zeros((), jnp.float32)
        new_caches = []
        for i in range(cfg.n_layers):
            p = jax.tree.map(lambda a: a[i], params["layers"])
            cache = (jax.tree.map(lambda a: a[i], caches)
                     if caches is not None else None)
            x, nc, a = layer(p, x, positions, cache, lengths, want_cache)
            new_caches.append(nc)
            aux = aux + a
        if new_caches and new_caches[0] is not None:
            new_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *new_caches)
        else:
            new_caches = None
        return x, new_caches, aux
