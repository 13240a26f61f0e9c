"""Multi-head Latent Attention (deepseek-v2) mixer.

KV is compressed to a ``kv_lora_rank`` latent plus a single shared RoPE key;
the decode cache stores only ``[B, S, kv_lora + rope]`` — ~10x smaller than a
GQA cache at these dims. Paper mapping: the latent cache is a *small regular
stream* (the paper's favourable prefetching-LSU case); decode cells for
deepseek are the least memory-bound of the MoE archs in the roofline table.

Two forms of the same attention:

* explicit (prefill, and decode on a dense cache): the latent expands into
  per-head keys ``[c W_UK, k_rope]`` and values ``c W_UV``;
* absorbed (decode on the paged latent pool): the query folds ``W_UK`` in,
  ``q_lat = q_nope W_UK^T``, so every head scores ``[q_lat, q_rope]``
  against the one shared ``[c, k_rope]`` row of each cached token and sums
  ``c`` itself; ``W_UV`` and ``wo`` then apply to the result
  (:func:`~repro.runtime.paged_kv.paged_latent_attention`).

The cache holds ``k_rope`` with its rotation applied. RoPE is YaRN-scaled
where ``cfg.rope_factor > 1`` (:func:`yarn_inv_freq`), and the softmax
scale is ``mscale**2 / sqrt(qk_nope + qk_rope)`` (:func:`softmax_scale`).

Shapes (lite defaults): d=2048, H=16, kv_lora=512, nope=128, rope=64, v=128.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.runtime.sharding import constrain


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: ``0.1 * mscale * ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: ArchConfig) -> jnp.ndarray:
    """RoPE frequencies of the ``qk_rope_dim`` rotary lanes. With YaRN
    (``rope_factor > 1``) the frequencies past the correction range are
    divided by the factor, those before it kept, and a linear ramp blends
    the two over it (DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``)."""
    dim, base = cfg.qk_rope_dim, cfg.rope_theta
    extra = base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if cfg.rope_factor <= 1:
        return extra

    def corr(rot):
        return (dim * math.log(cfg.rope_original_max_pos
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    lo = max(math.floor(corr(cfg.rope_beta_fast)), 0)
    hi = min(math.ceil(corr(cfg.rope_beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo)
                    / max(hi - lo, 1e-3), 0.0, 1.0)
    return extra / cfg.rope_factor * ramp + extra * (1.0 - ramp)


def _rope_scale(cfg: ArchConfig) -> float:
    """cos/sin scale: ``mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)`` (1.0 for DeepSeek-V2-Lite, where the two agree)."""
    if cfg.rope_factor <= 1 or not cfg.rope_mscale_all_dim:
        return 1.0
    return (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
            / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))


def softmax_scale(cfg: ArchConfig) -> float:
    """``mscale(factor, mscale_all_dim)**2 / sqrt(qk_nope + qk_rope)``."""
    m = yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) \
        if cfg.rope_mscale_all_dim else 1.0
    return m * m / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)


def _rope(cfg: ArchConfig, x, positions):
    """Rotate-half RoPE with the YaRN frequencies. x: [..., S, H, rope]."""
    ang = positions[..., :, None].astype(jnp.float32) * yarn_inv_freq(cfg)
    cos = (jnp.cos(ang) * _rope_scale(cfg))[..., None, :]
    sin = (jnp.sin(ang) * _rope_scale(cfg))[..., None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def mla_specs(cfg: ArchConfig) -> Dict[str, Any]:
    d, h = cfg.d_model, cfg.n_heads
    r, nope, rope_d, vd = (cfg.kv_lora_rank, cfg.qk_nope_dim,
                           cfg.qk_rope_dim, cfg.v_head_dim)
    return {
        "wq": L.ParamSpec((d, h, nope + rope_d), ("embed", "heads", None)),
        "wdkv": L.ParamSpec((d, r + rope_d), ("embed", None)),
        "kv_norm": L.norm_specs("rmsnorm", r),
        "wuk": L.ParamSpec((r, h, nope), (None, "heads", None)),
        "wuv": L.ParamSpec((r, h, vd), (None, "heads", None)),
        "wo": L.ParamSpec((h, vd, d), ("heads", None, "embed")),
    }


def _compress(cfg: ArchConfig, p, x, positions):
    """x: [B,S,D] -> latent c_kv [B,S,r], rotated k_rope [B,S,rope]."""
    dt = x.dtype
    ckv = x @ p["wdkv"].astype(dt)
    c, k_rope = jnp.split(ckv, [cfg.kv_lora_rank], axis=-1)
    c = L.rmsnorm(c, p["kv_norm"]["w"])
    k_rope = _rope(cfg, k_rope[:, :, None, :], positions)[:, :, 0]
    return c, k_rope


def _decompress(cfg: ArchConfig, p, c, k_rope):
    """latent -> per-head k [B,S,H,nope+rope], v [B,S,H,vd]."""
    dt = c.dtype
    k_nope = jnp.einsum("bsr,rhk->bshk", c, p["wuk"].astype(dt))
    v = jnp.einsum("bsr,rhk->bshk", c, p["wuv"].astype(dt))
    k_rope = jnp.broadcast_to(k_rope[:, :, None, :],
                              (*k_nope.shape[:3], cfg.qk_rope_dim))
    return jnp.concatenate([k_nope, k_rope], axis=-1), v


def _explicit_prefill(cfg: ArchConfig, q, k, v):
    """Causal attention of the explicit form through ``attention_op``,
    whose score scale is ``1/sqrt(q width)``: q carries the rest of
    :func:`softmax_scale`. The kernel path takes one head width in whole
    128-lane words, so q, k and v are zero-padded to it (zero lanes add
    nothing to a score, and the padded value lanes are dropped)."""
    vd = v.shape[-1]
    if cfg.attn_impl != "xla":
        d = -(-max(q.shape[-1], vd) // 128) * 128
        pad = lambda a: jnp.pad(a, ((0, 0),) * 3 + ((0, d - a.shape[-1]),))
        q, k, v = pad(q), pad(k), pad(v)
    q = q * (softmax_scale(cfg) * math.sqrt(q.shape[-1]))
    q = constrain(q, ("batch", "seq", "heads", None))
    out = L.attention_op(q, k, v, causal=True, impl=cfg.attn_impl)
    return out[..., :vd]


def _absorbed_decode(cfg: ArchConfig, p, q_nope, q_rope, cache, lengths):
    """One token per row against the paged latent pool: ``[q_nope W_UK^T,
    q_rope]`` scores each cached ``[c, k_rope]`` row, the softmax sums
    ``c``, and ``W_UV`` maps it back to the heads' values."""
    dt = q_nope.dtype
    q_lat = jnp.einsum("bhk,rhk->bhr", q_nope, p["wuk"].astype(dt))
    q = jnp.concatenate([q_lat, q_rope], axis=-1)             # [B,H,r+rope]
    o_lat = L.paged_latent_attention_op(
        q, cache["kv_pool"], cache["block_tables"], lengths,
        v_dim=cfg.kv_lora_rank, scale=softmax_scale(cfg),
        impl=cfg.attn_impl)                                    # [B,H,r]
    return jnp.einsum("bhr,rhk->bhk", o_lat.astype(dt), p["wuv"].astype(dt))


def mla_apply(cfg: ArchConfig, p, x, *, positions, cache=None,
              lengths=None) -> Tuple[jnp.ndarray, Optional[Dict]]:
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_dim], axis=-1)
    q_rope = _rope(cfg, q_rope, positions)
    c_new, k_rope_new = _compress(cfg, p, x, positions)

    if cache is None:
        k, v = _decompress(cfg, p, c_new, k_rope_new)
        out = _explicit_prefill(
            cfg, jnp.concatenate([q_nope, q_rope], axis=-1), k, v)
        new_cache = {"c": c_new, "k_rope": k_rope_new}
    elif "kv_pool" in cache:
        # paged latent pool: write this token's row through the block
        # table, then attend in the absorbed form (sentinel table entries
        # drop the write and mask the read, so idle slots are inert)
        from repro.runtime.paged_kv import scatter_latent_token
        pool = scatter_latent_token(cache["kv_pool"], cache["block_tables"],
                                    lengths, c_new[:, 0], k_rope_new[:, 0])
        new_cache = {"kv_pool": pool, "block_tables": cache["block_tables"]}
        out = _absorbed_decode(cfg, p, q_nope[:, 0], q_rope[:, 0],
                               new_cache, lengths + 1)[:, None]
    else:
        cc = jax.vmap(lambda cch, u, i: jax.lax.dynamic_update_slice_in_dim(
            cch, u, i, axis=0))(cache["c"], c_new, lengths)
        cr = jax.vmap(lambda cch, u, i: jax.lax.dynamic_update_slice_in_dim(
            cch, u, i, axis=0))(cache["k_rope"], k_rope_new, lengths)
        # decompress the whole cached latent stream (explicit form)
        k, v = _decompress(cfg, p, cc, cr)
        qf = jnp.concatenate([q_nope, q_rope], axis=-1)
        qf = qf * (softmax_scale(cfg) * math.sqrt(qf.shape[-1]))
        out = L.decode_attention_op(qf[:, 0], k, v, lengths + 1,
                                    impl="xla")[:, None]
        new_cache = {"c": cc, "k_rope": cr}
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt)), new_cache


def mla_cache_spec(cfg: ArchConfig, batch: int, s_max: int):
    spec = {
        "c": jax.ShapeDtypeStruct((batch, s_max, cfg.kv_lora_rank), cfg.cdtype),
        "k_rope": jax.ShapeDtypeStruct((batch, s_max, cfg.qk_rope_dim),
                                       cfg.cdtype),
    }
    axes = {"c": ("batch", "kv", None), "k_rope": ("batch", "kv", None)}
    return spec, axes
