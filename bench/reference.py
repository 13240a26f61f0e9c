"""Pieces that every architecture's plain reference (``archs/<model_type>.py``
``logits_at``) is built from: the published equations in ``jax.numpy``,
float32 throughout, matmuls at ``highest`` precision (the caller sets it).

A reference imports nothing of the program and reads only the canonical
weights its architecture draws from the seed. Attention is computed one
block of ``Q_BLOCK`` query rows at a time, so a 4k-token context of a
6144-wide model fits beside nothing else on one chip.

``precision="fp8"`` is the control: every matmul operand (weights per
tensor, activations per row, attention's q, k, v and softmax weights)
rounded to float8 e4m3 with an absmax scale, accumulated in float32 — the
next precision below the bf16 the configurations state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Q_BLOCK = 256
F8_MAX = 448.0          # largest finite float8 e4m3


def _fp8(x, axes):
    """Round ``x`` to float8 e4m3 under an absmax scale over ``axes``."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _act(x, precision):
    """An activation operand: per row (the last axis is reduced)."""
    return _fp8(x, -1) if precision == "fp8" else x


def _wt(w, precision):
    """A weight operand: one scale per tensor."""
    return _fp8(w, tuple(range(w.ndim))) if precision == "fp8" else w


def _norm(m, x, w, b=None):
    """``m.norm`` (``rmsnorm`` | ``layernorm``) at ``m.norm_eps``."""
    if m.norm == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + m.norm_eps) * w
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + m.norm_eps) * w + b


def _rope(x, pos, theta):
    """Rotate-half RoPE. x: [T, heads, hd]; pos: [T]."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                    / x.shape[-1])
    ang = pos[:, None].astype(jnp.float32) * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, precision):
    """Causal attention with grouped KV heads, one block of ``Q_BLOCK``
    query rows at a time, scores scaled by q's head size to the -1/2.
    q: [T, H, dk]; k: [T, KVH, dk]; v: [T, KVH, dv] -> [T, H, dv]."""
    t, h, dk = q.shape
    kvh = k.shape[1]
    qg = _act(q, precision).reshape(t // Q_BLOCK, Q_BLOCK, kvh, h // kvh,
                                    dk)
    k, v = _act(k, precision), _act(v, precision)
    cols = jnp.arange(t)

    def block(args):
        i, qb = args
        s = jnp.einsum("qkgd,skd->kgqs", qb, k) * dk ** -0.5
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(cols[None, None, None] <= rows[None, None, :, None],
                      s, -jnp.inf)
        p = _act(jax.nn.softmax(s, axis=-1), precision)
        return jnp.einsum("kgqs,skd->qkgd", p, v)

    out = jax.lax.map(block, (jnp.arange(t // Q_BLOCK), qg))
    return out.reshape(t, h, v.shape[-1])
