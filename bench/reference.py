"""The plain reference: the published layer equations in ``jax.numpy``,
float32 throughout, matmuls at ``highest`` precision.

It imports nothing of the program and reads only the canonical weights of
``weights.py``. Attention is computed one block of query rows at a time and
the layers one at a time (a scan), so a 4k-token context of a 6144-wide
model fits beside nothing else on one chip.

``precision="fp8"`` is the control: every matmul operand (weights per
tensor, activations per row, attention's q, k, v and softmax weights)
rounded to float8 e4m3 with an absmax scale, accumulated in float32 — the
next precision below the bf16 the configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from model_spec import ModelSpec

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


def _norm(m: ModelSpec, x, w, b=None):
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


def _attention(m: ModelSpec, q, k, v, precision):
    """Causal GQA attention, one block of ``Q_BLOCK`` query rows at a
    time. q: [T, H, hd]; k, v: [T, KVH, hd] -> [T, H, hd]."""
    t = q.shape[0]
    g = m.n_heads // m.n_kv_heads
    qg = _act(q, precision).reshape(t // Q_BLOCK, Q_BLOCK, m.n_kv_heads, g,
                                    m.head_dim)
    k, v = _act(k, precision), _act(v, precision)
    cols = jnp.arange(t)

    def block(args):
        i, qb = args
        s = jnp.einsum("qkgd,skd->kgqs", qb, k) * m.head_dim ** -0.5
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(cols[None, None, None] <= rows[None, None, :, None],
                      s, -jnp.inf)
        p = _act(jax.nn.softmax(s, axis=-1), precision)
        return jnp.einsum("kgqs,skd->qkgd", p, v)

    out = jax.lax.map(block, (jnp.arange(t // Q_BLOCK), qg))
    return out.reshape(t, m.n_heads, m.head_dim)


def _mlp(m: ModelSpec, w, h, precision):
    a = _act(h, precision)
    if m.act == "swiglu":
        gate = a @ _wt(w["w_gate"], precision)
        up = a @ _wt(w["w_up"], precision)
        z = jax.nn.silu(gate) * up
        return _act(z, precision) @ _wt(w["w_down"], precision)
    z = jax.nn.gelu(a @ _wt(w["w_fc"], precision) + w["b_fc"],
                    approximate=True)
    return _act(z, precision) @ _wt(w["w_proj"], precision) + w["b_proj"]


def _layer(m: ModelSpec, precision, x, w):
    pos = jnp.arange(x.shape[0])
    ln = lambda p: _norm(m, x, w[p + "_w"], w.get(p + "_b"))
    h = _act(ln("ln1"), precision)
    q = jnp.einsum("td,dhk->thk", h, _wt(w["wq"], precision))
    k = jnp.einsum("td,dhk->thk", h, _wt(w["wk"], precision))
    v = jnp.einsum("td,dhk->thk", h, _wt(w["wv"], precision))
    if m.qkv_bias:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q, k = _rope(q, pos, m.rope_theta), _rope(k, pos, m.rope_theta)
    o = _attention(m, q, k, v, precision)
    x = x + jnp.einsum("thk,hkd->td", _act(o, precision),
                       _wt(w["wo"], precision))
    return x + _mlp(m, w, ln("ln2"), precision)


@functools.partial(jax.jit, static_argnums=(0, 4))
def logits_at(m: ModelSpec, w, tokens, rows, precision: str = "f32"):
    """Next-token logits of ``tokens`` ([T], T a multiple of ``Q_BLOCK``)
    at positions ``rows`` ([K]): [K, vocab] float32. Causal, so whatever
    pads ``tokens`` past a row does not change that row."""
    stacked = {k: v for k, v in w.items()
               if k not in ("embed", "unembed", "final_w", "final_b")}
    with jax.default_matmul_precision("highest"):
        x = w["embed"][tokens]

        def body(x, lw):
            return _layer(m, precision, x, lw), None

        x, _ = jax.lax.scan(body, x, stacked)
        xr = _norm(m, x[rows], w["final_w"], w.get("final_b"))
        head = w["embed"] if m.tie_embeddings else w["unembed"]
        return _act(xr, precision) @ _wt(head, precision).T
