"""A plain forward pass of DeepSeek-V2's published equations, for the CPU
tests: ``jax.numpy`` in float32 at ``highest`` matmul precision, one
sequence, no cache, no kernels. It reads the program's parameter tree
(``repro.models`` layout) and nothing else of the program.

Departures from the published model, each shared with the program:
RoPE is rotate-half on the de-interleaved layout (HF permutes q_pe and
k_pe into it first: a fixed permutation of drawn weight columns), and of
the routed experts only the held share (``n_experts_held`` from
``expert_offset``) contributes, as on one chip of an expert-parallel
deployment; the shared experts always do.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _rms(x, w, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def yarn_inv_freq(dim, base, factor, orig, beta_fast, beta_slow):
    """HF ``DeepseekV2YarnRotaryEmbedding``'s frequencies."""
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if factor <= 1:
        return extra
    inter = extra / factor

    def corr_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rope(x, pos, inv):
    ang = pos[:, None].astype(jnp.float32) * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    h = x.shape[-1] // 2
    return jnp.concatenate([x[..., :h] * cos - x[..., h:] * sin,
                            x[..., h:] * cos + x[..., :h] * sin], -1)


def _attention(cfg, p, h, pos):
    r, nope = cfg.kv_lora_rank, cfg.qk_nope_dim
    inv = yarn_inv_freq(cfg.qk_rope_dim, cfg.rope_theta, cfg.rope_factor,
                        cfg.rope_original_max_pos, cfg.rope_beta_fast,
                        cfg.rope_beta_slow)
    q = jnp.einsum("td,dhk->thk", h, p["wq"])
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, inv)], -1)
    ckv = h @ p["wdkv"]
    c = _rms(ckv[:, :r], p["kv_norm"]["w"])
    k_rope = _rope(ckv[:, None, r:], pos, inv)
    k_nope = jnp.einsum("tr,rhk->thk", c, p["wuk"])
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope, (*k_nope.shape[:2], k_rope.shape[-1]))], -1)
    v = jnp.einsum("tr,rhk->thk", c, p["wuv"])
    m = yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) \
        if cfg.rope_mscale_all_dim else 1.0
    scale = m * m / math.sqrt(q.shape[-1])
    s = jnp.einsum("qhd,khd->hqk", q, k) * scale
    t = h.shape[0]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
    return jnp.einsum("thk,hkd->td", o, p["wo"])


def _swiglu(wi, wo, h):
    f = wi.shape[-1] // 2
    z = h @ wi
    return (jax.nn.silu(z[:, :f]) * z[:, f:]) @ wo


def moe(cfg, p, h):
    """Softmax over all experts, top-k, the held experts' weighted sum
    (renormalised only with ``norm_topk_prob``), plus the shared experts."""
    gates = jax.nn.softmax(h @ p["router"], -1)
    probs, idx = jax.lax.top_k(gates, cfg.top_k)
    if cfg.norm_topk_prob:
        probs = probs / jnp.sum(probs, -1, keepdims=True)
    probs = probs * cfg.routed_scaling_factor
    out = jnp.zeros_like(h)
    for e in range(p["w1"].shape[0]):
        w = jnp.sum(jnp.where(idx == cfg.expert_offset + e, probs, 0.0), -1)
        out = out + w[:, None] * _swiglu(p["w1"][e], p["w2"][e], h)
    if cfg.n_shared_experts:
        out = out + _swiglu(p["shared"]["wi"], p["shared"]["wo"], h)
    return out


def logits(cfg, params, tokens):
    """[T, vocab] next-token logits of one sequence."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        pos = jnp.arange(tokens.shape[0])
        stacks = [("dense_stack", False)] * bool(cfg.first_k_dense) + \
            [("stack", True)]
        for name, routed in stacks:
            layers = params[name]["layers"]
            for i in range(layers["norm1"]["w"].shape[0]):
                p = jax.tree.map(lambda a: a[i].astype(jnp.float32), layers)
                x = x + _attention(cfg, p["mixer"],
                                   _rms(x, p["norm1"]["w"]), pos)
                h = _rms(x, p["norm2"]["w"])
                x = x + (moe(cfg, p["ffn"], h) if routed else
                         _swiglu(p["ffn"]["wi"], p["ffn"]["wo"], h))
        x = _rms(x, params["final_norm"]["w"])
        return x @ params["unembed"].astype(jnp.float32).T
