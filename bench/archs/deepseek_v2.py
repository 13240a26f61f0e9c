"""DeepSeek-V2 (``model_type: deepseek_v2``): multi-head latent attention
(MLA) in every layer, a dense SwiGLU MLP in the first
``first_k_dense_replace`` layers and an expert layer after them: softmax
routing over ``n_routed_experts`` (the published count), the top
``num_experts_per_tok`` weighted as chosen (``norm_topk_prob: false``)
and scaled by ``routed_scaling_factor``, plus ``n_shared_experts`` always
on. RoPE on the 64 rotary lanes is YaRN-scaled (``rope_scaling``).

One chip of an expert-parallel deployment holds ``n_routed_experts`` of
the published experts (the file's count, from ``expert_offset``), routes
over all of them (``published.n_routed_experts``) and computes only its
own experts' part of the result; the reference is given the same share.

Departures from the published model, shared by program and reference:
RoPE is rotate-half on the de-interleaved layout (HF permutes q_pe and
k_pe into it first: a fixed permutation of drawn weight columns); the
absent experts' part is left out.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

import weights
from reference import _act, _attention, _norm, _wt


@dataclasses.dataclass(frozen=True)
class Spec:
    n_layers: int
    n_dense: int            # leading dense layers
    d_model: int
    n_heads: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    d_ff: int               # the dense layers' width
    moe_d_ff: int           # each expert's width
    n_experts: int          # the router's width (published)
    n_held: int             # experts held here
    expert_offset: int
    n_shared: int
    top_k: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    vocab: int
    norm_eps: float
    rope_theta: float
    rope_factor: float
    rope_original_max_pos: int
    rope_beta_fast: float
    rope_beta_slow: float
    rope_mscale: float
    rope_mscale_all_dim: float
    norm: str = "rmsnorm"


# keys whose values this module implements (anything else set differently
# is refused)
_FIXED = dict(hidden_act="silu", attention_bias=False, q_lora_rank=None,
              moe_layer_freq=1, n_group=1, topk_group=1,
              topk_method="greedy", scoring_func="softmax",
              tie_word_embeddings=False)


def spec(c: Dict) -> Spec:
    bad = {k: c.get(k) for k, v in _FIXED.items() if c.get(k, v) != v}
    if bad:
        raise ValueError(f"{c.get('name')}: unsupported {bad}")
    rs = c.get("rope_scaling") or {"type": "none", "factor": 1.0}
    if rs.get("type") not in ("yarn", "none"):
        raise ValueError(f"{c.get('name')}: unsupported rope_scaling {rs}")
    pub = c.get("published", {})
    return Spec(
        n_layers=c["num_hidden_layers"],
        n_dense=c["first_k_dense_replace"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_dim=c["qk_nope_head_dim"], qk_rope_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"], d_ff=c["intermediate_size"],
        moe_d_ff=c["moe_intermediate_size"],
        n_experts=pub.get("n_routed_experts", c["n_routed_experts"]),
        n_held=c["n_routed_experts"],
        expert_offset=c.get("expert_offset", 0),
        n_shared=c["n_shared_experts"], top_k=c["num_experts_per_tok"],
        norm_topk_prob=bool(c["norm_topk_prob"]),
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        vocab=c["vocab_size"], norm_eps=float(c["rms_norm_eps"]),
        rope_theta=float(c["rope_theta"]),
        rope_factor=float(rs.get("factor", 1.0)),
        rope_original_max_pos=int(rs.get("original_max_position_embeddings",
                                         4096)),
        rope_beta_fast=float(rs.get("beta_fast", 32)),
        rope_beta_slow=float(rs.get("beta_slow", 1)),
        rope_mscale=float(rs.get("mscale", 1.0)),
        rope_mscale_all_dim=float(rs.get("mscale_all_dim", 0.0)))


def program_config(c: Dict, m: Spec, *, page: int):
    """The program's ``ArchConfig``: its repo arch with the file's sizes,
    routing and RoPE scaling, the held experts and the compiled ``ff``
    kernels. Raises where the program's layer math would differ from the
    file's (norm, activation, dropping tokens, vocabulary, dtypes)."""
    from repro.configs.base import get_config
    cfg = get_config(c["repro_arch"]).replace(
        n_layers=m.n_layers, first_k_dense=m.n_dense, d_model=m.d_model,
        n_heads=m.n_heads, n_kv_heads=m.n_heads, d_ff=m.d_ff,
        moe_d_ff=m.moe_d_ff, vocab=m.vocab, n_experts=m.n_experts,
        n_experts_held=m.n_held, expert_offset=m.expert_offset,
        n_shared_experts=m.n_shared, top_k=m.top_k,
        norm_topk_prob=m.norm_topk_prob,
        routed_scaling_factor=m.routed_scaling_factor,
        kv_lora_rank=m.kv_lora_rank, qk_nope_dim=m.qk_nope_dim,
        qk_rope_dim=m.qk_rope_dim, v_head_dim=m.v_head_dim,
        rope_theta=m.rope_theta, rope_factor=m.rope_factor,
        rope_original_max_pos=m.rope_original_max_pos,
        rope_beta_fast=m.rope_beta_fast, rope_beta_slow=m.rope_beta_slow,
        rope_mscale=m.rope_mscale, rope_mscale_all_dim=m.rope_mscale_all_dim,
        attn_impl="ff", decode_block_kv=page)
    want = dict(padded_vocab=m.vocab, norm="rmsnorm", act="swiglu",
                capacity_factor=0.0, experts_held=m.n_held,
                tie_embeddings=False, param_dtype="float32",
                compute_dtype="bfloat16")
    got = {k: getattr(cfg, k) for k in want}
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    if m.norm_eps != 1e-6:      # the program's RMSNorm epsilon
        bad["norm_eps"] = (1e-6, m.norm_eps)
    if bad:
        raise ValueError(f"program config differs from the file "
                         f"(program, file): {bad}")
    return cfg


# ---------------------------------------------------------------- weights

def _shapes(m: Spec) -> Dict[str, tuple]:
    L, d, h, r = m.n_layers, m.d_model, m.n_heads, m.kv_lora_rank
    qk, nope, vd = m.qk_nope_dim + m.qk_rope_dim, m.qk_nope_dim, m.v_head_dim
    nd, nm, e, f, sf = (m.n_dense, m.n_layers - m.n_dense, m.n_held,
                        m.moe_d_ff, m.n_shared * m.moe_d_ff)
    return {
        "embed": (m.vocab, d), "unembed": (m.vocab, d), "final_w": (d,),
        "ln1_w": (L, d), "ln2_w": (L, d), "kv_norm": (L, r),
        "wq": (L, d, h, qk), "wdkv": (L, d, r + m.qk_rope_dim),
        "wuk": (L, r, h, nope), "wuv": (L, r, h, vd), "wo": (L, h, vd, d),
        "dense_gate": (nd, d, m.d_ff), "dense_up": (nd, d, m.d_ff),
        "dense_down": (nd, m.d_ff, d),
        "router": (nm, d, m.n_experts),
        "exp_gate": (nm, e, d, f), "exp_up": (nm, e, d, f),
        "exp_down": (nm, e, f, d),
        "sh_gate": (nm, d, sf), "sh_up": (nm, d, sf), "sh_down": (nm, sf, d),
    }


def _scale(name: str, m: Spec) -> float:
    """Fan-in scaling of every matrix, so activations keep a spread of
    about 1 and the logits come out with a spread of about 1; small
    offsets for the norm weights, so a program that drops one is seen."""
    if name == "embed":
        return 1.0
    if name in ("wuk", "wuv"):
        return m.kv_lora_rank ** -0.5
    if name == "wo":
        return (m.n_heads * m.v_head_dim) ** -0.5
    if name == "dense_down":
        return m.d_ff ** -0.5
    if name == "exp_down":
        return m.moe_d_ff ** -0.5
    if name == "sh_down":
        return (m.n_shared * m.moe_d_ff) ** -0.5
    if name in ("ln1_w", "ln2_w", "kv_norm", "final_w"):
        return 0.1
    return m.d_model ** -0.5


def _draw(m: Spec, key_data) -> Dict[str, jnp.ndarray]:
    w = weights.draw(_shapes(m), lambda name: _scale(name, m), key_data)
    for name in ("ln1_w", "ln2_w", "kv_norm", "final_w"):
        w[name] = w[name] + 1.0
    return w


@functools.partial(jax.jit, static_argnums=(0,))
def _canonical(m: Spec, key_data):
    return _draw(m, key_data)


def canonical(m: Spec, seed: int) -> Dict[str, jnp.ndarray]:
    """The plain-layout weights of ``m`` for ``seed`` (float32)."""
    return _canonical(m, weights.seed_key_data(seed))


@functools.partial(jax.jit, static_argnums=(0,))
def _program_params(m: Spec, key_data):
    """The same draws in the program's tree: ``embed``, ``unembed``,
    ``final_norm``, and the layers in two stacks, ``dense_stack`` (the
    first ``n_dense``) and ``stack``, each ``{norm1, mixer, norm2,
    ffn}``."""
    w = _draw(m, key_data)
    nd = m.n_dense

    def attn(sl):
        return {"norm1": {"w": w["ln1_w"][sl]}, "norm2": {"w": w["ln2_w"][sl]},
                "mixer": {"wq": w["wq"][sl], "wdkv": w["wdkv"][sl],
                          "kv_norm": {"w": w["kv_norm"][sl]},
                          "wuk": w["wuk"][sl], "wuv": w["wuv"][sl],
                          "wo": w["wo"][sl]}}

    cat = lambda a, b: jnp.concatenate([a, b], axis=-1)
    moe = {"router": w["router"], "w1": cat(w["exp_gate"], w["exp_up"]),
           "w2": w["exp_down"]}
    if m.n_shared:
        moe["shared"] = {"wi": cat(w["sh_gate"], w["sh_up"]),
                         "wo": w["sh_down"]}
    tree = {"embed": w["embed"], "unembed": w["unembed"],
            "final_norm": {"w": w["final_w"]},
            "stack": {"layers": dict(attn(slice(nd, None)), ffn=moe)}}
    if nd:
        tree["dense_stack"] = {"layers": dict(
            attn(slice(0, nd)),
            ffn={"wi": cat(w["dense_gate"], w["dense_up"]),
                 "wo": w["dense_down"]})}
    return tree


def program_params(m: Spec, seed: int):
    return _program_params(m, weights.seed_key_data(seed))


# -------------------------------------------------------------- reference

def _yarn_inv_freq(m: Spec):
    """YaRN's RoPE frequencies (DeepSeek-V2's published code)."""
    dim, base = m.qk_rope_dim, m.rope_theta
    extra = base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if m.rope_factor <= 1:
        return extra

    def corr_dim(rot):
        return dim * math.log(m.rope_original_max_pos
                              / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr_dim(m.rope_beta_fast)), 0)
    high = min(math.ceil(corr_dim(m.rope_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / m.rope_factor * ramp + extra * (1.0 - ramp)


def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rope(m: Spec, x, pos):
    """Rotate-half RoPE at YaRN's frequencies. x: [T, heads, rope]."""
    ang = pos[:, None].astype(jnp.float32) * _yarn_inv_freq(m)[None]
    s = (_mscale(m.rope_factor, m.rope_mscale)
         / _mscale(m.rope_factor, m.rope_mscale_all_dim)
         if m.rope_mscale_all_dim else 1.0)
    cos, sin = (jnp.cos(ang) * s)[:, None], (jnp.sin(ang) * s)[:, None]
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mla(m: Spec, precision, x, w):
    """MLA in the explicit form: the latent expands into every head's key
    ``[c W_UK, k_rope]`` and value ``c W_UV``; scores scaled by
    ``mscale**2 / sqrt(qk_nope + qk_rope)``."""
    r, nope = m.kv_lora_rank, m.qk_nope_dim
    pos = jnp.arange(x.shape[0])
    h = _act(_norm(m, x, w["ln1_w"]), precision)
    q = jnp.einsum("td,dhk->thk", h, _wt(w["wq"], precision))
    ckv = h @ _wt(w["wdkv"], precision)
    c = _norm(m, ckv[:, :r], w["kv_norm"])
    k_rope = _rope(m, ckv[:, None, r:], pos)
    q = jnp.concatenate([q[..., :nope], _rope(m, q[..., nope:], pos)], -1)
    ca = _act(c, precision)
    k_nope = jnp.einsum("tr,rhk->thk", ca, _wt(w["wuk"], precision))
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope, (*k_nope.shape[:2], m.qk_rope_dim))], -1)
    v = jnp.einsum("tr,rhk->thk", ca, _wt(w["wuv"], precision))
    ms = _mscale(m.rope_factor, m.rope_mscale_all_dim) \
        if m.rope_mscale_all_dim else 1.0
    o = _attention(q * (ms * ms), k, v, precision)
    return x + jnp.einsum("thk,hkd->td", _act(o, precision),
                          _wt(w["wo"], precision))


def _swiglu(a, gate, up, down, precision):
    z = jax.nn.silu(a @ _wt(gate, precision)) * (a @ _wt(up, precision))
    return _act(z, precision) @ _wt(down, precision)


def _dense_layer(m: Spec, precision, x, w):
    x = _mla(m, precision, x, w)
    a = _act(_norm(m, x, w["ln2_w"]), precision)
    return x + _swiglu(a, w["dense_gate"], w["dense_up"], w["dense_down"],
                       precision)


def _moe_layer(m: Spec, precision, x, w):
    """Softmax routing over every published expert; the held experts'
    weighted part (each token through each held expert, weighted 0 where
    it did not pick it) and the shared experts."""
    x = _mla(m, precision, x, w)
    a = _act(_norm(m, x, w["ln2_w"]), precision)
    gates = jax.nn.softmax(a @ _wt(w["router"], precision), axis=-1)
    probs, idx = jax.lax.top_k(gates, m.top_k)
    if m.norm_topk_prob:
        probs = probs / jnp.sum(probs, -1, keepdims=True)
    probs = probs * m.routed_scaling_factor

    def expert(acc, e):
        g, u, dn, i = e
        wt = jnp.sum(jnp.where(idx == m.expert_offset + i, probs, 0.0), -1)
        return acc + wt[:, None] * _swiglu(a, g, u, dn, precision), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                          (w["exp_gate"], w["exp_up"], w["exp_down"],
                           jnp.arange(m.n_held)))
    if m.n_shared:
        out = out + _swiglu(a, w["sh_gate"], w["sh_up"], w["sh_down"],
                            precision)
    return x + out


_ATTN = ("ln1_w", "ln2_w", "kv_norm", "wq", "wdkv", "wuk", "wuv", "wo")


@functools.partial(jax.jit, static_argnums=(0, 4))
def logits_at(m: Spec, w, tokens, rows, precision: str = "f32"):
    """Next-token logits of ``tokens`` ([T], T a multiple of ``Q_BLOCK``)
    at positions ``rows`` ([K]): [K, vocab] float32. Causal, so whatever
    pads ``tokens`` past a row does not change that row. The dense
    layers, then the expert layers, run one at a time (two scans)."""
    nd = m.n_dense
    dense = {k: w[k][:nd] for k in _ATTN}
    dense.update({k: w[k] for k in ("dense_gate", "dense_up", "dense_down")})
    moe = {k: w[k][nd:] for k in _ATTN}
    moe.update({k: w[k] for k in ("router", "exp_gate", "exp_up",
                                  "exp_down", "sh_gate", "sh_up",
                                  "sh_down")})
    with jax.default_matmul_precision("highest"):
        x = w["embed"][tokens]
        for layer, stack in ((_dense_layer, dense), (_moe_layer, moe)):
            x, _ = jax.lax.scan(
                lambda x, lw, f=layer: (f(m, precision, x, lw), None), x,
                stack)
        xr = _norm(m, x[rows], w["final_w"])
        return _act(xr, precision) @ _wt(w["unembed"], precision).T


# ------------------------------------------------------------------ costs

def matmul_weights(m: Spec) -> Tuple[int, int]:
    """(every layer's matmul weights one token passes through, the LM
    head's). Attention: q, the joint latent-and-key down-projection, the
    latent's up-projections to keys and values, and o (the explicit form;
    the absorbed form a decode step runs multiplies by the same two
    up-projections). A dense layer's SwiGLU MLP; an expert layer's router,
    its shared experts, and of its routed experts the share this chip
    computes: ``top_k * n_held / n_experts`` experts a token on average
    (6 x 8/64 = 0.75 here, under even routing)."""
    d, h, r = m.d_model, m.n_heads, m.kv_lora_rank
    attn = (d * h * (m.qk_nope_dim + m.qk_rope_dim)
            + d * (r + m.qk_rope_dim)
            + r * h * (m.qk_nope_dim + m.v_head_dim)
            + h * m.v_head_dim * d)
    dense = 3 * d * m.d_ff
    expert = 3 * d * m.moe_d_ff
    routed = expert * m.top_k * m.n_held / m.n_experts
    moe = d * m.n_experts + m.n_shared * expert + routed
    n_moe = m.n_layers - m.n_dense
    return (int(m.n_layers * attn + m.n_dense * dense + n_moe * moe),
            d * m.vocab)


def attn_flops_per_pair(m: Spec) -> int:
    """q.k over ``qk_nope + qk_rope`` lanes and p.v over ``v_head_dim``,
    two operations a product, every head and layer (the explicit form)."""
    return m.n_layers * m.n_heads * (
        2 * (m.qk_nope_dim + m.qk_rope_dim) + 2 * m.v_head_dim)
