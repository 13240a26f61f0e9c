"""The dense decoder that ``qwen2`` and ``starcoder2`` share: in every layer
attention with grouped KV heads of one head size, RoPE, and a dense MLP
(SwiGLU or tanh-GELU), under RMSNorm or LayerNorm; optional q/k/v biases
and a tied LM head. The architecture modules differ only in how they read
a configuration file into ``Spec``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

import weights
from reference import _act, _attention, _norm, _rope, _wt


@dataclasses.dataclass(frozen=True)
class Spec:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm: str           # rmsnorm | layernorm
    norm_eps: float
    act: str            # swiglu | gelu (tanh approximation)
    qkv_bias: bool
    rope_theta: float
    tie_embeddings: bool = False    # the LM head is the embedding


def expect(c: Dict, **want) -> None:
    """Raise where the file sets a key to a value not implemented here."""
    bad = {k: c.get(k) for k, v in want.items() if c.get(k) != v}
    if bad:
        raise ValueError(f"{c.get('name')}: unsupported {bad}")


def make_spec(c: Dict, *, norm: str, norm_eps: float, act: str,
              qkv_bias: bool) -> Spec:
    """The sizes every dense file names alike, with the layer math the
    architecture module read from its own keys."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    return Spec(n_layers=c["num_hidden_layers"], d_model=d, n_heads=h,
                n_kv_heads=c["num_key_value_heads"],
                head_dim=c.get("head_dim", d // h),
                d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                norm=norm, norm_eps=norm_eps, act=act, qkv_bias=qkv_bias,
                rope_theta=float(c["rope_theta"]),
                tie_embeddings=bool(c.get("tie_word_embeddings", False)))


# the program's fixed norm epsilons (models/layers.py)
_PROGRAM_EPS = {"rmsnorm": 1e-6, "layernorm": 1e-5}


def program_config(c: Dict, m: Spec, *, page: int):
    """The program's ``ArchConfig`` for this file: its repo arch with the
    file's sizes, the compiled ``ff`` kernels and the dense decode tile
    pinned to the page. Raises where the program's layer math would differ
    from the file's (norm, activation, biases, RoPE base, dtypes)."""
    from repro.configs.base import get_config
    cfg = get_config(c["repro_arch"]).replace(
        n_layers=m.n_layers, d_model=m.d_model, n_heads=m.n_heads,
        n_kv_heads=m.n_kv_heads, head_dim=m.head_dim, d_ff=m.d_ff,
        vocab=m.vocab, tie_embeddings=m.tie_embeddings, attn_impl="ff",
        decode_block_kv=page)
    want = dict(padded_vocab=m.vocab, norm=m.norm, act=m.act,
                qkv_bias=m.qkv_bias, rope_theta=m.rope_theta,
                tie_embeddings=m.tie_embeddings, param_dtype="float32",
                compute_dtype="bfloat16")
    got = {k: getattr(cfg, k) for k in want}
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    if m.norm_eps != _PROGRAM_EPS[m.norm]:
        bad["norm_eps"] = (_PROGRAM_EPS[m.norm], m.norm_eps)
    if bad:
        raise ValueError(f"program config differs from the file "
                         f"(program, file): {bad}")
    return cfg


# ---------------------------------------------------------------- weights

def _shapes(m: Spec) -> Dict[str, tuple]:
    L, d, h, kvh, hd, f, v = (m.n_layers, m.d_model, m.n_heads,
                              m.n_kv_heads, m.head_dim, m.d_ff, m.vocab)
    s = {"embed": (v, d), "final_w": (d,),
         "ln1_w": (L, d), "ln2_w": (L, d),
         "wq": (L, d, h, hd), "wk": (L, d, kvh, hd), "wv": (L, d, kvh, hd),
         "wo": (L, h, hd, d)}
    if not m.tie_embeddings:
        s["unembed"] = (v, d)
    if m.qkv_bias:
        s.update(bq=(L, h, hd), bk=(L, kvh, hd), bv=(L, kvh, hd))
    if m.norm == "layernorm":
        s.update(final_b=(d,), ln1_b=(L, d), ln2_b=(L, d))
    if m.act == "swiglu":
        s.update(w_gate=(L, d, f), w_up=(L, d, f), w_down=(L, f, d))
    else:
        s.update(w_fc=(L, d, f), b_fc=(L, f), w_proj=(L, f, d),
                 b_proj=(L, d))
    return s


def _scale(name: str, m: Spec) -> float:
    """Fan-in scaling for matrices (unit-variance activations in, so the
    logits come out with a spread of about 1; a tied embedding is the LM
    head too, so it is scaled as one); small offsets for the norm weights
    and the biases, so a program that drops one is seen."""
    if name == "embed":
        return m.d_model ** -0.5 if m.tie_embeddings else 1.0
    if name in ("unembed", "wq", "wk", "wv", "w_gate", "w_up", "w_fc"):
        return m.d_model ** -0.5
    if name == "wo":
        return (m.n_heads * m.head_dim) ** -0.5
    if name in ("w_down", "w_proj"):
        return m.d_ff ** -0.5
    return 0.1                      # norm weights (around 1) and biases


def _draw(m: Spec, key_data) -> Dict[str, jnp.ndarray]:
    w = weights.draw(_shapes(m), lambda name: _scale(name, m), key_data)
    for name in ("ln1_w", "ln2_w", "final_w"):     # norm weights around 1
        w[name] = w[name] + 1.0
    return w


@functools.partial(jax.jit, static_argnums=(0,))
def _canonical(m: Spec, key_data) -> Dict[str, jnp.ndarray]:
    return _draw(m, key_data)


def canonical(m: Spec, seed: int) -> Dict[str, jnp.ndarray]:
    """The plain-layout weights of ``m`` for ``seed`` (float32)."""
    return _canonical(m, weights.seed_key_data(seed))


@functools.partial(jax.jit, static_argnums=(0,))
def _program_params(m: Spec, key_data):
    """The same draws in the program's parameter tree (``repro.models``'
    ``param_specs`` layout: ``embed``, ``stack.layers.{norm1, mixer, norm2,
    ffn}``, ``final_norm``, and ``unembed`` unless the embedding is
    tied)."""
    w = _draw(m, key_data)

    def norm(p):
        return ({"w": w[p + "_w"], "b": w[p + "_b"]}
                if m.norm == "layernorm" else {"w": w[p + "_w"]})

    mixer = {k: w[k] for k in ("wq", "wk", "wv", "wo")}
    if m.qkv_bias:
        mixer.update({k: w[k] for k in ("bq", "bk", "bv")})
    if m.act == "swiglu":
        ffn = {"wi": jnp.concatenate([w["w_gate"], w["w_up"]], axis=-1),
               "wo": w["w_down"]}
    else:
        ffn = {"wi": w["w_fc"], "bi": w["b_fc"], "wo": w["w_proj"],
               "bo": w["b_proj"]}
    tree = {"embed": w["embed"],
            "stack": {"layers": {"norm1": norm("ln1"), "mixer": mixer,
                                 "norm2": norm("ln2"), "ffn": ffn}},
            "final_norm": norm("final")}
    if not m.tie_embeddings:
        tree["unembed"] = w["unembed"]
    return tree


def program_params(m: Spec, seed: int):
    return _program_params(m, weights.seed_key_data(seed))


# -------------------------------------------------------------- reference

def _mlp(m: Spec, w, h, precision):
    a = _act(h, precision)
    if m.act == "swiglu":
        gate = a @ _wt(w["w_gate"], precision)
        up = a @ _wt(w["w_up"], precision)
        z = jax.nn.silu(gate) * up
        return _act(z, precision) @ _wt(w["w_down"], precision)
    z = jax.nn.gelu(a @ _wt(w["w_fc"], precision) + w["b_fc"],
                    approximate=True)
    return _act(z, precision) @ _wt(w["w_proj"], precision) + w["b_proj"]


def _layer(m: Spec, precision, x, w):
    pos = jnp.arange(x.shape[0])
    ln = lambda p: _norm(m, x, w[p + "_w"], w.get(p + "_b"))
    h = _act(ln("ln1"), precision)
    q = jnp.einsum("td,dhk->thk", h, _wt(w["wq"], precision))
    k = jnp.einsum("td,dhk->thk", h, _wt(w["wk"], precision))
    v = jnp.einsum("td,dhk->thk", h, _wt(w["wv"], precision))
    if m.qkv_bias:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q, k = _rope(q, pos, m.rope_theta), _rope(k, pos, m.rope_theta)
    o = _attention(q, k, v, precision)
    x = x + jnp.einsum("thk,hkd->td", _act(o, precision),
                       _wt(w["wo"], precision))
    return x + _mlp(m, w, ln("ln2"), precision)


@functools.partial(jax.jit, static_argnums=(0, 4))
def logits_at(m: Spec, w, tokens, rows, precision: str = "f32"):
    """Next-token logits of ``tokens`` ([T], T a multiple of ``Q_BLOCK``)
    at positions ``rows`` ([K]): [K, vocab] float32. Causal, so whatever
    pads ``tokens`` past a row does not change that row. The layers run
    one at a time (a scan)."""
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


# ------------------------------------------------------------------ costs

def matmul_weights(m: Spec) -> Tuple[int, int]:
    """(every layer's matmul weights, the LM head's): q, k, v and o, and
    the MLP's two (GELU) or three (SwiGLU) matrices."""
    d, h, kvh, hd, f = m.d_model, m.n_heads, m.n_kv_heads, m.head_dim, m.d_ff
    attn = d * (h + 2 * kvh) * hd + h * hd * d
    mlp = 3 * d * f if m.act == "swiglu" else 2 * d * f
    return m.n_layers * (attn + mlp), d * m.vocab


def attn_flops_per_pair(m: Spec) -> int:
    """q.k and p.v over every query head, two operations a product."""
    return m.n_layers * 4 * m.n_heads * m.head_dim
