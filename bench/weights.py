"""Weights from the seed, made on the device in one jitted call.

``canonical`` draws one tree in a plain layout (named after the published
modules). The reference reads that tree; ``program_params`` rearranges the
same draws into the program's own parameter tree. Both come from the
seed alone, so the reference never takes a weight that the program made.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from model_spec import ModelSpec


def _shapes(m: ModelSpec) -> Dict[str, tuple]:
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


def _scale(name: str, m: ModelSpec) -> float:
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


def seed_key_data(seed: int) -> np.ndarray:
    """Any whole-number seed (more than 32 bits too) as the two uint32
    words of a threefry key, so one compiled program serves every seed."""
    seed = int(seed) % (1 << 64)
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def _draw(m: ModelSpec, key_data) -> Dict[str, jnp.ndarray]:
    shapes = _shapes(m)
    key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
    out = {}
    for i, name in enumerate(sorted(shapes)):
        x = _scale(name, m) * jax.random.normal(
            jax.random.fold_in(key, i), shapes[name], jnp.float32)
        if name.endswith("_w") and name[:-2] in ("ln1", "ln2", "final"):
            x = x + 1.0
        out[name] = x
    return out


@functools.partial(jax.jit, static_argnums=(0,))
def _canonical(m: ModelSpec, key_data) -> Dict[str, jnp.ndarray]:
    return _draw(m, key_data)


def canonical(m: ModelSpec, seed: int) -> Dict[str, jnp.ndarray]:
    """The plain-layout weights of ``m`` for ``seed`` (float32)."""
    return _canonical(m, seed_key_data(seed))


@functools.partial(jax.jit, static_argnums=(0,))
def _program_params(m: ModelSpec, key_data):
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


def program_params(m: ModelSpec, seed: int):
    return _program_params(m, seed_key_data(seed))
