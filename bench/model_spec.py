"""A configuration file read into the sizes the benchmark works with.

The file keeps the published ``config.json`` keys; ``ModelSpec`` is what
the weights, the reference and the cost functions need, and
``program_config`` is the program's own ``ArchConfig`` for it, checked key
by key against the file.
"""

from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class ModelSpec:
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

    @property
    def kv_bytes_per_token(self) -> int:
        """bf16 K and V of one token over every layer."""
        return 2 * self.n_layers * self.n_kv_heads * self.head_dim * 2


def _expect(c: Dict, **want) -> None:
    bad = {k: c.get(k) for k, v in want.items() if c.get(k) != v}
    if bad:
        raise ValueError(f"{c.get('name')}: unsupported {bad}")


def spec_from_file(c: Dict) -> ModelSpec:
    kind = c["model_type"]
    d, h = c["hidden_size"], c["num_attention_heads"]
    common = dict(n_layers=c["num_hidden_layers"], d_model=d, n_heads=h,
                  n_kv_heads=c["num_key_value_heads"],
                  head_dim=c.get("head_dim", d // h),
                  d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                  rope_theta=float(c["rope_theta"]),
                  tie_embeddings=bool(c.get("tie_word_embeddings", False)))
    if kind == "qwen2":
        _expect(c, hidden_act="silu")
        return ModelSpec(norm="rmsnorm", norm_eps=c["rms_norm_eps"],
                         act="swiglu", qkv_bias=True, **common)
    if kind == "starcoder2":
        _expect(c, hidden_act="gelu_pytorch_tanh", norm_type="layer_norm")
        return ModelSpec(norm="layernorm", norm_eps=c["norm_epsilon"],
                         act="gelu", qkv_bias=bool(c["use_bias"]), **common)
    raise ValueError(f"no ModelSpec reading for model_type {kind!r}")


# the program's fixed norm epsilons (models/layers.py)
_PROGRAM_EPS = {"rmsnorm": 1e-6, "layernorm": 1e-5}


def program_config(c: Dict, m: ModelSpec, *, page: int):
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
