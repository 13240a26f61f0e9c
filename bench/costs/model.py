"""The model's operations for a decode step and for a prefill: the
matmuls (two operations per weight per token, the LM head included) and
attention. The embedding lookup, norms and activations are not counted.
"""

from __future__ import annotations

from typing import Iterable


def matmul_weights_per_layer(spec) -> int:
    d, h, kvh, hd, f = (spec.d_model, spec.n_heads, spec.n_kv_heads,
                        spec.head_dim, spec.d_ff)
    attn = d * (h + 2 * kvh) * hd + h * hd * d
    mlp = 3 * d * f if spec.act == "swiglu" else 2 * d * f
    return attn + mlp


def token_flops(spec) -> float:
    """Matmul operations for one token through every layer and the head."""
    return 2.0 * (spec.n_layers * matmul_weights_per_layer(spec)
                  + spec.d_model * spec.vocab)


def decode_step(spec, contexts: Iterable[int]) -> float:
    """One decode step of the rows with these contexts (new token
    included); rows of context 0 are idle slots and cost nothing."""
    live = [int(n) for n in contexts if n > 0]
    attn = sum(4.0 * spec.n_heads * spec.head_dim * n for n in live)
    return len(live) * token_flops(spec) + spec.n_layers * attn


def prefill(spec, n: int) -> float:
    """A prefill of ``n`` tokens: every token through the layers, causal
    attention, and the LM head on the last position only (the program
    computes no other logits in a prefill)."""
    layers = 2.0 * spec.n_layers * matmul_weights_per_layer(spec) * n
    attn = 2.0 * spec.n_heads * spec.head_dim * n * (n + 1)
    return layers + spec.n_layers * attn + 2.0 * spec.d_model * spec.vocab
