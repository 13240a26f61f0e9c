"""The model's operations for a decode step and for a prefill: the
matmuls (two operations per weight per token, the LM head included) and
attention. The embedding lookup, norms and activations are not counted.
What one token and one (query, key) pair cost comes from the
architecture (``archs/<model_type>.py``: ``matmul_weights``,
``attn_flops_per_pair``).
"""

from __future__ import annotations

from typing import Iterable


def token_flops(arch, spec) -> float:
    """Matmul operations for one token through every layer and the head."""
    layers, head = arch.matmul_weights(spec)
    return 2.0 * (layers + head)


def decode_step(arch, spec, contexts: Iterable[int]) -> float:
    """One decode step of the rows with these contexts (new token
    included); rows of context 0 are idle slots and cost nothing."""
    live = [int(n) for n in contexts if n > 0]
    pair = arch.attn_flops_per_pair(spec)
    return len(live) * token_flops(arch, spec) + sum(pair * n for n in live)


def prefill(arch, spec, n: int) -> float:
    """A prefill of ``n`` tokens: every token through the layers, causal
    attention (``n (n + 1) / 2`` pairs), and the LM head on the last
    position only (the program computes no other logits in a prefill)."""
    layers, head = arch.matmul_weights(spec)
    pairs = n * (n + 1) // 2
    return 2.0 * layers * n + arch.attn_flops_per_pair(spec) * pairs \
        + 2.0 * head
