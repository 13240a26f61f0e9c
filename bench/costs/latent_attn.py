"""``paged_latent_attention`` (ff_gather latent-page producer -> MLA
absorbed-form consumer): operations and bytes of one decode step.

Bytes count what the work needs, whatever implements it: each row's
*live* latent pages (``ceil(n / page)`` pages of ``page`` tokens, where
``n`` is the row's context with the new token), a token's row being its
``kv_lora_rank`` latent and its ``qk_rope_dim`` rotated key in bf16 (not
the zero lanes that pad it to whole 128-lane words), plus the row's
query (every head, ``kv_lora_rank + qk_rope_dim`` wide) and output (every
head, ``kv_lora_rank`` wide). Operations: every head scores each cached
row over ``kv_lora_rank + qk_rope_dim`` lanes and sums its
``kv_lora_rank`` latent lanes, two operations a product. Inactive rows
(context 0) cost nothing.
"""

from __future__ import annotations

from typing import Iterable, Tuple

BF16 = 2


def step(spec, contexts: Iterable[int], page: int) -> Tuple[float, float]:
    """(flops, bytes) over every layer, for rows of the given contexts."""
    h, r, L = spec.n_heads, spec.kv_lora_rank, spec.n_layers
    w = r + spec.qk_rope_dim
    flops = bytes_ = 0.0
    for n in contexts:
        if n <= 0:
            continue
        pages = -(-int(n) // page)
        flops += (2.0 * h * w + 2.0 * h * r) * n
        bytes_ += pages * page * w * BF16 + h * (w + r) * BF16
    return L * flops, L * bytes_
