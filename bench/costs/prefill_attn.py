"""``ff_attention``, causal, for one prompt: operations and bytes at the
prompt's real length ``n`` (not the power-of-two bucket it is padded to).

Causal attention computes ``n (n + 1) / 2`` scores per head for q k^T and
as many products for p v, two operations each. Bytes: q and the output
(every query head) and K and V (every KV head), each read or written once.
"""

from __future__ import annotations

from typing import Tuple

BF16 = 2


def prompt(spec, n: int) -> Tuple[float, float]:
    """(flops, bytes) over every layer for one prompt of ``n`` tokens."""
    h, kvh, hd, L = spec.n_heads, spec.n_kv_heads, spec.head_dim, \
        spec.n_layers
    flops = 2.0 * h * hd * n * (n + 1)
    bytes_ = (2 * h + 2 * kvh) * n * hd * BF16
    return L * flops, L * bytes_
