"""``paged_decode_attention`` (ff_gather page producer -> online-softmax
consumer): operations and bytes of one decode step.

Bytes count what the work needs, whatever implements it: each row's
*live* pages of K and V (``ceil(n / page)`` pages of ``page`` tokens,
where ``n`` is the row's context with the new token), its q and its
output. Pages that a slot reserved but has not filled are not counted,
so a kernel that stops reading them shows as a gain. Inactive rows
(context 0) cost nothing.
"""

from __future__ import annotations

from typing import Iterable, Tuple

BF16 = 2


def step(spec, contexts: Iterable[int], page: int) -> Tuple[float, float]:
    """(flops, bytes) over every layer, for rows of the given contexts."""
    h, kvh, hd, L = spec.n_heads, spec.n_kv_heads, spec.head_dim, \
        spec.n_layers
    flops = bytes_ = 0.0
    for n in contexts:
        if n <= 0:
            continue
        pages = -(-int(n) // page)
        flops += 4.0 * h * hd * n                   # q k^T and p v
        bytes_ += pages * page * 2 * kvh * hd * BF16 + 2 * h * hd * BF16
    return L * flops, L * bytes_
