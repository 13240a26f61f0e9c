"""The one traffic generator: a traffic file's parameters and a seed in,
the list of requests out.

Every seed gets the same (prompt length, output length) pairs in the same
order: the lengths are the distribution's quantiles at ``(i + 0.5) / n``,
paired and ordered by fixed permutations; the first ``clients`` pairs are
the first wave (one request each, all at once, in set-up). The seed draws
only the prompts' token ids. In a closed loop with no end-of-sequence
token the schedule follows from the lengths alone, so every seed runs the
same schedule (which requests share an admission pass, how many steps the
window holds) and two seeds differ only in the tokens and the timing. A
seed that reordered the lengths would change the work: which requests
queue behind others' prefills moves the first-token tail by a whole
prefill.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np

# the fixed pairing of prompt and output lengths (not the run's seed)
PAIRING_SEED = 20240917


@dataclasses.dataclass(frozen=True)
class Req:
    rid: int
    prompt: np.ndarray      # [prompt_len] int32
    max_new: int


def quantile_lengths(spec: Dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles ``(i + 0.5) / n`` of ``spec``'s
    distribution, rounded and clipped to ``[min, max]``."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = NormalDist()
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    raw = [math.exp(mu + sigma * z.inv_cdf((i + 0.5) / n)) for i in range(n)]
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def n_requests(traffic: Dict, seconds: float) -> int:
    """The first wave (one per client, sent in set-up) and the requests
    that the window's ``seconds`` complete at the file's measured rate, at
    least one more per client, so that every client sends again."""
    c = traffic["clients"]
    return c + max(c, math.ceil(traffic["requests_per_window_second"]
                                * seconds))


def make_requests(traffic: Dict, *, seed: int, seconds: float,
                  vocab: int) -> List[Req]:
    """The requests of one run, in the order the clients send them."""
    if traffic["loop"] != "closed":
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    n, c = n_requests(traffic, seconds), traffic["clients"]
    fixed = np.random.default_rng(PAIRING_SEED)
    prompts = quantile_lengths(traffic["prompt_len"], n)
    outputs = quantile_lengths(traffic["output_len"], n)[fixed.permutation(n)]
    order = fixed.permutation(n)
    rng = np.random.default_rng(seed)
    return [Req(i, rng.integers(0, vocab, int(prompts[k])).astype(np.int32),
                int(outputs[k]))
            for i, k in enumerate(order)]
