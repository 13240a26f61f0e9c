"""What decides ``correct``: the served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and always holding the longest
one, is run through the plain reference of the cell's architecture
(``archs/<model_type>.py`` ``logits_at``, float32, highest precision)
over its prompt and the tokens it was served. At each served
position the reference's best logit minus its logit for the served token
is that token's gap; the number compared is the widest gap. A greedy
server at the configuration's precision serves the reference's best token
or one within rounding of it, so its widest gap stays small.

Besides: every request got its whole token budget, and every token the
program fed back into its next step was the argmax of its own logits.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from reference import Q_BLOCK

SAMPLE_SALT = 0x5EED


def sample(tl, check_tokens: int, seed: int) -> List[int]:
    """Request ids to compare: the longest, then others in an order drawn
    from the seed, until ``check_tokens`` served tokens are covered."""
    done = [q for q in tl.reqs.values() if len(q.tokens) == q.max_new]
    if not done:
        return []
    longest = max(done, key=lambda q: (q.prompt_len + q.max_new, -q.rid))
    rest = [q for q in done if q.rid != longest.rid]
    rng = np.random.default_rng([seed, SAMPLE_SALT])
    out, n = [longest.rid], len(longest.tokens)
    for i in rng.permutation(len(rest)):
        if n >= check_tokens:
            break
        out.append(rest[i].rid)
        n += len(rest[i].tokens)
    return out


def padded_len(traffic: Dict) -> int:
    t = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    return -(-t // Q_BLOCK) * Q_BLOCK


def _inputs(prompt, served, t_pad: int, k_pad: int):
    seq = np.zeros(t_pad, np.int32)
    full = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    seq[:len(full)] = full
    rows = len(prompt) - 1 + np.arange(len(served))
    rows = np.concatenate([rows, np.full(k_pad - len(rows), rows[-1])])
    return seq, rows.astype(np.int32)


def gaps(cell, seed: int, items, *, control: bool = False):
    """Widest gap over ``items`` (``(prompt, served_tokens)`` pairs) and
    the number of tokens compared. With ``control`` the token judged at
    each position is the fp8 reference's first choice instead of the
    served one."""
    import jax.numpy as jnp
    arch, spec = cell.arch, cell.spec
    w = arch.canonical(spec, seed)
    t_pad = padded_len(cell.traffic)
    k_pad = cell.traffic["output_len"]["max"]
    worst, n = 0.0, 0
    for prompt, served in items:
        seq, rows = _inputs(prompt, served, t_pad, k_pad)
        ref = arch.logits_at(spec, w, jnp.asarray(seq), jnp.asarray(rows),
                             "f32")[:len(served)]
        if control:
            ctl = arch.logits_at(spec, w, jnp.asarray(seq),
                                 jnp.asarray(rows), "fp8")
            pick = jnp.argmax(ctl[:len(served)], axis=-1)
        else:
            pick = jnp.asarray(np.asarray(served, np.int32))
        g = jnp.max(ref, -1) - jnp.take_along_axis(ref, pick[:, None],
                                                   -1)[:, 0]
        worst = max(worst, float(jnp.max(g)))
        n += len(served)
    return worst, n


def compare(cell, tl, run, seed: int) -> Dict[str, Dict]:
    """The numbers that decide ``correct``, each with its limit."""
    short = sum(len(q.tokens) != q.max_new for q in tl.reqs.values())
    short += len(run.requests) - len(tl.reqs)
    fed = sum(q.fed_mismatch for q in tl.reqs.values())
    rids = sample(tl, cell.traffic["check_tokens"], seed)
    by_rid = {r.rid: r for r in run.requests}
    gap, n = gaps(cell, seed,
                  [(by_rid[r].prompt, tl.reqs[r].tokens) for r in rids])
    limit = cell.config.get("limit_logit_gap")
    return {
        "logit_gap": {"value": gap, "limit": limit, "tokens": n},
        "fed_not_argmax": {"value": fed, "limit": 0},
        "short_requests": {"value": short, "limit": 0},
    }


def is_correct(checks: Dict[str, Dict]) -> bool:
    return all(c["limit"] is not None and c["value"] <= c["limit"]
               for c in checks.values())
