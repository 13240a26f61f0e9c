"""From the hooks' records to the times of every request, on the
harness's own reading of the host's wall clock (``serve_driver.py``).

* The window starts when the tokens of the first decode step reach the
  host: every client has sent its first request and had its first token
  (the first wave, its prefills one after another, is part of set-up).
  It ends when the tokens of the first decode step after the last
  admission reach the host: while the window lasts, every client has a
  request in flight (the cell's load), and every request sent in it was
  sent when a slot freed. The steps after it drain the last requests at a
  falling load; they are served and checked, but not timed.
* A request emits a token each time a decode step's tokens reach the host
  while its slot is active, between its admission and its retirement.
* It is sent when a slot frees: when the tokens of the decode step whose
  retirement freed the slot it was then admitted into reached the host;
  the first request in each slot is sent when the window's KV cache is
  made, in set-up.

The scheduler admits the requests in the order it was given them, so the
k-th admission is request k.

The program's spans are kept for the per-layer metrics that read them
(``decode_spans``, ``admit_spans``); they close at ``ts`` (wall clock)
after ``dur_s``, and their ids follow the order in which they opened, so
a gap in the ids from the window's anchor on means the ring dropped some.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class ReqTimes:
    rid: int
    prompt_len: int
    max_new: int
    sent: float
    emits: List[float]
    tokens: List[int]           # what the program emitted (the last one
                                # is its logits' argmax: it is not fed)
    fed_mismatch: int           # fed tokens that were not the argmax


@dataclasses.dataclass
class Timeline:
    start: float
    end: float
    n_steps: int                # decode steps of the window: 1..n_steps
    reqs: Dict[int, ReqTimes]
    decode_spans: List[tuple]   # (start, end) of each serve_decode_step
    admit_spans: List[tuple]    # (start, end, prompt_len) of serve_admit

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def steps(self) -> slice:
        """The decode steps that ended inside the window."""
        return slice(1, self.n_steps + 1)

    def emits(self, q: ReqTimes) -> List[float]:
        """The request's emit times inside the window, its start included
        (a token emitted at the start opens a gap but is not counted)."""
        return [t for t in q.emits if self.start <= t <= self.end]

    def window_admits(self) -> List[tuple]:
        """The ``serve_admit`` spans that began inside the window."""
        return [a for a in self.admit_spans
                if self.start <= a[0] <= self.end]


def _interval(r):
    return (r["ts"] - r["dur_s"], r["ts"])


def _spans(run):
    spans = sorted((r for r in run.spans if r["id"] >= run.first_span_id),
                   key=lambda r: r["id"])
    ids = [r["id"] for r in spans]
    if ids != list(range(run.first_span_id, run.first_span_id + len(ids))):
        raise RuntimeError("the span ring dropped records")
    dec = [_interval(r) for r in spans if r["name"] == "serve_decode_step"]
    adm = [(*_interval(r), int(r["attrs"]["prompt_len"])) for r in spans
           if r["name"] == "serve_admit"]
    if len(dec) != len(run.steps) or len(adm) != len(run.admits):
        raise RuntimeError(f"spans ({len(dec)} decode, {len(adm)} admit) "
                           f"disagree with the hooks ({len(run.steps)} "
                           f"steps, {len(run.admits)} admissions)")
    return dec, adm


def build(run) -> Timeline:
    dec, adm = _spans(run)
    owner: List[Optional[int]] = [None] * run.slots
    freed_at = [run.t_start] * run.slots
    reqs: Dict[int, ReqTimes] = {}
    admits = list(run.admits)
    retires = list(run.retires)
    for i, s in enumerate(run.steps + [None]):
        while admits and admits[0][2] == i:
            slot, plen, _ = admits.pop(0)
            r = run.requests[len(reqs)]
            if plen != len(r.prompt):
                raise RuntimeError(f"admission {len(reqs)} has a prompt of "
                                   f"{plen}, request {r.rid} {len(r.prompt)}")
            owner[slot] = r.rid
            reqs[r.rid] = ReqTimes(r.rid, plen, r.max_new,
                                   freed_at[slot], [], [], 0)
        if s is None:
            break
        for slot in np.nonzero(s.mask)[0]:
            q = reqs[owner[slot]]
            if s.tok_in is not None:
                # the token the program fed back is the one it emitted at
                # the slot's previous step (or the prompt's last)
                fed = int(s.tok_in[slot])
                if q.tokens:
                    q.fed_mismatch += fed != q.tokens[-1]
                    q.tokens[-1] = fed
                else:
                    q.fed_mismatch += fed != int(
                        run.requests[q.rid].prompt[-1])
            q.emits.append(s.t)
            q.tokens.append(int(s.tok_out[slot])
                            if s.tok_out is not None else -1)
        while retires and retires[0][1] == i + 1:
            slot = retires.pop(0)[0]
            owner[slot] = None
            freed_at[slot] = s.t
    last = run.admits[-1][2] if run.admits else 0
    if last >= len(run.steps):
        raise RuntimeError("no decode step after the last admission")
    if last < 1:
        raise RuntimeError("no admission after the first wave")
    return Timeline(run.steps[0].t, run.steps[last].t, last, reqs, dec,
                    adm)



def stalls(tl: Timeline, t0: float) -> str:
    """The longest decode step, admission and host gap between the
    program's spans, each with its start in seconds after ``t0`` (the
    window's cache made): where a slow run lost its time."""
    spans = sorted([(a, b, "decode") for a, b in tl.decode_spans]
                   + [(a, b, f"admit {n}") for a, b, n in tl.admit_spans])
    worst = {}
    for a, b, what in spans:
        kind = what.split()[0]
        if b - a > worst.get(kind, (0.0,))[0]:
            worst[kind] = (b - a, a, what)
    for (_, b, _), (a, _, what) in zip(spans, spans[1:]):
        if a - b > worst.get("host", (0.0,))[0]:
            worst["host"] = (a - b, b, f"host before {what}")
    return "bench: longest " + "; ".join(
        f"{w} {1e3 * d:.1f} ms at {a - t0:.2f} s"
        for d, a, w in worst.values())
