"""The program's own spans and counters, for the per-layer metrics that
read them from inside the work.

Two sources, both read as the harness already keeps them:

* the records of the program's tracer (``repro.obs``) for the run,
  ``ctx.run.spans``: name, interval on the wall clock, parent and
  attributes. The ``serve_decode_step`` spans from the window's anchor on
  line up one to one with the hooks' steps, as in ``timeline``; each
  carries the process's GC and compile totals (``gc_s``, ``gc_n``,
  ``compiles``) as it closes;
* the profile of a ``--trace 1`` run. While a profile records, every
  program span is also a ``TraceAnnotation`` on the profile's host plane,
  on the time base of the device's operations, so no marker conversion is
  needed to line the two up. :func:`idle_by_leaf` puts each stretch of
  device idle time in the traced window down to the innermost program
  span open then (its leaf), and splits it into idle inside a run of a
  jitted program (between the operations of one program) and idle between
  programs.

A program without these spans or attributes gives nothing to read: the
readers return None and the metric is left out.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Iterable, List, Optional, Tuple

import trace_reduce

Interval = Tuple[float, float]

# leaves in which the host puts a step's work on the device
LAUNCH = ("decode_inputs", "decode_launch", "admit_launch")
OUTSIDE = "outside the program's spans"


def interval(r: dict) -> Interval:
    return (r["ts"] - r["dur_s"], r["ts"])


def spans(run) -> List[dict]:
    """The records from the window's anchor on, in the order they
    opened."""
    return sorted((r for r in run.spans if r["id"] >= run.first_span_id),
                  key=lambda r: r["id"])


def children(recs: Iterable[dict]) -> Dict[int, List[dict]]:
    out: Dict[int, List[dict]] = {}
    for r in recs:
        if r.get("parent") is not None:
            out.setdefault(r["parent"], []).append(r)
    return out


def step_children_s(ctx, names) -> List[float]:
    """Per decode step of the window, the seconds in its direct children
    named ``names``; empty where no step has such a child."""
    recs = spans(ctx.run)
    steps = [r for r in recs if r["name"] == "serve_decode_step"]
    kids = children(recs)
    out, seen = [], False
    for r in steps[ctx.tl.steps]:
        mine = [c for c in kids.get(r["id"], []) if c["name"] in names]
        seen = seen or bool(mine)
        out.append(sum(c["dur_s"] for c in mine))
    return out if seen else []


def step_delta(ctx, key: str) -> Optional[float]:
    """The growth of the counter ``key`` from the step that opens the
    window to the one that closes it (None where the steps lack it)."""
    steps = [r for r in spans(ctx.run) if r["name"] == "serve_decode_step"]
    if len(steps) <= ctx.tl.n_steps:
        return None
    first = steps[0].get("attrs") or {}
    last = steps[ctx.tl.n_steps].get("attrs") or {}
    if key not in first or key not in last:
        return None
    return float(last[key]) - float(first[key])


# -- interval arithmetic over sorted, disjoint lists ------------------------

def total(iv: Iterable[Interval]) -> float:
    return sum(b - a for a, b in iv)


def intersect(xs: List[Interval], ys: List[Interval]) -> List[Interval]:
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def leaves(events: List[Tuple[float, float, str]]
           ) -> Dict[str, List[Interval]]:
    """Where each span name is the innermost open one: the open span that
    began last wins."""
    cuts = sorted({t for a, b, _ in events for t in (a, b)})
    by_start = sorted(events)
    out: Dict[str, List[Interval]] = {}
    active: List[Tuple[float, float, str]] = []
    k = 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(by_start) and by_start[k][0] <= a:
            active.append(by_start[k])
            k += 1
        active = [e for e in active if e[1] > a]
        if not active:
            continue
        name = max(active, key=lambda e: (e[0], -e[1]))[2]
        segs = out.setdefault(name, [])
        if segs and segs[-1][1] == a:
            segs[-1] = (segs[-1][0], b)
        else:
            segs.append((a, b))
    return out


# -- the profile ------------------------------------------------------------

@dataclasses.dataclass
class HostProfile:
    busy: Dict[str, List[Interval]]     # device plane -> operations, merged
    programs: Dict[str, List[Interval]]  # device plane -> program runs
    host: List[Tuple[float, float, str]]  # host-plane events named
    marker: Optional[float]             # the harness's marker, seconds


@functools.lru_cache(maxsize=2)
def load(path: str, names: frozenset) -> HostProfile:
    """Device operations (containers left out, as ``trace_reduce`` does),
    program runs and the host-plane events named in ``names`` (the
    profiler's Python tracer adds one per Python call besides) of an
    ``.xplane.pb``, in seconds of the profile's time base."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    busy, programs, host, marker = {}, {}, [], None
    for plane in pd.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:TPU:") and \
                trace_reduce.OPS_LINE in lines:
            ops = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                   for e in lines[trace_reduce.OPS_LINE].events
                   if trace_reduce.op_name(e.name)[1]
                   not in trace_reduce.CONTAINERS]
            busy[plane.name] = trace_reduce.merge(ops)
            mods = lines.get(trace_reduce.MODULES_LINE)
            programs[plane.name] = trace_reduce.merge(
                [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                 for e in (mods.events if mods else [])])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    t0 = e.start_ns * 1e-9
                    if e.name == trace_reduce.MARKER and marker is None:
                        marker = t0
                    elif e.name in names:
                        host.append((t0, t0 + e.duration_ns * 1e-9, e.name))
    return HostProfile(busy, programs, host, marker)


def attribute(prof: HostProfile, lo: float, hi: float) -> Dict[str, object]:
    """Device idle time in [lo, hi] by the leaf program span open during
    it, averaged over the device planes: ``in_program`` (inside a program
    run) and ``between`` (outside every program run), seconds per leaf,
    and the names of the leaves seen (``spans``)."""
    segs = leaves(prof.host)
    n = max(len(prof.busy), 1)
    inside: Dict[str, float] = {}
    between: Dict[str, float] = {}
    idle_s = 0.0
    for plane, busy in prof.busy.items():
        idle = trace_reduce.gaps(trace_reduce.clip(busy, lo, hi), lo, hi)
        idle_s += total(idle) / n
        runs = trace_reduce.clip(prof.programs.get(plane, []), lo, hi)
        for part, into in ((intersect(idle, runs), inside),
                           (intersect(idle, trace_reduce.gaps(runs, lo, hi)),
                            between)):
            left = total(part)
            for name, iv in segs.items():
                t = total(intersect(part, iv))
                if t:
                    into[name] = into.get(name, 0.0) + t / n
                    left -= t
            if left > 1e-12:
                into[OUTSIDE] = into.get(OUTSIDE, 0.0) + left / n
    return {"window_s": hi - lo, "idle_s": idle_s, "in_program": inside,
            "between": between, "spans": sorted(segs)}


def idle_by_leaf(ctx) -> Optional[Dict[str, object]]:
    """:func:`attribute` over the traced window of the run (the window of
    ``trace_reduce.reduce_run``), for the names of the run's own spans;
    None without a profile or where no program span is on its host
    plane."""
    run = ctx.run
    if ctx.trace is None or run.trace_dir is None:
        return None
    prof = load(trace_reduce.find_xplane(run.trace_dir),
                frozenset(r["name"] for r in run.spans))
    if prof.marker is None or run.trace_marker_wall is None or not prof.host:
        return None
    lo = prof.marker
    hi = run.trace_stop_host[0] + prof.marker - run.trace_marker_wall
    return attribute(prof, lo, hi)
