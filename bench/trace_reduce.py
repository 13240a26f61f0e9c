"""The profiler's ``.xplane.pb`` reduced to what the per-layer metrics
need: device busy intervals, device time per kernel, idle gaps and what
the host was doing in each.

Read with ``jax.profiler.ProfileData`` alone. A device is a plane named
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per operation
run, its ``XLA Modules`` line one per jitted program run. The host's
clock and the device events share the profile's time base; the harness's
own spans are on the wall clock, and a ``TraceAnnotation`` marker, whose
wall time the harness noted, ties the two together.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

MARKER = "bench_clock_marker"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Op:
    name: str           # the HLO instruction's name and opcode
    module: str
    start: float        # seconds, profile time base
    end: float


# ops that only contain others (a scan's loop): their own interval would
# count the gaps between the ops inside as busy, and their time twice
CONTAINERS = ("while", "conditional", "call")


def op_name(text: str) -> Tuple[str, str]:
    """``("%_run.9 custom-call", "custom-call")`` from an ``XLA Ops``
    event's name, which is the instruction's whole HLO text (``%name =
    <shape> <opcode>(<operands>), ...``)."""
    name, _, rest = text.partition(" = ")
    if rest.startswith("("):            # a tuple shape: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    opcode = rest.strip().partition("(")[0].strip()
    return (f"{name} {opcode}" if opcode else name), opcode


@dataclasses.dataclass
class Profile:
    ops: Dict[str, List[Op]]            # per device plane
    marker: Optional[float]             # marker start, profile seconds


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Profile:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Op]] = {}
    marker = None
    for plane in pd.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:TPU:") and OPS_LINE in lines:
            mods = sorted((e.start_ns * 1e-9, (e.start_ns + e.duration_ns)
                           * 1e-9, e.name)
                          for e in lines[MODULES_LINE].events) \
                if MODULES_LINE in lines else []
            starts = [m[0] for m in mods]
            out = []
            for e in lines[OPS_LINE].events:
                name, opcode = op_name(e.name)
                if opcode in CONTAINERS:
                    continue
                t0 = e.start_ns * 1e-9
                i = bisect.bisect_right(starts, t0) - 1
                mod = mods[i][2] if i >= 0 and t0 <= mods[i][1] else ""
                out.append(Op(name, mod, t0, t0 + e.duration_ns * 1e-9))
            ops[plane.name] = sorted(out, key=lambda o: o.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == MARKER and marker is None:
                        marker = e.start_ns * 1e-9
    return Profile(ops, marker)


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy, lo: float, hi: float):
    """Idle intervals of [lo, hi] between merged busy intervals."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def is_kernel(op: Op) -> bool:
    """A Pallas (Mosaic) kernel: XLA runs it as a custom call."""
    return op.name.endswith(" custom-call")


def module_kind(module: str) -> str:
    """Which jitted program of the serve path an op belongs to."""
    for kind in ("decode_step", "prefill_step", "scatter_prefill"):
        if kind in module:
            return kind
    return module or "other"


@dataclasses.dataclass
class Reduced:
    busy_s: float
    window_s: float
    kernel_s: Dict[str, float]          # module kind -> kernel seconds
    steps: Tuple[int, int]              # decode steps traced (inclusive)
    admits: List[int]                   # prompt lengths of traced prefills
    breakdown: Dict[str, list]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce_profile(prof: Profile, *, lo: float, hi: float,
                   host_spans: List[Tuple[float, float, str]]) -> dict:
    """Busy, per-kernel time, top device ops and idle gaps in [lo, hi]
    (profile seconds), averaged over the device planes. ``host_spans``
    (profile seconds) name what the host was doing in each gap."""
    n = max(len(prof.ops), 1)
    busy_s, kernel_s, by_op, idle = 0.0, {}, {}, {}
    spans = sorted(host_spans)
    for ops in prof.ops.values():
        inside = [o for o in ops if o.end > lo and o.start < hi]
        busy = merge(clip([(o.start, o.end) for o in inside], lo, hi))
        busy_s += sum(b - a for a, b in busy) / n
        for o in inside:
            d = (min(o.end, hi) - max(o.start, lo)) / n
            kind = module_kind(o.module)
            if is_kernel(o):
                kernel_s[kind] = kernel_s.get(kind, 0.0) + d
            key = f"{kind}/{o.name}"
            by_op[key] = by_op.get(key, 0.0) + d
        for a, b in gaps(busy, lo, hi):
            mid = 0.5 * (a + b)
            name = "host outside the serve spans"
            i = bisect.bisect_right(spans, (mid, float("inf"), "")) - 1
            if i >= 0 and spans[i][0] <= mid <= spans[i][1]:
                name = spans[i][2]
            idle[name] = idle.get(name, 0.0) + (b - a) / n
    top = lambda d: [[k, v] for k, v in sorted(d.items(),
                                               key=lambda kv: -kv[1])[:10]]
    return {"busy_s": busy_s, "kernel_s": kernel_s,
            "breakdown": {"device_ops": top(by_op), "idle_gaps": top(idle)}}


def reduce_run(run, tl) -> Optional[Reduced]:
    """The traced part of a serve run."""
    if run.trace_dir is None or run.trace_steps is None:
        return None
    prof = load(find_xplane(run.trace_dir))
    if prof.marker is None or not prof.ops:
        return None
    to_prof = prof.marker - run.trace_marker_wall    # wall -> profile
    lo = prof.marker
    hi = run.trace_stop_host[0] + to_prof
    first, last = run.trace_steps
    host = [(a + to_prof, b + to_prof, "serve_decode_step")
            for a, b in tl.decode_spans[first:last + 1]]
    host += [(a + to_prof, b + to_prof, "serve_admit")
             for a, b, _ in tl.admit_spans]
    r = reduce_profile(prof, lo=lo, hi=hi, host_spans=host)
    wall_lo, wall_hi = run.trace_marker_wall, run.trace_stop_host[0]
    admits = [n for a, b, n in tl.admit_spans
              if a >= wall_lo and b <= wall_hi]
    return Reduced(r["busy_s"], hi - lo, r["kernel_s"],
                   (first, last), admits, r["breakdown"])
