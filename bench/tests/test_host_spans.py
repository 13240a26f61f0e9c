"""The readers of the program's own spans and counters: each new metric's
``read`` on hand-built span lists, the idle attribution by leaf span on
a made-up profile, and the host-plane reader on a small trace recorded
on a TPU v5e (``record_host_spans_trace.py``: three serve-shaped steps,
each with a 2 ms ``serve_schedule``, a ``decode_inputs`` that sleeps
3 ms, a jitted program of three matmuls, and a 1 ms ``serve_bookkeep``).
"""

import os
import shutil
import types

import pytest

import harness
import host_spans as hs

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "tpu_v5e_host_spans.xplane.pb")


def _rec(i, name, a, b, parent=None, **attrs):
    r = {"name": name, "id": i, "parent": parent, "ts": b, "dur_s": b - a,
         "thread": 1, "status": "ok"}
    if attrs:
        r["attrs"] = attrs
    return r


def _ctx(spans, *, start, end, n_steps, pauses=(None, None), first_id=1,
         trace=None, trace_dir=None, marker_wall=None):
    run = types.SimpleNamespace(
        spans=spans, first_span_id=first_id, trace_start_host=pauses[0],
        trace_stop_host=pauses[1], trace_dir=trace_dir,
        trace_marker_wall=marker_wall)
    tl = types.SimpleNamespace(start=start, end=end, n_steps=n_steps,
                               steps=slice(1, n_steps + 1))
    return types.SimpleNamespace(run=run, tl=tl, trace=trace)


def _steps(n, *, counters=True):
    """``n`` decode steps of 10 ms from t=1.0, 5 ms apart, each with its
    three children (inputs 1 ms, launch 0.5 ms, wait the rest)."""
    out, i = [], 1
    for k in range(n):
        a = 1.0 + 0.015 * k
        attrs = ({"gc_s": 0.001 * k * k, "gc_n": k, "compiles": 2 * k}
                 if counters else {})
        step = _rec(i, "serve_decode_step", a, a + 0.010, **attrs)
        out += [_rec(i + 1, "decode_inputs", a, a + 0.001, i),
                _rec(i + 2, "decode_launch", a + 0.001, a + 0.0015, i),
                _rec(i + 3, "decode_wait", a + 0.0015, a + 0.010, i), step]
        i += 4
    return out


def test_decode_launch_ms_means_inputs_and_launch_over_window_steps():
    ctx = _ctx(_steps(4), start=1.01, end=1.055, n_steps=3)
    assert harness.metric("decode_launch_ms").read(ctx) == \
        pytest.approx(1.5)


def test_readers_return_none_for_a_program_without_the_spans():
    bare = [r for r in _steps(4, counters=False)
            if r["name"] == "serve_decode_step"]
    ctx = _ctx(bare, start=1.01, end=1.055, n_steps=3)
    for name in ("decode_launch_ms", "sched_self_ms", "gc_pause_ms",
                 "compiles.window", "compile_s.setup",
                 "idle_share.launch"):
        assert harness.metric(name).read(ctx) is None, name


def test_counter_deltas_over_the_window():
    ctx = _ctx(_steps(4), start=1.01, end=1.055, n_steps=3)
    # gc_s from step 0 (0.0) to step 3 (0.009 s) over 3 steps
    assert harness.metric("gc_pause_ms").read(ctx) == pytest.approx(3.0)
    assert harness.metric("compiles.window").read(ctx) == 6.0


def test_sched_self_ms_subtracts_children_and_profiler_pauses():
    spans = [
        # before the window: not counted
        _rec(1, "serve_schedule", 0.5, 0.6),
        # a pass of admission with a 6 ms admission inside it: 4 ms own
        _rec(2, "serve_schedule", 10.000, 10.010),
        _rec(3, "serve_admit", 10.002, 10.008, 2, prompt_len=5),
        # bookkeeping with a 1 ms retirement and the profiler's start
        # (30 ms) inside it: 40 - 1 - 30 = 9 ms own
        _rec(4, "serve_bookkeep", 10.020, 10.060),
        _rec(5, "serve_retire", 10.021, 10.022, 4),
        # a second step: 2 ms of each, no children
        _rec(6, "serve_schedule", 10.070, 10.072),
        _rec(7, "serve_bookkeep", 10.080, 10.082),
    ]
    ctx = _ctx(spans, start=9.9, end=10.1, n_steps=2,
               pauses=((10.025, 10.055), None))
    assert harness.metric("sched_self_ms").read(ctx) == \
        pytest.approx((4 + 9 + 2 + 2) / 2)


def test_compile_s_setup_is_the_union_before_the_window():
    spans = [_rec(1, "jax_compile", 1.0, 3.0, phase="trace"),
             _rec(2, "jax_compile", 2.0, 4.0, phase="compile"),
             _rec(3, "jax_compile", 3.5, 3.8, phase="cache_load"),
             _rec(4, "jax_compile", 9.0, 11.0, phase="trace"),   # crosses
             _rec(5, "jax_compile", 12.0, 13.0, phase="trace")]  # after
    ctx = _ctx(spans, start=10.0, end=20.0, n_steps=1, first_id=99)
    # [1, 4] and [9, 10]: spans before the anchor count too
    assert harness.metric("compile_s.setup").read(ctx) == pytest.approx(4.0)


def test_leaves_take_the_innermost_open_span():
    segs = hs.leaves([(0.0, 10.0, "step"), (1.0, 3.0, "inputs"),
                      (3.0, 4.0, "launch"), (4.0, 9.0, "wait")])
    assert segs == {"step": [(0.0, 1.0), (9.0, 10.0)],
                    "inputs": [(1.0, 3.0)], "launch": [(3.0, 4.0)],
                    "wait": [(4.0, 9.0)]}


def test_attribute_splits_idle_inside_and_between_programs():
    prof = hs.HostProfile(
        busy={"/device:TPU:0": [(4.0, 5.0), (6.0, 8.0)]},
        programs={"/device:TPU:0": [(4.0, 8.0)]},
        host=[(0.0, 10.0, "serve_decode_step"), (1.0, 3.0, "decode_inputs"),
              (3.0, 4.0, "decode_launch"), (4.0, 9.0, "decode_wait")],
        marker=0.0)
    names = {"serve_decode_step", "decode_inputs", "decode_launch",
             "decode_wait"}
    a = hs.attribute(prof, 0.0, 12.0)
    assert a["idle_s"] == pytest.approx(9.0)
    # [5, 6] lies inside the program's run, while the host waits
    assert a["in_program"] == {"decode_wait": pytest.approx(1.0)}
    assert a["between"] == {"serve_decode_step": pytest.approx(2.0),
                            "decode_inputs": pytest.approx(2.0),
                            "decode_launch": pytest.approx(1.0),
                            "decode_wait": pytest.approx(1.0),
                            hs.OUTSIDE: pytest.approx(2.0)}
    assert a["spans"] == sorted(names)


NAMES = frozenset(["serve_schedule", "serve_decode_step", "decode_inputs",
                   "decode_launch", "decode_wait", "serve_bookkeep"])


@pytest.fixture(scope="module")
def recorded():
    return hs.load(RECORDED, NAMES)


def test_recorded_trace_has_the_spans_on_its_host_plane(recorded):
    assert list(recorded.busy) == ["/device:TPU:0"]
    assert recorded.marker is not None
    names = [e[2] for e in sorted(recorded.host)
             if e[2] in ("serve_decode_step", "decode_inputs",
                         "decode_launch", "decode_wait")]
    assert names == ["serve_decode_step", "decode_inputs",
                     "decode_launch", "decode_wait"] * 3
    steps = [e for e in recorded.host if e[2] == "serve_decode_step"]
    kids = [e for e in recorded.host if e[2].startswith("decode_")]
    # every child lies inside a step, on the device trace's time base
    assert all(any(s[0] <= k[0] <= k[1] <= s[1] for s in steps)
               for k in kids)
    assert len(recorded.programs["/device:TPU:0"]) >= 3


def test_recorded_trace_idle_by_leaf(recorded, tmp_path):
    shutil.copy(RECORDED, tmp_path / "t.xplane.pb")
    hi = max(iv[-1][1] for iv in recorded.busy.values())
    ctx = _ctx([_rec(i, n, 0.0, 1.0) for i, n in enumerate(sorted(NAMES))],
               start=0.0, end=1.0, n_steps=1, trace=object(),
               trace_dir=str(tmp_path), marker_wall=100.0,
               pauses=(None, (100.0 + hi - recorded.marker, 0.0)))
    a = hs.idle_by_leaf(ctx)
    assert a["window_s"] == pytest.approx(hi - recorded.marker)
    between = a["between"]
    # the 3 ms sleeps before each upload and the 2 ms scheduling passes
    # show as idle between programs, each under its own leaf
    assert between["decode_inputs"] >= 3 * 0.003
    assert between["serve_schedule"] >= 2 * 0.002
    named = sum(v for part in (between, a["in_program"])
                for k, v in part.items() if k != hs.OUTSIDE)
    assert named >= 0.9 * a["idle_s"]
    share = harness.metric("idle_share.launch").read(ctx)
    launch = sum(between.get(n, 0.0) for n in hs.LAUNCH)
    assert share == pytest.approx(100.0 * launch / a["window_s"])
    assert 0.0 < share < 100.0
    # a program whose spans are not on the host plane reads nothing
    ctx.run.spans = [_rec(1, "serve_admit", 0.0, 1.0)]
    assert harness.metric("idle_share.launch").read(ctx) is None
