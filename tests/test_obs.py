"""Observability stack: tracing spans, their profiler bridge, the GC and
compile recorders, the metrics registry, and the live-telemetry wiring
through autotune and serve."""

import json
import os
import time
import warnings

import pytest

from repro import obs
from repro.core import autotune


@pytest.fixture
def obs_memory():
    """Tracing on, in-memory ring, drained before and after."""
    prev = obs.enable()
    obs.drain()
    yield
    obs.drain()
    obs.restore(prev)


@pytest.fixture
def obs_off():
    prev = obs.disable()
    yield
    obs.restore(prev)


# ---------------------------------------------------------------------------
# Tracing spans
# ---------------------------------------------------------------------------

def test_disabled_span_is_shared_noop(obs_off):
    s = obs.span("anything", k=1)
    assert s is obs.NOOP_SPAN
    assert s.set(more=2) is obs.NOOP_SPAN     # chainable, still no-op
    with s:
        assert obs.current_span() is obs.NOOP_SPAN
    assert obs.drain() == []                  # nothing was emitted
    assert obs.trace_path() is None


def test_disabled_span_overhead_is_negligible(obs_off):
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        with obs.span("hot", a=1):
            pass
    per_call = (time.perf_counter() - t0) / n
    # the disabled path is one bool check + a shared singleton; anything
    # near 10us/call means an allocation or clock read snuck in
    assert per_call < 10e-6


def test_span_nesting_records_parent_ids(obs_memory):
    with obs.span("outer", op="o") as so:
        with obs.span("inner") as si:
            assert obs.current_span() is si
        assert obs.current_span() is so
    recs = {r["name"]: r for r in obs.drain()}
    assert recs["inner"]["parent"] == recs["outer"]["id"]
    assert recs["outer"]["parent"] is None
    assert recs["inner"]["dur_s"] <= recs["outer"]["dur_s"]
    assert recs["outer"]["status"] == "ok"


def test_span_closes_under_exception_and_unwinds_stack(obs_memory):
    with pytest.raises(ValueError):
        with obs.span("outer"):
            with obs.span("boom"):
                raise ValueError("x")
    recs = {r["name"]: r for r in obs.drain()}
    assert recs["boom"]["status"] == "error"
    assert recs["boom"]["error"] == "ValueError"
    assert recs["outer"]["status"] == "error"
    # the thread-local stack fully unwound: a fresh span is a root again
    with obs.span("after"):
        pass
    assert obs.drain()[0]["parent"] is None


def test_span_set_attaches_late_attributes(obs_memory):
    with obs.span("resolve", op="ff_x") as sp:
        sp.set(source="memory", origin="plandb")
    (rec,) = obs.drain()
    assert rec["attrs"] == {"op": "ff_x", "source": "memory",
                            "origin": "plandb"}


def test_trace_jsonl_sink(tmp_path):
    path = os.path.join(tmp_path, "trace.jsonl")
    prev = obs.enable(path)
    try:
        with obs.span("a", n=1):
            with obs.span("b"):
                pass
    finally:
        obs.restore(prev)
    lines = [json.loads(x) for x in open(path)]
    assert [r["name"] for r in lines] == ["b", "a"]
    assert lines[0]["parent"] == lines[1]["id"]


def test_tuning_config_trace_path_scopes_tracing(tmp_path, obs_off):
    path = os.path.join(tmp_path, "scoped.jsonl")
    with autotune.tuning_config(trace_path=path):
        assert obs.enabled() and obs.trace_path() == path
        with obs.span("scoped"):
            pass
    assert not obs.enabled()                  # prior state restored
    assert json.loads(open(path).readline())["name"] == "scoped"


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_snapshot_and_text_roundtrip():
    obs.metrics_clear("t_")
    obs.counter("t_requests_total", "requests", route="a").inc()
    obs.counter("t_requests_total", route="a").inc(2)
    obs.counter("t_requests_total", route="b").inc()
    obs.gauge("t_depth", "queue depth").set(7.5)
    h = obs.histogram("t_latency_seconds", "latency")
    for v in (0.001, 0.002, 0.004):
        h.observe(v)
    snap = obs.metrics_snapshot()
    assert snap["counters"]["t_requests_total{route=a}"] == 3
    assert snap["counters"]["t_requests_total{route=b}"] == 1
    assert snap["gauges"]["t_depth"] == 7.5
    assert snap["histograms"]["t_latency_seconds"]["count"] == 3
    assert snap["histograms"]["t_latency_seconds"]["min"] == 0.001

    text = obs.render_text()
    parsed = obs.parse_text(text)
    assert parsed['t_requests_total{route="a"}'] == 3
    assert parsed["t_depth"] == 7.5
    assert parsed["t_latency_seconds_count"] == 3
    assert parsed["t_latency_seconds_sum"] == pytest.approx(0.007)
    obs.metrics_clear("t_")
    assert not [k for k in obs.metrics_snapshot() if k.startswith("t_")]


def test_metric_kind_collision_raises():
    obs.metrics_clear("t_kind")
    obs.counter("t_kind_x", "a counter").inc()
    with pytest.raises(ValueError):
        obs.gauge("t_kind_x")
    obs.metrics_clear("t_kind")


def test_histogram_quantiles_track_percentiles():
    obs.metrics_clear("t_q")
    h = obs.histogram("t_q_seconds")
    vals = [0.001 + 0.001 * i / 999 for i in range(1000)]   # uniform [1,2]ms
    for v in vals:
        h.observe(v)
    s = h.summary()
    # exponential buckets at 2**(1/8) spacing: <= ~4.4% quantile error
    assert s["p50"] == pytest.approx(0.0015, rel=0.05)
    assert s["p99"] == pytest.approx(0.00199, rel=0.05)
    assert s["min"] == 0.001 and s["max"] == 0.002
    assert s["p50"] <= s["p90"] <= s["p99"] <= s["max"]
    obs.metrics_clear("t_q")


# ---------------------------------------------------------------------------
# Profiler bridge and process counters (GC, compilations)
# ---------------------------------------------------------------------------

def _host_events(trace_dir, names):
    import glob

    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events if e.name in names]
    return sorted(out)


def test_spans_land_on_the_profiler_host_plane(obs_memory, tmp_path):
    import jax
    import jax.numpy as jnp

    # the bridge binds at enable() once JAX is loaded
    obs.restore(obs.enable())
    x = jnp.ones((8, 8))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("outer_span", k=1):
            with obs.span("inner_a"):
                jax.block_until_ready(x @ x)
            with obs.span("inner_b"):
                pass
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(str(tmp_path), {"outer_span", "inner_a", "inner_b"})
    assert [e[2] for e in ev] == ["outer_span", "inner_a", "inner_b"]
    (o0, o1, _), (a0, a1, _), (b0, b1, _) = ev
    assert o0 <= a0 <= a1 <= b0 <= b1 <= o1     # nested, in order
    # the ring record keeps its schema
    recs = {r["name"]: r for r in obs.drain()}
    assert set(recs["outer_span"]) == {"name", "id", "parent", "ts",
                                       "dur_s", "thread", "status",
                                       "attrs"}
    assert recs["inner_a"]["parent"] == recs["outer_span"]["id"]


def test_fresh_jit_yields_compile_spans_until_disabled():
    import jax

    def triple_plus_one(x):
        return x * 3 + 1

    def quadruple(x):
        return x * 4

    prev = obs.enable()
    try:
        obs.drain()
        before = obs.counters()["compiles"]
        with obs.span("outer") as outer:
            jax.block_until_ready(jax.jit(triple_plus_one)(1.0))
        recs = [r for r in obs.drain() if r["name"] == "jax_compile"]
    finally:
        obs.disable()
    mine = [r for r in recs if "triple_plus_one" in r["attrs"]["fun"]]
    assert {r["attrs"]["phase"] for r in mine} >= {"trace", "lower",
                                                   "compile"}
    assert all(r["parent"] == outer.id and r["dur_s"] >= 0 for r in mine)
    assert obs.counters()["compiles"] - before == sum(
        r["attrs"]["phase"] != "cache_load" for r in recs)
    try:
        jax.block_until_ready(jax.jit(quadruple)(1.0))
        after = obs.counters()["compiles"]
        assert obs.drain() == []
        assert after == obs.counters()["compiles"]
    finally:
        obs.restore(prev)


def test_gc_inside_spans_counts_without_deadlock():
    import gc
    import threading

    old = gc.get_threshold()
    before = obs.counters()
    prev = obs.enable()

    def work():
        with obs.span("holder"):
            for i in range(20_000):
                with obs.span("churn", i=i):
                    [{} for _ in range(3)]
            gc.collect()
        obs.drain()

    try:
        gc.set_threshold(1)          # a collection on nearly every alloc
        t = threading.Thread(target=work, daemon=True)
        t.start()
        t.join(timeout=120)
        alive = t.is_alive()
    finally:
        gc.set_threshold(*old)
        obs.restore(prev)
    assert not alive, "a GC pause inside a span deadlocked the tracer"
    after = obs.counters()
    assert after["gc_n"] > before["gc_n"]
    assert after["gc_s"] > before["gc_s"]
    n, s = zip(*obs.gc_by_generation())
    assert sum(n) == after["gc_n"] and n[2] >= 1


def test_disabled_installs_no_recorder(obs_off):
    import gc

    import jax  # noqa: F401 — the bridge binds only when JAX is loaded
    from jax._src import monitoring    # the listener lists themselves

    from repro.obs import recorders, tracing

    def installed():
        return (recorders._on_gc in gc.callbacks,
                recorders._on_time_span
                in monitoring.get_event_time_span_listeners(),
                recorders._on_duration
                in monitoring.get_event_duration_listeners(),
                tracing._annotation is not None)

    assert installed() == (False,) * 4
    assert obs.span("x") is obs.NOOP_SPAN
    prev = obs.enable()
    assert installed() == (True,) * 4
    obs.restore(prev)
    assert installed() == (False,) * 4


def test_record_emits_a_closed_span_under_the_open_one(obs_memory):
    with obs.span("parent") as p:
        obs.record("after_the_fact", 10.0, 10.5, phase="x")
    recs = {r["name"]: r for r in obs.drain()}
    r = recs["after_the_fact"]
    assert r["parent"] == p.id and r["ts"] == 10.5 and r["dur_s"] == 0.5
    # ids follow the order in which spans opened
    assert r["attrs"] == {"phase": "x"} and r["id"] > recs["parent"]["id"]


def test_obs_imports_only_the_standard_library():
    import subprocess
    import sys
    code = ("import sys; from repro import obs; obs.enable(); "
            "obs.span('a').__enter__(); "
            "print(sorted(m for m in ('jax', 'numpy', 'jaxlib') "
            "if m in sys.modules))")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"


# ---------------------------------------------------------------------------
# Autotune wiring: plan-source counters, origin split, deprecation shim
# ---------------------------------------------------------------------------

def test_plan_stats_deprecation_shim():
    autotune._warned_plan_stats_deprecated = False
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        old = autotune.plan_stats()
    assert any(issubclass(w.category, DeprecationWarning) for w in caught)
    assert old == autotune.plan_stats_snapshot()


def test_memory_hit_keeps_plandb_origin(tmp_path, monkeypatch):
    """Satellite: a PlanDB-prewarm-then-hit is distinguishable from a
    plain memory hit — the second resolution counts under
    ``memory.plandb`` and tags the plan_resolutions_total counter."""
    import jax
    import jax.numpy as jnp

    from repro.core.program import PipePolicy
    from repro.kernels.ff_gather import gather
    from repro.plans import plandb as plandb_lib
    from repro.plans import record_traffic, sweep_profile

    monkeypatch.setenv("REPRO_PLAN_CACHE",
                       os.path.join(tmp_path, "host.json"))
    monkeypatch.delenv("REPRO_PLAN_DB", raising=False)
    monkeypatch.delenv("REPRO_PLAN_NAMESPACE", raising=False)
    autotune.tuned_cache_clear()
    plandb_lib.clear_cache()
    autotune.plan_stats_clear()
    obs.metrics_clear("plan_resolutions_total")

    pol = PipePolicy(mode="autotune", depth=2, streams=1, interpret=True)
    tab = jax.random.normal(jax.random.key(0), (64, 8), jnp.float32)
    idx = jax.random.randint(jax.random.key(1), (16,), 0, 64)

    with record_traffic() as prof, \
            autotune.tuning_config(cache_path=os.path.join(tmp_path,
                                                           "rec.json")):
        gather(tab, idx, policy=pol)
    sweep = sweep_profile(
        prof, scratch_cache=os.path.join(tmp_path, "scratch.json"),
        warmup=0, iters=1)
    dbp = os.path.join(tmp_path, "db.json")
    sweep.db.save(dbp)

    # fresh process simulation: only the swept DB in the lookup chain
    autotune.tuned_cache_clear()
    plandb_lib.clear_cache()
    autotune.plan_stats_clear()
    obs.metrics_clear("plan_resolutions_total")
    cold = os.path.join(tmp_path, "cold.json")
    with autotune.tuning_config(cache_path=cold, plan_db=dbp), \
            warnings.catch_warnings():
        warnings.simplefilter("error")       # a re-measure warning = failure
        gather(tab, idx, policy=pol)         # 1st: PlanDB hit -> memory
        gather(tab, idx, policy=pol)         # 2nd: memory hit, plandb origin
    stats = autotune.plan_stats_snapshot()
    assert stats.get("plandb") == 1
    assert stats.get("memory") == 1
    assert stats.get("memory.plandb") == 1   # the fix under test
    assert stats["hit_rate"] == 1.0
    counters = obs.metrics_snapshot()["counters"]
    assert counters.get(
        "plan_resolutions_total{origin=plandb,source=plandb}") == 1
    assert counters.get(
        "plan_resolutions_total{origin=plandb,source=memory}") == 1


def test_resolve_call_span_carries_source_tag(obs_memory, tmp_path,
                                              monkeypatch):
    import jax
    import jax.numpy as jnp

    from repro.core.program import PipePolicy
    from repro.kernels.ff_gather import gather

    monkeypatch.setenv("REPRO_PLAN_CACHE",
                       os.path.join(tmp_path, "host.json"))
    monkeypatch.delenv("REPRO_PLAN_DB", raising=False)
    autotune.tuned_cache_clear()
    tab = jax.random.normal(jax.random.key(0), (64, 8), jnp.float32)
    idx = jax.random.randint(jax.random.key(1), (16,), 0, 64)
    obs.drain()
    gather(tab, idx, policy=PipePolicy(mode="ff", interpret=True))
    spans = [r for r in obs.drain() if r["name"] == "resolve_call"]
    assert spans, "op entrypoint did not open a resolve_call span"
    assert spans[0]["attrs"]["op"] == "ff_gather"
    assert spans[0]["attrs"]["source"]   # plan-source tag present


# ---------------------------------------------------------------------------
# Serve: --metrics-json live telemetry
# ---------------------------------------------------------------------------

def test_serve_metrics_json_snapshot_parses(tmp_path):
    import argparse

    from repro.launch import serve as serve_lib

    obs.metrics_clear("serve_")
    path = os.path.join(tmp_path, "serve_metrics.json")
    ap = argparse.ArgumentParser()
    serve_lib.add_serve_args(ap)
    args = ap.parse_args(
        ["--smoke", "--requests", "3", "--slots", "2", "--prompt-len", "8",
         "--max-new", "4", "--rate", "50", "--metrics-json", path])
    result = serve_lib.serve_bench(args)
    assert result["metrics_json"] == path
    assert not obs.enabled()                 # bench restored the prior state
    snap = json.load(open(path))
    lock = snap["histograms"]["serve_token_latency_seconds{scheduler=lockstep}"]
    paged = snap["histograms"]["serve_token_latency_seconds{scheduler=paged}"]
    assert lock["count"] == paged["count"] == result["paged"]["tokens"]
    # the gauge tracks live pool utilization; at drain end it reads 0
    assert 0.0 <= snap["gauges"]["serve_kv_utilization"] <= 1.0
    # live histogram vs the bench's post-hoc percentiles: same samples,
    # so only bucket resolution separates them (acceptance bar: 10%)
    for sched, m in (("lockstep", result["lockstep"]),
                     ("paged", result["paged"])):
        live = snap["histograms"][
            f"serve_token_latency_seconds{{scheduler={sched}}}"]
        assert live["p50"] * 1e3 == pytest.approx(m["p50_ms"], rel=0.10)
        assert live["p99"] * 1e3 == pytest.approx(m["p99_ms"], rel=0.10)
