"""Drives the program's serve path for one run and records what it did.

The window runs ``repro.launch.serve.run_continuous`` (paged continuous
batching, compiled ``ff`` kernels, one chip, no EOS) over the requests of
the traffic file, all due at once, so the scheduler keeps ``clients``
slots busy: each retirement frees a slot that the next request takes (a
closed loop of ``clients`` clients). The program is not changed; the
harness hooks it in three places, each named in PERF.md:

* ``TapModel.decode_step`` wraps the model's ``decode_step``: besides the
  logits it hands out the step's input tokens and the argmax of its
  logits, riding next to the pool in ``new_caches["kv_pool"]``;
* ``TapCache`` (swapped in for ``repro.launch.serve.PagedKVCache``)
  unpacks them in ``update_pool`` and keeps the device references (no sync
  in the window), and in ``append`` records which slots stepped, their
  context lengths and the pool's utilization, drains the span ring, and
  starts and stops the profiler in a traced run;
* ``TapCache.__init__``, ``admit`` and ``retire`` note, on the harness's
  own reading of the host's wall clock, when the window's cache was made
  (the warm-up's cache comes first and is superseded), which slot each
  admission took and which slot each retirement freed. ``admit`` passes
  on whatever the cache's own ``admit`` takes (a slot first) and reads
  the admitted length from ``lengths[slot]`` after it, so a cache that
  admits another kind of state is tapped unchanged.

Every end-to-end time is the harness's own clock reading in these hooks.
The program's ``obs`` spans (``serve_admit``, ``serve_decode_step``) are
read only by the per-layer metrics whose source is ``program_span``.
"""

from __future__ import annotations

import dataclasses
import gc
import os
import time
from typing import Dict, List, Optional

import numpy as np

import generator
import trace_reduce


@dataclasses.dataclass
class Step:
    t: float                # wall clock as the step's tokens reached the host
    mask: np.ndarray        # [slots] 1 where the slot decoded this step
    lengths: np.ndarray     # [slots] context length before the step
    pool_util: float        # util_vs_pool after the step's append
    tok_in: object = None   # device refs, read after the window
    tok_out: object = None


class Recorder:
    """What the hooks see during one ``run_continuous`` call."""

    def __init__(self, *, trace_dir: Optional[str], trace_start_s: float,
                 trace_s: float):
        self.steps: List[Step] = []
        self.spans: List[dict] = []
        self.t_start: Optional[float] = None
        self.admits: List[tuple] = []    # (slot, prompt_len, steps before)
        self.retires: List[tuple] = []   # (slot, steps before)
        self.pending = None
        self.t_first_step: Optional[float] = None
        self.trace_dir = trace_dir
        self.trace_start_s = trace_start_s
        self.trace_s = trace_s
        self.trace_on: Optional[float] = None
        self.first_traced: Optional[int] = None
        self.trace_steps = None          # (first, last) step index traced
        self.trace_marker_wall: Optional[float] = None
        self.trace_start_host = None     # (start, end) wall of start_trace
        self.trace_stop_host = None      # (start, end) wall of stop_trace

    def on_new_cache(self):
        """A new cache supersedes the warm-up's: the window starts."""
        self.t_start = time.time()
        self.admits, self.retires, self.steps = [], [], []

    def on_admit(self, slot, length):
        self.admits.append((int(slot), int(length), len(self.steps)))

    def on_retire(self, slot):
        self.retires.append((int(slot), len(self.steps)))

    def on_pool(self, tok_in, tok_out):
        self.pending = (tok_in, tok_out)

    def on_append(self, kv, n_per_slot):
        from repro import obs
        t = time.time()
        mask = np.asarray(n_per_slot, np.int32).copy()
        tin, tout = self.pending if self.pending else (None, None)
        self.pending = None
        self.steps.append(Step(t, mask, kv.lengths.copy(), 0.0, tin,
                               tout))
        self.spans.extend(obs.drain())

    def after_append(self, kv):
        self.steps[-1].pool_util = kv.utilization()["util_vs_pool"]
        now = time.time()
        if self.t_first_step is None:
            self.t_first_step = now
        if self.trace_dir is None or self.trace_steps is not None:
            return
        import jax
        if self.trace_on is None:
            if now - self.t_first_step >= self.trace_start_s:
                a = time.time()
                jax.profiler.start_trace(self.trace_dir)
                with jax.profiler.TraceAnnotation(trace_reduce.MARKER):
                    self.trace_marker_wall = time.time()
                self.trace_on = time.time()
                self.trace_start_host = (a, self.trace_on)
                self.first_traced = len(self.steps)
        elif now - self.trace_on >= self.trace_s:
            a = time.time()
            jax.profiler.stop_trace()
            self.trace_stop_host = (a, time.time())
            self.trace_steps = (self.first_traced, len(self.steps) - 1)


def _tap_classes(base_cache, rec: Recorder):
    class TapCache(base_cache):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            rec.on_new_cache()

        def admit(self, slot, *args, **kwargs):
            super().admit(slot, *args, **kwargs)
            rec.on_admit(slot, self.lengths[slot])

        def retire(self, slot):
            super().retire(slot)
            rec.on_retire(slot)

        def update_pool(self, new_pool):
            pool, tok_in, tok_out = new_pool
            rec.on_pool(tok_in, tok_out)
            super().update_pool(pool)

        def append(self, n_per_slot):
            rec.on_append(self, n_per_slot)
            super().append(n_per_slot)
            rec.after_append(self)

    return TapCache


class TapModel:
    """The program's model with the decode step's tokens handed out."""

    def __init__(self, model):
        self.model = model

    def prefill(self, params, batch):
        return self.model.prefill(params, batch)

    def decode_step(self, params, batch, caches):
        import jax.numpy as jnp
        logits, new = self.model.decode_step(params, batch, caches)
        new = dict(new)
        new["kv_pool"] = (new["kv_pool"], batch["token"],
                          jnp.argmax(logits, axis=-1).astype(jnp.int32))
        return logits, new


@dataclasses.dataclass
class ServeRun:
    requests: List[generator.Req]
    t_start: float
    admits: List[tuple]
    retires: List[tuple]
    steps: List[Step]
    spans: List[dict]
    slots: int
    page: int
    peak_bytes: Optional[int]
    first_span_id: int
    trace_dir: Optional[str]
    trace_steps: Optional[tuple]
    trace_marker_wall: Optional[float]
    trace_stop_host: Optional[tuple]
    trace_start_host: Optional[tuple] = None


def run(cell, *, seed: int, seconds: float, trace_dir: Optional[str],
        trace_s: float = 3.0) -> ServeRun:
    """One window of ``run_continuous`` on the first chip."""
    import jax
    from repro import obs
    from repro.core.program import PipePolicy
    from repro.launch import serve
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.runtime import sharding as shlib

    traffic = cell.traffic
    cfg = cell.program_config()
    reqs = generator.make_requests(traffic, seed=seed, seconds=seconds,
                                   vocab=cell.spec.vocab)
    rec = Recorder(trace_dir=trace_dir, trace_start_s=0.3 * seconds,
                   trace_s=trace_s)
    model = build_model(cfg)
    mesh = make_host_mesh(devices=jax.devices()[:1])
    obs.enable()
    obs.drain()
    base_cache = serve.PagedKVCache
    serve.PagedKVCache = _tap_classes(base_cache, rec)
    try:
        with shlib.use_sharding(mesh, overrides=dict(cfg.rule_overrides
                                                     or {})):
            params = cell.arch.program_params(cell.spec, seed)
            _check_tree(params, model.abstract_params())
            jax.block_until_ready(params)
            with obs.span("bench_window") as anchor:
                first_id = anchor.id
                serve.run_continuous(
                    TapModel(model), params, cfg,
                    [serve.Request(r.rid, 0.0, r.prompt, r.max_new)
                     for r in reqs],
                    n_slots=traffic["clients"], page=traffic["page"],
                    eos_id=None, policy=PipePolicy(mode="ff"))
    finally:
        serve.PagedKVCache = base_cache
        if rec.trace_on is not None and rec.trace_steps is None:
            jax.profiler.stop_trace()
            rec.trace_steps = (rec.first_traced, len(rec.steps) - 1)
    rec.spans.extend(obs.drain())
    obs.disable()
    stats = jax.devices()[0].memory_stats() or {}
    for s in rec.steps:                 # the window has closed: read back
        s.tok_in = np.asarray(s.tok_in) if s.tok_in is not None else None
        s.tok_out = np.asarray(s.tok_out) if s.tok_out is not None else None
    del params
    gc.collect()
    return ServeRun(reqs, rec.t_start, rec.admits, rec.retires,
                    rec.steps, rec.spans, traffic["clients"],
                    traffic["page"], stats.get("peak_bytes_in_use"),
                    first_id, trace_dir, rec.trace_steps,
                    rec.trace_marker_wall, rec.trace_stop_host,
                    rec.trace_start_host)


def _check_tree(params, abstract):
    """The drawn tree has the program's structure, shapes and dtypes."""
    import jax
    got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    want = jax.tree.map(lambda x: (x.shape, x.dtype), abstract)
    if got != want:
        raise ValueError(f"weights do not fit the program's parameter "
                         f"tree:\n{got}\nvs\n{want}")
