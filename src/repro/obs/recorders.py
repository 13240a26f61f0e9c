"""Process-wide counters that spans read at their boundaries: Python
garbage-collection pauses and JAX compilations.

:func:`repro.obs.enable` installs both recorders and
:func:`repro.obs.disable` removes them:

* a ``gc.callbacks`` hook adds up the number of collections and their
  pause seconds per generation. A collection can start anywhere, also
  while the tracer's or the metrics registry's lock is held (an
  allocation inside ``_emit``, say), so the hook takes no lock and emits
  no record: it only updates these module lists;
* where JAX is loaded, ``jax.monitoring`` listeners turn each phase of a
  compilation, ``trace`` (jaxpr tracing), ``lower`` (jaxpr to an MLIR
  module) and ``compile`` (the backend's compile), into a ``jax_compile``
  span with the phase's own start and end and the function's name
  (``fun``), and each load from the persistent compilation cache into a
  ``jax_compile`` span with ``phase="cache_load"``. The three phases count
  in ``compiles``.

:func:`counters` reads the totals since the process started; the serve
loop attaches them to every ``serve_decode_step``, so the delta between
two steps is what happened between them.
"""

from __future__ import annotations

import gc
import sys
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

_gc_n: List[int] = [0, 0, 0]          # collections per generation
_gc_s: List[float] = [0.0, 0.0, 0.0]  # pause seconds per generation
_gc_t0: Optional[float] = None
_compiles = 0
_compiles_lock = threading.Lock()     # compilations may run on any thread
_record: Optional[Callable] = None    # tracing.record while installed
_monitoring = None                    # jax.monitoring while listening


def _on_gc(phase: str, info: dict) -> None:
    global _gc_t0
    if phase == "start":
        _gc_t0 = time.perf_counter()
    elif _gc_t0 is not None:
        g = info["generation"]
        _gc_n[g] += 1
        _gc_s[g] += time.perf_counter() - _gc_t0
        _gc_t0 = None


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    global _compiles
    phase = _PHASES.get(event)
    record = _record
    if phase is None or record is None:
        return
    with _compiles_lock:
        _compiles += 1
    record("jax_compile", start, end, phase=phase,
           fun=str(kw.get("fun_name", "")))


def _on_duration(event: str, duration: float, **kw) -> None:
    record = _record
    if event == _CACHE_LOAD and record is not None:
        end = time.time()
        record("jax_compile", end - duration, end, phase="cache_load")


def install(record: Callable) -> None:
    """Start counting; ``record(name, start, end, **attrs)`` emits the
    ``jax_compile`` spans. Idempotent."""
    global _record, _monitoring
    _record = record
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    if _monitoring is None and "jax" in sys.modules:
        from jax import monitoring
        monitoring.register_event_time_span_listener(_on_time_span)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _monitoring = monitoring


def remove() -> None:
    """Stop counting (the totals so far are kept). Idempotent."""
    global _record, _monitoring
    _record = None
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)
    if _monitoring is not None:
        _monitoring.unregister_event_time_span_listener(_on_time_span)
        _monitoring.unregister_event_duration_listener(_on_duration)
        _monitoring = None


def counters() -> Dict[str, float]:
    """Totals since the process started: GC pause seconds (``gc_s``) and
    collections (``gc_n``) over all generations, and compilation phases
    (``compiles``)."""
    return {"gc_s": sum(_gc_s), "gc_n": sum(_gc_n), "compiles": _compiles}


def gc_by_generation() -> List[Tuple[int, float]]:
    """``(collections, pause seconds)`` of generations 0, 1 and 2."""
    return list(zip(_gc_n, _gc_s))
