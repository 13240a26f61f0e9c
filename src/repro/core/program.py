"""Declarative stream programs: kernels as producer→pipe→consumer graphs.

The paper restructures a monolithic kernel into a *memory kernel* and a
*compute kernel* joined by a pipe; MKPipe (arXiv 2002.01614) argues the
decomposition pays off most when the multi-kernel program is a first-class
object the compiler can schedule. This module is that surface for the repo:
a kernel is *declared* as

  * producer stages — :class:`Stream` edges (regular block copies or
    irregular per-row gathers), each naming its HBM operand, pipe word
    shape, and address stream (``slicer``);
  * passive operands — :class:`BlockIn` (Pallas-blocked inputs such as the
    q tile) and :class:`ScalarIn` (scalar-prefetched index/length vectors);
  * a consumer compute body — ``consumer(ctx)`` reading landed pipe words
    via ``ctx.word(name)`` and folding them into scratch carries / the
    output block;

and :func:`compile_program` lowers the graph through the shared
:class:`~repro.core.emitter.RingPipe` / ``GatherRingPipe`` emitter into one
``pallas_call``: it owns the ring scratch, binds slicers, and emits the
acquire → consume → release word schedule. No kernel hand-rolls ring-buffer
plumbing; a new workload is a ~50-line declaration.

Sizing and mode selection are carried by one frozen :class:`PipePolicy`
(``mode`` / ``depth`` / ``streams`` / ``interpret`` / ``hw``), threaded
through the roofline planner (:func:`repro.core.planner.resolve_policy`)
instead of five copies of keyword plumbing. Session defaults are set with
the :func:`policy` context manager::

    with repro.policy(mode="baseline"):      # A/B the paper's strawman
        y = repro.ops.attention(q, k, v)
    with repro.policy(hw=ARRIA_CX):          # plan pipes for the paper's board
        y = repro.ops.matmul(a, b)

Old per-kernel keyword signatures (``mode=``/``depth=``/``streams=``/
``interpret=``) keep working through :func:`resolve_call_policy`, which
folds them into a PipePolicy and warns once per op.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
import warnings
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple, \
    Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.core import planner
from repro.core.emitter import GatherRingPipe, RingPipe, acquire, release
from repro.core.meshspec import MeshSpec, localize_workload, resolve_sharding
from repro.core.pipe import Pipe, clamp_streams
from repro.core.pipeline_model import HardwareModel, Workload, \
    device_hardware

# ---------------------------------------------------------------------------
# PipePolicy: one frozen knob bundle for every kernel call site
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PipePolicy:
    """How to size and run the pipes of one kernel call.

    Attributes:
      mode: "ff" (DAE pipeline), "baseline" (synchronous depth=1 strawman),
        "ref" (pure-jnp oracle), "autotune" (pipelined like "ff" but the
        (tile, depth, streams) configuration is *measured* per call site by
        :mod:`repro.core.autotune` and served from the persistent plan
        cache), or a kernel-specific extra mode.
      depth: ring slots — int, "auto" (roofline-planned per call site), or
        "measured" (empirically tuned at the kernel's default tile).
      streams: producer DMAs per word — int, "auto", or "measured".
      interpret: run the Pallas kernel in interpret mode. ``None`` (the
        default) derives it from the backend (:func:`default_interpret`):
        compiled on a TPU, interpreted elsewhere. The resolved bool is
        what the field holds after construction.
      hw: hardware model the planner sizes against (TPU_V5E / ARRIA_CX);
        also part of the tuned-plan cache key. ``None`` (the default)
        takes the model of the local device
        (:func:`~repro.core.pipeline_model.device_hardware`); on a TPU
        whose kind has no model that is an error.
      stream_options: candidate stream counts the planner/tuner may pick
        from.
      mesh: the mesh topology this policy's call sites run under
        (:class:`~repro.core.meshspec.MeshSpec`) — part of every plan and
        tuned-plan cache key, so plans sized for one topology never leak
        to another. ``None`` (the default) picks up the ambient
        :class:`~repro.runtime.sharding.ShardingContext` at resolve time;
        :func:`repro.runtime.streams.mesh_policy` tags a policy explicitly.
    """

    mode: str = "ff"
    depth: Union[int, str] = "auto"
    streams: Union[int, str] = "auto"
    interpret: Optional[bool] = None
    hw: Optional[HardwareModel] = None
    stream_options: Tuple[int, ...] = (1, 2, 4)
    mesh: Optional[MeshSpec] = None

    def __post_init__(self):
        if self.interpret is None:
            object.__setattr__(self, "interpret", default_interpret())
        if self.hw is None:
            object.__setattr__(self, "hw", device_hardware(jax.devices()[0]))
        if not isinstance(self.mode, str):
            raise TypeError(f"mode must be a str, got {self.mode!r}")
        if self.mesh is not None and not isinstance(self.mesh, MeshSpec):
            raise TypeError(
                f"mesh must be a MeshSpec or None, got {self.mesh!r}")
        for label, val in (("depth", self.depth), ("streams", self.streams)):
            if isinstance(val, str):
                if val not in ("auto", "measured"):
                    raise ValueError(f"{label} must be an int, 'auto', or "
                                     f"'measured', got {val!r}")
            elif int(val) < 1:
                raise ValueError(f"{label} must be >= 1, got {val!r}")

    def replace(self, **fields) -> "PipePolicy":
        return dataclasses.replace(self, **fields)

    def resolve(self, op: str, *, workload, tile, dtype) -> Tuple[int, int]:
        """Resolve this policy's (depth, streams) for one call site."""
        return planner.resolve_policy(op, self, workload=workload, tile=tile,
                                      dtype=dtype)


def default_interpret() -> bool:
    """Interpret mode derived from the backend: Pallas kernels compile for
    a TPU and run in the interpreter on every other backend."""
    return jax.default_backend() != "tpu"


class _PolicyStack(threading.local):
    def __init__(self):
        # the session default is built on first use, not at import:
        # building it reads the backend, which initializes (and on a TPU
        # host claims) the devices
        self.stack: List[PipePolicy] = []


_policies = _PolicyStack()


def current_policy() -> PipePolicy:
    """The session's active policy (innermost :func:`policy` context)."""
    if not _policies.stack:
        _policies.stack.append(PipePolicy())
    return _policies.stack[-1]


@contextlib.contextmanager
def policy(base: Optional[PipePolicy] = None, **fields):
    """Set session pipe-policy defaults without touching call sites.

    ``policy(mode="baseline")`` overrides just that field of the current
    policy; ``policy(some_policy)`` installs it wholesale (plus any field
    overrides). Nests and restores on exit; thread-local.

    Trace-time semantics: ops read the session policy when they are
    *traced*. The built-in kernel entrypoints re-resolve it on every call,
    but if you wrap an op in your own ``jax.jit``, a cached trace will NOT
    see a later policy change (the policy is not part of the jit cache
    key). Inside user jits, pass ``policy=PipePolicy(...)`` explicitly —
    it is hashable and works as a static argument — or enter the context
    before the first traced call.
    """
    pol = current_policy() if base is None else base
    if fields:
        pol = dataclasses.replace(pol, **fields)
    _policies.stack.append(pol)
    try:
        yield pol
    finally:
        _policies.stack.pop()


# -- deprecation shim: legacy keyword plumbing -> PipePolicy -----------------

_LEGACY_KWARGS = ("mode", "depth", "streams", "interpret")
_warned_ops = set()


def resolve_call_policy(op: str, call_policy: Optional[PipePolicy] = None,
                        **legacy) -> PipePolicy:
    """Fold one call's (policy=, legacy kwargs) into the effective policy.

    ``policy=`` overrides the session :func:`policy` context wholesale;
    legacy kwargs override individual fields of the session policy and warn
    once per op (the pre-StreamProgram keyword plumbing is deprecated).
    Mixing ``policy=`` with legacy kwargs in one call is ambiguous and
    raises TypeError.
    """
    given = {k: v for k, v in legacy.items() if v is not None}
    unknown = set(given) - set(_LEGACY_KWARGS)
    if unknown:
        raise TypeError(f"{op}: unknown policy kwargs {sorted(unknown)}")
    base = current_policy() if call_policy is None else call_policy
    if not given:
        return base
    if call_policy is not None:
        raise TypeError(
            f"{op}: pass either policy= or the deprecated "
            f"{sorted(given)} keywords, not both")
    if op not in _warned_ops:
        _warned_ops.add(op)
        warnings.warn(
            f"{op}: the {sorted(given)} keywords are deprecated; pass "
            f"policy=PipePolicy(...) or set session defaults with "
            f"`with repro.policy(...)`", DeprecationWarning, stacklevel=3)
    return dataclasses.replace(base, **given)


def make_entrypoint(op: str, apply_fn: Callable[..., Any],
                    modes: Tuple[str, ...] = ("ff", "baseline", "ref",
                                              "autotune"),
                    ) -> Callable[..., Any]:
    """Generate the public op wrapper from a policy-driven apply function.

    ``apply_fn(*arrays, policy: PipePolicy, **statics)`` implements the op;
    the generated entrypoint accepts the new ``policy=`` argument, the
    session policy context, and the deprecated per-kernel keywords
    (``mode``/``depth``/``streams``/``interpret``), all funneled through
    :func:`resolve_call_policy`. ``modes`` is the op's supported mode set —
    validated here, once, so apply functions never hand-roll the check.
    """

    @functools.wraps(apply_fn)
    def entrypoint(*args, policy=None, mode=None, depth=None, streams=None,
                   interpret=None, **kwargs):
        pol = resolve_call_policy(op, policy, mode=mode, depth=depth,
                                  streams=streams, interpret=interpret)
        if pol.mode not in modes:
            raise ValueError(
                f"{op}: unknown mode {pol.mode!r}; supported: {modes}")
        return apply_fn(*args, policy=pol, **kwargs)

    entrypoint.op_name = op
    entrypoint.__name__ = op
    entrypoint.__qualname__ = apply_fn.__qualname__.replace("_apply", op)
    return entrypoint


# ---------------------------------------------------------------------------
# The StreamProgram IR
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Stream:
    """A producer stage + pipe edge: operand ``name`` streams HBM→VMEM.

    ``slicer(ctx, word) -> hbm-ref-slice`` is the memory kernel's address
    stream (regular block copy); for ``gather=True`` it is a row slicer
    ``slicer(ctx, word, row)`` (irregular per-row gather — the row bundle is
    the stream decomposition). Slicers may depend only on the word index and
    input operands (typically scalar-prefetched indices), never on consumer
    state — the feed-forward restriction, enforced structurally: slicers
    receive a :class:`ProducerCtx` that exposes ``ref()`` only, no scratch
    or output.

    ``index`` optionally *declares* the stream's block schedule for the
    graph fuser (:mod:`repro.core.graph`): ``index(word) -> block-index
    tuple`` names which tile of the operand word ``word`` consumes, in the
    operand's own ``tile`` blocking. It must be a pure function of the word
    index (valid on Python ints for legality analysis and on traced ints
    inside the kernel) and must agree with ``slicer`` — the slicer of a
    declared stream is ``ref.at[index(word) * tile]``. Streams whose
    addresses are data-dependent (gathers) cannot declare one; an edge into
    such a stream always lowers staged.
    """

    name: str
    spec: Pipe
    slicer: Callable[..., Any]
    gather: bool = False
    index: Optional[Callable[..., Tuple[int, ...]]] = None


@dataclasses.dataclass(frozen=True)
class BlockIn:
    """A Pallas-blocked (non-streamed) input operand.

    ``dtype`` declares the operand element type. Plain ``compile_program``
    lowering never needs it (Pallas blocks carry the operand's own dtype),
    but the fused graph lowering (:mod:`repro.core.graph`) promotes producer
    BlockIns to ring-pipe streams, and a ring buffer must be sized at trace
    time — so the declaration carries the dtype.
    """

    name: str
    block: Tuple[int, ...]
    index_map: Callable[..., Any]
    dtype: Any = jnp.float32


@dataclasses.dataclass(frozen=True)
class ScalarIn:
    """A scalar-prefetched input (index/length vectors the slicers read)."""

    name: str


@dataclasses.dataclass(frozen=True)
class ScratchSpec:
    """One VMEM scratch carry owned by the consumer (accumulators etc.)."""

    name: str
    shape: Tuple[int, ...]
    dtype: Any = jnp.float32


InputSpec = Union[Stream, BlockIn, ScalarIn]


class ScheduleOpaqueError(ValueError):
    """A block schedule could not be evaluated statically.

    Raised by :meth:`StreamProgram.out_schedule` /
    :meth:`StreamProgram.stream_schedule` when the requested schedule is
    data-dependent (an index map that reads scalar-prefetch operands, or a
    stream with no declared ``index``). The graph fuser treats this as
    "not fusible along this edge" and falls back to staged lowering — it is
    a rationale, never a hard failure.
    """


class _OpaqueScalar:
    """Stand-in for a scalar-prefetch ref during static schedule evaluation:
    any attempt to *read* it proves the schedule is data-dependent."""

    def _opaque(self, *_, **__):
        raise ScheduleOpaqueError(
            "schedule depends on a scalar-prefetch operand (data-dependent)")

    __getitem__ = __getattr__ = __index__ = __int__ = _opaque
    __add__ = __radd__ = __mul__ = __rmul__ = _opaque
    __floordiv__ = __rfloordiv__ = __mod__ = __rmod__ = _opaque


class ProducerCtx:
    """What a Stream slicer sees: the input refs, nothing else.

    The producer has no access to scratch carries or the output block, so
    a slicer *cannot* depend on consumer state — the paper's feed-forward
    restriction falls out of the type.
    """

    __slots__ = ("_refs",)

    def __init__(self, refs):
        self._refs = refs

    def ref(self, name: str):
        """Raw ref of input ``name`` (HBM for streams, block/scalar else)."""
        return self._refs[name]


class ProgramCtx(ProducerCtx):
    """What the consumer body (and slicers) see inside the kernel.

    Attributes:
      g: current word index (grid step).
      n_words: total pipe words.
      out: output block ref.
    """

    __slots__ = ("g", "n_words", "out", "_pipes", "_scratch")

    def __init__(self, g, n_words, refs, pipes, out, scratch):
        super().__init__(refs)
        self.g = g
        self.n_words = n_words
        self.out = out
        self._pipes = pipes
        self._scratch = scratch

    def word(self, name: str):
        """VMEM ref of stream ``name``'s landed word ``g`` (pipe read end)."""
        return self._pipes[name].slot(self.g)

    def scratch(self, name: str):
        return self._scratch[name]


@dataclasses.dataclass(frozen=True)
class StreamProgram:
    """A kernel declared as producer stages → pipes → consumer body.

    Attributes:
      name: op name (planner / registry key).
      n_words: trip count of the word schedule (the 1-D grid).
      inputs: call-ordered operand specs; ScalarIn entries must lead (the
        Pallas scalar-prefetch convention). Block/out index maps receive
        ``(g, *scalar_refs)`` when ScalarIn operands exist, else ``(g,)``.
      consumer: ``f(ctx: ProgramCtx) -> None`` — the compute kernel. All
        arithmetic, DLCD carries, and output stores live here.
      out_shape / out_dtype / out_block / out_index_map: the output block
        mapping.
      scratch: consumer-owned VMEM carries (ring scratch is implicit —
        compile_program appends each stage's buffer + semaphores).
    """

    name: str
    n_words: int
    inputs: Tuple[InputSpec, ...]
    consumer: Callable[[ProgramCtx], None]
    out_shape: Tuple[int, ...]
    out_dtype: Any
    out_block: Tuple[int, ...]
    out_index_map: Callable[..., Any]
    scratch: Tuple[ScratchSpec, ...] = ()

    def __post_init__(self):
        names = [i.name for i in self.inputs] + [s.name for s in self.scratch]
        if len(set(names)) != len(names):
            raise ValueError(f"{self.name}: duplicate operand/scratch names "
                             f"in {names}")
        seen_tensor = False
        for i in self.inputs:
            if isinstance(i, ScalarIn):
                if seen_tensor:
                    raise ValueError(
                        f"{self.name}: ScalarIn operands must precede tensor "
                        f"operands (Pallas scalar-prefetch convention)")
            else:
                seen_tensor = True
        if not self.streams:
            raise ValueError(f"{self.name}: a StreamProgram needs at least "
                             f"one Stream edge")
        if self.n_words < 1:
            raise ValueError(f"{self.name}: n_words must be >= 1")

    @property
    def streams(self) -> Tuple[Stream, ...]:
        return tuple(i for i in self.inputs if isinstance(i, Stream))

    @property
    def num_scalar_prefetch(self) -> int:
        return sum(isinstance(i, ScalarIn) for i in self.inputs)

    @property
    def vmem_bytes(self) -> int:
        """Ring-buffer VMEM of all pipe edges (the BRAM analogue)."""
        return sum(s.spec.vmem_bytes for s in self.streams)

    def stream(self, name: str) -> Stream:
        for s in self.streams:
            if s.name == name:
                return s
        raise KeyError(f"{self.name}: no stream {name!r}; streams: "
                       f"{[s.name for s in self.streams]}")

    # -- static block schedules (the graph fuser's legality surface) --------

    def out_schedule(self) -> Tuple[Tuple[int, ...], ...]:
        """The output block schedule: ``out_index_map`` evaluated per word.

        Returns one block-index tuple per grid word — the write schedule the
        graph fuser matches against a downstream consumer's stream schedule
        (:mod:`repro.core.graph`). Raises :class:`ScheduleOpaqueError` when
        the map reads scalar-prefetch operands (data-dependent output
        placement): such a program cannot be a fused producer.
        """
        dummies = (_OpaqueScalar(),) * self.num_scalar_prefetch
        sched = []
        for g in range(self.n_words):
            try:
                idx = self.out_index_map(g, *dummies)
                sched.append(tuple(int(i) for i in idx))
            except ScheduleOpaqueError:
                raise
            except Exception as e:   # noqa: BLE001 — map not int-evaluable
                raise ScheduleOpaqueError(
                    f"{self.name}: out_index_map is not statically "
                    f"evaluable at word {g}: {type(e).__name__}: {e}") from e
        return tuple(sched)

    def stream_schedule(self, name: str) -> Tuple[Tuple[int, ...], ...]:
        """Stream ``name``'s declared block schedule, one tuple per word.

        Requires the stream to declare :attr:`Stream.index`; raises
        :class:`ScheduleOpaqueError` otherwise (irregular/gather streams) —
        the fuser's staged-fallback signal.
        """
        st = self.stream(name)
        if st.index is None:
            raise ScheduleOpaqueError(
                f"{self.name}: stream {name!r} declares no block schedule "
                f"(Stream.index); its addresses are data-dependent")
        try:
            return tuple(tuple(int(i) for i in st.index(g))
                         for g in range(self.n_words))
        except ScheduleOpaqueError:
            raise
        except Exception as e:   # noqa: BLE001
            raise ScheduleOpaqueError(
                f"{self.name}: stream {name!r} index is not statically "
                f"evaluable: {type(e).__name__}: {e}") from e


# ---------------------------------------------------------------------------
# Lowering: StreamProgram -> one pallas_call
# ---------------------------------------------------------------------------


def program_workload(program: StreamProgram) -> Workload:
    """Synthesize a conservative analytic Workload from a program's streams
    (n_words, per-word load/store bytes, regularity) — the planner input
    for programs whose kernel did not declare a workload builder."""
    import numpy as np

    store = (float(np.prod(program.out_shape))
             * jnp.dtype(program.out_dtype).itemsize) / program.n_words
    return Workload(
        n_words=program.n_words,
        word_bytes=float(sum(s.spec.word_bytes for s in program.streams)),
        flops_per_word=0.0,
        regular=not any(s.gather for s in program.streams),
        store_bytes_per_word=store,
    )


def _traced_compile(fn):
    """Wrap the program lowering in an obs span (no-op when tracing is
    off) so compile time and ring structure land in the trace."""
    @functools.wraps(fn)
    def wrapper(program, **kw):
        with obs.span("compile_program", program=program.name,
                      n_words=program.n_words,
                      streams=len(program.streams)):
            return fn(program, **kw)
    return wrapper


@_traced_compile
def compile_program(program: StreamProgram, *,
                    interpret: Optional[bool] = None,
                    pipe_overrides: Optional[Mapping[str, Pipe]] = None,
                    policy: Optional[PipePolicy] = None, sharding=None):
    """Lower a :class:`StreamProgram` into one ``pallas_call``.

    Returns a callable taking the program's operands in ``inputs`` order.
    The lowering instantiates one :class:`RingPipe` (or ``GatherRingPipe``)
    per Stream edge, appends the ring scratch it owns after the consumer's
    scratch, and wraps the consumer body in the emitter's word schedule::

        acquire(g, n_words, pipes)   # prologue fill + block on word g
        consumer(ctx)                # compute kernel
        release(g, n_words, pipes)   # refill consumed slots

    ``depth == 1`` pipes degenerate to the synchronous copy-then-compute
    baseline, so mode="baseline" reuses this exact path.

    ``pipe_overrides`` re-sizes named Stream edges at compile time: each
    entry replaces that stream's :class:`Pipe` spec with one of a
    different ``depth``/``streams`` without re-declaring the program —
    useful for sweeping ring sizes over a hand-built program (the
    built-in kernels instead rebuild through ``build_program(depth=,
    streams=)``). The word geometry is fixed by the declaration's
    slicers, so an override must keep ``tile`` and ``dtype`` unchanged —
    a different *tile* candidate is a different program, built through
    the kernel's ``build_program(...)`` / the registry's
    ``program(tile=...)`` hook.

    ``policy`` (optional) asks compile_program to *plan* the pipes
    instead: every regular stream is re-sized to the planner's (depth,
    streams) for the program's synthesized workload under the policy
    (gather streams keep their declared stream count — their row bundle
    is part of the word geometry), and ``policy.interpret`` supplies the
    interpret flag unless ``interpret=`` is passed explicitly. With
    neither, the flag comes from the backend (:func:`default_interpret`).
    ``sharding`` localizes that planning to the mesh: pass a
    :class:`~repro.runtime.sharding.ShardingContext` (or a bare
    :class:`~repro.core.meshspec.MeshSpec`), or leave ``None`` to pick up
    the ambient context — the planner then sizes against the per-shard
    local word schedule, not the global one, and the plan is cache-keyed
    by the mesh topology. Mutually exclusive with explicit
    ``pipe_overrides``.
    """
    if policy is not None:
        if pipe_overrides is not None:
            raise TypeError(f"{program.name}: pass either policy= or "
                            f"pipe_overrides=, not both")
        sh = sharding if sharding is not None else policy.mesh
        mesh, shards = resolve_sharding(sh)
        w = localize_workload(program_workload(program), shards)
        tile = tuple(program.streams[0].spec.tile)
        depth, streams = planner.resolve_policy(
            program.name, policy, workload=w, tile=tile,
            dtype=program.streams[0].spec.dtype, mesh=mesh)
        pipe_overrides = {
            st.name: dataclasses.replace(
                st.spec, depth=depth,
                streams=(st.spec.streams if st.gather else
                         clamp_streams(st.spec.tile[0], streams,
                                       st.spec.dtype)))
            for st in program.streams
        }
        if interpret is None:
            interpret = policy.interpret
    if interpret is None:
        interpret = default_interpret()
    scalar_ins = [i for i in program.inputs if isinstance(i, ScalarIn)]
    tensor_ins = [i for i in program.inputs if not isinstance(i, ScalarIn)]
    specs: Dict[str, Pipe] = {s.name: s.spec for s in program.streams}
    for name, pipe in (pipe_overrides or {}).items():
        if name not in specs:
            raise KeyError(f"{program.name}: pipe override for unknown "
                           f"stream {name!r}; streams: {sorted(specs)}")
        old = specs[name]
        if tuple(pipe.tile) != tuple(old.tile) or \
                jnp.dtype(pipe.dtype) != jnp.dtype(old.dtype):
            raise ValueError(
                f"{program.name}: pipe override for {name!r} must keep "
                f"tile/dtype ({old.tile}, {jnp.dtype(old.dtype).name}); "
                f"rebuild the program for a different tile")
        specs[name] = pipe
    rings: Dict[str, RingPipe] = {
        s.name: (GatherRingPipe if s.gather else RingPipe)(specs[s.name])
        for s in program.streams
    }

    def kernel(*refs):
        it = iter(refs)
        named = {i.name: next(it) for i in scalar_ins}
        named.update({i.name: next(it) for i in tensor_ins})
        out = next(it)
        scratch = {s.name: next(it) for s in program.scratch}

        g = pl.program_id(0)
        ctx = ProgramCtx(g, program.n_words, named, {}, out, scratch)
        pctx = ProducerCtx(named)    # slicers never see scratch/out
        pipes = []
        for i in tensor_ins:
            if not isinstance(i, Stream):
                continue
            buf, sems = next(it), next(it)
            if i.gather:
                bound = rings[i.name].bind(
                    buf, sems, lambda word, r, s=i: s.slicer(pctx, word, r))
            else:
                bound = rings[i.name].bind(
                    buf, sems, lambda word, s=i: s.slicer(pctx, word))
            ctx._pipes[i.name] = bound
            pipes.append(bound)

        acquire(g, program.n_words, pipes)
        program.consumer(ctx)
        release(g, program.n_words, pipes)

    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY) if isinstance(i, Stream)
        else pl.BlockSpec(i.block, i.index_map)
        for i in tensor_ins
    ]
    scratch_shapes = [pltpu.VMEM(s.shape, s.dtype) for s in program.scratch]
    for i in tensor_ins:
        if isinstance(i, Stream):
            scratch_shapes.extend(rings[i.name].scratch_shapes)
    out_spec = pl.BlockSpec(program.out_block, program.out_index_map)
    out_shape = jax.ShapeDtypeStruct(program.out_shape, program.out_dtype)

    if scalar_ins:
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(scalar_ins),
                grid=(program.n_words,),
                in_specs=in_specs,
                out_specs=out_spec,
                scratch_shapes=scratch_shapes,
            ),
            out_shape=out_shape,
            interpret=interpret,
        )
    return pl.pallas_call(
        kernel,
        grid=(program.n_words,),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        interpret=interpret,
    )
