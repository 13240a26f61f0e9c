"""StreamGraph: multi-kernel pipe graphs with fused/staged lowering.

The paper splits *one* kernel into a memory stage and a compute stage
joined by a pipe. MKPipe (arXiv 2002.01614) shows the bigger win comes when
the *multi-kernel* program is a first-class object the compiler schedules:
producer→consumer kernels pipeline through on-chip channels so intermediates
never round-trip global memory — exactly the memory-controller bottleneck
quantified by The Memory Controller Wall (arXiv 1910.06726). This module is
that compiler layer for the repo, one level above
:mod:`repro.core.program`:

* a :class:`StreamGraph` composes registered :class:`StreamProgram` nodes
  into a DAG whose inter-kernel edges are declared :class:`GraphEdge`\\ s
  ("node ``dst`` streams node ``src``'s output through its ``dst_input``
  stream");
* :func:`compile_graph` chooses **per edge** between

  - **fused** lowering — when the producer's output block schedule matches
    the consumer's stream slicer (checked statically via
    ``StreamProgram.out_schedule`` / ``Stream.index``), the edge becomes an
    in-VMEM ring pipe inside a *single* ``pallas_call``: the producer's
    words are inlined ahead of the consumer words that need them and the
    intermediate block lands in a VMEM ring slot, never in HBM;
  - **staged** lowering — a double-buffered HBM handoff: the producer's
    ``pallas_call`` materializes the intermediate, the consumer streams it
    back through its declared ring pipe (depth ≥ 2 double-buffers the
    reload), and the planner charges the round trip in
    :func:`repro.core.pipeline_model.estimate_graph`;

* fusion legality, the per-edge VMEM split (``planner.split_graph_budget``),
  the MKPipe-style cost model (``estimate_graph``), and the graph-keyed
  measured autotuner (``autotune.resolve_graph``) all hang off the same
  compiled plan, so every rejection is observable as a rationale line —
  never a silent fallback.

Fused word schedule
-------------------

Legality analysis runs entirely on Python ints: the producer's output
schedule is grouped into equal-length contiguous runs (one per output
block, in completion order), the consumer's declared stream schedule is
mapped onto those blocks through row-major element offsets (so an
``edge.reshape`` between a ``[BH, S, D]`` producer and a ``[BH*S, D]``
consumer is handled exactly), and the request order must walk the
completion order contiguously. The resulting per-word (block ordinal,
first-request) tables ride into the fused kernel as scalar-prefetched
int32 vectors — the TPU analogue of the FPGA address FIFO — so the kernel
needs no data-dependent control flow beyond ``pl.when``.

At consumer word ``g`` the fused kernel runs::

    b = ord[g]; fresh[g]?            # scalar-prefetched schedule tables
    when fresh:                      # first word that needs block b
        for j in range(words_per_block):       # inlined producer stage
            w = b * words_per_block + j
            acquire(w, producer pipes); producer.consumer(w -> ring[b]);
            release(w, producer pipes)
    acquire(g, consumer's other pipes)
    consumer.consumer(g, edge word served from ring[b])   # compute stage
    release(g, consumer's other pipes)

Producer ``BlockIn`` operands are promoted to ring streams (Pallas block
delivery follows the grid, but the inlined producer's words are
schedule-driven), which is why :class:`repro.core.program.BlockIn` carries
a declared dtype.

Whole-layer chains, epilogues, multi-consumer edges
---------------------------------------------------

Fusion is not limited to pairs: fused edges compose into linear *chains*
(``qkv → attention → out-proj → mlp``), lowered recursively — each stage's
words inline the words of the stage above it on first request, every
intermediate living in its own VMEM ring, the whole chain one
``pallas_call`` checked against the *sum* of the member nodes' split VMEM
budgets (``planner.split_graph_budget``).

A :class:`GraphNode` may carry an :class:`Epilogue` — a residual add or
RMSNorm folded into the consumer body at the output write (the paper's
"compute stage owns the final store"). Epilogue inputs are extra
``BlockIn`` operands of the node, so an edge may feed them
(``dst_input`` naming a BlockIn rather than a Stream): such *block
edges* stage by default, but when the producer is fused away inside the
same chain the consumer is served directly from the chain's intermediate
VMEM ring ("ring-served", a fused edge) — this is how one producer feeds
both the next stage's stream and a later stage's residual epilogue
without ever materializing in HBM. When a fused-away producer's other
consumers *cannot* be served in-chain, the fusion unwinds to staged with
a rationale — never a silent wrong answer.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, \
    Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.core import planner
from repro.core.emitter import GatherRingPipe, RingPipe, acquire, release
from repro.core.meshspec import MeshSpec, SINGLE_DEVICE, localize_workload, \
    resolve_sharding
from repro.core.pipe import DEFAULT_VMEM_BUDGET_BYTES, Pipe, clamp_streams
from repro.core.pipeline_model import EdgeEstimate, GraphStage, Workload, \
    estimate_graph
from repro.core.planner import PlanError
from repro.core.program import BlockIn, ProducerCtx, ProgramCtx, ScalarIn, \
    ScheduleOpaqueError, Stream, StreamProgram, _OpaqueScalar, \
    compile_program, program_workload

_VMEM_BUDGET_BYTES = DEFAULT_VMEM_BUDGET_BYTES


# ---------------------------------------------------------------------------
# The graph IR
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Epilogue:
    """A per-output-write transform folded into a node's consumer body.

    ``fn(ctx, idx, value) -> value`` runs at every ``ctx.out[idx] = value``
    the node's consumer performs, inside the kernel, before the store —
    residual adds and RMSNorm live here so they ride the fused chain
    instead of costing an extra HBM round trip. ``ctx`` is the node's
    :class:`~repro.core.program.ProgramCtx` (so ``fn`` may read
    ``ctx.g`` and ``ctx.ref(...)``); ``inputs`` declares extra
    :class:`~repro.core.program.BlockIn` operands ``fn`` reads (a residual
    tensor, a norm weight). They are appended to the program's inputs and
    may be fed by a graph edge like any other block operand.
    """

    fn: Callable
    inputs: Tuple[BlockIn, ...] = ()


class _EpilogueOut:
    """Output-ref proxy that applies the epilogue at each write."""

    __slots__ = ("_ctx", "_fn", "_out")

    def __init__(self, ctx, fn, out):
        self._ctx = ctx
        self._fn = fn
        self._out = out

    def __setitem__(self, idx, value):
        self._out[idx] = self._fn(self._ctx, idx, value)

    def __getitem__(self, idx):
        return self._out[idx]

    @property
    def at(self):
        return self._out.at


class _EpilogueCtx:
    """ProgramCtx proxy whose ``out`` routes writes through the epilogue.

    The epilogue ``fn`` receives the *underlying* ctx, so it can read its
    declared BlockIns via ``ctx.ref`` without re-entering the proxy.
    """

    __slots__ = ("_ctx", "out")

    def __init__(self, ctx, fn):
        self._ctx = ctx
        self.out = _EpilogueOut(ctx, fn, ctx.out)

    @property
    def g(self):
        return self._ctx.g

    @property
    def n_words(self):
        return self._ctx.n_words

    def ref(self, name):
        return self._ctx.ref(name)

    def word(self, name):
        return self._ctx.word(name)

    def scratch(self, name):
        return self._ctx.scratch(name)


def _with_epilogue(program: StreamProgram,
                   ep: Optional[Epilogue]) -> StreamProgram:
    """The node's effective program: epilogue inputs appended, consumer
    wrapped so every output write passes through ``ep.fn``. A pure program
    transform — the result lowers through every path (standalone node,
    fused producer, fused consumer) with no special cases."""
    if ep is None:
        return program
    orig = program.consumer

    def consumer(ctx, _orig=orig, _fn=ep.fn):
        _orig(_EpilogueCtx(ctx, _fn))

    return dataclasses.replace(
        program, name=f"{program.name}+ep",
        inputs=tuple(program.inputs) + tuple(ep.inputs),
        consumer=consumer)


@dataclasses.dataclass(frozen=True)
class GraphNode:
    """One kernel of the multi-kernel program.

    ``workload`` (optional) is the node's analytic
    :class:`~repro.core.pipeline_model.Workload` — kernels' registry
    ``workload`` builders produce it; when omitted a conservative one is
    synthesized from the program's streams. ``plan_tile`` is the tile the
    planner sizes pipes against (default: the first stream's tile).
    ``epilogue`` folds residual/norm math into the consumer body at the
    output write (see :class:`Epilogue`).
    """

    name: str
    program: StreamProgram
    workload: Optional[Workload] = None
    plan_tile: Optional[Tuple[int, ...]] = None
    epilogue: Optional[Epilogue] = None

    @property
    def effective_program(self) -> StreamProgram:
        """The program as compiled: epilogue folded into the consumer."""
        return _with_epilogue(self.program, self.epilogue)


@dataclasses.dataclass(frozen=True)
class GraphEdge:
    """One inter-kernel dataflow edge: ``dst`` reads ``src``'s output
    through its Stream input ``dst_input``.

    ``prefer``: "auto" fuses when legal and VMEM-feasible (staged fallback
    with a rationale otherwise), "fused" demands fusion (infeasibility
    raises :class:`~repro.core.planner.PlanError` with the per-edge
    rationale), "staged" pins the HBM handoff. ``reshape`` declares the
    view the consumer takes of the intermediate (e.g. ``[BH, S, D]`` →
    ``[BH*S, D]`` between attention and its out-projection); it must
    preserve the element count and is applied to the materialized array in
    staged mode and to the offset arithmetic of the legality check in
    fused mode.
    """

    src: str
    dst: str
    dst_input: str
    prefer: str = "auto"
    reshape: Optional[Tuple[int, ...]] = None

    def __post_init__(self):
        if self.prefer not in ("auto", "fused", "staged"):
            raise ValueError(f"edge {self.src}->{self.dst}: prefer must be "
                             f"auto|fused|staged, got {self.prefer!r}")

    @property
    def label(self) -> str:
        return f"{self.src}->{self.dst}"


@dataclasses.dataclass(frozen=True)
class StreamGraph:
    """A DAG of stream programs joined by pipe edges.

    Validated at construction: node names unique, edges name known nodes
    and Stream/BlockIn inputs (epilogue inputs count — that is how a
    residual epilogue is fed by an upstream node), no input is fed twice,
    and the graph is acyclic
    (a pipe cycle would deadlock the FPGA channels it models — rejected
    here, like the paper rejects true memory loop-carried dependencies).
    """

    name: str
    nodes: Tuple[GraphNode, ...]
    edges: Tuple[GraphEdge, ...] = ()

    def __post_init__(self):
        names = [n.name for n in self.nodes]
        if len(set(names)) != len(names):
            raise ValueError(f"{self.name}: duplicate node names {names}")
        by_name = {n.name: n for n in self.nodes}
        fed = set()
        for e in self.edges:
            for end in (e.src, e.dst):
                if end not in by_name:
                    raise ValueError(f"{self.name}: edge {e.label} names "
                                     f"unknown node {end!r}")
            if e.src == e.dst:
                raise ValueError(f"{self.name}: self-edge on {e.src!r}")
            dst_node = by_name[e.dst]
            prog = dst_node.effective_program
            names = {i.name for i in prog.inputs
                     if not isinstance(i, ScalarIn)}
            if e.dst_input not in names:
                raise ValueError(
                    f"{self.name}: edge {e.label} must feed a Stream input "
                    f"or BlockIn operand of {e.dst!r}: {e.dst_input!r} not "
                    f"in {sorted(names)}")
            key = (e.dst, e.dst_input)
            if key in fed:
                raise ValueError(f"{self.name}: input {e.dst}.{e.dst_input} "
                                 f"is fed by more than one edge")
            fed.add(key)
            if e.reshape is not None:
                src_prog = by_name[e.src].program
                if int(np.prod(e.reshape)) != int(np.prod(src_prog.out_shape)):
                    raise ValueError(
                        f"{self.name}: edge {e.label} reshape {e.reshape} "
                        f"does not preserve the element count of "
                        f"{src_prog.out_shape}")
        self.topo_order()    # raises on cycles

    def node(self, name: str) -> GraphNode:
        for n in self.nodes:
            if n.name == name:
                return n
        raise KeyError(f"{self.name}: unknown node {name!r}")

    def topo_order(self) -> Tuple[GraphNode, ...]:
        """Kahn topological order (stable in declaration order); raises
        ValueError on cycles."""
        indeg = {n.name: 0 for n in self.nodes}
        for e in self.edges:
            indeg[e.dst] += 1
        order: List[GraphNode] = []
        ready = [n for n in self.nodes if indeg[n.name] == 0]
        while ready:
            n = ready.pop(0)
            order.append(n)
            for e in self.edges:
                if e.src == n.name:
                    indeg[e.dst] -= 1
                    if indeg[e.dst] == 0:
                        ready.extend(m for m in self.nodes
                                     if m.name == e.dst)
        if len(order) != len(self.nodes):
            cyc = sorted(set(indeg) - {n.name for n in order})
            raise ValueError(f"{self.name}: graph has a cycle through "
                             f"{cyc}")
        return tuple(order)

    def sinks(self) -> Tuple[str, ...]:
        """Nodes with no out-edge — the graph's outputs, in topo order."""
        srcs = {e.src for e in self.edges}
        return tuple(n.name for n in self.topo_order() if n.name not in srcs)


# ---------------------------------------------------------------------------
# Workload synthesis + graph identity (autotune key)
# ---------------------------------------------------------------------------


def node_workload(node: GraphNode) -> Workload:
    """The node's analytic workload (declared, or synthesized from the
    program's streams when the builder did not provide one)."""
    if node.workload is not None:
        return node.workload
    return program_workload(node.program)


def _node_tile(node: GraphNode) -> Tuple[int, ...]:
    return tuple(node.plan_tile or node.program.streams[0].spec.tile)


def _node_dtype(node: GraphNode):
    return jnp.dtype(node.program.streams[0].spec.dtype)


def graph_workload(graph: StreamGraph) -> Tuple[Workload, Tuple[int, ...]]:
    """Summarize the whole graph as one Workload (the joint tuner's call
    site): total words, byte/flop averages, irregular if any node is."""
    ws = [node_workload(n) for n in graph.topo_order()]
    n_words = max(sum(w.n_words for w in ws), 1)
    w = Workload(
        n_words=n_words,
        word_bytes=sum(w.word_bytes * w.n_words for w in ws) / n_words,
        flops_per_word=sum(w.flops_per_word * w.n_words for w in ws) / n_words,
        regular=all(w.regular for w in ws),
        store_bytes_per_word=sum(w.store_bytes_per_word * w.n_words
                                 for w in ws) / n_words,
    )
    return w, _node_tile(graph.topo_order()[0])


def graph_signature(graph: StreamGraph) -> str:
    """Structural identity of the graph for the tuned-plan cache key:
    nodes (program, words, shapes, pipe tiles) + edges. Two graphs with
    the same signature lower identically, so a tuned plan transfers."""
    parts = []
    for n in graph.topo_order():
        p = n.program
        tiles = ",".join("x".join(map(str, s.spec.tile)) for s in p.streams)
        ep = f"+ep{len(n.epilogue.inputs)}" if n.epilogue else ""
        parts.append(f"{n.name}={p.name}{ep}/{p.n_words}w/"
                     f"{'x'.join(map(str, p.out_shape))}"
                     f"{jnp.dtype(p.out_dtype).name}/[{tiles}]")
    for e in graph.edges:
        parts.append(f"{e.label}.{e.dst_input}.{e.prefer}"
                     + (f".r{'x'.join(map(str, e.reshape))}"
                        if e.reshape else ""))
    return ";".join(parts)


# ---------------------------------------------------------------------------
# Fusion legality
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FusionReport:
    """Outcome of the static legality analysis of one edge.

    When ``ok``: ``wpb`` producer words complete each of ``n_blocks``
    output blocks (contiguous, in ordinal order); ``ord_seq[g]`` is the
    block ordinal consumer word ``g`` reads; ``squeeze`` leading unit dims
    of the producer block are dropped to match the consumer tile;
    ``inter_depth`` sizes the in-VMEM intermediate ring.
    """

    ok: bool
    reason: str
    wpb: int = 1
    n_blocks: int = 0
    ord_seq: Tuple[int, ...] = ()
    squeeze: int = 0
    inter_depth: int = 1


def _strides(shape: Sequence[int]) -> List[int]:
    st = [1] * len(shape)
    for i in range(len(shape) - 2, -1, -1):
        st[i] = st[i + 1] * shape[i + 1]
    return st


def _block_offset(idx, block, shape) -> int:
    return sum(int(i) * b * s for i, b, s in zip(idx, block, _strides(shape)))


def _is_contiguous_slab(block, shape) -> bool:
    """Is a block at any grid-aligned start a contiguous row-major slab?
    Leading unit dims are free; after the first non-unit dim every dim must
    be full."""
    dims = list(zip(block, shape))
    i = 0
    while i < len(dims) and dims[i][0] == 1:
        i += 1
    return all(b == d for b, d in dims[i + 1:])


def check_fusion(producer: StreamProgram, consumer: StreamProgram,
                 edge: GraphEdge) -> FusionReport:
    """Static legality of fusing ``edge`` (pure-Python schedule analysis).

    Legal iff the producer's output block schedule *is* the consumer's
    stream schedule: same tile (modulo leading unit dims), blocks completed
    in contiguous equal-length word runs, and the consumer's declared
    request order walks the completion order contiguously (revisits allowed
    — a block may serve several consecutive consumer words, the ring slot
    simply stays live). Anything else returns ``ok=False`` with the
    rationale that ends up in the plan / bench JSON.
    """

    def no(reason: str) -> FusionReport:
        return FusionReport(False, reason)

    try:
        st = consumer.stream(edge.dst_input)
    except KeyError as e:
        return no(str(e))
    if st.gather:
        return no(f"consumer stream {edge.dst_input!r} is an irregular "
                  f"gather (data-dependent addresses)")
    try:
        pout = producer.out_schedule()
    except ScheduleOpaqueError as e:
        return no(f"producer schedule opaque: {e}")
    try:
        creq = consumer.stream_schedule(edge.dst_input)
    except ScheduleOpaqueError as e:
        return no(f"consumer schedule opaque: {e}")

    pblock = tuple(producer.out_block)
    tile = tuple(st.spec.tile)
    squeeze = 0
    while len(pblock) - squeeze > len(tile) and pblock[squeeze] == 1:
        squeeze += 1
    if pblock[squeeze:] != tile:
        return no(f"mismatched block schedules: producer out_block {pblock} "
                  f"vs consumer tile {tile}")
    if jnp.dtype(producer.out_dtype) != jnp.dtype(st.spec.dtype):
        return no(f"dtype mismatch: producer {jnp.dtype(producer.out_dtype).name} "
                  f"vs consumer pipe {jnp.dtype(st.spec.dtype).name}")
    cshape = tuple(edge.reshape) if edge.reshape else tuple(producer.out_shape)
    if len(cshape) != len(tile):
        return no(f"consumer operand rank {len(cshape)} (shape {cshape}) "
                  f"!= stream tile rank {len(tile)}")
    if not _is_contiguous_slab(producer.out_block, producer.out_shape):
        return no(f"producer blocks {pblock} of {producer.out_shape} are "
                  f"not contiguous slabs (cannot be matched through a "
                  f"reshape)")
    if not _is_contiguous_slab(tile, cshape):
        return no(f"consumer tiles {tile} of {cshape} are not contiguous "
                  f"slabs (k-dim must fit one tile)")
    for b in (i for i in producer.inputs if isinstance(i, BlockIn)):
        try:
            Pipe(tile=tuple(b.block), dtype=b.dtype, depth=2)
        except ValueError as e:
            return no(f"producer BlockIn {b.name!r} cannot be promoted to a "
                      f"ring stream: {e}")

    # rank guards: _block_offset zips index against block dims, so a
    # short/long tuple would silently drop schedule components and could
    # legalize a fusion that reads the wrong ring slot
    bad = {len(b) for b in pout} - {len(producer.out_block)}
    if bad:
        return no(f"producer out_index_map rank {sorted(bad)} != out_block "
                  f"rank {len(producer.out_block)}")
    bad = {len(b) for b in creq} - {len(tile)}
    if bad:
        return no(f"consumer stream index rank {sorted(bad)} != tile rank "
                  f"{len(tile)}")

    # producer completion runs: contiguous, equal length, each block once
    runs: List[List[Any]] = []    # [block, start, length]
    for w, blk in enumerate(pout):
        if runs and runs[-1][0] == blk:
            runs[-1][2] += 1
        else:
            runs.append([blk, w, 1])
    ordinal: Dict[Tuple[int, ...], int] = {}
    for o, (blk, _, _) in enumerate(runs):
        if blk in ordinal:
            return no(f"producer revisits output block {blk} "
                      f"non-contiguously")
        ordinal[blk] = o
    lengths = {r[2] for r in runs}
    if len(lengths) != 1:
        return no(f"producer block runs have unequal lengths "
                  f"{sorted(lengths)}")
    wpb, n_blocks = runs[0][2], len(runs)

    # map consumer requests onto producer ordinals through element offsets
    # (offsets survive the edge reshape; block tuples do not)
    p_by_off = {_block_offset(blk, producer.out_block, producer.out_shape): o
                for blk, o in ordinal.items()}
    ord_seq: List[int] = []
    prev = -1
    for g, blk in enumerate(creq):
        off = _block_offset(blk, tile, cshape)
        if off not in p_by_off:
            return no(f"consumer word {g} requests block {blk} (offset "
                      f"{off}) the producer never writes")
        o = p_by_off[off]
        if o not in (prev, prev + 1):
            return no(f"consumer request order is not contiguous "
                      f"non-decreasing (ordinal {prev}->{o} at word {g})")
        prev = o
        ord_seq.append(o)
    if prev != n_blocks - 1:
        return no(f"consumer consumes {prev + 1} of {n_blocks} produced "
                  f"blocks — the rest would never be scheduled")
    return FusionReport(
        ok=True,
        reason=(f"fusable: {n_blocks} blocks x {wpb} producer words each, "
                f"tile {tile}, consumer revisits "
                f"{len(ord_seq) / n_blocks:.1f}x"),
        wpb=wpb,
        n_blocks=n_blocks,
        ord_seq=tuple(ord_seq),
        squeeze=squeeze,
        inter_depth=1 if n_blocks == 1 else 2,
    )


# ---------------------------------------------------------------------------
# Lowering helpers
# ---------------------------------------------------------------------------


def _stream_overrides(program: StreamProgram, depth: int,
                      streams: int) -> Dict[str, Pipe]:
    """Re-size every stream of a program to (depth, streams), clamping
    streams per stream so each DMA keeps whole native row tiles (the
    planner's global choice refined per edge)."""
    return {
        st.name: dataclasses.replace(
            st.spec, depth=depth,
            streams=clamp_streams(st.spec.tile[0], streams, st.spec.dtype))
        for st in program.streams
    }


def _promote_blockin(b: BlockIn, scalar_names: Sequence[str],
                     depth: int) -> Stream:
    """Promote a producer BlockIn to a regular ring stream: the slicer
    replays the declared index map at the (dynamic) producer word."""
    def slicer(ctx, word, _b=b, _names=tuple(scalar_names)):
        scalars = [ctx.ref(n) for n in _names]
        idx = _b.index_map(word, *scalars) if _names else _b.index_map(word)
        sl = tuple(pl.ds(i * d, d) for i, d in zip(idx, _b.block))
        return ctx.ref(_b.name).at[sl]

    return Stream(b.name,
                  Pipe(tile=tuple(b.block), dtype=b.dtype, depth=depth),
                  slicer)


class _InterSlot:
    """The consumer-side endpoint of a fused edge: serves the current
    block from the in-VMEM intermediate ring (``ctx.word`` protocol)."""

    __slots__ = ("_buf", "_slot", "_squeeze")

    def __init__(self, buf, slot, squeeze):
        self._buf = buf
        self._slot = slot
        self._squeeze = squeeze

    def slot(self, word):
        del word    # the ring position tracks the block ordinal, not g
        return self._buf.at[(self._slot,) + (0,) * self._squeeze]


def _wrap_index_map(orig: Callable, lo: int, hi: int, takes_scalars: bool):
    """Adapt a node's index map to the fused kernel's scalar-prefetch
    signature: it sees only its own scalar refs (slice [lo:hi])."""
    if takes_scalars:
        return lambda g, *s: orig(g, *s[lo:hi])
    return lambda g, *s: orig(g)


@dataclasses.dataclass(frozen=True)
class _RingServe:
    """A second consumer edge served from a fused chain's intermediate
    VMEM ring: the producer at chain position ``src_pos`` feeds stage
    ``dst_pos``'s input ``edge.dst_input`` (a Stream or BlockIn) directly
    from the ring of edge ``src_pos -> src_pos+1``. ``slot_seq[w]`` is the
    ring slot holding the needed block at stage-``dst_pos`` word ``w``."""

    edge: GraphEdge
    src_pos: int
    dst_pos: int
    kind: str                     # "stream" | "block"
    slot_seq: Tuple[int, ...]
    squeeze: int


def _blockin_schedule(program: StreamProgram,
                      bi: BlockIn) -> Tuple[Tuple[int, ...], ...]:
    """A BlockIn's block schedule, one index tuple per word (static-only,
    like ``out_schedule``); raises ScheduleOpaqueError when data-dependent."""
    dummies = tuple(_OpaqueScalar()
                    for _ in range(program.num_scalar_prefetch))
    sched = []
    for g in range(program.n_words):
        try:
            idx = bi.index_map(g, *dummies)
            sched.append(tuple(int(i) for i in idx))
        except ScheduleOpaqueError:
            raise
        except Exception as e:   # noqa: BLE001 — map not int-evaluable
            raise ScheduleOpaqueError(
                f"{program.name}: BlockIn {bi.name!r} index_map is not "
                f"statically evaluable at word {g}: "
                f"{type(e).__name__}: {e}") from e
    return tuple(sched)


def _check_ring_serve(progs: Sequence[StreamProgram],
                      reps: Sequence[FusionReport], edge: GraphEdge,
                      src_pos: int, dst_pos: int):
    """Can ``edge`` be served from the fused chain's intermediate ring?

    Legal iff, at every word of the consuming stage, the block the input
    requests *is* the block the chain's demand-driven schedule most
    recently produced into the ring of edge ``src_pos -> src_pos+1`` (so
    the read is always of a live slot, no extra buffering). Returns
    ``(ok, rationale, _RingServe | None)``.
    """
    def no(reason: str):
        return False, reason, None

    P, D = progs[src_pos], progs[dst_pos]
    try:
        st = D.stream(edge.dst_input)
    except KeyError:
        st = None
    if st is not None:
        if st.gather:
            return no(f"input {edge.dst_input!r} is an irregular gather "
                      f"(data-dependent addresses)")
        kind, tile, dt = "stream", tuple(st.spec.tile), \
            jnp.dtype(st.spec.dtype)
        try:
            creq = D.stream_schedule(edge.dst_input)
        except ScheduleOpaqueError as e:
            return no(str(e))
    else:
        bi = next((i for i in D.inputs
                   if isinstance(i, BlockIn) and i.name == edge.dst_input),
                  None)
        if bi is None:
            return no(f"{D.name} has no input {edge.dst_input!r}")
        kind, tile, dt = "block", tuple(bi.block), jnp.dtype(bi.dtype)
        try:
            creq = _blockin_schedule(D, bi)
        except ScheduleOpaqueError as e:
            return no(str(e))

    pblock = tuple(P.out_block)
    squeeze = 0
    while len(pblock) - squeeze > len(tile) and pblock[squeeze] == 1:
        squeeze += 1
    if pblock[squeeze:] != tile:
        return no(f"mismatched block schedules: producer out_block {pblock} "
                  f"vs consumer block {tile}")
    if jnp.dtype(P.out_dtype) != dt:
        return no(f"dtype mismatch: producer "
                  f"{jnp.dtype(P.out_dtype).name} vs consumer {dt.name}")
    cshape = tuple(edge.reshape) if edge.reshape else tuple(P.out_shape)
    if len(cshape) != len(tile):
        return no(f"consumer operand rank {len(cshape)} != block rank "
                  f"{len(tile)}")
    if not _is_contiguous_slab(P.out_block, P.out_shape) \
            or not _is_contiguous_slab(tile, cshape):
        return no("blocks are not contiguous slabs (cannot be matched "
                  "through a reshape)")
    try:
        pout = P.out_schedule()
    except ScheduleOpaqueError as e:
        return no(f"producer schedule opaque: {e}")
    runs: List[List[Any]] = []
    for w, blk in enumerate(pout):
        if runs and runs[-1][0] == blk:
            runs[-1][1] += 1
        else:
            runs.append([blk, 1])
    p_by_off = {
        _block_offset(blk, P.out_block, P.out_shape): o
        for o, (blk, _) in enumerate(runs)}
    depth = reps[src_pos].inter_depth
    slot_seq = []
    for g, blk in enumerate(creq):
        off = _block_offset(blk, tile, cshape)
        if off not in p_by_off:
            return no(f"word {g} requests block {blk} the producer never "
                      f"writes")
        need = p_by_off[off]
        # the ring holds the block the chain most recently produced: walk
        # the demand-driven schedule from the consuming stage back to the
        # producer (block -> last word that completed it, per edge)
        w, j = g, dst_pos - 1
        while True:
            held = reps[j].ord_seq[w]
            if j == src_pos:
                break
            w = (held + 1) * reps[j].wpb - 1
            j -= 1
        if need != held:
            return no(f"input does not track the chain's live intermediate "
                      f"(word {g} needs producer block ordinal {need}, the "
                      f"ring holds {held})")
        slot_seq.append(need % depth)
    rationale = (f"served in-chain from {edge.src!r}'s intermediate VMEM "
                 f"ring (depth {depth}); the shared output never "
                 f"materializes in HBM")
    return True, rationale, _RingServe(edge, src_pos, dst_pos, kind,
                                       tuple(slot_seq), squeeze)


def _compile_chain(cnodes: Sequence[GraphNode], cedges: Sequence[GraphEdge],
                   reps: Sequence[FusionReport],
                   sizings: Sequence[Tuple[int, int]],
                   serves: Sequence[_RingServe], *, interpret: bool):
    """Lower one fused chain ``n0 -> n1 -> ... -> n{k-1}`` into a single
    ``pallas_call``.

    The grid runs the tail stage's words; each stage recursively inlines
    the words of the stage above it on first request (``fresh`` table per
    edge), every intermediate living in its own VMEM ring. ``serves`` are
    additional in-chain consumers fed straight from an intermediate ring.
    Returns ``(fn, operands)`` with ``operands`` the external inputs in
    call order as ``(node_name, input_name)`` pairs. A fused pair is the
    ``k == 2`` special case.
    """
    k = len(cnodes)
    progs = [n.program for n in cnodes]
    scalars = [[i for i in P.inputs if isinstance(i, ScalarIn)]
               for P in progs]
    excl: List[set] = [set() for _ in range(k)]
    for i, e in enumerate(cedges):
        excl[i + 1].add(e.dst_input)
    for s in serves:
        excl[s.dst_pos].add(s.edge.dst_input)
    tensors = [[i for i in P.inputs
                if not isinstance(i, ScalarIn) and i.name not in excl[pos]]
               for pos, P in enumerate(progs)]

    # Whether stage ``pos``'s word ordinal equals the grid index: true for
    # the tail, and propagates up through every edge whose consumer takes
    # exactly one producer word per block in identity order. Grid-aligned
    # stages can have their BlockIns delivered by BlockSpecs (same as the
    # tail, no ring machinery); only schedule-driven stages need rings.
    aligned = [False] * k
    aligned[k - 1] = True
    for pos in range(k - 2, -1, -1):
        r = reps[pos]
        aligned[pos] = (aligned[pos + 1] and r.wpb == 1
                        and tuple(r.ord_seq) == tuple(range(len(r.ord_seq))))

    # streams per stage: non-grid-aligned stages' BlockIns promote to
    # rings (their words are schedule-driven, not grid-driven); aligned
    # stages' BlockIns ride BlockSpecs like the tail's
    overs = [_stream_overrides(P, *sz) for P, sz in zip(progs, sizings)]
    stream_map: List[Dict[str, Stream]] = []
    promoted: List[set] = []
    for pos in range(k):
        m: Dict[str, Stream] = {}
        pr = set()
        scal_names = [s.name for s in scalars[pos]]
        for i in tensors[pos]:
            if isinstance(i, Stream):
                m[i.name] = dataclasses.replace(i, spec=overs[pos][i.name])
            elif pos < k - 1 and not aligned[pos]:
                pr.add(i.name)
                m[i.name] = _promote_blockin(i, scal_names, sizings[pos][0])
        stream_map.append(m)
        promoted.append(pr)
    rings = [{n: (GatherRingPipe if st.gather else RingPipe)(st.spec)
              for n, st in stream_map[pos].items()} for pos in range(k)]

    ord_arrs = [jnp.asarray(r.ord_seq, jnp.int32) for r in reps]
    fresh_arrs = [jnp.asarray(
        [1 if g == 0 or r.ord_seq[g] != r.ord_seq[g - 1] else 0
         for g in range(len(r.ord_seq))], jnp.int32) for r in reps]
    slot_arrs = [jnp.asarray(s.slot_seq, jnp.int32) for s in serves]
    # identity edges (one producer word per block, in order) need none of
    # the dynamic machinery: the producer word IS the consumer word, every
    # word is fresh, and the ring slot is w % depth — resolved statically
    # so the kernel skips the table reads and the (always-true) pl.when
    identity_edge = [r.wpb == 1
                     and tuple(r.ord_seq) == tuple(range(len(r.ord_seq)))
                     for r in reps]
    serve_inline = [s.slot_seq == tuple(
        w % reps[s.src_pos].inter_depth for w in range(len(s.slot_seq)))
        for s in serves]

    n_user_scal = sum(len(s) for s in scalars)
    n_scal = 2 * (k - 1) + n_user_scal + len(serves)
    scal_lo = [2 * (k - 1) + sum(len(scalars[j]) for j in range(pos))
               for pos in range(k)]
    last_lo = scal_lo[k - 1]
    last_hi = last_lo + len(scalars[-1])
    last_takes = progs[-1].num_scalar_prefetch > 0
    serves_by_dst: Dict[int, List[Tuple[int, _RingServe]]] = {}
    for si, s in enumerate(serves):
        serves_by_dst.setdefault(s.dst_pos, []).append((si, s))

    def kernel(*refs):
        it = iter(refs)
        ord_refs, fresh_refs = [], []
        for _ in range(k - 1):
            ord_refs.append(next(it))
            fresh_refs.append(next(it))
        named = [{s.name: next(it) for s in scalars[pos]}
                 for pos in range(k)]
        slot_refs = [next(it) for _ in serves]
        for pos in range(k):
            for i in tensors[pos]:
                named[pos][i.name] = next(it)
        out = next(it)
        scratch = [{s.name: next(it) for s in progs[pos].scratch}
                   for pos in range(k)]
        inters = [next(it) for _ in range(k - 1)]
        bound: List[Dict[str, Any]] = []
        for pos in range(k):
            raw = ProducerCtx(named[pos])
            bm: Dict[str, Any] = {}
            for name, st in stream_map[pos].items():
                buf, sems = next(it), next(it)
                if st.gather:
                    bm[name] = rings[pos][name].bind(
                        buf, sems,
                        lambda word, r, s=st, rw=raw: s.slicer(rw, word, r))
                else:
                    bm[name] = rings[pos][name].bind(
                        buf, sems,
                        lambda word, s=st, rw=raw: s.slicer(rw, word))
            bound.append(bm)
        ring_lists = [list(b.values()) for b in bound]

        def run_stage(pos, w):
            P = progs[pos]
            if pos > 0 and identity_edge[pos - 1]:
                # identity edge: producer word == consumer word, always
                # fresh — inline unconditionally, no table reads
                run_stage(pos - 1, w)
            elif pos > 0:
                rep = reps[pos - 1]
                b = ord_refs[pos - 1][w]

                # inlined upstream stage: block b's words on first request
                @pl.when(fresh_refs[pos - 1][w] == 1)
                def _():
                    for j in range(rep.wpb):
                        run_stage(pos - 1, b * rep.wpb + j)

            acquire(w, P.n_words, ring_lists[pos])
            body = dict(named[pos])
            for name in promoted[pos]:
                body[name] = bound[pos][name].slot(w)
            pipes_view = dict(bound[pos])
            if pos > 0:
                rep = reps[pos - 1]
                b = w if identity_edge[pos - 1] else ord_refs[pos - 1][w]
                pipes_view[cedges[pos - 1].dst_input] = _InterSlot(
                    inters[pos - 1], b % rep.inter_depth, rep.squeeze)
            for si, s in serves_by_dst.get(pos, ()):
                slot = (w % reps[s.src_pos].inter_depth
                        if serve_inline[si] else slot_refs[si][w])
                if s.kind == "stream":
                    pipes_view[s.edge.dst_input] = _InterSlot(
                        inters[s.src_pos], slot, s.squeeze)
                else:
                    body[s.edge.dst_input] = inters[s.src_pos].at[
                        (slot,) + (0,) * s.squeeze]
            if pos == k - 1:
                o = out
            else:
                o = inters[pos].at[
                    (w // reps[pos].wpb) % reps[pos].inter_depth]
            P.consumer(ProgramCtx(w, P.n_words, body, pipes_view, o,
                                  scratch[pos]))
            release(w, P.n_words, ring_lists[pos])

        run_stage(k - 1, pl.program_id(0))

    in_specs = []
    for pos in range(k):
        for i in tensors[pos]:
            if isinstance(i, Stream) or (pos < k - 1 and not aligned[pos]):
                in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
            else:
                in_specs.append(pl.BlockSpec(
                    i.block,
                    _wrap_index_map(i.index_map, scal_lo[pos],
                                    scal_lo[pos] + len(scalars[pos]),
                                    progs[pos].num_scalar_prefetch > 0)))
    scratch_shapes = []
    for P in progs:
        scratch_shapes += [pltpu.VMEM(s.shape, s.dtype) for s in P.scratch]
    for i in range(k - 1):
        scratch_shapes.append(pltpu.VMEM(
            (reps[i].inter_depth, *progs[i].out_block), progs[i].out_dtype))
    for pos in range(k):
        for name in stream_map[pos]:
            scratch_shapes.extend(rings[pos][name].scratch_shapes)

    C = progs[-1]
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_scal,
            grid=(C.n_words,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                C.out_block,
                _wrap_index_map(C.out_index_map, last_lo, last_hi,
                                last_takes)),
            scratch_shapes=scratch_shapes,
        ),
        out_shape=jax.ShapeDtypeStruct(C.out_shape, C.out_dtype),
        interpret=interpret,
    )

    tabs = []
    for i in range(k - 1):
        tabs += [ord_arrs[i], fresh_arrs[i]]

    def fn(*ops):
        return call(*tabs, *ops[:n_user_scal], *slot_arrs,
                    *ops[n_user_scal:])

    operands = ([(cnodes[pos].name, s.name)
                 for pos in range(k) for s in scalars[pos]]
                + [(cnodes[pos].name, i.name)
                   for pos in range(k) for i in tensors[pos]])
    return fn, operands


def _chain_vmem_parts(progs: Sequence[StreamProgram],
                      cedges: Sequence[GraphEdge],
                      reps: Sequence[FusionReport],
                      sizings: Sequence[Tuple[int, int]]) -> Dict[str, int]:
    """Itemized VMEM footprint of a fused chain (for the planner's split
    budget check); a pair is the length-2 case."""
    k = len(progs)
    p_rings = 0
    for pos in range(k - 1):
        over = _stream_overrides(progs[pos], *sizings[pos])
        skip = {cedges[pos - 1].dst_input} if pos > 0 else set()
        p_rings += sum(p.vmem_bytes for n, p in over.items()
                       if n not in skip)
        for b in (i for i in progs[pos].inputs if isinstance(i, BlockIn)):
            p_rings += Pipe(tile=tuple(b.block), dtype=b.dtype,
                            depth=sizings[pos][0]).vmem_bytes
    over_l = _stream_overrides(progs[-1], *sizings[-1])
    c_rings = sum(p.vmem_bytes for n, p in over_l.items()
                  if n != cedges[-1].dst_input)
    inter = sum(reps[i].inter_depth * int(np.prod(progs[i].out_block))
                * jnp.dtype(progs[i].out_dtype).itemsize
                for i in range(k - 1))
    scratch = sum(int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
                  for P in progs for s in P.scratch)
    scratch += int(np.prod(progs[-1].out_block)) \
        * jnp.dtype(progs[-1].out_dtype).itemsize
    return {"producer-rings": int(p_rings), "intermediate-ring": int(inter),
            "consumer-rings": int(c_rings), "scratch": int(scratch)}


# ---------------------------------------------------------------------------
# compile_graph
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EdgePlan:
    """One edge's lowering decision, with the rationale that justifies it
    (fused: legality + VMEM line; staged: why fusion was rejected)."""

    edge: GraphEdge
    mode: str                     # "fused" | "staged"
    rationale: str
    hbm_bytes_saved: float = 0.0


@dataclasses.dataclass(frozen=True)
class GraphPlan:
    """The compiled graph's plan: per-edge decisions, per-node pipe sizing,
    the VMEM budget split, and the MKPipe-style estimate (whose ``skipped``
    lines surface fusion rejections in bench JSON, like ``Plan.skipped``
    does for the kernel planner)."""

    edges: Tuple[EdgePlan, ...]
    sizing: Mapping[str, Tuple[int, int]]       # node -> (depth, streams)
    budgets: Mapping[str, int]                  # node -> vmem share
    estimate: Any                               # pipeline_model.GraphEstimate

    @property
    def fused(self) -> Tuple[EdgePlan, ...]:
        return tuple(e for e in self.edges if e.mode == "fused")

    @property
    def hbm_bytes_saved(self) -> float:
        return sum(e.hbm_bytes_saved for e in self.edges)


@dataclasses.dataclass(frozen=True)
class _Unit:
    """One executable of the compiled graph: a single node's pallas_call
    or a fused pair's."""

    kind: str                     # "node" | "fused"
    out_node: str
    fn: Callable
    operands: Tuple[Tuple[str, str], ...]     # (node, input) per call arg


class CompiledGraph:
    """The compiled multi-kernel program.

    Call it with the graph's external operands in :attr:`arg_names` order
    (``"node.input"`` labels; edge-fed inputs are internal). Returns the
    sink node's output (or a tuple for multi-sink graphs). ``plan`` carries
    the per-edge fused/staged decisions, rationales, and the analytic
    estimate; ``units`` shows the pallas_call structure (one "fused" unit =
    one kernel for a whole fused chain of nodes — the acceptance check
    that an edge really lowered into a single kernel).
    """

    def __init__(self, graph: StreamGraph, policy, plan: GraphPlan,
                 units: Tuple[_Unit, ...], arg_names: Tuple[str, ...],
                 edges_in: Mapping[Tuple[str, str], GraphEdge]):
        self.graph = graph
        self.policy = policy
        self.plan = plan
        self.units = units
        self.arg_names = arg_names
        self._edges_in = dict(edges_in)
        self._sinks = graph.sinks()
        # one jit over the whole unit chain: staged intermediates stay
        # device-resident between pallas_calls and repeat calls replay the
        # compiled program (parity with the jitted repro.ops entrypoints)
        self._jit = jax.jit(self._run)

    def __call__(self, *args):
        if len(args) != len(self.arg_names):
            raise TypeError(
                f"{self.graph.name}: expected {len(self.arg_names)} operands "
                f"{list(self.arg_names)}, got {len(args)}")
        return self._jit(*args)

    def _run(self, *args):
        vals = dict(zip(self.arg_names, args))
        outs: Dict[str, Any] = {}
        for unit in self.units:
            ops = []
            for node, name in unit.operands:
                e = self._edges_in.get((node, name))
                if e is not None:
                    v = outs[e.src]
                    ops.append(v.reshape(e.reshape) if e.reshape else v)
                else:
                    ops.append(vals[f"{node}.{name}"])
            outs[unit.out_node] = unit.fn(*ops)
        res = tuple(outs[s] for s in self._sinks)
        return res[0] if len(res) == 1 else res


def _resolve_node(graph: StreamGraph, node: GraphNode, policy,
                  budget: int, mesh: MeshSpec = SINGLE_DEVICE,
                  shards: int = 1) -> Tuple[Workload, int, int]:
    """Per-node (depth, streams) under the node's split VMEM budget:
    explicit policy ints pass through; "auto"/"measured" resolve through
    the planner (the graph-keyed *measured* path resolves above
    compile_graph, in ``registry.run_graph``, and arrives here as ints).
    ``shards`` localizes the node's word schedule to the mesh's per-shard
    view before planning (local shapes, not global); ``mesh`` keys the
    plan so topologies never share cache entries."""
    w = localize_workload(node_workload(node), shards)
    depth, streams = policy.depth, policy.streams
    if isinstance(depth, str) or isinstance(streams, str):
        try:
            plan = planner.planned_pipe(
                f"graph:{graph.name}/{node.name}", w, _node_tile(node),
                _node_dtype(node), policy.hw,
                stream_options=tuple(policy.stream_options),
                vmem_budget_bytes=budget, mesh=mesh)
            d_plan, s_plan = plan.pipe.depth, plan.pipe.streams
        except PlanError:
            # the split budget is too tight for the latency-hiding depth:
            # degrade to the shallowest ring that fits (double-buffer, else
            # synchronous) — the fused-pair VMEM check downstream is where
            # a genuinely infeasible fusion turns into PlanError/staging
            tile, dt = _node_tile(node), _node_dtype(node)
            d_plan = 2 if Pipe(tile=tile, dtype=dt,
                               depth=2).vmem_bytes <= budget else 1
            s_plan = 1
        depth = d_plan if isinstance(depth, str) else int(depth)
        streams = s_plan if isinstance(streams, str) else int(streams)
    depth, streams = int(depth), int(streams)
    # a ring deeper than the node's word count can never prefetch anything
    # real — the extra slots are dead VMEM charged against the split budget
    # (and dead scratch carried through every grid step)
    if w.n_words > 0:
        depth = max(1, min(depth, w.n_words))
    if policy.mode == "baseline":
        depth = 1
    return w, depth, streams


def _traced_compile_graph(fn):
    """Wrap the graph compile in an obs span carrying the per-edge
    fused/staged decision and rationale (no-op when tracing is off)."""
    @functools.wraps(fn)
    def wrapper(graph, **kw):
        with obs.span("compile_graph", graph=graph.name,
                      nodes=len(graph.nodes)) as sp:
            compiled = fn(graph, **kw)
            n_fused = sum(1 for e in compiled.plan.edges
                          if e.mode == "fused")
            sp.set(
                hbm_bytes_saved=compiled.plan.hbm_bytes_saved,
                fused_edges=n_fused,
                staged_edges=len(compiled.plan.edges) - n_fused,
                edges={f"{e.edge.src}->{e.edge.dst}":
                       {"mode": e.mode, "rationale": e.rationale}
                       for e in compiled.plan.edges})
            return compiled
    return wrapper


@_traced_compile_graph
def compile_graph(graph: StreamGraph, *, policy=None,
                  vmem_budget_bytes: int = _VMEM_BUDGET_BYTES,
                  prefer: Optional[str] = None,
                  sharding=None) -> CompiledGraph:
    """Compile a :class:`StreamGraph`, choosing fused/staged per edge.

    Per edge: "auto" fuses when the static legality analysis passes *and*
    the fused pair fits the planner's split VMEM budget, else stages with
    the rejection line as the edge rationale. ``prefer`` (or
    ``edge.prefer``) = "fused" turns an infeasible fusion into a
    :class:`~repro.core.planner.PlanError` carrying those lines; "staged"
    pins the HBM handoff (the A/B baseline for BENCH_graph.json).

    ``sharding`` makes the compile mesh-aware: pass a
    :class:`~repro.runtime.sharding.ShardingContext` (or a bare
    :class:`~repro.core.meshspec.MeshSpec`), or leave ``None`` to pick up
    the ambient context. Each node's workload is localized to the mesh's
    per-shard word schedule before planning (local shapes, not global) and
    every node plan is cache-keyed by the mesh topology, so a graph
    compiled under a mesh never reuses single-device plans or vice versa.

    Fusion scope: fused edges compose into linear chains — each node may
    have one fused in-edge and one fused out-edge, so a whole decode
    layer lowers into a single kernel. Every prospective fusion is checked
    against the *sum* of the chain members' split VMEM budgets. A
    fused-away producer may feed additional consumers only when each of
    them is served from the chain's intermediate VMEM ring (same chain,
    downstream, block schedule tracking the ring's live slot — see
    ``_check_ring_serve``); otherwise the fusion unwinds to staged with
    the multi-consumer rationale.
    """
    from repro.core.program import current_policy
    policy = policy or current_policy()
    sh = sharding if sharding is not None else policy.mesh
    mesh, shards = resolve_sharding(sh)
    order = graph.topo_order()
    # epilogues fold into the consumer once, up front: everything below
    # (planning, legality, lowering, operand naming) sees the effective
    # program, so epilogues ride every lowering path with no special cases
    nodes = {n.name: (dataclasses.replace(n, program=n.effective_program,
                                          epilogue=None)
                      if n.epilogue else n)
             for n in graph.nodes}
    budgets = planner.split_graph_budget(
        [n.name for n in order], vmem_budget_bytes)

    resolved = {n.name: _resolve_node(graph, nodes[n.name], policy,
                                      budgets[n.name], mesh=mesh,
                                      shards=shards)
                for n in order}

    pos = {n.name: i for i, n in enumerate(order)}

    def _is_stream(dst: str, input_name: str) -> bool:
        try:
            nodes[dst].program.stream(input_name)
            return True
        except KeyError:
            return False

    stream_edges = [e for e in graph.edges
                    if _is_stream(e.dst, e.dst_input)]
    block_edges = [e for e in graph.edges if not _is_stream(e.dst,
                                                            e.dst_input)]

    # -- pass A: greedy chain building over stream edges --------------------
    edge_plans: Dict[GraphEdge, EdgePlan] = {}
    reports: Dict[GraphEdge, FusionReport] = {}
    fused_in: Dict[str, GraphEdge] = {}       # consumer -> fused in-edge
    fused_next: Dict[str, GraphEdge] = {}     # producer -> fused out-edge
    for e in sorted(stream_edges, key=lambda e: (pos[e.dst], pos[e.src])):
        pref = prefer or e.prefer
        P, C = nodes[e.src].program, nodes[e.dst].program
        if pref == "staged":
            edge_plans[e] = EdgePlan(e, "staged", "staged by request")
            continue
        rep = check_fusion(P, C, e)
        reason = None
        if not rep.ok:
            reason = rep.reason
        elif e.src in fused_next:
            reason = (f"producer {e.src!r} already fuses into "
                      f"{fused_next[e.src].dst!r} (one fused out-edge "
                      f"per node)")
        elif e.dst in fused_in:
            reason = (f"consumer {e.dst!r} already has a fused in-edge "
                      f"from {fused_in[e.dst].src!r} (one fused in-edge "
                      f"per node)")
        else:
            # the prospective chain this fusion would create: everything
            # already fused through either endpoint, plus this edge — the
            # whole chain cohabits one kernel, so it is checked against
            # the sum of its members' split budgets
            cnames = [e.src]
            while cnames[0] in fused_in:
                cnames.insert(0, fused_in[cnames[0]].src)
            cnames.append(e.dst)
            while cnames[-1] in fused_next:
                cnames.append(fused_next[cnames[-1]].dst)
            chain_edges = [e if (a, b) == (e.src, e.dst) else fused_in[b]
                           for a, b in zip(cnames, cnames[1:])]
            parts = _chain_vmem_parts(
                [nodes[n].program for n in cnames], chain_edges,
                [rep if ce is e else reports[ce] for ce in chain_edges],
                [resolved[n][1:] for n in cnames])
            fits, line = planner.check_fused_vmem(
                e.label, parts, sum(budgets[n] for n in cnames))
            if fits:
                st = C.stream(e.dst_input)
                saved = (float(np.prod(P.out_shape))
                         * jnp.dtype(P.out_dtype).itemsize
                         + float(C.n_words) * st.spec.word_bytes)
                edge_plans[e] = EdgePlan(e, "fused",
                                         f"{rep.reason}; {line}", saved)
                reports[e] = rep
                fused_in[e.dst] = e
                fused_next[e.src] = e
                continue
            reason = line
        edge_plans[e] = EdgePlan(e, "staged", reason)

    # -- pass B: multi-consumer resolution (ring-serve or unwind) -----------
    def _chains() -> Dict[str, Tuple[Tuple[str, ...], int]]:
        res: Dict[str, Tuple[Tuple[str, ...], int]] = {}
        for tail in (n for n in fused_in if n not in fused_next):
            cn = [tail]
            while cn[0] in fused_in:
                cn.insert(0, fused_in[cn[0]].src)
            for i, n in enumerate(cn):
                res[n] = (tuple(cn), i)
        return res

    serves: Dict[GraphEdge, Tuple[_RingServe, str]] = {}
    while True:
        serves.clear()
        in_chain = _chains()
        conflict = None
        for src, fe in list(fused_next.items()):
            for e2 in graph.edges:
                if e2.src != src or e2 == fe:
                    continue
                pref2 = prefer or e2.prefer
                if pref2 == "staged":
                    conflict = (fe, f"producer {src!r} output has multiple "
                                    f"consumers and edge {e2.label} is "
                                    f"staged by request, so it must "
                                    f"materialize in HBM")
                    break
                sinfo, dinfo = in_chain.get(src), in_chain.get(e2.dst)
                if sinfo and dinfo and sinfo[0] == dinfo[0] \
                        and dinfo[1] > sinfo[1]:
                    cn = sinfo[0]
                    ok, why, serve = _check_ring_serve(
                        [nodes[n].program for n in cn],
                        [reports[fused_in[n]] for n in cn[1:]],
                        e2, sinfo[1], dinfo[1])
                else:
                    ok, why, serve = False, (
                        f"consumer {e2.dst!r} is not downstream of "
                        f"{src!r} in the fused chain"), None
                if ok:
                    serves[e2] = (serve, why)
                else:
                    conflict = (fe, f"producer {src!r} also feeds "
                                    f"{e2.dst}.{e2.dst_input}, which "
                                    f"cannot be served from the chain's "
                                    f"intermediate VMEM ring: {why}")
                    break
            if conflict:
                break
        if conflict is None:
            break
        fe, why = conflict
        edge_plans[fe] = EdgePlan(fe, "staged", why)
        del fused_in[fe.dst]
        del fused_next[fe.src]
        reports.pop(fe, None)

    for e2, (serve, why) in serves.items():
        D = nodes[e2.dst].program
        if serve.kind == "stream":
            load = float(D.n_words) * D.stream(e2.dst_input).spec.word_bytes
        else:
            bi = next(i for i in D.inputs
                      if isinstance(i, BlockIn) and i.name == e2.dst_input)
            load = float(D.n_words) * float(np.prod(bi.block)) \
                * jnp.dtype(bi.dtype).itemsize
        edge_plans[e2] = EdgePlan(e2, "fused", why, load)

    for e2 in block_edges:
        if e2 in edge_plans:
            continue
        if (prefer or e2.prefer) == "staged":
            edge_plans[e2] = EdgePlan(e2, "staged", "staged by request")
            continue
        edge_plans[e2] = EdgePlan(e2, "staged", (
            f"consumer input {e2.dst}.{e2.dst_input} is a block-delivered "
            f"operand (BlockIn), not a pipe stream; its producer is not "
            f"fused away, so the intermediate materializes in HBM and "
            f"Pallas delivers its blocks by grid index"))

    # a demanded fusion that ended staged (anywhere in planning) is a
    # PlanError carrying every per-edge rejection line, like Plan.skipped
    rejected = [
        f"{e.label}: {edge_plans[e].rationale}"
        for e in sorted(graph.edges, key=lambda e: (pos[e.dst], pos[e.src]))
        if edge_plans[e].mode == "staged"
        and (prefer or e.prefer) == "fused"]
    if rejected:
        first = next(e for e in graph.edges
                     if edge_plans[e].mode == "staged"
                     and (prefer or e.prefer) == "fused")
        raise PlanError(resolved[first.dst][0],
                        budgets[first.src] + budgets[first.dst], rejected)

    # -- build executable units (fused chains collapse into one kernel) ----
    # only staged edges feed a materialized operand; a fused edge's
    # intermediate never exists outside the kernel
    edges_in = {(e.dst, e.dst_input): e for e in graph.edges
                if edge_plans[e].mode == "staged"}
    chain_map = _chains()
    units: List[_Unit] = []
    for n in order:
        if n.name in fused_next:
            continue    # emitted inside its chain's fused unit
        if n.name in fused_in:
            cn, _ = chain_map[n.name]
            chain_serves = sorted(
                (s for s, _ in serves.values() if s.edge.dst in cn),
                key=lambda s: (s.dst_pos, s.src_pos))
            fn, operands = _compile_chain(
                [nodes[m] for m in cn], [fused_in[m] for m in cn[1:]],
                [reports[fused_in[m]] for m in cn[1:]],
                [resolved[m][1:] for m in cn], chain_serves,
                interpret=policy.interpret)
            units.append(_Unit("fused", n.name, fn, tuple(operands)))
        else:
            _, d, s = resolved[n.name]
            prog = nodes[n.name].program
            fn = compile_program(
                prog, interpret=policy.interpret,
                pipe_overrides=_stream_overrides(prog, d, s))
            units.append(_Unit(
                "node", n.name, fn,
                tuple((n.name, i.name) for i in prog.inputs)))

    fed_any = {(e.dst, e.dst_input) for e in graph.edges}
    arg_names = tuple(
        f"{n.name}.{i.name}" for n in order
        for i in nodes[n.name].program.inputs
        if (n.name, i.name) not in fed_any)

    # -- analytic estimate (MKPipe stage overlap + per-edge traffic) --------
    # stages follow the *execution* order of the units (a fused chain's
    # members are consecutive even when the declaration topo order
    # interleaves an unrelated node), so estimate_graph's
    # consecutive-stage fusion model lines up with plan.edges; edges not
    # between consecutive stages (ring-served residuals, skip edges)
    # surface through ``extra_edges``
    stage_order: List[GraphNode] = []
    for u in units:
        if u.kind == "fused":
            cn, _ = chain_map[u.out_node]
            stage_order.extend(nodes[m] for m in cn)
        else:
            stage_order.append(nodes[u.out_node])
    stages = []
    for n in stage_order:
        w, d, s = resolved[n.name]
        tile = _node_tile(n)
        pipe = Pipe(tile=tile, dtype=_node_dtype(n), depth=max(d, 1),
                    streams=clamp_streams(tile[0], s, _node_dtype(n)))
        e = fused_in.get(n.name)
        in_edges = [ed for ed in graph.edges if ed.dst == n.name]
        rationale = ""
        if e is not None:
            rationale = edge_plans[e].rationale
        elif in_edges:
            rationale = "; ".join(
                edge_plans[ed].rationale for ed in in_edges)
        prev_name = stages[-1].name if stages else None
        fused_with_prev = e is not None and e.src == prev_name
        saved_load = saved_store = 0.0
        if fused_with_prev:
            P = nodes[e.src].program
            st = nodes[e.dst].program.stream(e.dst_input)
            saved_store = float(np.prod(P.out_shape)) \
                * jnp.dtype(P.out_dtype).itemsize
            saved_load = float(nodes[e.dst].program.n_words) \
                * st.spec.word_bytes
        stages.append(GraphStage(
            name=n.name, workload=w, pipe=pipe,
            fused_with_prev=fused_with_prev,
            saved_load_bytes=saved_load, saved_store_bytes=saved_store,
            rationale=rationale))
    adjacent = {(a.name, b.name)
                for a, b in zip(stage_order, stage_order[1:])}
    extra = tuple(
        EdgeEstimate(edge=e.label, mode=edge_plans[e].mode,
                     hbm_bytes_saved=edge_plans[e].hbm_bytes_saved
                     if edge_plans[e].mode == "fused" else 0.0,
                     rationale=edge_plans[e].rationale)
        for e in graph.edges if (e.src, e.dst) not in adjacent)
    estimate = estimate_graph(tuple(stages), policy.hw, extra_edges=extra)

    plan = GraphPlan(
        edges=tuple(edge_plans[e] for e in graph.edges),
        sizing={k: (d, s) for k, (_, d, s) in resolved.items()},
        budgets=budgets,
        estimate=estimate,
    )
    return CompiledGraph(graph, policy, plan, tuple(units), arg_names,
                         edges_in)
