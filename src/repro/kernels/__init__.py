"""repro.kernels — Pallas TPU kernels implementing the feed-forward (DAE)
design model, one subpackage per hot spot:

  ff_matmul            DAE blocked matmul (regular streams)
  ff_attention         flash attention prefill, GQA, KV ring pipes
  ff_decode_attention  flash-decode vs. long KV caches
  ff_chunk_scan        gated linear-attention scan (Mamba2 / RWKV6)
  ff_gather            irregular row gather (embedding / MoE dispatch)

Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit
wrapper + exact tile-schedule cost model + registration), ref.py (pure-jnp
oracle). Kernels compile on a TPU and run in the Pallas interpreter
elsewhere (``PipePolicy.interpret`` derives the mode from the backend);
``tests/test_tpu_compile.py`` compiles the served path's kernels for a
described v5e.

The StreamProgram/registry contract — what a *new* kernel must provide
----------------------------------------------------------------------

1. **Declare the kernel as a StreamProgram**
   (:mod:`repro.core.program`), never hand-rolled DMA loops. In kernel.py,
   a ``build_program(shapes..., depth, streams) -> StreamProgram`` that
   states:

   * producer stages — one :class:`~repro.core.program.Stream` edge per
     streamed operand, carrying its :class:`~repro.core.pipe.Pipe` spec
     and a ``slicer(ctx, word)`` address stream (``gather=True`` +
     ``slicer(ctx, word, row)`` for irregular per-row gathers). Slicers
     may depend only on the word index and scalar-prefetched inputs —
     the feed-forward restriction, enforced structurally;
   * passive operands — :class:`~repro.core.program.BlockIn` blocked
     inputs and :class:`~repro.core.program.ScalarIn` prefetched scalars;
   * the consumer compute body — ``consumer(ctx)`` reading landed words
     via ``ctx.word(name)`` and carrying state in declared
     :class:`~repro.core.program.ScratchSpec` VMEM.

   :func:`~repro.core.program.compile_program` lowers the graph through
   the shared ring-pipe emitter (:mod:`repro.core.emitter`) into one
   ``pallas_call`` — ring scratch, binding, and the acquire/consume/
   release word schedule are owned there. ``depth == 1`` automatically
   degenerates to the synchronous copy-then-compute baseline.

2. **Expose a policy-driven op and register it**
   (:mod:`repro.kernels.registry`). In ops.py, implement
   ``_apply(*arrays, policy: PipePolicy, **statics)`` (ref-mode dispatch,
   padding, plan resolution via :func:`repro.core.autotune.resolve_call`,
   which covers both the analytic planner and the measured tuner), wrap
   it with
   :func:`repro.core.program.make_entrypoint` (which adds the ``policy=``
   argument, the session ``repro.policy`` context, and the deprecated
   keyword shims), and call
   :func:`~repro.kernels.registry.register_kernel` with the op, a short
   ``alias`` (becomes ``repro.ops.<alias>``), the pure-jnp oracle, the
   KernelCost model, a Workload builder (shapes -> (core.Workload, tile)),
   the ``program`` builder at the smoke shape point, tiny smoke inputs,
   and a benchmark shape point. The benchmark harness
   (benchmarks/kernel_bench.py, ``benchmarks/run.py --smoke``),
   ``repro.ops``, and the registry tests enumerate the registry — a new
   kernel is its subpackage plus the one ``register_kernel`` call, then
   add the ops module path to ``registry._BUILTIN``.

3. **Support planner auto-sizing and measured autotuning.** ``_apply``
   must resolve the policy through
   :func:`repro.core.autotune.resolve_call` with the op's Workload: the
   roofline model picks (depth, streams) for ``"auto"`` per call-site
   shape against the policy's hardware model (cached on (op, shape,
   dtype, hw)), and ``mode="autotune"`` / ``"measured"`` sizing searches
   the declared ``tile_options`` x depth x streams space empirically via
   a call-site ``runner`` closure, persisting tuned plans to the on-disk
   plan cache. Kernels with tunable tiles declare ``tile_options`` in
   their registry entry and accept the corresponding kwargs in ``_apply``
   and ``program(tile=...)``.
"""

from repro.core.emitter import cdiv, pad_to
from repro.kernels.registry import (
    KernelCost,
    KernelSpec,
    all_kernels,
    get_kernel,
    kernel_names,
    register_kernel,
    run_smoke,
)

__all__ = [
    "KernelCost",
    "KernelSpec",
    "all_kernels",
    "cdiv",
    "get_kernel",
    "kernel_names",
    "pad_to",
    "register_kernel",
    "run_smoke",
]
