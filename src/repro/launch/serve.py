"""Serving driver: paged-KV continuous batching vs. padded lockstep.

Two schedulers over the same Poisson request trace:

  * **lockstep** (the baseline this PR replaces): FIFO static batches —
    wait until ``n_slots`` requests have arrived, right-pad prompts into
    one prefill, then decode the whole batch in lockstep over a dense
    right-padded KV cache ``[L, B, S_max, KVH, hd]``. Rows retire at
    EOS / their token budget (and stop emitting), but their cache stays
    allocated and the batch keeps stepping until its *slowest* row
    finishes — the head-of-line blocking and ``B x S_max`` padding waste
    the paged path removes.
  * **paged** (continuous batching): requests are admitted the moment a
    decode slot and enough KV blocks are free, prefill is interleaved
    with decode (per-request, bucketed to power-of-2 prompt lengths so
    traces stay few), every step retires finished slots and recycles
    their blocks (:class:`~repro.runtime.paged_kv.PagedKVCache`). Decode
    attention reads KV through the block table as the fused
    ``paged_decode_attention`` StreamGraph (gather producer →
    online-softmax consumer).

Both replay the same trace on a virtual clock advanced by measured step
wall-times (discrete-event replay: no sleeping, real compute costs), and
both decode greedily with identical math — with ``--impl ff`` the dense
path's KV tile is pinned to the page size (``cfg.decode_block_kv``), so
paged decode is *bitwise-identical* to the contiguous path and the two
schedulers emit token-for-token equal sequences.

Example (CPU smoke):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1_5_0p5b --smoke \
      --requests 8 --max-new 16
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ARCH_IDS, get_config, smoke_config
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.runtime import sharding as shlib
from repro.runtime.paged_kv import OutOfBlocks, PagedKVCache


def pad_cache_to(cache, s_from: int, s_max: int, seq_dims):
    """Right-pad the declared sequence axes of a cache pytree.

    ``seq_dims`` names the sequence axis: an int applied to every leaf, or
    a pytree matching ``cache`` whose leaves are an axis index or None
    (None = leaf has no sequence axis, left untouched). Only the declared
    axis is padded — a head/layer dim that happens to equal ``s_from`` is
    never touched.
    """
    if seq_dims is None:
        raise TypeError("pad_cache_to requires seq_dims (an int axis or a "
                        "per-leaf pytree of axes); padding by shape match "
                        "corrupts non-sequence dims that equal s_from")
    if s_from == s_max:
        return cache

    def pad(x, axis):
        if axis is None:
            return x
        if x.shape[axis] != s_from:
            raise ValueError(
                f"cache leaf {x.shape} has {x.shape[axis]} at declared seq "
                f"axis {axis}, expected {s_from}")
        pads = [(0, 0)] * x.ndim
        pads[axis] = (0, s_max - s_from)
        return jnp.pad(x, pads)

    if isinstance(seq_dims, int):
        return jax.tree.map(lambda x: pad(x, seq_dims), cache)
    return jax.tree.map(pad, cache, seq_dims)


# ---------------------------------------------------------------------------
# Load generator
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Request:
    rid: int
    arrival: float          # seconds on the trace clock
    prompt: np.ndarray      # [len] int32
    max_new: int


def make_requests(n: int, *, prompt_len: int, max_new: int, rate: float,
                  vocab: int, seed: int = 0) -> List[Request]:
    """Poisson arrivals (rate req/s), prompt lengths uniform in
    [4, prompt_len], per-request token budgets uniform in
    [max(1, max_new//2), max_new] (the mixed-length traffic that makes
    lockstep's straggler barrier visible)."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate, size=n) if rate > 0 else np.zeros(n)
    arrivals = np.cumsum(gaps)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(4, prompt_len + 1))
        prompt = rng.integers(1, vocab, size=plen).astype(np.int32)
        budget = int(rng.integers(max(1, max_new // 2), max_new + 1))
        reqs.append(Request(i, float(arrivals[i]), prompt, budget))
    return reqs


def _bucket(n: int, lo: int = 8) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def _summarize(emits: Dict[int, List[float]], requests: List[Request],
               util_samples: List[float], prefill_s: float, decode_s: float,
               steps: int) -> Dict[str, object]:
    """Per-token latency (first token measured from arrival, later tokens
    from the previous emit), throughput over the whole trace."""
    lat = []
    t_end = 0.0
    total = 0
    for r in requests:
        prev = r.arrival
        for t in emits.get(r.rid, []):
            lat.append(t - prev)
            prev = t
            t_end = max(t_end, t)
            total += 1
    lat_ms = np.array(sorted(lat)) * 1e3
    return {
        "tokens": total,
        "tokens_per_s": total / max(t_end, 1e-9),
        "p50_ms": float(np.percentile(lat_ms, 50)) if total else None,
        "p99_ms": float(np.percentile(lat_ms, 99)) if total else None,
        "kv_util": float(np.mean(util_samples)) if util_samples else None,
        "prefill_s": prefill_s,
        "decode_s": decode_s,
        "decode_steps": steps,
        "per_request_tokens": [len(emits.get(r.rid, [])) for r in requests],
    }


# ---------------------------------------------------------------------------
# Scheduler 1: padded lockstep (the baseline)
# ---------------------------------------------------------------------------


def run_lockstep(model, params, cfg, requests: List[Request], *,
                 n_slots: int, page: int, eos_id: Optional[int],
                 policy) -> Dict[str, object]:
    """Static FIFO batches over a dense right-padded cache."""
    prefill = jax.jit(steps_lib.make_prefill_step(model, policy=policy))
    decode = jax.jit(steps_lib.make_decode_step(model, policy=policy))
    p_max = _bucket(max(len(r.prompt) for r in requests))
    total_max = max(len(r.prompt) + r.max_new for r in requests)
    s_max = max(-(-total_max // page) * page, -(-p_max // page) * page)

    # warm the two traces outside the clock
    wtoks = jnp.zeros((n_slots, p_max), jnp.int32)
    _, wcache = prefill(params, {"tokens": wtoks})
    wcache = pad_cache_to(wcache, p_max, s_max, 2)
    jax.block_until_ready(decode(
        params, {"token": jnp.zeros((n_slots,), jnp.int32),
                 "lengths": jnp.zeros((n_slots,), jnp.int32)}, wcache))

    clock = 0.0
    prefill_s = decode_s = 0.0
    steps = 0
    emits: Dict[int, List[float]] = {}
    utils: List[float] = []
    # live telemetry: one enabled check per run, then per-token histogram
    # observes of exactly the quantity _summarize computes post hoc (first
    # token from arrival, later tokens from the previous emit)
    telemetry = obs.enabled()
    hist = (obs.histogram("serve_token_latency_seconds",
                          "per-token emit latency (live)",
                          scheduler="lockstep") if telemetry else None)
    prev_emit: Dict[int, float] = {}
    queue = deque(sorted(requests, key=lambda r: r.arrival))
    while queue:
        batch = [queue.popleft() for _ in range(min(n_slots, len(queue)))]
        # static batching: the batch launches when its LAST request arrives
        clock = max(clock, max(r.arrival for r in batch))
        toks = np.zeros((n_slots, p_max), np.int32)
        lens = np.zeros((n_slots,), np.int32)
        for i, r in enumerate(batch):
            toks[i, :len(r.prompt)] = r.prompt
            lens[i] = len(r.prompt)

        t0 = time.perf_counter()
        with obs.span("serve_prefill", scheduler="lockstep",
                      batch=len(batch)):
            _, cache = prefill(params, {"tokens": jnp.asarray(toks)})
            cache = pad_cache_to(cache, p_max, s_max, 2)
            jax.block_until_ready(cache)
        dt = time.perf_counter() - t0
        clock += dt
        prefill_s += dt

        # re-feed each row's last prompt token at position len-1: the cache
        # write is idempotent (same k/v), and the step's logits are exactly
        # the model's next-token prediction at the prompt end
        cur = jnp.asarray(toks[np.arange(n_slots), np.maximum(lens - 1, 0)])
        lengths = jnp.asarray(np.maximum(lens - 1, 0))
        produced = np.zeros(n_slots, np.int64)
        active = np.array([i < len(batch) for i in range(n_slots)])
        # lockstep's cost: the batch steps until its slowest row finishes
        while active.any():
            t0 = time.perf_counter()
            with obs.span("serve_decode_step", scheduler="lockstep"):
                nxt, _, cache = decode(
                    params, {"token": cur, "lengths": lengths}, cache)
                nxt_np = np.asarray(nxt)
            dt = time.perf_counter() - t0
            clock += dt
            decode_s += dt
            steps += 1
            for i in np.nonzero(active)[0]:
                r = batch[i]
                tok = int(nxt_np[i])
                emits.setdefault(r.rid, []).append(clock)
                if telemetry:
                    hist.observe(clock - prev_emit.get(r.rid, r.arrival))
                    prev_emit[r.rid] = clock
                produced[i] += 1
                if tok == eos_id or produced[i] >= r.max_new:
                    active[i] = False      # retired; cache stays allocated
            cur = nxt
            lengths = lengths + 1
            live = sum(lens[i] + produced[i] for i in range(len(batch)))
            utils.append(live / (n_slots * s_max))
    return _summarize(emits, requests, utils, prefill_s, decode_s, steps)


# ---------------------------------------------------------------------------
# Scheduler 2: paged continuous batching
# ---------------------------------------------------------------------------


def run_continuous(model, params, cfg, requests: List[Request], *,
                   n_slots: int, page: int, eos_id: Optional[int],
                   policy, pool_blocks: Optional[int] = None
                   ) -> Dict[str, object]:
    """Continuous batching over a :class:`PagedKVCache`: admit on arrival
    into free slots, retire per step, recycle blocks."""
    prefill = jax.jit(steps_lib.make_prefill_step(model, policy=policy))
    decode = jax.jit(steps_lib.make_decode_step(model, policy=policy))
    n_pages_max = max(-(-(len(r.prompt) + r.max_new) // page)
                      for r in requests)
    if pool_blocks is None:
        pool_blocks = n_slots * n_pages_max
    # a single empty-pool admission must always fit, else admission stalls
    pool_blocks = max(pool_blocks, n_pages_max)

    def fresh_cache():
        return PagedKVCache(
            n_layers=cfg.n_layers, n_blocks=pool_blocks, page=page,
            kv_heads=cfg.n_kv_heads, head_dim=cfg.hd, n_slots=n_slots,
            n_pages_max=n_pages_max, dtype=cfg.cdtype)

    buckets = sorted({_bucket(len(r.prompt)) for r in requests})

    # warm every trace (per-bucket prefill + admission scatter, decode)
    warm = fresh_cache()
    for i, pb in enumerate(buckets):
        _, wc = prefill(params, {"tokens": jnp.zeros((1, pb), jnp.int32)})
        warm.admit(i % n_slots, wc["k"][:, 0], wc["v"][:, 0], 4, 4)
        warm.retire(i % n_slots)
    jax.block_until_ready(decode(
        params, {"token": jnp.zeros((n_slots,), jnp.int32),
                 "lengths": jnp.zeros((n_slots,), jnp.int32)},
        warm.cache_view()))

    kv = fresh_cache()
    clock = 0.0
    prefill_s = decode_s = 0.0
    steps = 0
    emits: Dict[int, List[float]] = {}
    utils: List[float] = []
    utils_pool: List[float] = []
    telemetry = obs.enabled()
    hist = (obs.histogram("serve_token_latency_seconds",
                          "per-token emit latency (live)",
                          scheduler="paged") if telemetry else None)
    kv_gauge = (obs.gauge("serve_kv_utilization",
                          "paged KV pool utilization vs allocated blocks")
                if telemetry else None)
    prev_emit: Dict[int, float] = {}
    pending = deque(sorted(requests, key=lambda r: r.arrival))
    slot_req: List[Optional[Request]] = [None] * n_slots
    cur = np.zeros(n_slots, np.int32)
    produced = np.zeros(n_slots, np.int64)

    def active_mask():
        return np.array([r is not None for r in slot_req])

    while pending or active_mask().any():
        # admit arrived requests into free slots while blocks allow
        while pending and pending[0].arrival <= clock:
            free = [i for i, r in enumerate(slot_req) if r is None]
            if not free:
                break
            r = pending[0]
            need = -(-(len(r.prompt) + r.max_new) // page)
            if need > kv.allocator.n_free:
                break                       # wait for a retirement
            pending.popleft()
            slot = free[0]
            plen = len(r.prompt)
            pb = _bucket(plen)
            toks = np.zeros((1, pb), np.int32)
            toks[0, :plen] = r.prompt
            t0 = time.perf_counter()
            with obs.span("serve_admit", scheduler="paged", rid=r.rid,
                          slot=slot, prompt_len=plen):
                _, pc = prefill(params, {"tokens": jnp.asarray(toks)})
                kv.admit(slot, pc["k"][:, 0], pc["v"][:, 0], plen,
                         plen + r.max_new)
                jax.block_until_ready(kv.pool)
            dt = time.perf_counter() - t0
            clock += dt
            prefill_s += dt
            slot_req[slot] = r
            cur[slot] = int(r.prompt[-1])
            produced[slot] = 0
            # first decode step re-feeds the last prompt token at
            # position plen-1 (idempotent cache write, exact logits)
            kv.lengths[slot] = plen - 1

        act = active_mask()
        if not act.any():
            if pending:
                clock = max(clock, pending[0].arrival)
                continue
            break

        t0 = time.perf_counter()
        with obs.span("serve_decode_step", scheduler="paged"):
            nxt, _, new_caches = decode(
                params, {"token": jnp.asarray(cur),
                         "lengths": jnp.asarray(kv.lengths)},
                kv.cache_view())
            nxt_np = np.asarray(nxt)
        dt = time.perf_counter() - t0
        clock += dt
        decode_s += dt
        steps += 1
        kv.update_pool(new_caches["kv_pool"])
        kv.append(act.astype(np.int32))
        for slot in np.nonzero(act)[0]:
            r = slot_req[slot]
            tok = int(nxt_np[slot])
            emits.setdefault(r.rid, []).append(clock)
            if telemetry:
                hist.observe(clock - prev_emit.get(r.rid, r.arrival))
                prev_emit[r.rid] = clock
            produced[slot] += 1
            if tok == eos_id or produced[slot] >= r.max_new:
                with obs.span("serve_retire", scheduler="paged",
                              rid=r.rid, slot=int(slot)):
                    kv.retire(slot)         # blocks recycle immediately
                slot_req[slot] = None
            else:
                cur[slot] = tok
        u = kv.utilization()
        utils.append(u["util_vs_allocated"])
        utils_pool.append(u["util_vs_pool"])
        if telemetry:
            kv_gauge.set(u["util_vs_allocated"])
    out = _summarize(emits, requests, utils, prefill_s, decode_s, steps)
    out["kv_util_pool"] = (float(np.mean(utils_pool))
                           if utils_pool else None)
    out["pool_blocks"] = pool_blocks
    out["page"] = page
    return out


# ---------------------------------------------------------------------------
# Bitwise parity probe (paged vs. contiguous decode on identical state)
# ---------------------------------------------------------------------------


def decode_parity_probe(model, params, cfg, policy, *, page: int,
                        n_steps: int = 3, seed: int = 0) -> float:
    """Run ``n_steps`` greedy decode steps from the same prefill state
    through (a) the dense right-padded cache and (b) the paged pool, and
    return the max abs logits difference (0.0 = bitwise identical).

    Requires the model's dense ff path to be pinned to the page tile
    (``cfg.decode_block_kv == page``) for ff impls; xla impls match because
    both views present the same ``[B, n_pages*page]`` KV extent.
    """
    rng = np.random.default_rng(seed)
    b = 2
    lens = np.array([11, 24], np.int32)
    p_max = int(lens.max())
    toks = np.zeros((b, p_max), np.int32)
    for i in range(b):
        toks[i, :lens[i]] = rng.integers(1, cfg.vocab, size=lens[i])
    n_pages = -(-(p_max + n_steps) // page)
    s_max = n_pages * page

    prefill = jax.jit(steps_lib.make_prefill_step(model, policy=policy))
    decode = jax.jit(steps_lib.make_decode_step(model, policy=policy))

    _, dense = prefill(params, {"tokens": jnp.asarray(toks)})
    dense_cache = pad_cache_to(dense, p_max, s_max, 2)

    kv = PagedKVCache(
        n_layers=cfg.n_layers, n_blocks=b * n_pages + 2, page=page,
        kv_heads=cfg.n_kv_heads, head_dim=cfg.hd, n_slots=b,
        n_pages_max=n_pages, dtype=cfg.cdtype)
    for i in range(b):
        kv.admit(i, dense["k"][:, i], dense["v"][:, i], int(lens[i]),
                 s_max)

    cur_d = jnp.asarray(toks[np.arange(b), lens - 1])
    cur_p = cur_d
    len_d = jnp.asarray(lens - 1)
    kv.lengths[:] = lens - 1
    max_diff = 0.0
    for _ in range(n_steps):
        nd, logits_d, dense_cache = decode(
            params, {"token": cur_d, "lengths": len_d}, dense_cache)
        np_, logits_p, new_caches = decode(
            params, {"token": cur_p, "lengths": jnp.asarray(kv.lengths)},
            kv.cache_view())
        kv.update_pool(new_caches["kv_pool"])
        kv.append(np.ones(b, np.int32))
        max_diff = max(max_diff, float(np.max(np.abs(
            np.asarray(logits_d) - np.asarray(logits_p)))))
        cur_d, cur_p = nd, np_
        len_d = len_d + 1
    return max_diff


# ---------------------------------------------------------------------------
# Benchmark entry (BENCH_serve.json)
# ---------------------------------------------------------------------------


def serve_bench(args) -> Dict[str, object]:
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.family == "encdec":
        raise SystemExit("serve driver targets decoder-only archs")
    if args.impl != "cfg":
        cfg = cfg.replace(attn_impl=args.impl)
    if cfg.attn_impl == "ff":
        # pin the dense path's KV tile to the page so lockstep decode is
        # bitwise-identical to the paged stream graph
        cfg = cfg.replace(decode_block_kv=args.page)
    if getattr(args, "layer_graph", False):
        # route dense-cache decode steps through the whole-layer
        # decode_layer StreamGraph (one planned multi-kernel program per
        # layer; the paged scheduler keeps its gather-attention graph)
        cfg = cfg.replace(layer_graph=True)
    from repro.core.program import PipePolicy
    policy = PipePolicy(mode=args.policy_mode)
    from repro.models import build_model
    model = build_model(cfg)
    # GSPMD cannot partition a compiled (Mosaic) kernel, so with compiled
    # kernels serving runs on one device even on a multi-chip host;
    # interpreted kernels are plain HLO and shard over the host mesh
    mesh = make_host_mesh(devices=None if policy.interpret
                          else jax.devices()[:1])

    requests = make_requests(
        args.requests, prompt_len=args.prompt_len, max_new=args.max_new,
        rate=args.rate, vocab=cfg.vocab, seed=args.seed)

    # plan-service hooks: --plan-db points the autotune lookup chain at a
    # release PlanDB (pre-warmed here so the first resolution is a dict
    # hit, not file IO); --record-profile captures this run's traffic for
    # an offline sweep (see repro.plans)
    import contextlib

    from repro.core import autotune
    plan_service: Dict[str, object] = {}
    # --metrics-json opts into live telemetry: per-token latency
    # histograms and the kv gauge observe only while obs is enabled
    metrics_path = getattr(args, "metrics_json", None)
    trace_state = None
    if metrics_path and not obs.enabled():
        trace_state = obs.enable()      # in-memory ring, no JSONL sink
    with contextlib.ExitStack() as stack:
        if getattr(args, "plan_db", None):
            from repro.plans import plandb as plandb_lib
            stack.enter_context(autotune.tuning_config(plan_db=args.plan_db))
            plan_service["prewarm"] = plandb_lib.prewarm(args.plan_db)
            print(f"# plan-db {args.plan_db}: "
                  f"{plan_service['prewarm']['records_in_namespace']} "
                  f"records for namespace "
                  f"{plan_service['prewarm']['namespace']}")
        profile = None
        if getattr(args, "record_profile", None):
            from repro.plans import record_traffic
            profile = stack.enter_context(
                record_traffic(args.record_profile))

        with shlib.use_sharding(mesh,
                                overrides=dict(cfg.rule_overrides or {})):
            params = model.init(jax.random.key(args.seed))
            lockstep = run_lockstep(
                model, params, cfg, requests, n_slots=args.slots,
                page=args.page, eos_id=args.eos_id, policy=policy)
            paged = run_continuous(
                model, params, cfg, requests, n_slots=args.slots,
                page=args.page, eos_id=args.eos_id, policy=policy,
                pool_blocks=args.pool_blocks)
            bitwise = decode_parity_probe(model, params, cfg, policy,
                                          page=args.page)
        if profile is not None:
            plan_service["recorded"] = {
                "path": args.record_profile,
                "buckets": len(profile),
                "observations": profile.total_count}
        if getattr(args, "plan_db", None) or profile is not None:
            plan_service["stats"] = autotune.plan_stats_snapshot()

    result = {
        "arch": args.arch,
        "mesh": dict(mesh.shape),
        "smoke": bool(args.smoke),
        "impl": cfg.attn_impl,
        "policy_mode": args.policy_mode,
        "requests": args.requests,
        "slots": args.slots,
        "page": args.page,
        "rate_req_per_s": args.rate,
        "prompt_len": args.prompt_len,
        "max_new": args.max_new,
        "lockstep": lockstep,
        "paged": paged,
        "speedup_tokens_per_s": (paged["tokens_per_s"]
                                 / max(lockstep["tokens_per_s"], 1e-9)),
        "p99_ratio": (lockstep["p99_ms"] / max(paged["p99_ms"], 1e-9)
                      if lockstep["p99_ms"] and paged["p99_ms"] else None),
        "bitwise_max_abs_diff": bitwise,
        "bitwise_identical": bitwise == 0.0,
        "token_count_parity": lockstep["tokens"] == paged["tokens"],
    }
    if plan_service:
        result["plan_service"] = plan_service
        if "recorded" in plan_service:
            rec = plan_service["recorded"]
            print(f"# recorded traffic profile: {rec['buckets']} buckets / "
                  f"{rec['observations']} observations -> {rec['path']}")
    if metrics_path:
        import json
        with open(metrics_path, "w") as f:
            json.dump(obs.metrics_snapshot(), f, indent=2, sort_keys=True)
        result["metrics_json"] = metrics_path
        print(f"# wrote live metrics snapshot -> {metrics_path}")
        if trace_state is not None:
            obs.restore(trace_state)
    return result


def add_serve_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen1_5_0p5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--page", type=int, default=16,
                    help="KV block (page) size in tokens; also pins the ff "
                         "dense path's block_kv for bitwise parity")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode slots (batch rows) for both schedulers")
    ap.add_argument("--rate", type=float, default=10.0,
                    help="Poisson arrival rate, requests/s (0 = all at t=0)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="retire a slot when it emits this token")
    ap.add_argument("--pool-blocks", type=int, default=None,
                    help="paged pool size in blocks (default: slots x "
                         "max pages per request)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--impl", choices=("ff", "xla", "cfg"), default="ff",
                    help="attention implementation: ff = repro.ops stream "
                         "kernels (default), xla = HLO reference, cfg = "
                         "whatever the arch config pins")
    ap.add_argument("--layer-graph", action="store_true",
                    help="fuse each dense-cache decode step into the "
                         "whole-layer decode_layer StreamGraph (QKV -> "
                         "attention -> out-proj -> MLP with residual/norm "
                         "epilogues, jointly planned)")
    ap.add_argument("--policy-mode", choices=("ff", "baseline", "autotune"),
                    default="ff",
                    help="session PipePolicy mode installed around the "
                         "prefill/decode step bodies (mesh-tagged)")
    ap.add_argument("--record-profile", default=None, metavar="PATH",
                    help="record every plan resolution into a "
                         "TrafficProfile JSON at PATH (the input of "
                         "`python -m repro.plans sweep`)")
    ap.add_argument("--plan-db", default=None, metavar="PATH",
                    help="release PlanDB consulted after the per-host plan "
                         "cache and before measuring (pre-warmed at "
                         "startup; overrides $REPRO_PLAN_DB)")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="enable live telemetry (per-token latency "
                         "histograms, plan-source counters) and write "
                         "obs.metrics_snapshot() to PATH at exit")


def main(argv=None):
    ap = argparse.ArgumentParser()
    add_serve_args(ap)
    ap.add_argument("--json", default=None,
                    help="write the benchmark dict to this path")
    args = ap.parse_args(argv)
    enable_compile_cache()
    result = serve_bench(args)
    ls, pg = result["lockstep"], result["paged"]
    print(f"impl={result['impl']} policy={args.policy_mode} "
          f"mesh={result['mesh']} "
          f"requests={args.requests} slots={args.slots} page={args.page}")
    for name, m in (("lockstep", ls), ("paged", pg)):
        print(f"{name:9s}: {m['tokens']} tokens, "
              f"{m['tokens_per_s']:.2f} tok/s, "
              f"p50 {m['p50_ms']:.0f} ms, p99 {m['p99_ms']:.0f} ms, "
              f"kv util {m['kv_util']:.2f}, "
              f"decode {m['decode_s']:.1f} s / {m['decode_steps']} steps")
    print(f"speedup x{result['speedup_tokens_per_s']:.2f} tok/s, "
          f"p99 x{result['p99_ratio']:.2f}, "
          f"bitwise diff {result['bitwise_max_abs_diff']:.1e}")
    if args.json:
        import json
        with open(args.json, "w") as f:
            json.dump(result, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")
    return result


if __name__ == "__main__":
    main()
