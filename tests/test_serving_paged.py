"""Paged-KV serving correctness: the paged decode path must be
token-for-token (and, against the contiguous ff path, *bitwise*) identical
to the dense-cache reference, and the block allocator must recycle without
leaks or external fragmentation.

Layers covered:
  * BlockAllocator unit tests — LIFO recycling, atomic out-of-blocks
    failure, no external fragmentation after random churn.
  * gather_indices layout — the row stream decodes back to the exact
    (block, k/v, offset, head) coordinates.
  * the registered ``paged_decode_attention`` StreamGraph vs. its XLA
    oracle (fused edge) and kernel-level bitwise parity vs. the contiguous
    ``ff_decode_attention`` at ``block_kv == ppw * page`` (``ppw`` pages
    per grid word, :func:`~repro.runtime.paged_kv.pages_per_word`); the
    graph's grid at the benchmark cells' shapes.
  * model-level decode: dense cache vs. paged pool, xla and ff impls,
    bitwise logits equality over multiple steps (mixed lengths).
  * scheduler semantics: lockstep terminates in exactly
    ``max(remaining)`` steps per batch, EOS retires early and recycles
    blocks, the end-to-end serve bench keeps token parity.
  * ``pad_cache_to`` pads only declared sequence axes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import smoke_config
from repro.core.program import PipePolicy
from repro.launch import serve as serve_lib
from repro.launch import steps as steps_lib
from repro.runtime.paged_kv import (BlockAllocator, OutOfBlocks,
                                    PagedKVCache, build_paged_decode_graph,
                                    gather_indices, pack_pool,
                                    pages_per_word, paged_decode_attention,
                                    paged_decode_attention_ref, pool_lanes,
                                    unpack_pool)

KEY = jax.random.key(11)
ARCH = "qwen1_5_0p5b"
PAGE = 8


# ---------------------------------------------------------------------------
# Allocator
# ---------------------------------------------------------------------------


def test_allocator_recycling():
    a = BlockAllocator(8)
    first = a.alloc(3)
    assert a.n_free == 5
    a.free(first)
    assert a.n_free == 8
    # LIFO: the most recently freed blocks are reissued first
    again = a.alloc(3)
    assert again == list(reversed(first))


def test_allocator_out_of_blocks_is_atomic():
    a = BlockAllocator(4)
    a.alloc(3)
    with pytest.raises(OutOfBlocks):
        a.alloc(2)
    # the failed allocation must not leak any blocks
    assert a.n_free == 1
    assert a.alloc(1) is not None


def test_allocator_no_external_fragmentation():
    """After arbitrary alloc/free churn, ANY request up to n_free must
    succeed — a free-list allocator over fixed-size blocks cannot
    externally fragment (waste is bounded by page-1 rows per request)."""
    rng = np.random.default_rng(0)
    a = BlockAllocator(32)
    held = []
    for _ in range(200):
        if held and rng.random() < 0.5:
            a.free(held.pop(rng.integers(len(held))))
        else:
            n = int(rng.integers(1, 5))
            if n <= a.n_free:
                held.append(a.alloc(n))
    if a.n_free:
        got = a.alloc(a.n_free)
        assert len(got) == len(set(got))
        assert a.n_free == 0


# ---------------------------------------------------------------------------
# Index layout
# ---------------------------------------------------------------------------


def test_gather_indices_layout():
    j, nb = 3, 6
    bt = jnp.array([[5, 2], [0, nb]], jnp.int32)   # second row: sentinel
    idx = np.asarray(gather_indices(bt, lane_words=j, n_blocks=nb))
    idx = idx.reshape(2, j, 2)                     # [B, J, npg]
    for b, w, pg in np.ndindex(2, j, 2):
        blk = min(int(bt[b, pg]), nb - 1)          # sentinel clips
        assert idx[b, w, pg] == blk * j + w
    # three pages per word: each row pads to 3 with clipped sentinels
    idx = np.asarray(gather_indices(bt, lane_words=j, n_blocks=nb, ppw=3))
    idx = idx.reshape(2, j, 3)
    for b, w, pg in np.ndindex(2, j, 3):
        blk = min(int(bt[b, pg]), nb - 1) if pg < 2 else nb - 1
        assert idx[b, w, pg] == blk * j + w


def test_pool_pages_are_whole_lane_words():
    """hd-64 heads pair up in 128-lane words: one gathered page block holds
    exactly its two heads' K rows then V rows, and the layout round-trips."""
    nb, page, kvh, d = 3, 4, 4, 64
    heads = jax.random.normal(KEY, (nb, 2, page, kvh, d), jnp.float32)
    pool = pack_pool(heads)
    assert pool.shape == (nb, 2, 2, page, 128)
    np.testing.assert_array_equal(unpack_pool(pool, kvh), heads)
    pages = pool.reshape(nb * 2, 2 * page, 128)
    blk, w = 2, 1
    want = jnp.concatenate(
        [heads[blk, which, :, 2 * w:2 * w + 2].reshape(page, 128)
         for which in (0, 1)])
    np.testing.assert_array_equal(pages[blk * 2 + w], want)


# ---------------------------------------------------------------------------
# Graph + kernel parity
# ---------------------------------------------------------------------------


def test_paged_graph_fused_matches_oracle():
    from repro.kernels import registry as R
    spec = R.get_graph("paged_decode_attention")
    out, ref, err, compiled = R.run_graph_smoke(spec)
    assert err <= spec.tol, err
    assert any(e.mode == "fused" for e in compiled.plan.edges), \
        [(e.edge.label, e.rationale) for e in compiled.plan.edges]


def test_paged_kernel_bf16_within_its_two_roundings():
    """In bf16 the graph's error against the f32 oracle is its two
    roundings (output, softmax weights for the PV matmul) and no more —
    the yardstick chip_smoke.py holds the compiled kernel to."""
    b, kvh, d, n_pages = 2, 4, 64, 4
    nb = b * n_pages
    k1, k2 = jax.random.split(KEY)
    pool = pack_pool(jax.random.normal(k1, (nb, 2, PAGE, kvh, d),
                                       jnp.bfloat16))
    q = jax.random.normal(k2, (b, kvh, d), jnp.bfloat16)
    bt = jnp.arange(nb, dtype=jnp.int32)[::-1].reshape(b, n_pages)
    lens = jnp.array([13, 30], jnp.int32)
    got = np.asarray(paged_decode_attention(q, pool, bt, lens,
                                            policy=PipePolicy(mode="ff")),
                     np.float32)
    with jax.default_matmul_precision("highest"):
        ref, ref_p = (np.asarray(paged_decode_attention_ref(
            q.astype(jnp.float32), pool, bt, lens, p_dtype=p_dt))
            for p_dt in (None, jnp.bfloat16))
    out_floor = np.linalg.norm(
        np.asarray(jnp.asarray(ref, jnp.bfloat16), np.float32) - ref)
    p_floor = np.linalg.norm(ref_p - ref)
    assert 0 < p_floor
    assert np.linalg.norm(got - ref) <= 1.2 * np.hypot(out_floor, p_floor)


@pytest.mark.parametrize("npg,lens", [
    (4, (97, 128)),       # one word per row: ppw == n_pages
    (13, (200, 416)),     # n_pages not a multiple of ppw: sentinel pages
    (5, (0, 160)),        # a length-0 row next to a full one
], ids=["one_word_per_row", "padded_sentinel_pages", "empty_row"])
def test_paged_kernel_bitwise_vs_contiguous(npg, lens):
    """Same pool dereferenced two ways: through the block-table stream
    graph and as a dense cache at block_kv == ppw * page. Identical tile
    order + identical f32 online softmax => bitwise-equal outputs, even
    with garbage in the masked tail and the sentinel pages that pad a row
    to whole words (exp underflows to exactly 0)."""
    import repro
    b, h, kvh, d, page = 2, 8, 2, 64, 32
    nb = b * npg + 2
    ppw = pages_per_word(npg, page, pool_lanes(kvh, d)[1], jnp.float32)
    n_words = -(-npg // ppw)
    if npg == 4:
        assert ppw == npg
    if npg == 13:
        assert npg % ppw and n_words > 1
    s = npg * page
    heads = jax.random.normal(KEY, (nb, 2, page, kvh, d), jnp.float32)
    pool = pack_pool(heads)
    perm = np.random.default_rng(3).permutation(nb)[:b * npg]
    bt = jnp.asarray(perm.reshape(b, npg), jnp.int32)
    lens = jnp.array(lens, jnp.int32)
    q = jax.random.normal(jax.random.fold_in(KEY, 1), (b, h, d),
                          jnp.float32)
    kv = heads[bt]
    k = kv[:, :, 0].reshape(b, s, kvh, d).transpose(0, 2, 1, 3)
    v = kv[:, :, 1].reshape(b, s, kvh, d).transpose(0, 2, 1, 3)
    pad = ((0, 0), (0, 0), (0, (n_words * ppw - npg) * page), (0, 0))
    k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    pol = PipePolicy(mode="ff", depth=2, streams=1, interpret=True)
    cont = repro.ops.decode_attention(q, k, v, lens, block_kv=ppw * page,
                                      policy=pol)
    paged = paged_decode_attention(q, pool, bt, lens, policy=pol)
    assert np.array_equal(np.asarray(cont), np.asarray(paged))


# the benchmark cells' decode shapes: (rows, heads, KV heads, head dim,
# pages reserved per row) -> pages per word; 16-token bf16 pages
_CELL_SHAPES = {
    "qwen_short_chat": ((32, 16, 16, 64, 48), 24),
    "qwen_long_decode": ((10, 16, 16, 64, 208), 32),
    "starcoder2_code_complete": ((16, 48, 4, 128, 248), 32),
}


@pytest.mark.parametrize("cell", sorted(_CELL_SHAPES))
def test_paged_graph_grid_fuses_at_cell_shapes(cell):
    """At each cell's shape the built graph fuses into one kernel (wpb 1)
    and runs B x J x ceil(n_pages / ppw) grid words of ppw pages each, not
    one word per page; the plan-cache signature tells it from a graph of
    one page per word."""
    from repro.core.graph import check_fusion, graph_signature
    (b, h, kvh, hd, n_pages), want_ppw = _CELL_SHAPES[cell]
    page = 16
    j, lanes = pool_lanes(kvh, hd)
    ppw = pages_per_word(n_pages, page, lanes, jnp.bfloat16)
    assert ppw == want_ppw
    g_pad = -(-(h // kvh) // 8) * 8
    kw = dict(b=b, kvh=kvh, g_pad=g_pad, n_pages=n_pages, page=page, d=hd,
              dtype=jnp.bfloat16)
    graph = build_paged_decode_graph(**kw)
    gather, attn = graph.node("gather").program, graph.node("attn").program
    rep = check_fusion(gather, attn, graph.edges[0])
    assert rep.ok and rep.wpb == 1, rep.reason
    assert attn.n_words == gather.n_words == b * j * -(-n_pages // ppw)
    assert tuple(gather.out_block) == (ppw, 2 * page, lanes)
    assert graph_signature(graph) != graph_signature(
        build_paged_decode_graph(**kw, ppw=1))


def test_paged_attn_grid_span():
    """Building the graph records the grid it runs in the trace: a
    ``paged_attn_grid`` span with the call's grid words and ppw."""
    from repro import obs
    b, kvh, d, npg, page = 2, 2, 64, 3, 16
    pool = pack_pool(jax.random.normal(KEY, (b * npg, 2, page, kvh, d),
                                       jnp.float32))
    q = jax.random.normal(jax.random.fold_in(KEY, 1), (b, kvh, d),
                          jnp.float32)
    bt = jnp.arange(b * npg, dtype=jnp.int32).reshape(b, npg)
    prev = obs.enable()
    obs.drain()
    try:
        paged_decode_attention(q, pool, bt, jnp.array([20, 48], jnp.int32),
                               policy=PipePolicy(mode="ff"))
        recs = obs.drain()
    finally:
        obs.restore(prev)
    grid = [r for r in recs if r["name"] == "paged_attn_grid"]
    assert len(grid) == 1
    ppw = pages_per_word(npg, page, pool_lanes(kvh, d)[1], jnp.float32)
    j = pool_lanes(kvh, d)[0]
    assert grid[0]["attrs"]["ppw"] == ppw
    assert grid[0]["attrs"]["grid_words"] == b * j * -(-npg // ppw)
    assert any(r["name"] == "compile_graph"
               and r["parent"] == grid[0]["id"] for r in recs)


def test_paged_sweep_replays_the_recorded_ppw():
    """A plan sweep replays a recorded call at its bucketed n_pages with
    the pages per word the call ran, not the rule's pick at the bucket."""
    from repro.plans import record_traffic
    from repro.runtime import paged_kv
    b, kvh, d, npg, page = 1, 1, 64, 40, 16       # one 64-lane word
    pool = pack_pool(jax.random.normal(KEY, (npg, 2, page, kvh, d),
                                       jnp.float32))
    q = jax.random.normal(jax.random.fold_in(KEY, 1), (b, 2, d),
                          jnp.float32)
    bt = jnp.arange(npg, dtype=jnp.int32)[None]
    with record_traffic() as prof:
        paged_decode_attention(q, pool, bt, jnp.array([600], jnp.int32),
                               policy=PipePolicy(mode="ff"))
    (entry,) = [e for e in prof.entries.values()
                if e.op == "graph:paged_decode_attention"]
    ppw = pages_per_word(npg, page, 64, jnp.float32)
    bucket = entry.site["n_pages"]
    assert bucket > npg
    assert pages_per_word(bucket, page, 64, jnp.float32) != ppw
    (_, pool_b, bt_b, _), kw = paged_kv._paged_sweep_inputs(
        KEY, dict(entry.site, dtype=entry.dtype))
    assert bt_b.shape == (b, bucket) and kw == {"ppw": ppw}


# ---------------------------------------------------------------------------
# Model-level decode parity (mixed-length batch)
# ---------------------------------------------------------------------------


def _model_for(impl, n_pages=None):
    """The model at ``impl``; with ``n_pages``, an ff model's dense decode
    tile is the paged graph's word at that many pages (``ppw * PAGE``),
    the tile order that makes the two paths bitwise-equal
    (``decode_parity_probe`` pins its dense path itself)."""
    cfg = smoke_config(ARCH).replace(remat="none", attn_impl=impl)
    if impl == "ff" and n_pages is not None:
        cfg = cfg.replace(decode_block_kv=serve_lib.paged_block_kv(
            cfg, n_pages, PAGE))
    from repro.models import build_model
    return build_model(cfg), cfg


@pytest.mark.parametrize("impl", ["xla", "ff"])
def test_decode_paged_vs_dense_bitwise(impl):
    model, cfg = _model_for(impl)
    params = model.init(KEY)
    policy = PipePolicy(mode="ff", interpret=True)
    diff = serve_lib.decode_parity_probe(model, params, cfg, policy,
                                         page=PAGE)
    assert diff == 0.0, diff


def _greedy(model, cfg, params, prompts, steps, *, paged):
    """Greedy tokens [B, steps] from a mixed-length prompt batch."""
    policy = PipePolicy(mode="ff", interpret=True)
    b = len(prompts)
    lens = np.array([len(p) for p in prompts], np.int32)
    p_max = int(lens.max())
    n_pages = -(-(p_max + steps) // PAGE)
    s_max = n_pages * PAGE
    toks = np.zeros((b, p_max), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    prefill = jax.jit(steps_lib.make_prefill_step(model, policy=policy))
    decode = jax.jit(steps_lib.make_decode_step(model, policy=policy))
    _, dense = prefill(params, {"tokens": jnp.asarray(toks)})
    if paged:
        kv = PagedKVCache(
            n_layers=cfg.n_layers, n_blocks=b * n_pages, page=PAGE,
            kv_heads=cfg.n_kv_heads, head_dim=cfg.hd, n_slots=b,
            n_pages_max=n_pages, dtype=cfg.cdtype)
        for i in range(b):
            kv.admit(i, dense["k"][:, i], dense["v"][:, i], int(lens[i]),
                     s_max)
        kv.lengths[:] = lens - 1
        cache = kv.cache_view()
    else:
        cache = serve_lib.pad_cache_to(dense, p_max, s_max, 2)
    cur = jnp.asarray(toks[np.arange(b), lens - 1])
    lengths = jnp.asarray(lens - 1)
    out = []
    for _ in range(steps):
        cur, _, cache = decode(
            params, {"token": cur, "lengths": lengths}, cache)
        out.append(np.asarray(cur))
        lengths = lengths + 1
    return np.stack(out, axis=1)


def test_token_parity_paged_contiguous_oracle():
    """paged(ff) == contiguous(ff) == XLA oracle, token for token, on a
    mixed-length batch. The two ff paths are bitwise so their equality is
    exact by construction; the xla oracle decode must agree greedily."""
    rng = np.random.default_rng(5)
    cfg0 = smoke_config(ARCH)
    prompts = [rng.integers(1, cfg0.vocab, size=n).astype(np.int32)
               for n in (5, 12, 9)]
    steps = 6
    n_pages = -(-(max(map(len, prompts)) + steps) // PAGE)   # as _greedy
    model_ff, cfg_ff = _model_for("ff", n_pages)
    model_x, cfg_x = _model_for("xla")
    params = model_ff.init(KEY)   # identical params for both impls
    t_paged = _greedy(model_ff, cfg_ff, params, prompts, steps, paged=True)
    t_cont = _greedy(model_ff, cfg_ff, params, prompts, steps, paged=False)
    t_oracle = _greedy(model_x, cfg_x, params, prompts, steps, paged=False)
    np.testing.assert_array_equal(t_paged, t_cont)
    np.testing.assert_array_equal(t_paged, t_oracle)


# ---------------------------------------------------------------------------
# Scheduler semantics
# ---------------------------------------------------------------------------


def _xla_setup():
    model, cfg = _model_for("xla")
    params = model.init(KEY)
    policy = PipePolicy(mode="ff", interpret=True)
    return model, cfg, params, policy


def _mk_requests(budgets, prompt_lens, vocab, arrival=0.0):
    rng = np.random.default_rng(9)
    return [serve_lib.Request(
        i, arrival, rng.integers(1, vocab, size=n).astype(np.int32), m)
        for i, (n, m) in enumerate(zip(prompt_lens, budgets))]


def test_lockstep_terminates_exactly():
    """Satellite: the decode loop must run exactly max(remaining budget)
    steps per batch — no runaway to max_new + prompt_len, no extra step
    flipping each finished row."""
    model, cfg, params, policy = _xla_setup()
    reqs = _mk_requests([3, 5, 2, 2], [6, 9, 4, 7], cfg.vocab)
    m = serve_lib.run_lockstep(model, params, cfg, reqs, n_slots=2,
                               page=PAGE, eos_id=None, policy=policy)
    assert m["decode_steps"] == 5 + 2       # max per batch of two
    assert m["tokens"] == 3 + 5 + 2 + 2


def test_eos_retires_and_recycles():
    """EOS retirement: with eos_id set to a token the model actually
    emits, requests finish early and the paged scheduler's recycled
    blocks let the same pool serve the trace."""
    model, cfg, params, policy = _xla_setup()
    reqs = _mk_requests([8] * 4, [5, 7, 6, 8], cfg.vocab)
    base = serve_lib.run_continuous(model, params, cfg, reqs, n_slots=2,
                                    page=PAGE, eos_id=None, policy=policy)
    assert base["tokens"] == 32
    # find a token the model actually emits by decoding one step, then
    # re-run the trace with that token as EOS
    dec = jax.jit(steps_lib.make_decode_step(model, policy=policy))
    pre = jax.jit(steps_lib.make_prefill_step(model, policy=policy))
    toks = np.zeros((1, 8), np.int32)
    toks[0, :5] = reqs[0].prompt
    _, cache = pre(params, {"tokens": jnp.asarray(toks)})
    cache = serve_lib.pad_cache_to(cache, 8, 16, 2)
    nxt, _, _ = dec(params, {"token": jnp.asarray([reqs[0].prompt[-1]]),
                             "lengths": jnp.asarray([4])}, cache)
    eos = int(np.asarray(nxt)[0])
    early = serve_lib.run_continuous(model, params, cfg, reqs, n_slots=2,
                                     page=PAGE, eos_id=eos, policy=policy)
    assert early["tokens"] < base["tokens"]


def test_continuous_respects_pool_pressure():
    """A pool sized for ~one request at a time still serves the whole
    trace (admission waits for retirements instead of failing)."""
    model, cfg, params, policy = _xla_setup()
    reqs = _mk_requests([4] * 3, [5, 6, 7], cfg.vocab)
    m = serve_lib.run_continuous(model, params, cfg, reqs, n_slots=2,
                                 page=PAGE, eos_id=None, policy=policy,
                                 pool_blocks=2)
    assert m["tokens"] == 12


def test_serve_schedulers_token_parity():
    """Lockstep and paged continuous emit the same number of tokens per
    request over the same trace (greedy decode of the same model)."""
    model, cfg, params, policy = _xla_setup()
    reqs = _mk_requests([3, 4, 5], [5, 9, 6], cfg.vocab)
    ls = serve_lib.run_lockstep(model, params, cfg, reqs, n_slots=2,
                                page=PAGE, eos_id=None, policy=policy)
    pg = serve_lib.run_continuous(model, params, cfg, reqs, n_slots=2,
                                  page=PAGE, eos_id=None, policy=policy)
    assert ls["tokens"] == pg["tokens"] == 12


def test_continuous_spans_split_each_step():
    """With tracing on, every ``serve_decode_step`` holds exactly one
    ``decode_inputs``, ``decode_launch`` and ``decode_wait`` (in that
    order, inside its interval) and carries the GC and compile totals;
    every ``serve_admit`` holds one ``admit_launch`` and ``admit_wait``
    and keeps its ``prompt_len``; the step and admission spans stay one
    per step and per request, siblings of the scheduler's own spans."""
    from repro import obs
    model, cfg, params, policy = _xla_setup()
    reqs = _mk_requests([3, 5, 2], [6, 9, 4], cfg.vocab)
    prev = obs.enable()
    obs.drain()
    try:
        m = serve_lib.run_continuous(model, params, cfg, reqs, n_slots=2,
                                     page=PAGE, eos_id=None, policy=policy)
        recs = obs.drain()
    finally:
        obs.restore(prev)
    by_id = {r["id"]: r for r in recs}
    kids = {}
    for r in recs:
        kids.setdefault(r["parent"], []).append(r)

    def span_of(r):
        return r["ts"] - r["dur_s"], r["ts"]

    steps = [r for r in recs if r["name"] == "serve_decode_step"]
    admits = [r for r in recs if r["name"] == "serve_admit"]
    assert len(steps) == m["decode_steps"]
    assert sorted(r["attrs"]["prompt_len"] for r in admits) == [4, 6, 9]
    for parent, names in ((steps, ["decode_inputs", "decode_launch",
                                   "decode_wait"]),
                          (admits, ["admit_launch", "admit_wait"])):
        for p in parent:
            mine = sorted((c for c in kids.get(p["id"], [])
                           if c["name"] != "jax_compile"),
                          key=lambda c: c["id"])
            assert [c["name"] for c in mine] == names
            a, b = span_of(p)
            for c in mine:
                ca, cb = span_of(c)
                assert a - 1e-3 <= ca <= cb <= b + 1e-3
    for p in admits:
        assert by_id[p["parent"]]["name"] == "serve_schedule"
    for p in steps:
        assert p["parent"] is None
        assert {"gc_s", "gc_n", "compiles"} <= set(p["attrs"])
    totals = [p["attrs"]["compiles"] for p in steps]
    assert totals == sorted(totals)
    for name in ("decode_inputs", "decode_launch", "decode_wait"):
        assert sum(r["name"] == name for r in recs) == len(steps)
    assert sum(r["name"] == "serve_bookkeep" for r in recs) == len(steps)
    assert all(by_id[r["parent"]]["name"] == "serve_bookkeep"
               for r in recs if r["name"] == "serve_retire")


# ---------------------------------------------------------------------------
# pad_cache_to (satellite)
# ---------------------------------------------------------------------------


def test_pad_cache_to_pads_only_declared_axis():
    # head dim (axis 2) equals the prompt length — the old shape-matching
    # pad would corrupt it
    leaf = jnp.ones((2, 4, 4, 3))
    out = serve_lib.pad_cache_to({"k": leaf}, 4, 8, 1)
    assert out["k"].shape == (2, 8, 4, 3)
    # per-leaf axes: None leaves untouched
    cache = {"k": leaf, "state": jnp.ones((4, 4))}
    out = serve_lib.pad_cache_to(cache, 4, 8, {"k": 1, "state": None})
    assert out["k"].shape == (2, 8, 4, 3)
    assert out["state"].shape == (4, 4)


def test_pad_cache_to_requires_seq_dims():
    with pytest.raises(TypeError):
        serve_lib.pad_cache_to({"k": jnp.ones((2, 4))}, 4, 8, None)
    with pytest.raises(ValueError):
        serve_lib.pad_cache_to({"k": jnp.ones((2, 5))}, 4, 8, 1)
