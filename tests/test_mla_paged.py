"""DeepSeek-V2 (MLA + held-expert MoE) on the paged serve path, at a
small size on the CPU, against the plain reference in
``deepseek_reference.py`` (f32, highest precision, no cache).

* YaRN's frequencies and temperature follow the published formulas.
* The absorbed decode through the latent pool equals the explicit form.
* The expert layer's shares add up to the uncut layer, and it drops no
  token however the router concentrates them.
* The registered ``paged_latent_attention`` graph (interpret mode) equals
  its XLA oracle with recycled and sentinel pages in the table.
* ``run_continuous`` (prefill, then decode through the latent
  ``PagedKVCache``) serves logits equal to the reference's full forward
  pass at every served position.

Every model here computes in float32, so the tolerances bound summation
order alone (online softmax over pages, the absorbed form's products and
the grouped experts' order): 1e-4 on logits of magnitude about 1.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepseek_reference as ref
from repro.configs.base import get_config, smoke_config
from repro.core.program import PipePolicy
from repro.launch import serve as serve_lib
from repro.models import build_model, mla, moe
from repro.runtime.paged_kv import (PagedKVCache, gather_indices,
                                    latent_lanes, pages_per_word,
                                    paged_latent_attention,
                                    paged_latent_attention_ref)

ARCH = "deepseek_v2_lite_16b"
KEY = jax.random.key(5)
TOL = 1e-4


def _cfg(**kw):
    return smoke_config(ARCH).replace(remat="none", **kw)


def _params(cfg, seed=0):
    """The program's tree with weights scaled so the logits spread about
    1 and routing is not a near-tie everywhere."""
    params = build_model(cfg).init(jax.random.key(seed))
    return jax.tree.map(lambda a: a * 3.0 if a.ndim >= 2 else a, params)


# ---------------------------------------------------------------- YaRN

def test_yarn_matches_the_published_formulas():
    cfg = get_config(ARCH)
    inv = np.asarray(mla.yarn_inv_freq(cfg))
    want = np.asarray(ref.yarn_inv_freq(64, 1e4, 40.0, 4096, 32, 1))
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    base = 1e4 ** (-np.arange(0, 64, 2) / 64)
    # the ramp runs over rope dims 10 to 23: kept before, /40 after
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-6)
    assert np.all((inv[11:23] < base[11:23]) & (inv[11:23] > base[11:23] / 40))
    m = mla.yarn_mscale(40.0, 0.707)
    assert round(m, 4) == 1.2608
    assert mla.softmax_scale(cfg) == pytest.approx(m * m / math.sqrt(192))
    # plain RoPE where there is no scaling
    plain = mla.yarn_inv_freq(cfg.replace(rope_factor=1.0))
    np.testing.assert_allclose(np.asarray(plain), base, rtol=1e-6)


# ---------------------------------------------------------- MoE shares

def _moe_input(cfg, t=40, seed=1):
    return jax.random.normal(jax.random.key(seed), (1, t, cfg.d_model))


@pytest.mark.parametrize("grouped", [False, True])
def test_expert_shares_add_up_to_the_uncut_layer(grouped):
    """Eight shares of one expert each: their outputs, the shared expert
    counted once, add up to the layer that holds all eight."""
    cfg = _cfg()
    e = cfg.n_experts
    p = _params(cfg)["stack"]["layers"]["ffn"]
    p = jax.tree.map(lambda a: a[0], p)
    x = _moe_input(cfg)
    whole, _, _ = moe._dropless_apply(cfg, p, x, grouped=grouped)
    shared = moe.L.mlp_apply(p["shared"], x, "swiglu")
    total = -(e - 1) * shared
    for i in range(e):
        part = dict(p, w1=p["w1"][i:i + 1], w2=p["w2"][i:i + 1])
        out, _, assign = moe._dropless_apply(
            cfg.replace(n_experts_held=1, expert_offset=i), part, x,
            grouped=grouped)
        total = total + out
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=TOL, atol=TOL)
    with jax.default_matmul_precision("highest"):
        want = ref.moe(cfg, p, x[0])
    np.testing.assert_allclose(np.asarray(whole[0]), np.asarray(want),
                               rtol=TOL, atol=TOL)
    assert int(assign.sum()) == x.shape[1] * cfg.top_k


@pytest.mark.parametrize("grouped", [False, True])
def test_no_token_dropped_when_all_route_to_one_expert(grouped):
    cfg = _cfg()
    p = jax.tree.map(lambda a: a[0], _params(cfg)["stack"]["layers"]["ffn"])
    p = dict(p, router=p["router"].at[:, 3].set(0.0).at[0, 3].set(50.0))
    x = _moe_input(cfg, t=64).at[..., 0].set(4.0)
    out, _, assign = moe._dropless_apply(cfg, p, x, grouped=grouped)
    assert int(assign[:, 3].sum()) == 64            # every token picked 3
    with jax.default_matmul_precision("highest"):
        want = ref.moe(cfg, p, x[0])
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want),
                               rtol=TOL, atol=TOL)


def test_capacity_path_keeps_renormalising():
    """Grok's capacity layer is untouched: top-k weights still sum to 1."""
    cfg = smoke_config("grok1_314b")
    assert cfg.norm_topk_prob and cfg.capacity_factor > 0
    gates = jax.nn.softmax(jax.random.normal(KEY, (16, cfg.n_experts)))
    _, probs, _, _ = moe._dispatch_indices(gates, cfg.top_k, 64)
    np.testing.assert_allclose(np.asarray(probs.sum(-1)), 1.0, rtol=1e-5)


# ------------------------------------------------ latent pool decode

def _admitted(cfg, params, tokens, page=8, extra=8):
    """Prefill ``tokens`` [B, S] and admit every row into a latent pool."""
    model = build_model(cfg)
    _, pc = model.prefill(params, {"tokens": tokens})
    b, s = tokens.shape
    n_pages = -(-(s + extra) // page)
    kv = PagedKVCache(n_layers=cfg.n_layers, n_blocks=b * n_pages + 3,
                      page=page, n_slots=b, n_pages_max=n_pages,
                      latent_dim=cfg.kv_lora_rank + cfg.qk_rope_dim,
                      dtype=cfg.cdtype)
    assert kv.streams == ("c", "k_rope")
    for i in range(b):
        kv.admit(i, pc["c"][:, i], pc["k_rope"][:, i], s, s + extra)
    return model, pc, kv


@pytest.mark.parametrize("impl", ["xla", "ff"])
def test_absorbed_decode_matches_explicit(impl):
    """One decode step from the same prefill: the latent pool's absorbed
    form against the dense cache's explicit form, in f32."""
    cfg = _cfg(attn_impl=impl)
    params = _params(cfg)
    toks = jax.random.randint(KEY, (2, 13), 0, cfg.vocab)
    model, pc, kv = _admitted(cfg, params, toks)
    batch = {"token": toks[:, -1], "lengths": jnp.full((2,), 12, jnp.int32)}
    kv.lengths[:] = 12
    lp, new = model.decode_step(params, batch, kv.cache_view())
    assert new["kv_pool"].shape == kv.pool.shape
    assert new["expert_counts"].shape == (2, cfg.n_experts)
    dense = jax.tree.map(lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, 3),
                                               (0, 0))), pc)
    ld, _ = model.decode_step(params, batch, dense)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(ld), rtol=TOL,
                               atol=TOL)


def test_latent_graph_masks_recycled_and_sentinel_pages():
    """The fused graph (interpret mode) against its oracle: row 0 holds a
    recycled block of stale rows past its length and a sentinel entry,
    row 1 is an idle slot (length 0)."""
    b, h, dq, v_dim, page, n_pages = 3, 4, 96, 64, 8, 5
    w = latent_lanes(dq)
    nb = 16
    k1, k2 = jax.random.split(KEY)
    pool = jax.random.normal(k1, (nb, page, w), jnp.float32)
    bt = jnp.array([[3, 9, 14, nb, nb], [nb] * 5, [0, 1, 2, 5, 7]],
                   jnp.int32)
    lens = jnp.array([19, 0, 38], jnp.int32)
    q = jax.random.normal(k2, (b, h, dq), jnp.float32)
    got = paged_latent_attention(q, pool, bt, lens, v_dim=v_dim, scale=0.1,
                                 policy=PipePolicy(mode="ff"))
    want = paged_latent_attention_ref(q, pool, bt, lens, v_dim=v_dim,
                                      scale=0.1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=TOL, atol=TOL)
    assert not np.any(np.asarray(got[1]))
    # stale rows past row 0's length change nothing
    stale = pool.at[14, 19 - 16:].set(1e3)
    again = paged_latent_attention(q, stale, bt, lens, v_dim=v_dim,
                                   scale=0.1, policy=PipePolicy(mode="ff"))
    np.testing.assert_array_equal(np.asarray(again[0]), np.asarray(got[0]))
    idx = gather_indices(bt, lane_words=1, n_blocks=nb,
                         ppw=pages_per_word(n_pages, page, w, jnp.float32,
                                            page_streams=1))
    assert idx.shape[0] % b == 0 and int(idx.max()) < nb


# ---------------------------------------- run_continuous vs reference

class _Tap:
    """The model with each decode step's logits riding beside the pool."""

    def __init__(self, model):
        self.model = model

    def prefill(self, params, batch):
        return self.model.prefill(params, batch)

    def decode_step(self, params, batch, caches):
        logits, new = self.model.decode_step(params, batch, caches)
        new = dict(new, kv_pool=(new["kv_pool"], batch["token"], logits))
        return logits, new


def test_run_continuous_serves_the_reference_logits(monkeypatch):
    """Prefill, then decode through the latent pool under the scheduler
    (compiled-path kernels in interpret mode): at every served position
    the program's logits equal the reference's full forward pass over the
    prompt and the tokens served before it."""
    cfg = _cfg(attn_impl="ff")
    params = _params(cfg, seed=3)
    rng = np.random.default_rng(0)
    reqs = [serve_lib.Request(i, 0.0, rng.integers(1, cfg.vocab, n).astype(
        np.int32), m) for i, (n, m) in enumerate([(11, 5), (20, 4), (7, 6)])]
    events = []

    class Rec(PagedKVCache):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            events.clear()

        def admit(self, slot, *a, **k):
            super().admit(slot, *a, **k)
            events.append(("admit", slot))

        def update_pool(self, new_pool):
            pool, tok, logits = new_pool
            events.append(("step", self.lengths.copy(), np.asarray(tok),
                           np.asarray(logits)))
            super().update_pool(pool)

    monkeypatch.setattr(serve_lib, "PagedKVCache", Rec)
    serve_lib.run_continuous(_Tap(build_model(cfg)), params, cfg, reqs,
                             n_slots=2, page=8, eos_id=None,
                             policy=PipePolicy(mode="ff"))
    slot_req, seqs, got = {}, {}, {r.rid: [] for r in reqs}
    order = iter(reqs)
    for ev in events:
        if ev[0] == "admit":
            r = next(order)
            slot_req[ev[1]] = r
            seqs[r.rid] = list(r.prompt)
            continue
        _, lengths, tok, logits = ev
        for slot, r in list(slot_req.items()):
            if len(got[r.rid]) == r.max_new:
                continue
            assert lengths[slot] == len(seqs[r.rid]) - 1
            assert tok[slot] == seqs[r.rid][-1]
            got[r.rid].append(logits[slot])
            seqs[r.rid].append(int(np.argmax(logits[slot])))
    for r in reqs:
        assert len(got[r.rid]) == r.max_new
        toks = jnp.asarray(seqs[r.rid][:-1], jnp.int32)
        want = ref.logits(cfg, params, toks)[len(r.prompt) - 1:]
        np.testing.assert_allclose(np.stack(got[r.rid]), np.asarray(want),
                                   rtol=TOL, atol=TOL)
