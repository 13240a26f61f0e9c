"""``correct`` on the CPU at a toy size: a sound run passes; the timed
path broken underneath fails; the fp8 control fails the same limit.

Each test drives the whole run (``run.run_cell``) past the harness's
look for a chip."""

import time

import jax
import jax.numpy as jnp
import pytest

import check
import run as runmod
import serve_driver
import timeline
from tiny import SECONDS, tiny_cell

SEED = 2**31 + 11           # wider than 32 bits, as a seed may be


def _run(cell=None, seed=SEED):
    cell = cell or tiny_cell(("setup_s", "output_tokens_per_s",
                              "itl_p95_ms", "ttft_p90_ms"))
    return runmod.run_cell(cell, seed=seed, seconds=SECONDS, trace=False,
                           dev=jax.devices()[0],
                           peaks={"bf16_flops_per_s": 1.0},
                           process_start=time.time())


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 3
    assert set(res["metrics"]) == {"setup_s", "output_tokens_per_s",
                                   "itl_p95_ms", "ttft_p90_ms"}
    assert list(res)[-1] == "checks"


def test_step_returning_its_state_unchanged_fails(monkeypatch):
    """The decode step writes no K/V into the pool: the state a step
    returns is the one it was given."""
    from repro.runtime import paged_kv
    monkeypatch.setattr(paged_kv, "scatter_token",
                        lambda pool, *a, **k: pool)
    res = _run()
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]


def test_token_altered_where_produced_fails(monkeypatch):
    """The step's argmax is altered (every row at a position that is a
    multiple of 3) before the scheduler takes it."""
    from repro.launch import steps
    orig = steps.make_decode_step

    def broken(model, *, policy=None):
        step = orig(model, policy=policy)

        def altered(params, batch, cache):
            tok, logits, new = step(params, batch, cache)
            hit = batch["lengths"] % 3 == 0
            return jnp.where(hit, (tok + 1) % logits.shape[-1], tok), \
                logits, new
        return altered

    monkeypatch.setattr(steps, "make_decode_step", broken)
    res = _run()
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]
    assert res["checks"]["fed_not_argmax"]["value"] > 0


def test_fp8_control_fails_the_limit():
    """The reference at fp8, put in the program's place, reads a widest
    gap above the cell's limit on the same prompts and served tokens."""
    cell = tiny_cell()
    r = serve_driver.run(cell, seed=SEED, seconds=SECONDS, trace_dir=None)
    tl = timeline.build(r)
    by = {q.rid: q for q in r.requests}
    items = [(by[i].prompt, tl.reqs[i].tokens) for i in tl.reqs]
    sound, _ = check.gaps(cell, SEED, items)
    ctl, n = check.gaps(cell, SEED, items, control=True)
    limit = cell.config["limit_logit_gap"]
    assert n >= 30
    assert sound <= limit < ctl, (sound, limit, ctl)


@pytest.mark.parametrize("bad_seed", [0, 2**40 + 3])
def test_weights_follow_the_seed(bad_seed):
    cell = tiny_cell()
    a = cell.arch.canonical(cell.spec, bad_seed)["wq"]
    b = cell.arch.canonical(cell.spec, bad_seed)["wq"]
    c = cell.arch.canonical(cell.spec, bad_seed + 1)["wq"]
    assert jnp.array_equal(a, b) and not jnp.array_equal(a, c)


def test_tied_embedding_is_the_lm_head():
    """With ``tie_word_embeddings`` the program's tree has no separate
    head, and the reference reads its logits through the embedding."""
    import dataclasses

    import numpy as np

    from reference import Q_BLOCK
    from repro.models import build_model
    from tiny import TINY_CONFIG
    cell = tiny_cell()
    arch = cell.arch
    tied = dataclasses.replace(cell.spec, tie_embeddings=True)
    conf = dict(TINY_CONFIG, tie_word_embeddings=True)
    assert arch.spec(conf) == tied
    cfg = arch.program_config(conf, tied, page=16)
    assert cfg.tie_embeddings
    params = arch.program_params(tied, SEED)
    serve_driver._check_tree(params, build_model(cfg).abstract_params())
    w = arch.canonical(tied, SEED)
    assert "unembed" not in w and "unembed" not in params
    untied = dict(w, unembed=w["embed"])
    toks = jnp.arange(Q_BLOCK, dtype=jnp.int32) % tied.vocab
    rows = jnp.array([3, 100], jnp.int32)
    a = arch.logits_at(tied, w, toks, rows)
    b = arch.logits_at(cell.spec, untied, toks, rows)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
