"""DeepSeek-V2 (``archs/deepseek_v2.py``) on the CPU at a toy size: its
weights, reference logits and FLOP counts match the record in
``data/deepseek_v2_fingerprints.json``; its reference agrees with the
repo's own (``tests/deepseek_reference.py``) on the same draws; the
latent kernel's cost counts by hand; a served run is ``correct`` and the
fp8 control fails the same limit; and the two metrics this architecture
adds read nothing from a program without what they read."""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from conftest import ROOT
from reference import Q_BLOCK
from tiny import SECONDS, TINY_TRAFFIC, tiny_cell

# MLA with a 128-lane latent and a 64-lane key (one 256-lane row a
# token), 2 heads, a dense layer and two expert layers holding 4 of 8
# experts (top 2, not renormalised, one shared expert), YaRN over 64
# original positions so the ramp falls inside the toy contexts. The limit
# sits between the sound runs' widest gaps on the CPU (0.0-0.069 over 7
# seeds) and the fp8 control's (0.184-0.417).
TINY_DEEPSEEK_CONFIG = {
    "name": "tiny_deepseek_v2", "repro_arch": "deepseek_v2_lite_16b",
    "model_type": "deepseek_v2", "hidden_act": "silu",
    "attention_bias": False, "q_lora_rank": None, "moe_layer_freq": 1,
    "n_group": 1, "topk_group": 1, "topk_method": "greedy",
    "scoring_func": "softmax", "tie_word_embeddings": False,
    "hidden_size": 256, "intermediate_size": 512,
    "moe_intermediate_size": 128, "num_attention_heads": 2,
    "num_key_value_heads": 2, "kv_lora_rank": 128,
    "qk_nope_head_dim": 64, "qk_rope_head_dim": 64, "v_head_dim": 64,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "n_routed_experts": 4, "published": {"n_routed_experts": 8},
    "expert_offset": 2, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "norm_topk_prob": False, "routed_scaling_factor": 1.0,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 40, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 0.707,
                     "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 64},
    "vocab_size": 512, "limit_logit_gap": 0.12,
}
SEED = 2**33 + 7

with open(os.path.join(os.path.dirname(__file__), "data",
                       "deepseek_v2_fingerprints.json")) as f:
    RECORDED = json.load(f)


def _cell(end_to_end=()):
    return harness.Cell("tiny.deepseek", 1, TINY_DEEPSEEK_CONFIG,
                        TINY_TRAFFIC, [{"name": n, "unit": "1"}
                                       for n in end_to_end], [])


def test_fingerprints_match_the_record():
    from test_archs import _fingerprint
    arch = harness.arch("deepseek_v2")
    spec = arch.spec(TINY_DEEPSEEK_CONFIG)
    for seed in RECORDED["seeds"]:
        assert _fingerprint(arch, spec, seed) == \
            RECORDED["fingerprints"][str(seed)], seed


def test_reference_agrees_with_the_repo_reference():
    """The benchmark's reference (canonical draws) and the repo's plain
    forward pass (the same draws in the program's tree), both f32 at
    highest precision: equal up to summation order."""
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    import deepseek_reference
    cell = _cell()
    arch, spec = cell.arch, cell.spec
    cfg = cell.program_config()
    toks = jnp.asarray((11 * np.arange(Q_BLOCK) + 5) % spec.vocab,
                       jnp.int32)
    rows = jnp.arange(Q_BLOCK, dtype=jnp.int32)
    got = arch.logits_at(spec, arch.canonical(spec, SEED), toks, rows)
    want = deepseek_reference.logits(cfg, arch.program_params(spec, SEED),
                                     toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_spec_refuses_what_it_does_not_implement():
    arch = harness.arch("deepseek_v2")
    for key, value in (("topk_method", "group_limited_greedy"),
                       ("q_lora_rank", 1536), ("scoring_func", "sigmoid")):
        with pytest.raises(ValueError, match=key):
            arch.spec(dict(TINY_DEEPSEEK_CONFIG, **{key: value}))
    spec = arch.spec(TINY_DEEPSEEK_CONFIG)
    assert (spec.n_experts, spec.n_held, spec.expert_offset) == (8, 4, 2)


def test_latent_cost_by_hand():
    latent = harness.cost("latent_attn")
    spec = harness.arch("deepseek_v2").spec(TINY_DEEPSEEK_CONFIG)
    # rows of context 5 and 17 at page 4 (2 and 5 live pages), one idle.
    # A token's row: 128 latent + 64 key lanes in bf16 = 384 B; a row's q
    # is 2 heads of 192 lanes, its output 2 heads of 128: 2*(192+128)*2 B.
    # Three layers.
    flops, bytes_ = latent.step(spec, [5, 17, 0], page=4)
    assert bytes_ == 3 * ((2 + 5) * 4 * 384 + 2 * (2 * 320 * 2))
    # every head scores 192 lanes and sums 128, two operations a product
    assert flops == 3 * (2 * 2 * 192 + 2 * 2 * 128) * (5 + 17)


def test_served_run_is_correct_and_fp8_control_fails():
    import check
    import run as runmod
    import serve_driver
    import timeline
    cell = _cell(("setup_s", "output_tokens_per_s", "moe_load_max",
                  "kv_pool_util"))
    res = runmod.run_cell(cell, seed=SEED, seconds=SECONDS, trace=False,
                          dev=jax.devices()[0],
                          peaks={"bf16_flops_per_s": 1.0},
                          process_start=time.time())
    assert res["correct"], res["checks"]
    # 4 held experts: an even load reads 25%, one expert taking all 100%
    assert 25.0 <= res["metrics"]["moe_load_max"]["value"] <= 100.0
    r = serve_driver.run(cell, seed=SEED, seconds=SECONDS, trace_dir=None)
    tl = timeline.build(r)
    by = {q.rid: q for q in r.requests}
    items = [(by[i].prompt, tl.reqs[i].tokens) for i in tl.reqs]
    sound, _ = check.gaps(cell, SEED, items)
    ctl, n = check.gaps(cell, SEED, items, control=True)
    assert n >= 30
    assert sound <= cell.config["limit_logit_gap"] < ctl, (sound, ctl)


@pytest.mark.parametrize("name", ["moe_load_max", "latent_attn_roofline"])
def test_new_metrics_read_nothing_without_their_source(name):
    """A dense cell's run has no ``moe_tokens`` and no latent pool: the
    reader returns None (the metric is left out), it does not raise."""
    import run as runmod
    cell = tiny_cell((name,))
    res = runmod.run_cell(cell, seed=3, seconds=SECONDS, trace=False,
                          dev=jax.devices()[0],
                          peaks={"bf16_flops_per_s": 1.0,
                                 "hbm_bytes_per_s": 1.0},
                          process_start=time.time())
    assert name not in res["metrics"]
