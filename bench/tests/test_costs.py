"""Each kernel's and the model's operation and byte counts against a
count made by hand at a small shape."""

import pytest

import harness
from archs._dense import Spec

SPEC = Spec(n_layers=2, d_model=8, n_heads=4, n_kv_heads=2, head_dim=4,
            d_ff=16, vocab=10, norm="rmsnorm", norm_eps=1e-6, act="swiglu",
            qkv_bias=True, rope_theta=1e4)


def test_paged_attn_counts_only_live_pages():
    paged = harness.cost("paged_attn")
    # rows of context 5 and 17 at page 4: 2 and 5 live pages; a row of
    # context 0 is an idle slot. Per layer, a page holds 4 tokens of K and
    # V for 2 KV heads of 4 lanes in bf16: 4*2*2*4*2 = 128 B; q and the
    # output of a row are 4 heads * 4 * 2 B each.
    flops, bytes_ = paged.step(SPEC, [5, 17, 0], page=4)
    assert bytes_ == 2 * ((2 + 5) * 128 + 2 * (2 * 32))
    # q k^T and p v over 4 heads of 4: 2*4*4*n each
    assert flops == 2 * (4 * 4 * 4 * (5 + 17))


def test_paged_attn_ignores_reserved_pages():
    paged = harness.cost("paged_attn")
    one = paged.step(SPEC, [8], page=4)
    assert paged.step(SPEC, [8, 0, 0], page=4) == one
    # one token more opens one more page
    assert paged.step(SPEC, [9], page=4)[1] - one[1] == 2 * 128


def test_prefill_attn_causal_count():
    pre = harness.cost("prefill_attn")
    flops, bytes_ = pre.prompt(SPEC, 3)
    # 6 scores per head (3*4/2), for q k^T and p v, 2 ops each, hd 4, 4
    # heads, 2 layers
    assert flops == 2 * (4 * 4 * 6 * 2 * 2)
    # q, out: 4 heads; k, v: 2 heads; 3 tokens * 4 lanes * 2 B
    assert bytes_ == 2 * ((4 + 4 + 2 + 2) * 3 * 4 * 2)


def test_model_flops_by_hand():
    model, qwen2 = harness.cost("model"), harness.arch("qwen2")
    # per layer: q 8*16, k 8*8, v 8*8, o 16*8, mlp 3*8*16
    per_layer = 128 + 64 + 64 + 128 + 384
    assert qwen2.matmul_weights(SPEC) == (2 * per_layer, 8 * 10)
    # q k^T and p v: 2 * 4 heads * 4 lanes, two operations each, 2 layers
    assert qwen2.attn_flops_per_pair(SPEC) == 2 * 64
    tok = 2 * (2 * per_layer + 8 * 10)
    assert model.token_flops(qwen2, SPEC) == tok
    # two live rows (contexts 3 and 5), one idle: attention 4*4*4*n/layer
    assert model.decode_step(qwen2, SPEC, [3, 0, 5]) == \
        2 * tok + 2 * (64 * 3 + 64 * 5)
    # a prefill of 3 tokens: layers, causal attention, one head row
    assert model.prefill(qwen2, SPEC, 3) == \
        2 * 2 * per_layer * 3 + 2 * (2 * 16 * 3 * 4) + 2 * 8 * 10


@pytest.mark.parametrize("act,mlp", [("swiglu", 3), ("gelu", 2)])
def test_mlp_weights_by_activation(act, mlp):
    import dataclasses
    s = dataclasses.replace(SPEC, act=act)
    layers, _ = harness.arch("qwen2").matmul_weights(s)
    assert layers == 2 * (384 + mlp * 8 * 16)
