"""Per-arch smoke tests (assignment deliverable f): a REDUCED config of the
same family runs one forward + one train step on CPU, asserting output
shapes and no NaNs; full configs are exercised only via the dry-run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCH_IDS, SHAPES, get_config, smoke_config, \
    shape_applicable
from repro.launch import steps as steps_lib
from repro.models import build_model
from repro.optim import adamw

KEY = jax.random.key(0)


def make_batch(cfg, b=2, s=16):
    batch = {
        "tokens": jax.random.randint(KEY, (b, s), 0, cfg.vocab),
        "labels": jax.random.randint(KEY, (b, s), 0, cfg.vocab),
    }
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            KEY, (b, cfg.n_frames, cfg.d_model), cfg.cdtype)
    if cfg.family == "vlm":
        batch["image_embeds"] = jax.random.normal(
            KEY, (b, cfg.n_patches, cfg.d_model), cfg.cdtype)
    return batch


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_smoke_forward_and_train_step(arch_id):
    cfg = smoke_config(arch_id)
    model = build_model(cfg)
    params = model.init(KEY)
    batch = make_batch(cfg)

    loss, metrics = model.loss(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), arch_id

    opt_cfg = adamw.AdamWConfig(lr_peak=1e-3, warmup_steps=1, total_steps=10)
    train_step = jax.jit(steps_lib.make_train_step(
        model, optimizer=cfg.optimizer,
        opt_cfg=None if cfg.optimizer == "adafactor" else opt_cfg))
    opt_init, _ = steps_lib.opt_init_and_update(cfg.optimizer, opt_cfg)
    opt_state = opt_init(params)
    new_params, new_opt, m = train_step(params, opt_state, batch)
    assert bool(jnp.isfinite(m["loss"])), arch_id
    # params actually moved
    moved = any(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                              b.astype(jnp.float32)))) > 0
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(new_params)))
    assert moved, arch_id


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_smoke_prefill_shapes(arch_id):
    cfg = smoke_config(arch_id)
    model = build_model(cfg)
    params = model.init(KEY)
    batch = make_batch(cfg, b=2, s=24)
    batch.pop("labels")
    logits, cache = model.prefill(params, batch)
    assert logits.shape == (2, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits).all()), arch_id
    assert cache is not None


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_input_specs_cover_all_shapes(arch_id):
    """Every applicable (arch x shape) cell must produce well-formed
    ShapeDtypeStruct inputs (the dry-run contract)."""
    cfg = get_config(arch_id)
    model = build_model(cfg)
    for shape in SHAPES.values():
        ok, _ = shape_applicable(cfg, shape)
        if not ok:
            continue
        specs = model.input_specs(shape)
        axes = model.input_axes(shape)
        assert set(axes) == set(specs)
        for name, sds in specs.items():
            assert isinstance(sds, jax.ShapeDtypeStruct)
            assert len(axes[name]) == len(sds.shape), (arch_id, shape.name,
                                                       name)
        if shape.kind == "decode":
            cache, cache_axes = model.cache_spec(shape)
            flat_c = jax.tree.leaves(cache)
            assert flat_c, (arch_id, shape.name)
            tupleish = lambda x: isinstance(x, tuple) and all(
                a is None or isinstance(a, str) for a in x)
            flat_a = jax.tree.leaves(cache_axes, is_leaf=tupleish)
            assert len(flat_a) == len(flat_c)


def test_assigned_dims_exact():
    """Assignment sheet dims must match the configs bit-for-bit."""
    rows = {
        "zamba2_2p7b": (54, 2560, 32, 32, 10240, 32000),
        "starcoder2_15b": (40, 6144, 48, 4, 24576, 49152),
        "qwen2_72b": (80, 8192, 64, 8, 29568, 152064),
        "llama3_2_1b": (16, 2048, 32, 8, 8192, 128256),
        "qwen1_5_0p5b": (24, 1024, 16, 16, 2816, 151936),
        "grok1_314b": (64, 6144, 48, 8, 32768, 131072),
        "deepseek_v2_lite_16b": (27, 2048, 16, 16, 10944, 102400),
        "whisper_tiny": (4, 384, 6, 6, 1536, 51865),
        "internvl2_1b": (24, 896, 14, 2, 4864, 151655),
        "rwkv6_7b": (32, 4096, 64, 64, 14336, 65536),
    }
    for arch_id, (L, d, h, kvh, ff, v) in rows.items():
        cfg = get_config(arch_id)
        got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
               cfg.d_ff, cfg.vocab)
        assert got == (L, d, h, kvh, ff, v), (arch_id, got)
    assert get_config("zamba2_2p7b").ssm_state == 64
    assert get_config("grok1_314b").n_experts == 8
    assert get_config("grok1_314b").top_k == 2
    ds = get_config("deepseek_v2_lite_16b")
    assert ds.kv_lora_rank == 512 and ds.n_experts == 64 and ds.top_k == 6
    assert ds.n_shared_experts == 2
    # the published dense width is layer 0's; the experts are 1408 wide
    assert ds.moe_d_ff == 1408 and ds.first_k_dense == 1


def test_shape_set_exact():
    assert SHAPES["train_4k"].seq_len == 4096
    assert SHAPES["train_4k"].global_batch == 256
    assert SHAPES["prefill_32k"].seq_len == 32768
    assert SHAPES["prefill_32k"].global_batch == 32
    assert SHAPES["decode_32k"].global_batch == 128
    assert SHAPES["long_500k"].seq_len == 524288
    assert SHAPES["long_500k"].global_batch == 1


def test_long_500k_applicability():
    """long_500k runs only for sub-quadratic archs (zamba2, rwkv6)."""
    runs = [a for a in ARCH_IDS
            if shape_applicable(get_config(a), SHAPES["long_500k"])[0]]
    assert sorted(runs) == ["rwkv6_7b", "zamba2_2p7b"]


def test_hillclimb_knobs_preserve_semantics():
    """loss_chunk / moe_local_dispatch / xla_tiled scan are pure perf knobs:
    outputs must match the baseline implementations."""
    import jax.numpy as jnp
    key = jax.random.key(11)
    # chunked-vocab CE
    cfg = smoke_config("llama3_2_1b").replace(remat="none")
    m = build_model(cfg)
    p = m.init(key)
    batch = {"tokens": jax.random.randint(key, (2, 16), 0, cfg.vocab),
             "labels": jax.random.randint(key, (2, 16), 0, cfg.vocab)}
    l1, _ = m.loss(p, batch)
    l2, _ = build_model(cfg.replace(loss_chunk=4)).loss(p, batch)
    assert abs(float(l1) - float(l2)) < 1e-5
    # local MoE dispatch (1 shard == global path)
    cfg = smoke_config("grok1_314b").replace(remat="none")
    m = build_model(cfg)
    p = m.init(key)
    batch = {"tokens": jax.random.randint(key, (2, 16), 0, cfg.vocab),
             "labels": jax.random.randint(key, (2, 16), 0, cfg.vocab)}
    l1, _ = m.loss(p, batch)
    l2, _ = build_model(cfg.replace(moe_local_dispatch=True)).loss(p, batch)
    assert abs(float(l1) - float(l2)) < 1e-5
    # tiled scan in a full model
    cfg = smoke_config("rwkv6_7b").replace(remat="none")
    m = build_model(cfg)
    p = m.init(key)
    batch = {"tokens": jax.random.randint(key, (2, 16), 0, cfg.vocab),
             "labels": jax.random.randint(key, (2, 16), 0, cfg.vocab)}
    l1, _ = m.loss(p, batch)
    l2, _ = build_model(cfg.replace(scan_impl="xla_tiled")).loss(p, batch)
    assert abs(float(l1) - float(l2)) < 1e-4
