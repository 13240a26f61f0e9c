#!/usr/bin/env python3
"""Device check: the paged serve path runs on a TPU at published widths.

With no arguments (one chip), this serves ``qwen1_5_0p5b`` at its published
widths (24 layers, d_model 1024, 16 heads of hd 64, d_ff 2816, vocab
151936, bf16, random weights from ``SEED``) through
``repro.launch.serve.serve_bench`` — paged continuous batching with the
compiled ``ff`` stream kernels — and checks that

* every request got its whole token budget, under both schedulers;
* one paged decode step's logits through the ``ff`` kernels agree with the
  ``impl="xla"`` reference path on the same state and params (bf16
  tolerance, reason below);
* the paged decode attention graph alone agrees with its f32 oracle on the
  prefilled pool, within the bf16 rounding of its output (reason below);
* and reports the lockstep-vs-paged probe's logits difference (0.0 means
  bitwise identical; a nonzero value is printed, not hidden).

``--four-chips`` runs only the multi-chip path: one ``make_train_step``
step of ``qwen1_5_0p5b`` (widths unchanged, depth cut to 4 layers so the
one-device reference fits one chip) on the ``(data 2, model 2)`` mesh,
compared with the same step on one device: loss, gradient and parameter
update, the last two leaf by leaf.

The script fails — exit code not 0, no result line — when JAX finds no
TPU; it never falls back to the CPU. Every time it prints was taken on the
device it names. The last line of standard output is the result::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Usage::

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # a 2x2 host
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen1_5_0p5b"
SEED = 0

# the serve run: 8 requests on 4 slots, prompts up to 256 tokens, 16-32
# new tokens each, 16-token pages
REQUESTS, SLOTS, PROMPT_LEN, MAX_NEW, PAGE = 8, 4, 256, 32, 16
# prompt lengths of the logits check's four rows: mixed, one at the serve
# cap, one that ends mid-page
LOGITS_LENS = (37, PROMPT_LEN, 5, 130)
# the four-chip train step: 4 of the 24 layers, batch of 8 x 128 tokens
TRAIN_LAYERS, TRAIN_BATCH = 4, (8, 128)

# ff vs xla decode logits: both paths read the same bf16 KV cache and q and
# accumulate in f32; they differ in summation order and in where values
# are rounded to bf16. Through 24 random-weight residual layers such
# rounding differences grow to a few percent of the logits, so a fixed
# bf16 step is no yardstick. The yardstick is measured instead: the same
# step through the xla path in float32 (same params, same cache). bf16
# rounding alone moves the xla path that far from it; two bf16 paths that
# are both right differ by at most twice that. Two hd-64 heads swapped in
# the paged path read 1.22 against a floor of 0.017 (CPU, smoke widths).
# A paged kernel that accumulates in bf16 is not caught here (it read 1.37 x
# the floor, CPU, 24 layers of d_model 256); the kernel check below is.
LOGITS_FLOOR_FACTOR = 2.0

# paged decode attention graph alone vs the f32 oracle, on the prefilled
# pool (L2 over the output). A sound bf16 kernel rounds twice: its output
# to bf16, and the softmax weights to bf16 for the PV matmul. Each is
# measured on the oracle (its output rounded; its weights rounded), and the
# floor combines the two as independent errors, sqrt(a^2 + b^2). A sound
# kernel read 0.99-1.02 x that floor, one accumulating in bf16 across
# pages 1.48-1.62 (CPU: smoke widths on the prefilled pool, and 16 KV
# heads of hd 64 on three random pools).
KERNEL_FLOOR_FACTOR = 1.2

# sharded vs one-device train step: the same bf16 step with the matmul
# reductions split over the model axis, so f32 partial sums are reordered
# and some bf16 roundings flip. Loss: absolute, ~0.1% of log(vocab).
# Gradient: AdamW's first moment after one step is 0.1 x the clipped
# gradient, so the worst leaf's |m_mesh - m_one| / |m_one|, and the
# relative grad-norm gap, read the sharded gradient itself. Update: the
# worst leaf's |g (d_mesh - d_one)| / |g d_one|, d = new minus initial
# params, weighted by the gradient g: AdamW's first step moves each weight
# by about lr * sign(g), so where g is within rounding of zero its sign,
# and so its step, is a coin toss; weighting by |g| keeps those weights
# from counting. A step that leaves the params unchanged reads 1.0. On 4
# CPU host devices at smoke widths a sound step read 0.037 (gradient) and
# 0.020 (update); a mesh step on half the batch read 1.14 and 0.85.
LOSS_ATOL = 1e-2
GRAD_RTOL = 0.2
UPDATE_RTOL = 0.2


def _tpu():
    """The first device, which must be a TPU (no CPU fallback)."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX reports platform "
                 f"{dev.platform!r}); this check runs only on a TPU")
    return dev


def _say(msg: str) -> None:
    print(msg, flush=True)


def serve_phase(kind: str):
    """Serve through the entry point; check every request's token budget."""
    from repro.launch import serve
    ap = argparse.ArgumentParser()
    serve.add_serve_args(ap)
    sargs = ap.parse_args([
        "--arch", ARCH, "--impl", "ff", "--policy-mode", "ff",
        "--requests", str(REQUESTS), "--slots", str(SLOTS),
        "--prompt-len", str(PROMPT_LEN), "--max-new", str(MAX_NEW),
        "--page", str(PAGE), "--rate", "0", "--seed", str(SEED)])
    t0 = time.perf_counter()
    result = serve.serve_bench(sargs)
    wall = time.perf_counter() - t0
    budgets = [r.max_new for r in serve.make_requests(
        sargs.requests, prompt_len=sargs.prompt_len, max_new=sargs.max_new,
        rate=sargs.rate, vocab=serve.get_config(ARCH).vocab,
        seed=sargs.seed)]
    for name in ("lockstep", "paged"):
        got = result[name]["per_request_tokens"]
        if got != budgets:
            raise AssertionError(f"{name}: tokens per request {got} != "
                                 f"budgets {budgets}")
        m = result[name]
        _say(f"serve {name}: {m['tokens']} tokens in {m['decode_steps']} "
             f"decode steps; prefill {m['prefill_s']:.3f} s, decode "
             f"{m['decode_s']:.3f} s on {kind}")
    _say(f"serve: every request got its budget {budgets}; whole serve_bench "
         f"call (compiles included) {wall:.1f} s on {kind}")
    diff = result["bitwise_max_abs_diff"]
    _say(f"parity probe: lockstep (dense cache) vs paged decode "
         f"max|dlogits| = {diff!r} "
         f"({'bitwise identical' if diff == 0.0 else 'NOT bitwise identical'})")
    return result


def logits_phase(kind: str) -> None:
    """One paged decode step through the ff kernels vs the xla path, on the
    same prefilled paged state and the same params."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import get_config
    from repro.core.program import PipePolicy
    from repro.launch import serve
    from repro.launch import steps as steps_lib
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.runtime import sharding as shlib
    from repro.runtime.paged_kv import PagedKVCache

    cfg = get_config(ARCH).replace(attn_impl="ff")
    model, model_x = build_model(cfg), build_model(
        cfg.replace(attn_impl="xla"))
    policy = PipePolicy(mode="ff")
    if policy.interpret and jax.default_backend() == "tpu":
        raise AssertionError("the ff kernels would run interpreted")
    rng = np.random.default_rng(SEED)
    lens = np.array(LOGITS_LENS, np.int32)
    page = PAGE
    n_pages = -(-int(lens.max() + 1) // page)
    mesh = make_host_mesh(devices=jax.devices()[:1])
    with shlib.use_sharding(mesh, overrides=dict(cfg.rule_overrides or {})):
        params = model.init(jax.random.key(SEED))
        prefill = jax.jit(steps_lib.make_prefill_step(model, policy=policy))
        kv = PagedKVCache(
            n_layers=cfg.n_layers, n_blocks=len(lens) * n_pages, page=page,
            kv_heads=cfg.n_kv_heads, head_dim=cfg.hd, n_slots=len(lens),
            n_pages_max=n_pages, dtype=cfg.cdtype)
        last = np.zeros(len(lens), np.int32)
        for i, n in enumerate(lens):
            toks = np.zeros((1, serve._bucket(int(n))), np.int32)
            toks[0, :n] = rng.integers(1, cfg.vocab, size=int(n))
            _, c = prefill(params, {"tokens": jnp.asarray(toks)})
            kv.admit(i, c["k"][:, 0], c["v"][:, 0], int(n), int(n) + 1)
            last[i] = toks[0, n - 1]
        kv.lengths[:] = lens - 1       # re-feed each prompt's last token
        batch = {"token": jnp.asarray(last),
                 "lengths": jnp.asarray(kv.lengths)}
        dec = jax.jit(steps_lib.make_decode_step(model, policy=policy))
        dec_x = jax.jit(steps_lib.make_decode_step(model_x, policy=policy))
        dec_32 = jax.jit(steps_lib.make_decode_step(build_model(
            cfg.replace(attn_impl="xla", compute_dtype="float32")),
            policy=policy))
        t0 = time.perf_counter()
        _, lf, _ = jax.block_until_ready(dec(params, batch, kv.cache_view()))
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        jax.block_until_ready(dec(params, batch, kv.cache_view()))
        warm = time.perf_counter() - t0
        _, lx, _ = dec_x(params, batch, kv.cache_view())
        view32 = dict(kv.cache_view())
        view32["kv_pool"] = view32["kv_pool"].astype(jnp.float32)
        _, l32, _ = dec_32(params, batch, view32)
    lf, lx, l32 = (np.asarray(a, np.float32) for a in (lf, lx, l32))
    if not lf.shape == lx.shape == l32.shape or lf.shape[0] != len(lens) \
            or not np.isfinite(lf).all():
        raise AssertionError(f"ff logits: shape {lf.shape} (xla "
                             f"{lx.shape}), finite "
                             f"{bool(np.isfinite(lf).all())}")
    err = float(np.max(np.abs(lf - lx)))
    floor = float(np.max(np.abs(lx - l32)))
    _say(f"paged decode step (ff kernels, {cfg.n_layers} layers, "
         f"{len(lens)} rows): first call (compile+run) {cold:.2f} s, "
         f"second call {warm * 1e3:.2f} ms on {kind}")
    _say(f"decode logits: max|ff - xla| = {err:.4g}; bf16 floor "
         f"max|xla - xla_f32| = {floor:.4g}; max|logits| = "
         f"{float(np.max(np.abs(l32))):.4g}; limit "
         f"{LOGITS_FLOOR_FACTOR:g} x floor")
    if not err <= LOGITS_FLOOR_FACTOR * floor:
        raise AssertionError(f"ff decode logits differ from the xla path "
                             f"by {err}, more than {LOGITS_FLOOR_FACTOR} x "
                             f"the bf16 floor {floor}")
    kernel_phase(kind, cfg, policy, kv, lens, rng)


def kernel_phase(kind, cfg, policy, kv, lens, rng) -> None:
    """The paged decode attention graph on layer 0 of the prefilled pool,
    with a random bf16 q, against the f32 oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.runtime import paged_kv

    q = jnp.asarray(rng.standard_normal((len(lens), cfg.n_heads, cfg.hd)),
                    cfg.cdtype)
    pool, tables = kv.cache_view()["kv_pool"][0], kv.device_tables()[0]
    lengths = jnp.asarray(lens)
    got = paged_kv.paged_decode_attention(q, pool, tables, lengths,
                                          policy=policy)
    with jax.default_matmul_precision("highest"):
        ref, ref_p = (np.asarray(paged_kv.paged_decode_attention_ref(
            q.astype(jnp.float32), pool, tables, lengths, p_dtype=p_dt))
            for p_dt in (None, cfg.cdtype))
    got = np.asarray(got, np.float32)
    if got.shape != ref.shape or not np.isfinite(got).all():
        raise AssertionError(f"paged attention: shape {got.shape} (oracle "
                             f"{ref.shape}), finite "
                             f"{bool(np.isfinite(got).all())}")
    err = float(np.linalg.norm(got - ref))
    out_floor = float(np.linalg.norm(
        np.asarray(jnp.asarray(ref, cfg.cdtype), np.float32) - ref))
    p_floor = float(np.linalg.norm(ref_p - ref))
    floor = float(np.hypot(out_floor, p_floor))
    _say(f"paged decode attention graph vs f32 oracle (layer 0, "
         f"{cfg.n_kv_heads} KV heads of hd {cfg.hd}, lengths "
         f"{list(map(int, lens))}): |d| = {err:.4g}, {err / floor:.4g} x "
         f"the bf16 floor {floor:.4g} (output rounding {out_floor:.4g}, "
         f"softmax-weight rounding {p_floor:.4g}; |oracle| "
         f"{float(np.linalg.norm(ref)):.4g}); limit "
         f"{KERNEL_FLOOR_FACTOR:g} x floor, on {kind}")
    if not err <= KERNEL_FLOOR_FACTOR * floor:
        raise AssertionError(f"paged decode attention differs from the f32 "
                             f"oracle by {err}, more than "
                             f"{KERNEL_FLOOR_FACTOR} x the bf16 floor "
                             f"{floor}")


def _worst_leaf_gap(ref, got, *, base=None, weight=None):
    """Max over leaves of |w (got - ref)| / |w ref| (L2 norms, float32 on
    the host), with ``base`` subtracted from both and ``w`` the leaf of
    ``weight`` (1 without); and that leaf's path."""
    import jax
    import numpy as np

    zeros = jax.tree.map(lambda x: 0, ref)
    worst, where = 0.0, None
    for (path, r), g, b, w in zip(
            jax.tree_util.tree_leaves_with_path(ref), jax.tree.leaves(got),
            jax.tree.leaves(zeros if base is None else base),
            jax.tree.leaves(zeros if weight is None else weight)):
        b = np.asarray(b, np.float32)
        w = 1.0 if weight is None else np.abs(np.asarray(w, np.float32))
        r = np.asarray(r, np.float32) - b
        d = float(np.linalg.norm(w * (np.asarray(g, np.float32) - b - r)))
        n = float(np.linalg.norm(w * r))
        gap = d / n if n else (0.0 if d == 0 else float("inf"))
        if not gap <= worst:            # a NaN gap is the worst
            worst, where = gap, jax.tree_util.keystr(path)
    return worst, where


def four_chip_phase(kind: str) -> None:
    """One train step on the (data 2, model 2) mesh vs one device."""
    import jax
    import numpy as np

    from repro.configs.base import get_config
    from repro.launch import steps as steps_lib
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.optim import adamw
    from repro.runtime import sharding as shlib

    devs = jax.devices()
    if len(devs) != 4:
        raise AssertionError(f"--four-chips needs 4 devices, found "
                             f"{len(devs)}")
    cfg = get_config(ARCH).replace(n_layers=TRAIN_LAYERS)
    model = build_model(cfg)
    params = model.init(jax.random.key(SEED))
    opt_cfg = adamw.AdamWConfig(lr_peak=1e-3, warmup_steps=1, total_steps=10)
    opt_init, _ = steps_lib.opt_init_and_update("adamw", opt_cfg)
    k1, k2 = jax.random.split(jax.random.key(SEED + 1))
    batch = {"tokens": jax.random.randint(k1, TRAIN_BATCH, 0, cfg.vocab),
             "labels": jax.random.randint(k2, TRAIN_BATCH, 0, cfg.vocab)}
    step = steps_lib.make_train_step(model, optimizer="adamw",
                                     opt_cfg=opt_cfg)

    def run(mesh):
        with shlib.use_sharding(mesh, overrides=dict(cfg.rule_overrides
                                                     or {})):
            t0 = time.perf_counter()
            out = jax.block_until_ready(
                jax.jit(step)(params, opt_init(params), batch))
            return out, time.perf_counter() - t0

    (p1, s1, m1), t1 = run(make_host_mesh(devices=devs[:1]))
    (p2, s2, m2), t2 = run(make_host_mesh(devices=devs[:4]))
    l1, l2 = float(m1["loss"]), float(m2["loss"])
    g1, g2 = float(m1["grad_norm"]), float(m2["grad_norm"])
    norm_gap = abs(g2 - g1) / g1
    grad_gap, grad_leaf = _worst_leaf_gap(s1["m"], s2["m"])
    upd_gap, upd_leaf = _worst_leaf_gap(p1, p2, base=params, weight=s1["m"])
    _say(f"train step ({cfg.n_layers} of {get_config(ARCH).n_layers} "
         f"layers, batch {TRAIN_BATCH}): one device {t1:.1f} s, (data 2, "
         f"model 2) {t2:.1f} s, compiles included, on {kind}")
    _say(f"loss one device {l1!r}, mesh {l2!r} (limit |d| {LOSS_ATOL:g}); "
         f"grad norm {g1!r} vs {g2!r}, relative gap {norm_gap!r} (limit "
         f"{GRAD_RTOL:g})")
    _say(f"worst leaf, mesh vs one device: gradient {grad_gap!r} at "
         f"{grad_leaf} (limit {GRAD_RTOL:g}); parameter update {upd_gap!r} "
         f"at {upd_leaf} (limit {UPDATE_RTOL:g}); no update reads 1.0")
    for v in (l1, l2, g1, g2):
        if not np.isfinite(v):
            raise AssertionError(f"non-finite train metric {v}")
    if not abs(l1 - l2) <= LOSS_ATOL:
        raise AssertionError(f"loss differs: {l1} vs {l2}")
    if not (norm_gap <= GRAD_RTOL and grad_gap <= GRAD_RTOL):
        raise AssertionError(f"sharded gradient differs: grad norm gap "
                             f"{norm_gap}, worst leaf {grad_gap} at "
                             f"{grad_leaf}")
    if not upd_gap <= UPDATE_RTOL:
        raise AssertionError(f"sharded parameter update differs: worst leaf "
                             f"{upd_gap} at {upd_leaf}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (data 2, model 2) train step vs one "
                         "device, on a 4-chip host")
    args = ap.parse_args(argv)

    dev = _tpu()
    import jax
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.core import autotune
    from repro.launch.compile_cache import enable_compile_cache

    kind = dev.device_kind
    _say(f"device: {dev.platform} {kind!r} x{len(jax.devices())}; compile "
         f"cache {enable_compile_cache()}")
    # the per-host plan cache stays inside the checkout (never ~/.cache)
    with autotune.tuning_config(
            cache_path=os.path.join(HERE, ".cache", "plans.json")):
        if args.four_chips:
            four_chip_phase(kind)
        else:
            serve_phase(kind)
            logits_phase(kind)
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        _say(f"peak_bytes_in_use {stats['peak_bytes_in_use']} on {kind}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
