import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces (into experiments/dryrun/<cell>.json):

  * proof of compilability on the production mesh (16x16) and the 2-pod
    mesh (2x16x16) — sharding mismatches / unsupported collectives fail here;
  * ``memory_analysis()`` of the full scanned program (bytes per device);
  * ``cost_analysis()`` + HLO collective stats of *unrolled* L=1 and L=2
    variants, from which the roofline extrapolates exact per-layer terms
    (scan bodies are counted once by XLA's cost model — measured, see
    DESIGN.md — so the scanned program's numbers are not used for FLOPs).

Usage:
  python -m repro.launch.dryrun --arch qwen2_72b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--skip-variants]
"""

import argparse
import json
import time
import traceback

import jax

from repro.configs.base import (ARCH_IDS, SHAPES, get_config,
                                shape_applicable)
from repro.launch import steps as steps_lib
from repro.launch.hlo_stats import collective_stats
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.models import build_model
from repro.runtime import sharding as shlib

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _mem_dict(m):
    return {
        "argument_bytes": m.argument_size_in_bytes,
        "output_bytes": m.output_size_in_bytes,
        "temp_bytes": m.temp_size_in_bytes,
        "alias_bytes": m.alias_size_in_bytes,
        "code_bytes": m.generated_code_size_in_bytes,
        "peak_bytes_est": (m.argument_size_in_bytes + m.output_size_in_bytes
                           + m.temp_size_in_bytes - m.alias_size_in_bytes),
    }


def _cost_dict(c):
    return {"flops": c.get("flops", 0.0),
            "bytes": c.get("bytes accessed", 0.0)}


def lower_cell(cfg, shape, mesh, overrides):
    """Lower the entry point for one cell; returns (lowered, model)."""
    with shlib.use_sharding(mesh, overrides=overrides) as ctx:
        model = build_model(cfg)
        sh = steps_lib.shardings_for_cell(model, shape, ctx,
                                          optimizer=cfg.optimizer)
        p_abs = model.abstract_params()
        batch_abs = model.input_specs(shape)
        if shape.kind == "train":
            train_step = steps_lib.make_train_step(model,
                                                   optimizer=cfg.optimizer)
            opt_init, _ = steps_lib.opt_init_and_update(cfg.optimizer)
            opt_abs = jax.eval_shape(opt_init, p_abs)
            fn = jax.jit(
                train_step,
                in_shardings=(sh["params"], sh["opt"], sh["batch"]),
                donate_argnums=(0, 1))
            lowered = fn.lower(p_abs, opt_abs, batch_abs)
        elif shape.kind == "prefill":
            fn = jax.jit(steps_lib.make_prefill_step(model),
                         in_shardings=(sh["params"], sh["batch"]))
            lowered = fn.lower(p_abs, batch_abs)
        else:
            cache_abs, _ = model.cache_spec(shape)
            fn = jax.jit(steps_lib.make_decode_step(model),
                         in_shardings=(sh["params"], sh["batch"],
                                       sh["cache"]),
                         donate_argnums=(2,))
            lowered = fn.lower(p_abs, batch_abs, cache_abs)
        return lowered, model


def _reduced_cfg(cfg, n_units: int):
    """Cost-extraction variant: n_units 'layer units', unrolled."""
    if cfg.family == "hybrid":
        k = cfg.attn_every_n
        return cfg.replace(n_layers=k * n_units, scan_layers=False)
    if cfg.family == "encdec":
        return cfg.replace(n_layers=n_units, n_enc_layers=n_units,
                           scan_layers=False)
    return cfg.replace(n_layers=n_units, scan_layers=False)


def n_layer_units(cfg) -> int:
    if cfg.family == "hybrid":
        return cfg.n_layers // cfg.attn_every_n
    return cfg.n_layers


def run_cell(arch_id: str, shape_name: str, *, multi_pod: bool,
             skip_variants: bool = False, out_dir: str = OUT_DIR,
             cfg_patch=None, tag: str = "", mesh_axes=None) -> dict:
    """mesh_axes: optional ((name, size), ...) replacing the production mesh
    (same chip count) — used by §Perf mesh-refactoring iterations."""
    cfg = get_config(arch_id)
    if cfg_patch:
        cfg = cfg.replace(**cfg_patch)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell = f"{arch_id}__{shape_name}__{mesh_name}{tag}"
    result = {"cell": cell, "arch": arch_id, "shape": shape_name,
              "mesh": mesh_name, "ok": False}

    ok, why = shape_applicable(cfg, shape)
    if not ok:
        result.update(skipped=True, reason=why, ok=True)
        _write(out_dir, cell, result)
        return result

    overrides = {**(cfg.rule_overrides or {}),
                 **(shape.rule_overrides or {})}
    if mesh_axes is not None:
        names = tuple(n for n, _ in mesh_axes)
        sizes = tuple(s for _, s in mesh_axes)
        mesh = make_mesh(sizes, names)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    try:
        t0 = time.time()
        lowered, model = lower_cell(cfg, shape, mesh, overrides)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        result["memory"] = _mem_dict(compiled.memory_analysis())
        result["cost_scan_program"] = _cost_dict(compiled.cost_analysis())
        result["timings"] = {"lower_s": t1 - t0, "compile_s": t2 - t1}
        result["n_params"] = model.param_count()
        result["n_active_params"] = model.active_param_count()
        result["n_layer_units"] = n_layer_units(cfg)
        result["ok"] = True
        del lowered, compiled

        if not skip_variants:
            variants = {}
            for nl in (1, 2):
                cfgv = _reduced_cfg(cfg, nl)
                lv, _ = lower_cell(cfgv, shape, mesh, overrides)
                cv = lv.compile()
                variants[f"L{nl}"] = {
                    **_cost_dict(cv.cost_analysis()),
                    "collectives": collective_stats(cv.as_text()),
                }
                del lv, cv
            result["variants"] = variants
    except Exception as e:   # noqa: BLE001 — report per-cell failures
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-4000:]
    _write(out_dir, cell, result)
    return result


def _write(out_dir, cell, result):
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{cell}.json"), "w") as f:
        json.dump(result, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--skip-variants", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    n_fail = 0
    for a, s in cells:
        r = run_cell(a, s, multi_pod=args.multi_pod,
                     skip_variants=args.skip_variants, out_dir=args.out)
        status = ("SKIP" if r.get("skipped")
                  else "OK" if r["ok"] else "FAIL")
        n_fail += status == "FAIL"
        mem = r.get("memory", {}).get("peak_bytes_est", 0) / 2**30
        print(f"[{status:4s}] {r['cell']:60s} peak={mem:7.2f} GiB "
              f"{r.get('error', '')}", flush=True)
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
