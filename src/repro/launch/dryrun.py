import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay the first statements in this module: jax locks
the device count at first backend init, and the production meshes need 512
placeholder host devices (deliverable e).

Two-pass analysis per cell (see DESIGN.md §7):
  1. FULL pass — the production config, layers scanned: proves the sharded
     program lowers + compiles, and gives the true per-device memory
     footprint (``memory_analysis``). XLA's ``cost_analysis`` counts a scan
     body ONCE, so this pass cannot give FLOPs.
  2. COST pass — the same model at depth 1 and 2 "layer units" with every
     compute scan fully unrolled (``layers.unroll_scans``): cost_analysis
     and the collective-bytes HLO parse are exact there; per-unit deltas
     extrapolate linearly to the full depth (layers are shape-identical).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite_3_2b
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out results/dryrun.jsonl
"""

import argparse
import dataclasses
import json
import time
import traceback

from .. import configs
from ..analysis.hlo import parse_collectives
from ..analysis.terms import RooflineTerms, model_flops
from ..distributed import sharding as sh
from ..models import layers as layers_lib
from ..models.config import SHAPES, ModelConfig, cell_is_applicable
from ..models.transformer import StepConfig
from ..train.steps import build_step
from .mesh import make_production_mesh

MESHES = {"single": dict(multi_pod=False), "multi": dict(multi_pod=True)}


def layer_unit(cfg: ModelConfig) -> int:
    """Smallest layer count that preserves the arch's repeating structure."""
    if cfg.family == "vlm":
        return cfg.cross_attn_every
    if cfg.family == "hybrid" and cfg.attn_every:
        return cfg.attn_every
    if cfg.family == "granite_hybrid":
        from ..models.granite_hybrid import pattern
        periods, pre, post = pattern(cfg)
        return pre + 1 + post
    return 1


def scaled_config(cfg: ModelConfig, units: int) -> ModelConfig:
    unit = layer_unit(cfg)
    changes = {"n_layers": unit * units}
    if cfg.family == "encdec":
        changes["n_enc_layers"] = units
    return dataclasses.replace(cfg, **changes)


def _compile_cell(cfg, shape, mesh, rules, step_cfg):
    with mesh:
        bundle = build_step(cfg, shape, mesh, rules, step_cfg)
        return bundle.lower().compile()


def _costs(compiled, chips):
    ca = compiled.cost_analysis() or {}
    coll = parse_collectives(compiled.as_text(), chips)
    return (float(ca.get("flops", 0.0)),
            float(ca.get("bytes accessed", 0.0)),
            coll.total_bytes, coll)


def run_cell(arch: str, shape_name: str, mesh_name: str,
             step_cfg: StepConfig | None = None,
             rules_override: dict | None = None,
             cfg_override: dict | None = None,
             analyze: bool = True, verbose: bool = True) -> dict:
    """Lower + compile one cell; returns the record dict."""
    from ..train.steps import default_step_cfg
    cfg = configs.get(arch)
    if cfg_override:
        cfg = dataclasses.replace(cfg, **cfg_override)
    shape = SHAPES[shape_name]
    if step_cfg is None:
        step_cfg = default_step_cfg(cfg, shape)
    if not cell_is_applicable(cfg, shape):
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped",
                "reason": "full-attention arch; long_500k requires "
                          "sub-quadratic attention (DESIGN.md §4)"}
    mesh = make_production_mesh(**MESHES[mesh_name])
    chips = int(mesh.devices.size)
    rules = sh.TRAIN_RULES if shape.kind == "train" else sh.SERVE_RULES
    if rules_override:
        rules = rules.replace(**rules_override)
    t0 = time.perf_counter()
    try:
        # ---- pass 1: full config, scanned (compile + memory proof) ----
        compiled = _compile_cell(cfg, shape, mesh, rules, step_cfg)
        t_full = time.perf_counter() - t0
        ma = compiled.memory_analysis()
        peak_bytes = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                      + ma.temp_size_in_bytes)

        record = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                  "status": "ok", "chips": chips,
                  "compile_s": round(t_full, 1),
                  "args_gb": round(ma.argument_size_in_bytes / 1e9, 3),
                  "temp_gb": round(ma.temp_size_in_bytes / 1e9, 3),
                  "out_gb": round(ma.output_size_in_bytes / 1e9, 3),
                  "peak_gb": round(peak_bytes / 1e9, 3)}

        if analyze:
            # ---- pass 2: unrolled small-depth cost extrapolation ----
            unit = layer_unit(cfg)
            total_units = cfg.n_layers // unit
            with layers_lib.unroll_scans():
                c1 = _compile_cell(scaled_config(cfg, 1), shape, mesh, rules,
                                   step_cfg)
                f1, b1, cb1, _ = _costs(c1, chips)
                c2 = _compile_cell(scaled_config(cfg, 2), shape, mesh, rules,
                                   step_cfg)
                f2, b2, cb2, coll2 = _costs(c2, chips)
            flops = f1 + (f2 - f1) * (total_units - 1)
            bytes_ = b1 + (b2 - b1) * (total_units - 1)
            coll_bytes = cb1 + (cb2 - cb1) * (total_units - 1)
            terms = RooflineTerms(
                arch=cfg.name, shape=shape_name, mesh=mesh_name,
                flops_per_dev=flops, bytes_per_dev=bytes_,
                coll_bytes_per_dev=coll_bytes,
                coll_summary=coll2.summary(),
                peak_bytes_per_dev=peak_bytes,
                model_flops_total=model_flops(cfg, shape), chips=chips)
            record.update(terms.row())
            record.update({
                "flops_per_dev": flops, "bytes_per_dev": bytes_,
                "coll_bytes_per_dev": coll_bytes,
                "model_flops_total": terms.model_flops_total,
            })
            if verbose:
                print(f"[{arch} × {shape_name} × {mesh_name}] OK "
                      f"compile={t_full:.0f}s peak={record['peak_gb']}GB/dev")
                print(f"  roofline: compute={terms.compute_s*1e3:.2f}ms "
                      f"memory={terms.memory_s*1e3:.2f}ms "
                      f"collective={terms.collective_s*1e3:.2f}ms "
                      f"-> {terms.dominant}-bound "
                      f"useful={terms.useful_flops_ratio:.2f} "
                      f"mfu_bound={terms.mfu_bound:.3f}")
                print(f"  collectives(2-unit model): {terms.coll_summary}")
        elif verbose:
            print(f"[{arch} × {shape_name} × {mesh_name}] OK "
                  f"compile={t_full:.0f}s peak={record['peak_gb']}GB/dev")
        return record
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        if verbose:
            print(f"[{arch} × {shape_name} × {mesh_name}] FAIL: {e}")
            traceback.print_exc()
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "error", "error": f"{type(e).__name__}: {e}"}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append", default=None,
                    help="arch id(s); default: all")
    ap.add_argument("--shape", action="append", default=None,
                    choices=list(SHAPES), help="shape(s); default: all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-analysis", action="store_true",
                    help="compile-only (skip the cost-extrapolation pass)")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    args = ap.parse_args()

    archs = args.arch or configs.ARCH_IDS
    shapes = args.shape or list(SHAPES)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    n_ok = n_fail = n_skip = 0
    for arch in archs:
        for shape_name in shapes:
            for mesh_name in meshes:
                rec = run_cell(arch, shape_name, mesh_name,
                               analyze=not args.no_analysis)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(rec) + "\n")
                n_ok += rec["status"] == "ok"
                n_fail += rec["status"] == "error"
                n_skip += rec["status"] == "skipped"
    print(f"\ndry-run complete: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
