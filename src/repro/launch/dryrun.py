import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
extract memory, cost, and loop-aware roofline analysis.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                    # full matrix
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --multi-pod-only   # (2,16,16)

Per cell, writes results/dryrun/<arch>__<shape>__<mesh>.json with:
  memory_analysis (per-device bytes), cost_analysis raw, loop-aware
  flops/bytes/collective table, roofline terms, MODEL_FLOPS + useful ratio.
"""
import argparse
import gc
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.common.config import (SHAPE_SETS, applicable_shapes, get_arch,
                                 list_archs)
from repro.launch.mesh import make_production_mesh
from repro.launch import specs as SP
from repro.models.api import get_model
from repro.models.dims import make_dims
from repro.parallel import (LOGICAL_RULES_MULTI_POD, LOGICAL_RULES_SINGLE_POD,
                            sharding_context)
from repro.parallel.hlo_analysis import analyze_hlo, PEAK_FLOPS
from repro.train.step import make_train_step


def model_flops(cfg, shape) -> float:
    """Analytic MODEL_FLOPS: 6*N*D (dense) / 6*N_active*D (MoE) for train;
    2*N*D for prefill; 2*N_active per token * new tokens for decode."""
    n = cfg.active_param_count()
    d_tokens = shape.seq_len * shape.global_batch
    if shape.kind == "train":
        return 6.0 * n * d_tokens
    if shape.kind == "prefill":
        return 2.0 * n * d_tokens
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             overwrite: bool = False) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell_id = f"{arch}__{shape_name}__{mesh_name}"
    path = os.path.join(out_dir, cell_id + ".json")
    if os.path.exists(path) and not overwrite:
        with open(path) as f:
            return json.load(f)
    cfg = get_arch(arch)
    shape = SHAPE_SETS[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = LOGICAL_RULES_MULTI_POD if multi_pod else LOGICAL_RULES_SINGLE_POD
    # batch=1 cells cannot shard the batch axis
    disabled = {"batch"} if shape.global_batch < mesh.shape["data"] else set()
    dims = make_dims(cfg, tp=mesh.shape["model"])
    mod = get_model(cfg)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind, "ok": False}
    t0 = time.time()
    try:
        with sharding_context(mesh, rules, disabled):
            state_shapes, state_specs = SP.state_shapes_and_specs(
                cfg, dims, shape.kind, shape)
            state_shardings = SP.to_shardings(mesh, state_specs)
            if shape.kind == "train":
                batch = SP.batch_specs(cfg, shape, with_labels=True)
                b_shardings = SP.to_shardings(
                    mesh, SP.batch_spec_axes(cfg, batch))
                step = make_train_step(cfg, dims, SP.opt_config_for(cfg),
                                       accum=SP.accum_for(cfg, shape))
                fn = jax.jit(step, in_shardings=(state_shardings, b_shardings),
                             donate_argnums=(0,))
                args = (state_shapes, batch)
            elif shape.kind == "prefill":
                batch = SP.batch_specs(cfg, shape, with_labels=False)
                b_shardings = SP.to_shardings(
                    mesh, SP.batch_spec_axes(cfg, batch))

                def pf(params, b):
                    return mod.prefill(params, b, cfg, dims)

                fn = jax.jit(pf, in_shardings=(state_shardings, b_shardings))
                args = (state_shapes, batch)
            else:  # decode
                b = shape.global_batch
                if cfg.frontend == "embed" and cfg.family != "encdec":
                    tok = {"embed": SP.sds((b, cfg.d_model), jnp.bfloat16)}
                    tok_axes = {"embed": ("batch", None)}
                else:
                    tok = {"token": SP.sds((b,), jnp.int32)}
                    tok_axes = {"token": ("batch",)}

                def dec(sd, tk, pos):
                    logits, st = mod.decode_step(
                        sd["params"], sd["state"], cfg, dims, pos=pos, **tk)
                    return logits, st

                fn = jax.jit(
                    dec,
                    in_shardings=(state_shardings,
                                  SP.to_shardings(mesh, tok_axes), None),
                    donate_argnums=(0,))
                args = (state_shapes, tok, SP.sds((), jnp.int32))
            lowered = fn.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis() or {}
            txt = compiled.as_text()
            hlo = analyze_hlo(txt)
        mf = model_flops(cfg, shape)
        per_dev_model_flops = mf / mesh.size
        roof = hlo.roofline()
        rec.update({
            "ok": True,
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "devices": mesh.size,
            "memory": {
                "argument_gb": mem.argument_size_in_bytes / 1e9,
                "output_gb": mem.output_size_in_bytes / 1e9,
                "temp_gb": mem.temp_size_in_bytes / 1e9,
                "alias_gb": mem.alias_size_in_bytes / 1e9,
                "peak_gb": (mem.argument_size_in_bytes
                            + mem.output_size_in_bytes
                            + mem.temp_size_in_bytes
                            - mem.alias_size_in_bytes) / 1e9,
            },
            "cost_analysis_flops_body_once": cost.get("flops", 0.0),
            "hlo": {
                "flops_per_dev": hlo.flops,
                "dot_flops_per_dev": hlo.dot_flops,
                "hbm_bytes_per_dev": hlo.hbm_bytes,
                "wire_bytes_per_dev": hlo.wire_bytes,
                "collective_counts": {k: round(v, 1) for k, v in
                                      hlo.collective_counts.items()},
                "collective_wire_bytes": hlo.collective_wire,
                "hlo_text_bytes": len(txt),
            },
            "roofline": roof,
            "model_flops_global": mf,
            "useful_flop_ratio": (per_dev_model_flops / hlo.flops
                                  if hlo.flops else 0.0),
            "roofline_fraction": (
                (per_dev_model_flops / PEAK_FLOPS) / roof["bound_s"]
                if roof["bound_s"] > 0 else 0.0),
        })
    except Exception as e:  # record failures for triage; dryrun must pass
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc(limit=12)
    os.makedirs(out_dir, exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    status = "OK " if rec["ok"] else "FAIL"
    extra = ""
    if rec["ok"]:
        extra = (f"peak={rec['memory']['peak_gb']:.2f}GB "
                 f"dom={rec['roofline']['dominant']} "
                 f"roof%={100*rec['roofline_fraction']:.1f} "
                 f"compile={rec['compile_s']}s")
    else:
        extra = rec["error"][:160]
    print(f"[{status}] {cell_id} {extra}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--overwrite", action="store_true")
    args = ap.parse_args()
    archs = [args.arch] if args.arch else list_archs()
    meshes = [False, True]
    if args.single_pod_only:
        meshes = [False]
    if args.multi_pod_only:
        meshes = [True]
    n_ok = n_fail = 0
    for arch in archs:
        cfg = get_arch(arch)
        shapes = ([SHAPE_SETS[args.shape]] if args.shape
                  else applicable_shapes(cfg))
        for mp in meshes:
            for shape in shapes:
                rec = run_cell(arch, shape.name, mp, args.out,
                               overwrite=args.overwrite)
                n_ok += rec["ok"]
                n_fail += not rec["ok"]
                gc.collect()
    print(f"dryrun done: {n_ok} ok, {n_fail} failed")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
