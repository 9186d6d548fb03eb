import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first init.

DOC = """Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this runs ``jax.jit(step).lower(...).compile()`` under the
production mesh — 16x16 single-pod and 2x16x16 multi-pod — and records
memory_analysis(), cost_analysis() and the collective schedule parsed from
the post-SPMD HLO. Failures (sharding mismatch, OOM-at-compile, unsupported
collectives) are system bugs and are recorded as such.

Usage:
  python -m repro.launch.dryrun --arch all --shape all --mesh both \
      --out results/dryrun.json
  python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k --mesh single
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get_config
from repro.configs.base import SHAPES
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (collective_bytes, model_flops,
                                   roofline_terms)
from repro.launch import specs as S

DRYRUN_ARCHS = [a for a in ARCHS if a != "llama1_7b"]  # 10 assigned archs


def cell_skip_reason(cfg, shape) -> str | None:
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return ("full quadratic attention at 524k context; run only for "
                "sub-quadratic archs (DESIGN.md §5)")
    return None


def lower_train(cfg, shape, mesh):
    from repro.train.train_step import make_train_step
    from repro.optim.schedule import cosine_schedule
    model, opt, sshape, bshape, sspec, bspec = S.train_cell_specs(
        cfg, shape, mesh)
    step = make_train_step(model, opt, cosine_schedule(3e-4, 100, 10000))
    return jax.jit(step, in_shardings=(sspec, bspec),
                   donate_argnums=0).lower(sshape, bshape)


def lower_decode(cfg, shape, mesh):
    scfg = S.serve_config(cfg)
    model, pshape, cshape, tok, pspec, cspec, tspec = S.serve_cell_specs(
        scfg, shape, mesh)

    def decode(params, caches, token, step):
        return model.decode_step(params, caches, token, step)

    return jax.jit(decode,
                   in_shardings=(pspec, cspec, tspec, None),
                   donate_argnums=1).lower(
        pshape, cshape, tok, jax.ShapeDtypeStruct((), jnp.int32))


def lower_prefill(cfg, shape, mesh):
    scfg = S.serve_config(cfg)
    model, pshape, batch, s_eff, pspec, bspec = S.prefill_cell_specs(
        scfg, shape, mesh)

    def prefill(params, batch):
        return model.prefill(params, batch, s_eff)

    return jax.jit(prefill, in_shardings=(pspec, bspec)).lower(pshape, batch)


def run_cell(arch: str, shape_name: str, multi_pod: bool) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = 512 if multi_pod else 256
    rec = {"arch": cfg.name, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "devices": n_dev, "kind": shape.kind}
    reason = cell_skip_reason(cfg, shape)
    if reason:
        rec["status"] = "skipped"
        rec["reason"] = reason
        return rec
    if cfg.max_target_positions and shape.seq_len > cfg.max_target_positions:
        rec["note"] = (f"seq clamped to architectural max "
                       f"{cfg.max_target_positions} (+{cfg.n_context_tokens}"
                       f" encoder frames)")
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            if shape.kind == "train":
                lowered = lower_train(cfg, shape, mesh)
            elif shape.kind == "prefill":
                lowered = lower_prefill(cfg, shape, mesh)
            else:
                lowered = lower_decode(cfg, shape, mesh)
            compiled = lowered.compile()
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        coll = collective_bytes(compiled.as_text())
        flops = float(ca.get("flops", 0.0))
        byts = float(ca.get("bytes accessed", 0.0))
        terms = roofline_terms(flops, byts, coll["total"])
        mf = model_flops(cfg, shape)
        rec.update({
            "status": "ok",
            "compile_s": round(time.time() - t0, 1),
            "arg_bytes_per_dev": int(ma.argument_size_in_bytes),
            "temp_bytes_per_dev": int(ma.temp_size_in_bytes),
            "out_bytes_per_dev": int(ma.output_size_in_bytes),
            "hlo_flops_per_dev": flops,
            "hlo_bytes_per_dev": byts,
            "collectives": {k: coll[k] for k in
                            ("all-reduce", "all-gather", "reduce-scatter",
                             "all-to-all", "collective-permute", "total",
                             "count")},
            "model_flops_global": mf,
            "useful_flops_ratio": (mf / (flops * n_dev))
            if flops else 0.0,
            **terms,
        })
    except Exception as e:  # a failed cell is a bug — record it loudly
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all",
                    choices=["all", *SHAPES.keys()])
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun.json")
    args = ap.parse_args()

    archs = DRYRUN_ARCHS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    existing = {}
    if os.path.exists(args.out):
        for r in json.load(open(args.out)):
            existing[(r["arch"], r["shape"], r["mesh"])] = r

    records = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                cfgname = get_config(arch).name
                key = (cfgname, shape, "2x16x16" if mp else "16x16")
                if key in existing and existing[key].get("status") == "ok":
                    records.append(existing[key])
                    print(f"[cached] {key}")
                    continue
                rec = run_cell(arch, shape, mp)
                records.append(rec)
                status = rec["status"]
                extra = (f"compile {rec.get('compile_s')}s "
                         f"dom={rec.get('dominant')}"
                         if status == "ok" else rec.get("error", rec.get(
                             "reason", "")))[:110]
                print(f"[{status:7s}] {key} {extra}", flush=True)
                # merge + persist incrementally
                existing[key] = rec
                with open(args.out, "w") as f:
                    json.dump(list(existing.values()), f, indent=1)
    ok = sum(r["status"] == "ok" for r in records)
    sk = sum(r["status"] == "skipped" for r in records)
    fl = sum(r["status"] == "fail" for r in records)
    print(f"\n{ok} ok / {sk} skipped / {fl} FAILED "
          f"of {len(records)} cells -> {args.out}")
    return 1 if fl else 0


if __name__ == "__main__":
    raise SystemExit(main())
