import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).

DOC = """Roofline calibration: exact per-layer HLO costs via depth-Δ.

XLA's HloCostAnalysis counts a while-loop body ONCE regardless of trip
count, so the production (scanned) dry-run under-reports flops/bytes/
collective traffic by ~n_repeats. This tool lowers each cell at depth 1 and
depth 2 super-blocks with ALL scans unrolled, takes the per-super-block
delta, and extrapolates:

    corrected_X = X(1) + (n_repeats - 1) * (X(2) - X(1))

Known residual under-counts (documented in EXPERIMENTS.md §Roofline):
the sLSTM per-timestep scan and the mLSTM inter-chunk scan stay rolled
(unrolling 32k steps is not compilable); xlstm-125m train/prefill terms are
therefore lower bounds. Decode cells have no inner scans — exact.

Usage: python -m repro.launch.calibrate --out results/calib.json
"""

import argparse
import json
import time
import traceback

import jax

from repro.configs import get_config
from repro.configs.base import SHAPES
from repro.launch.dryrun import (DRYRUN_ARCHS, cell_skip_reason, lower_train,
                                 lower_decode, lower_prefill)
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import collective_bytes, model_flops, roofline_terms
from repro.models import attention, model


def _measure(cfg, shape, mesh):
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            lowered = lower_train(cfg, shape, mesh)
        elif shape.kind == "prefill":
            lowered = lower_prefill(cfg, shape, mesh)
        else:
            lowered = lower_decode(cfg, shape, mesh)
        compiled = lowered.compile()
    ca = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": coll["total"], "coll_detail": coll}


def calibrate_cell(arch: str, shape_name: str) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rec = {"arch": cfg.name, "shape": shape_name, "mesh": "16x16"}
    if cell_skip_reason(cfg, shape):
        rec["status"] = "skipped"
        return rec
    mesh = make_production_mesh(multi_pod=False)
    pat, tail = len(cfg.block_pattern), len(cfg.block_tail)
    repeats = cfg.n_repeats
    t0 = time.time()
    try:
        model.SCAN_UNROLL = True
        attention.ATTN_UNROLL = True
        xs = []
        for r in (1, 2):
            cal = cfg.replace(n_layers=r * pat + tail, grad_accum=1)
            xs.append(_measure(cal, shape, mesh))
        d = {k: xs[1][k] - xs[0][k] for k in ("flops", "bytes", "coll")}
        accum = 1  # calibration at accum=1 covers the same total tokens
        corr = {k: xs[0][k] + (repeats - 1) * d[k]
                for k in ("flops", "bytes", "coll")}
        terms = roofline_terms(corr["flops"], corr["bytes"], corr["coll"])
        mf = model_flops(cfg, shape)
        rec.update({
            "status": "ok", "compile_s": round(time.time() - t0, 1),
            "per_layer": {k: d[k] / pat for k in d},
            "once": {k: xs[0][k] - d[k] for k in d},
            "flops_per_dev": corr["flops"], "bytes_per_dev": corr["bytes"],
            "coll_bytes_per_dev": corr["coll"],
            "model_flops_global": mf,
            "useful_flops_ratio": mf / (corr["flops"] * 256)
            if corr["flops"] else 0.0,
            **terms,
        })
        del accum
    except Exception as e:
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-1500:]
    finally:
        model.SCAN_UNROLL = 1
        attention.ATTN_UNROLL = 1
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--out", default="results/calib.json")
    args = ap.parse_args()
    archs = DRYRUN_ARCHS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    existing = {}
    if os.path.exists(args.out):
        for r in json.load(open(args.out)):
            existing[(r["arch"], r["shape"])] = r
    for arch in archs:
        for shape in shapes:
            key = (get_config(arch).name, shape)
            if key in existing and existing[key]["status"] in ("ok",
                                                               "skipped"):
                print(f"[cached ] {key}")
                continue
            rec = calibrate_cell(arch, shape)
            existing[key] = rec
            msg = (f"dom={rec.get('dominant')} "
                   f"frac={rec.get('roofline_fraction', 0):.3f}"
                   if rec["status"] == "ok"
                   else rec.get("error", "")[:90])
            print(f"[{rec['status']:7s}] {key} {msg}", flush=True)
            with open(args.out, "w") as f:
                json.dump(list(existing.values()), f, indent=1)
    fails = sum(r["status"] == "fail" for r in existing.values())
    print(f"done; {fails} failures -> {args.out}")
    return 1 if fails else 0


if __name__ == "__main__":
    raise SystemExit(main())
