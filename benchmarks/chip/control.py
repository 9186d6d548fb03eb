#!/usr/bin/env python3
"""Readings that a cell's limits are set from: the program and the
control on the same served tokens, seed after seed, in one process.

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \\
        --seeds 11,12,13

Runs on a TPU. Each seed is one whole run of the cell (``run.run_cell``:
weights, warm-up, a window at the cell's own load, the drain), followed
by the reference over the sampled requests twice: at float32, which
gives the program's gaps, and with linear inputs and K/V rounded to 4
bits (the control, one step below the served 8), which gives the gaps of
the tokens the control ranks first. The control's gaps go through the
cell's own checks in the program's place (``run.verdict``). One JSON
line per seed, with each side's readings and ``correct``. The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--bits", type=int, default=4)
    args = ap.parse_args(argv)
    files = run.cell_files(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            res = run.run_cell(files, seed, args.seconds, False,
                               chips=files["cell"]["chips"],
                               control_bits=args.bits)
        except run.NoChip as e:
            print(f"control.py: {e}", file=sys.stderr)
            return 1
        c = res["checks"]
        print(json.dumps({
            "seed": seed, "failed": res["failed"],
            "checked_tokens": c["checked_tokens"]["value"],
            "program": {"gap_max": c["gap_max"]["value"],
                        "gap_mean": c["gap_mean"]["value"],
                        "correct": res["correct"]},
            "control": res["control"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
