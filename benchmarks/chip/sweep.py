#!/usr/bin/env python3
"""Find a chat cell's knee: the highest offered rate the engine sustains.

    python3 benchmarks/chip/sweep.py --workload <chat cell> --seed <n> \\
        --seconds <s> --rates 10,20,40

Runs on a TPU. Builds the cell once, then offers its mix at each rate in
turn (open loop, ``--seconds`` each, then a drain) and prints one row per
rate: offered and completed requests per second, the mean queue in the
first and second half of the window, and the latency tails. The queue
grows past the knee. The cell's mix then takes about four fifths of the
knee as its fixed ``rate_per_s``; the sweep is not part of a run.
"""
from __future__ import annotations

import argparse
import sys

import numpy as np

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import jax

    import drive
    from traffic import sizes
    from repro.serve import ServeEngine
    files = run.cell_files(args.workload)
    try:
        print(run.devices(files["cell"]["chips"]))
    except run.NoChip as e:
        print(f"sweep.py: {e}", file=sys.stderr)
        return 1
    run.compile_cache()
    conf, mix = files["conf"], files["traffic"]
    dims = run.dims_of(conf)
    ref = run.reference(files)
    model = run.program(conf, dims, ref)
    params = run.served_params(ref, model, args.seed, dims,
                               conf["program"]["serve"]["w_bits"])
    e = conf["engine"]
    eng = ServeEngine(model, params, n_slots=e["n_slots"],
                      max_len=e["max_len"], page_size=e["page_size"])
    drive.warm_up(eng, sizes.prompt_lengths(mix), dims["vocab"],
                  np.random.default_rng([args.seed, 3]))
    kind = run.traffic_kind(mix)
    kind.DRAIN_S = 30.0              # past the knee nothing drains anyway
    print("| offered req/s | completed req/s | queue 1st half | "
          "queue 2nd half | ttft p50 ms | ttft p95 ms | itl p50 ms | "
          "itl p95 ms | unfinished |\n|---|---|---|---|---|---|---|---|---|")
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        offered = kind.make(
            {**mix, "rate_per_s": rate}, args.seed + i, args.seconds,
            dims["vocab"])
        drv = drive.Driver(eng)
        queue = []
        win = offered.window(
            drv, args.seconds,
            on_tick=lambda el: queue.append((el, len(eng.queue))))
        done = [t for t in drv.done if t.times]
        ttft = [t.times[0] - t.due for t in done]
        gaps = np.concatenate([np.diff(t.times) for t in done
                               if len(t.times) > 1] or [[np.nan]])
        in_win = sum(t.req.done and t.times[-1] <= win["end"]
                     for t in drv.done)
        half = args.seconds / 2
        q1 = np.mean([q for el, q in queue if el < half] or [0])
        q2 = np.mean([q for el, q in queue if half <= el < args.seconds]
                     or [0])
        print(f"| {rate:g} | {in_win / args.seconds:.2f} | {q1:.1f} | "
              f"{q2:.1f} | {np.percentile(ttft, 50) * 1e3:.1f} | "
              f"{np.percentile(ttft, 95) * 1e3:.1f} | "
              f"{np.percentile(gaps, 50) * 1e3:.2f} | "
              f"{np.percentile(gaps, 95) * 1e3:.2f} | "
              f"{len(drv.live)} |", flush=True)
        for _ in range(len(eng.queue)):
            eng.queue.pop()
        while eng.active:
            eng.step()
    return 0


if __name__ == "__main__":
    sys.exit(main())
