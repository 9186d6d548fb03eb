#!/usr/bin/env python3
"""Decode step time against the number of slots, on a TPU.

    python3 benchmarks/chip/slots.py --workload <cell> --seed <n> \\
        --slots 16,64,128,256 --steps 40

Builds the cell's model and weights once. For each slot count it builds
an engine with the cell's page size and ``max_len``, fills every slot
with a request of ``--prompt`` tokens that will not finish, and times
``--steps`` decode steps after two untimed ones. One row per count:
milliseconds a step, tokens per second (slots over step time) and the
device memory in use. Says what a deployment of the program gains from
more slots; not part of a run.
"""
from __future__ import annotations

import argparse
import gc
import sys
import time

import numpy as np

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--slots", required=True)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--prompt", type=int, default=128)
    args = ap.parse_args(argv)
    import jax

    from repro.serve import ServeEngine
    files = run.cell_files(args.workload)
    try:
        print(run.devices(files["cell"]["chips"]))
    except run.NoChip as e:
        print(f"slots.py: {e}", file=sys.stderr)
        return 1
    run.compile_cache()
    conf = files["conf"]
    dims = run.dims_of(conf)
    ref = run.reference(files)
    model = run.program(conf, dims, ref)
    params = run.served_params(ref, model, args.seed, dims,
                               conf["program"]["serve"]["w_bits"])
    e = conf["engine"]
    rng = np.random.default_rng(args.seed)
    print("| slots | ms a step | tokens/s | device GB in use |\n"
          "|---|---|---|---|")
    for n in (int(s) for s in args.slots.split(",")):
        eng = ServeEngine(model, params, n_slots=n, max_len=e["max_len"],
                          page_size=e["page_size"])
        for _ in range(n):
            eng.submit(rng.integers(0, dims["vocab"], args.prompt).tolist(),
                       e["max_len"] - args.prompt)
        for _ in range(3):               # the prefill wave, two decodes
            eng.step()
        assert len(eng.active) == n and not eng.queue
        t = time.perf_counter()
        for _ in range(args.steps):
            eng.step()
        ms = (time.perf_counter() - t) / args.steps * 1e3
        used = jax.devices()[0].memory_stats()["bytes_in_use"]
        print(f"| {n} | {ms:.2f} | {n / ms * 1e3:.0f} | {used / 1e9:.2f} |",
              flush=True)
        del eng
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
