"""Benchmark driver — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Usage:
  PYTHONPATH=src python -m benchmarks.run [section ...]
"""
from __future__ import annotations

import sys
import time

from benchmarks import (bench_attention, bench_dse, bench_energy_area,
                        bench_fc, bench_kernel, bench_resnet,
                        bench_roofline, bench_scoreboard)
from repro.launch.compile_cache import enable_compile_cache

SECTIONS = {
    "dse": bench_dse.run,                # Fig. 9
    "fc": bench_fc.run,                  # Fig. 10
    "energy_area": bench_energy_area.run,  # Fig. 11 + Tbl. 2
    "attention": bench_attention.run,    # Fig. 12
    "scoreboard": bench_scoreboard.run,  # Fig. 13 + Sec. 5.9
    "resnet": bench_resnet.run,          # Fig. 14
    "kernel": bench_kernel.run,          # kernels + TPU memory story
    "roofline": bench_roofline.run,      # EXPERIMENTS.md §Roofline
}


def main() -> None:
    enable_compile_cache()
    picks = sys.argv[1:] or list(SECTIONS)
    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    for name in picks:
        SECTIONS[name]()
    print(f"all,{(time.perf_counter()-t0)*1e6:.0f},sections={picks}")


if __name__ == "__main__":
    main()
