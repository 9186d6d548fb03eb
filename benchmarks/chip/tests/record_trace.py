"""Record the small device trace that ``test_trace.py`` reduces.

    python benchmarks/chip/tests/record_trace.py [--out DIR]

Runs on a TPU only. Serves a two-layer cut of smollm-135m (published
widths) through ``ServeEngine`` for a few steps under the JAX profiler,
with the harness's host spans around ``submit`` and ``step``, and writes
``small.xplane.pb`` next to this file (or under ``--out``). It also prints
every plane and line of the trace with its event count and a few event
names, which is how the reduction's plane and line names were found.
"""
from __future__ import annotations

import argparse
import glob
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[2]
sys.path[:0] = [str(HERE.parent), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(HERE))
    args = ap.parse_args()
    import jax
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    from jax.profiler import ProfileData, TraceAnnotation

    import tracereduce
    from repro.configs import get_config
    from repro.launch.specs import serve_config
    from repro.models.model import Model
    from repro.serve import ServeEngine

    cfg = serve_config(get_config("smollm-135m").replace(n_layers=2),
                       w_bits=4, backend="int_dot")
    model = Model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, n_slots=4, max_len=256, page_size=16)
    rng = np.random.default_rng(0)

    def wave():
        for n in (24, 40, 33):
            with TraceAnnotation("submit"):
                eng.submit(rng.integers(0, cfg.vocab, n).tolist(), 6)
        while eng.queue or eng.active:
            with TraceAnnotation("step"):
                eng.step()

    wave()                                   # compile outside the trace
    tmp = tempfile.mkdtemp()
    tracereduce.start(tmp)
    wave()
    jax.profiler.stop_trace()
    path = glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")[0]
    out = Path(args.out) / "small.xplane.pb"
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(path, out)
    shutil.rmtree(tmp)
    print(f"wrote {out} ({out.stat().st_size} bytes) on "
          f"{jax.devices()[0].device_kind}")
    for plane in ProfileData.from_file(str(out)).planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({e.name for e in evs})
            print(f"  LINE {line.name!r}: {len(evs)} events; "
                  f"{len(names)} names: {[n[:60] for n in names[:8]]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
