#!/usr/bin/env python3
"""Chip benchmark of the serving path: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<mix>.json``),
which the module of its ``kind`` (``traffic/<kind>.py``) turns into
load. The configuration names its reference (``references/<name>.py``,
see ``reference``), which says what the architecture is: the run builds
the served model from the configuration, checks its sizes and draws its
weights on the device from the seed through that module, warms every
program the mix can ask for, and then drives ``ServeEngine`` with the
mix for ``--seconds``. It checks what the window served against the
reference's plain float32 pass (limits in ``limits/<cell>.json``) and
prints one JSON line last.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
profiles the last few seconds of the window and reports the cell's
per-layer metrics, each read by ``metrics/<name>.py``. Without a TPU, or
with fewer chips than the cell asks for, it exits 1 and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                       # noqa: E402
import gc                                             # noqa: E402
import importlib.util                                 # noqa: E402
import json                                           # noqa: E402
import os                                             # noqa: E402
import shutil                                         # noqa: E402
import sys                                            # noqa: E402
import tempfile                                       # noqa: E402
import traceback                                      # noqa: E402
import types                                          # noqa: E402
from pathlib import Path                              # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

TRACE_S = 3.0        # traced part of the window, at its end
SAMPLE_ROWS = 8      # requests checked against the reference
MIN_CHECKED = 200    # served tokens they must hold at least


class NoChip(Exception):
    """No TPU, or fewer chips than the cell asks for."""


def load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def module(path: Path) -> types.ModuleType:
    spec = importlib.util.spec_from_file_location(
        path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def dims_of(conf: dict) -> dict:
    """The reference's sizes: each entry of ``dims`` is a key of the
    configuration file or a value stated in place."""
    return {k: conf[v] if isinstance(v, str) else v
            for k, v in conf["dims"].items()}


def cell_files(name: str) -> dict:
    bench = load(ROOT / "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    conf = load(ROOT / entry["file"])
    return {"bench": bench, "cell": cell, "conf": conf,
            "traffic": load(HERE / "traffic" / f"{cell['traffic']}.json"),
            "limits": HERE / "limits" / f"{name}.json",
            "reference": HERE / "references" / f"{conf['reference']}.py"}


def reference(files: dict) -> types.ModuleType:
    """The configuration's reference module, the one place that says what
    its architecture is: ``program_dims(cfg)`` (each ``dims`` key as the
    program's ``ModelConfig`` has it), ``builder(dims, bits)`` and
    ``program_params(seed, dims, bits)`` (the served pytree, drawn on the
    device), ``counts`` (operations and bytes of a decode step and a
    prefill, as ``counts.py``) and ``gaps(...)`` (the comparison). It may
    give ``layer_step(x, key, index, dims, bits, lowbits)``, its pass over
    one layer, for ``rehearse.py`` to compile against the chip's memory."""
    return module(files["reference"])


def traffic_kind(mix: dict) -> types.ModuleType:
    """``traffic/<kind>.py``: its ``make(mix, seed, seconds, vocab)``
    returns a load with ``setup(driver)`` (set-up, before the window) and
    ``window(driver, seconds, on_tick)`` -> {``t0``, ``end``, ``stop``,
    ``late_s``, ``cut``}; ``cut`` says the window cut what was in flight,
    which is then not attempted, and ``stop`` is when it stopped waiting
    for what was due."""
    return module(HERE / "traffic" / f"{mix['kind']}.py")


def devices(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    peaks = load(HERE / "peaks.json")["devices"]
    if devs[0].device_kind not in peaks:
        raise NoChip(f"no peaks for {devs[0].device_kind!r} in peaks.json")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, or
    where JAX_COMPILATION_CACHE_DIR says; every program is kept."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def program(conf: dict, dims: dict, ref: types.ModuleType):
    """The served model as the configuration file states it: every key of
    ``dims`` must be what the reference reads from the program's config
    (``ref.program_dims``)."""
    from repro.configs import get_config
    from repro.launch.specs import serve_config
    from repro.models.model import Model
    p = conf["program"]
    cfg = serve_config(get_config(p["arch"]).replace(**p["overrides"]),
                       **p["serve"])
    have = ref.program_dims(cfg)
    unknown = sorted(set(dims) - set(have))
    if unknown:
        raise ValueError(f"dims key(s) {unknown} are not stated by the "
                         f"reference's program_dims")
    wrong = {k: (have[k], v) for k, v in dims.items() if have[k] != v}
    if wrong:
        raise ValueError(f"program config differs from the file: {wrong}")
    return Model(cfg)


def served_params(ref: types.ModuleType, model, seed: int, dims: dict,
                  bits: int):
    """The reference's draw of the served weights, on the device, checked
    against the program's pytree, shapes and dtypes."""
    import jax

    import weights
    params = ref.program_params(seed, dims, bits)
    weights.check_layout(params, jax.eval_shape(model.init,
                                                jax.random.PRNGKey(0)))
    return params


def trace_numbers(path: str, conf: dict) -> dict | None:
    """``tracereduce.reduce`` of a trace, with the engine's phases and the
    model's scopes (``phases.reduce``) added as ``scope_s``,
    ``phase_idle_s`` and ``idle_by_phase``; None where ``tracereduce``
    finds nothing. The scopes are ``phases.SCOPES`` and any that the
    configuration lists under ``"scopes"``. Where the phases cannot be
    read, it says so on stderr and leaves those keys out."""
    import phases
    import tracereduce as tr
    ev = tr.events(path)
    red = tr.reduce(ev)
    if red is None:
        return None
    scopes = phases.SCOPES + tuple(conf.get("scopes", ()))
    try:
        ph = phases.reduce(phases.annotate(path, ev), scopes)
    except Exception:            # the run still reports every other metric
        print("[trace] phases and scopes not read:", file=sys.stderr)
        traceback.print_exc()
        return red
    red.update({k: ph[k] for k in ("scope_s", "phase_idle_s",
                                   "idle_by_phase")})
    return red


class CompileClock:
    """Compile events JAX reports while the ``with`` block runs."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.events, self.seconds = 0, 0.0

    def _duration(self, event, duration_secs, **_):
        if event in self.EVENTS:
            self.events += 1
            self.seconds += duration_secs

    def __enter__(self):
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._duration)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._duration)


def sample(done, seed: int) -> list:
    """Finished requests to check: the one with most served tokens and
    SAMPLE_ROWS - 1 others drawn from the seed."""
    import numpy as np
    order = sorted(done, key=lambda t: (-len(t.req.out), t.req.rid))
    rest = np.random.default_rng([seed, 7]).permutation(len(order))
    picked = order[:1] + [order[i] for i in rest if i][:SAMPLE_ROWS - 1]
    return [(list(t.req.prompt), list(t.req.out)) for t in picked]


def ref_length(mix: dict) -> int:
    """Positions of the reference's pass: the longest prompt plus the
    most new tokens the mix can ask for, in steps of 256."""
    from traffic import sizes
    n = sizes.prompt_lengths(mix)[1] + mix["output"]["max"]
    return -(-n // 256) * 256


AT_MOST = ("unfinished", "bad_length_or_id", "gap_max", "gap_mean")


def verdict(checks: dict) -> bool:
    """``correct``: every check within its limit. ``checks``: {name:
    [value, limit]}; each of ``AT_MOST`` at most its limit, and at least
    ``checked_tokens`` compared."""
    return bool(
        all(checks[k][1] is not None and checks[k][0] <= checks[k][1]
            for k in AT_MOST)
        and checks["checked_tokens"][0] >= checks["checked_tokens"][1])


def gap_checks(gaps, lim: dict) -> dict:
    return {"gap_max": [float(gaps.max(initial=0.0)), lim.get("gap_max")],
            "gap_mean": [float(gaps.mean()) if gaps.size else 0.0,
                         lim.get("gap_mean")],
            "checked_tokens": [int(gaps.size), MIN_CHECKED]}


def run_cell(files: dict, seed: int, seconds: float, trace: bool,
             chips: int = 1, control_bits: int = 0) -> dict:
    """One run of a cell; returns the result line as a dict. With
    ``control_bits``, the control's gaps take the program's place in the
    same checks, and their readings and verdict go under ``"control"``."""
    import jax
    import numpy as np

    import drive
    import tracereduce
    from traffic import sizes
    from repro.serve import ServeEngine
    dev = devices(chips)
    compile_cache()
    conf, mix, cell = files["conf"], files["traffic"], files["cell"]
    dims = dims_of(conf)
    bits = conf["program"]["serve"]["w_bits"]
    ref = reference(files)
    model = program(conf, dims, ref)
    params = served_params(ref, model, seed, dims, bits)
    e = conf["engine"]
    eng = ServeEngine(model, params, n_slots=e["n_slots"],
                      max_len=e["max_len"], page_size=e["page_size"])
    drive.warm_up(eng, sizes.prompt_lengths(mix), dims["vocab"],
                  np.random.default_rng([seed, 3]))
    drv = drive.Driver(eng)
    offered = traffic_kind(mix).make(mix, seed, seconds, dims["vocab"])
    offered.setup(drv)
    setup_s = time.perf_counter() - T_START

    tdir = tempfile.mkdtemp() if trace else None
    tstate = {}

    def on_tick(elapsed):
        if not trace:
            return
        if "start" not in tstate and elapsed >= seconds - TRACE_S:
            tracereduce.start(tdir)
            tstate["start"] = time.perf_counter()
        elif "start" in tstate and "stop" not in tstate \
                and elapsed >= seconds:
            tstate["stop"] = time.perf_counter()
            jax.profiler.stop_trace()
    with CompileClock() as clock:
        win = offered.window(drv, seconds, on_tick)
        if "start" in tstate and "stop" not in tstate:
            tstate["stop"] = time.perf_counter()
            jax.profiler.stop_trace()
    stats = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    tracks = drv.done + list(drv.live.values())
    attempted = drv.done if win["cut"] else tracks
    failed = [t for t in attempted if not t.req.done]
    vocab = dims["vocab"]
    bad = sum(len(t.req.out) != t.req.max_new_tokens
              or not all(0 <= x < vocab for x in t.req.out)
              for t in drv.done)
    ctx = types.SimpleNamespace(
        setup_s=setup_s, window=win, tracks=tracks, attempted=attempted,
        steps=drv.steps, dims=dims, bits=bits, trace=None,
        counts=ref.counts,
        peak=load(HERE / "peaks.json")["devices"].get(dev["kind"]),
        stop_s=win["stop"])
    result = {"correct": False, "attempted": len(attempted),
              "failed": len(failed), "metrics": {}, "device": dev}
    samples = sample(drv.done, seed)
    print(f"[window] {len(attempted)} attempted, {len(failed)} failed, "
          f"{len(drv.done)} done, {len(drv.steps)} steps; compiles in "
          f"window: {clock.events} ({clock.seconds:.3f} s); generator "
          f"late max {max(win['late_s'], default=0.0) * 1e3:.3f} ms over "
          f"{len(win['late_s'])} waits; setup {setup_s:.3f} s",
          file=sys.stderr)
    result["window_compiles"] = clock.events

    if trace:
        paths = list(Path(tdir).glob("plugins/profile/*/*.xplane.pb"))
        red = trace_numbers(str(paths[0]), conf) if paths else None
        shutil.rmtree(tdir, ignore_errors=True)
        if red is not None:
            t0, t1 = tstate["start"], tstate["stop"]
            ctx.trace = red
            ctx.steps = [s for s in drv.steps
                         if s.start >= t0 and s.end <= t1]
            dev["busy_s"] = red["busy_s"]
            dev["window_s"] = red["window_s"]
            result["breakdown"] = tracereduce.breakdown(red)
    kind = "per_layer" if trace else "end_to_end"
    for m in files["bench"][kind]:
        if files["cell"]["name"] not in m.get("workloads",
                                              [files["cell"]["name"]]):
            continue
        value = module(HERE / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value,
                                            "unit": m["unit"]}

    # the reference runs on a chip the program has left
    del eng, drv, params, stats
    gc.collect()
    length = ref_length(mix)
    gaps = np.concatenate(ref.gaps(seed, dims, bits, samples, length,
                                   SAMPLE_ROWS) if samples else [[]])
    lim = load(files["limits"]) if files["limits"].exists() else {}
    checks = {"unfinished": [len(failed), 0], "bad_length_or_id": [bad, 0],
              **gap_checks(gaps, lim)}
    if control_bits:
        cg = np.concatenate(ref.gaps(seed, dims, bits, samples, length,
                                     SAMPLE_ROWS, lowbits=control_bits)
                            if samples else [[]])
        cc = {**checks, **gap_checks(cg, lim)}
        result["control"] = {**{k: cc[k][0] for k in
                                ("gap_max", "gap_mean", "checked_tokens")},
                             "correct": verdict(cc)}
    result["correct"] = verdict(checks)
    result["checks"] = {k: {"value": v, "limit": lim_}
                        for k, (v, lim_) in checks.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    files = cell_files(args.workload)
    try:
        result = run_cell(files, args.seed, args.seconds, bool(args.trace),
                          chips=files["cell"]["chips"])
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
