"""Reduce a profiler trace (``.xplane.pb``) to the numbers the readers use.

From the device planes (``/device:TPU:<n>``): the op intervals of the
``XLA Ops`` line, merged into busy intervals, and the program executions
of the ``XLA Modules`` line. From the host plane: the harness's spans
(``submit``, ``step``, ``wait``). Both planes share the trace's clock.

``reduce`` returns a plain dict:
  window      (start, end) ns: from the first host span's start to the
              last one's end
  window_s    its length in seconds
  busy_s      seconds in the window in which some op ran, averaged over
              the devices
  programs    {program name: [seconds, executions]} from the modules
  ops         {op name: self seconds}, prefixed with their program
  spans       {span name: [(start, end), ...]} of the harness's spans
  step_idle_s [idle seconds inside each ``step`` span]
  idle_by_span {span name or "none": idle seconds of the window inside
              that kind of span (spans do not nest), or outside all}
"""
from __future__ import annotations

import bisect
import re

HOST_SPANS = ("submit", "step", "wait")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def start(log_dir: str) -> None:
    """Start the profiler as every traced run does: device and host
    tracers on, Python's function tracer off (it would slow the host
    path being measured and swell the trace)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def op_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def program_name(module: str) -> str:
    """``jit__decode_fn(6685304070188173580)`` -> ``_decode_fn``."""
    name = re.sub(r"\(\d+\)$", "", module)
    return name[4:] if name.startswith("jit_") else name


def merge(intervals):
    """Union of (start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def self_times(ops):
    """(name, start, end, self ns) of each op: its length less the ops
    nested in it (a ``while`` holds its body's ops)."""
    out, stack = [], []
    for n, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        rec = [n, s, e, e - s]
        if stack:
            stack[-1][3] -= min(e, stack[-1][2]) - s
        out.append(rec)
        stack.append(rec)
    return [tuple(r) for r in out]


def overlap(busy, s, e) -> float:
    """Length of [s, e) covered by the disjoint sorted ``busy``."""
    i = max(0, bisect.bisect_right(busy, (s, float("inf"))) - 1)
    tot = 0.0
    while i < len(busy) and busy[i][0] < e:
        tot += max(0.0, min(e, busy[i][1]) - max(s, busy[i][0]))
        i += 1
    return tot


def events(path: str) -> dict:
    """{"devices": [{"ops": [...], "modules": [...]}], "spans": {...}},
    each event (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData
    devices, spans = [], {n: [] for n in HOST_SPANS}
    for plane in ProfileData.from_file(path).planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [(e.name, e.start_ns, e.end_ns)
                                for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in spans:
                        spans[e.name].append((e.start_ns, e.end_ns))
    return {"devices": devices, "spans": spans}


def reduce(ev: dict) -> dict | None:
    """The numbers of one traced window; None without device ops or
    host spans."""
    spans = {k: sorted(v) for k, v in ev["spans"].items()}
    edges = [x for v in spans.values() for x in v]
    devs = [d for d in ev["devices"] if d["ops"]]
    if not edges or not devs:
        return None
    w0, w1 = min(s for s, _ in edges), max(e for _, e in edges)
    busy_ns, programs, ops = 0.0, {}, {}
    idle_by_span: dict[str, float] = {}
    step_idle = [0.0] * len(spans["step"])
    for d in devs:
        busy = merge((max(s, w0), min(e, w1)) for _, s, e in d["ops"]
                     if e > w0 and s < w1)
        busy_ns += sum(e - s for s, e in busy)
        mods = sorted((s, e, program_name(n)) for n, s, e in d["modules"]
                      if w0 <= s < w1)
        for s, e, name in mods:
            p = programs.setdefault(name, [0.0, 0])
            p[0] += (e - s) * 1e-9
            p[1] += 1
        starts = [m[0] for m in mods]
        for n, s, e, own in self_times(d["ops"]):
            if not (w0 <= s < w1):
                continue
            i = bisect.bisect_right(starts, s) - 1
            owner = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
            key = f"{owner}/{op_name(n)}"
            ops[key] = ops.get(key, 0.0) + own * 1e-9
        for i, (s, e) in enumerate(spans["step"]):
            step_idle[i] += ((e - s) - overlap(busy, s, e)) * 1e-9
        idle = (w1 - w0) - sum(e - s for s, e in busy)
        for name, v in spans.items():
            x = sum((e - s) - overlap(busy, s, e) for s, e in v)
            if x:
                idle_by_span[name] = idle_by_span.get(name, 0.0) + x * 1e-9
            idle -= x
        if idle > 0:
            idle_by_span["none"] = idle_by_span.get("none", 0.0) \
                + idle * 1e-9
    n = len(devs)
    return {"window": (w0, w1), "window_s": (w1 - w0) * 1e-9,
            "busy_s": busy_ns * 1e-9 / n,
            "programs": {k: [v[0] / n, v[1] / n] for k, v in programs.items()},
            "ops": {k: v / n for k, v in ops.items()},
            "spans": spans,
            "step_idle_s": [x / n for x in step_idle],
            "idle_by_span": {k: v / n for k, v in idle_by_span.items()}}


def breakdown(red: dict) -> dict:
    """The ten device ops that took most time and the idle time by what
    the host was doing, as [name, seconds] lists."""
    top = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(red["idle_by_span"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in idle]}
