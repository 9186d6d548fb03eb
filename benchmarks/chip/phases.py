#!/usr/bin/env python3
"""Engine phases and layer scopes in a profiler trace.

``ServeEngine.step()`` records its phases as host spans (``engine.admit``,
``engine.launch``, ``engine.sync``, ``engine.retire``; ``engine.sync``
also nests in ``engine.admit`` for prefill), and the served model names
three layers with ``jax.named_scope`` (``linear``, ``kv_gather``,
``attention``). This module reduces a trace to what they tell, next to
what ``tracereduce.py`` reduces from the harness's spans:

  engine_spans  {phase: [(start, end), ...]}
  phase_idle_s  {phase: [idle seconds of each harness ``step`` span whose
                innermost enclosing span is that phase]}
  idle_by_phase {span name or "none": idle seconds of the window by the
                innermost span around them (harness spans count too)}
  scope_s       {program: {scope or "unscoped": op self seconds}}
  scoped_ops    {``<program>/<scope>/<op>`` (``<program>/<op>`` when
                unscoped): op self seconds}

A device op's scope is the innermost of ``SCOPES`` (and of any scopes
a configuration adds) in its ``tf_op`` stat
(the name-scope path; a fusion's is that of its root op). The stat sits
in the event *metadata* of the device plane, which
``jax.profiler.ProfileData`` does not expose, so ``op_scopes`` reads it
from the ``.xplane.pb`` protobuf with a small wire-format reader.

    python3 benchmarks/chip/phases.py --workload <cell> --seed <n> \\
        --seconds <s>

runs a cell as ``run.py --trace 1`` does (which reduces its trace with
this module too, for the ``host_<phase>_ms`` and ``decode_<scope>_ms``
metrics) and prints one JSON line: the run's per-layer metrics, the
split of ``engine_host_ms`` by phase and of ``decode_step_ms`` by scope,
the ten ops that took most time by scope, and every program's time by
scope.
"""
from __future__ import annotations

import bisect
import re
import sys
from pathlib import Path

import tracereduce as tr

PHASES = ("engine.admit", "engine.launch", "engine.sync", "engine.retire")
SCOPES = ("linear", "kv_gather", "attention")
DEVICE_PLANE = r"/device:TPU:\d+"
U64 = (1 << 64) - 1


# -- protobuf wire format ----------------------------------------------------

def _varint(buf: bytes, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf: bytes, i: int, end: int):
    """(field number, value) of each field of the message in
    ``buf[i:end]``; a length-delimited value is its (start, end)."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_value(buf: bytes, span):
    """The value (field 2) of a protobuf map entry."""
    return next((v for f, v in _fields(buf, *span) if f == 2), (0, 0))


def op_scopes(path: str) -> list[tuple[str, dict]]:
    """For each device plane, in file order: (plane name, {(program id,
    op event name): ``tf_op`` path}). Field numbers are those of
    ``tsl/profiler/protobuf/xplane.proto``: XSpace.planes 1; XPlane.name
    2, lines 3 (skipped by its length), event_metadata 4, stat_metadata
    5; XEventMetadata.name 2, stats 5; XStatMetadata.id 1, name 2;
    XStat.metadata_id 1, uint64 3, int64 4, str 5, ref 7 (the id of a
    stat metadata whose name is the string)."""
    buf = Path(path).read_bytes()
    out = []
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for g, v in _fields(buf, *plane):
            if g == 2:
                name = _text(buf, v)
            elif g == 4:
                events.append(_map_value(buf, v))
            elif g == 5:
                meta = dict(_fields(buf, *_map_value(buf, v)))
                if 2 in meta:
                    stat_names[meta.get(1, 0)] = _text(buf, meta[2])
        if not re.fullmatch(DEVICE_PLANE, name):
            continue
        ids = {v: k for k, v in stat_names.items()}
        tf_id, pid_id = ids.get("tf_op"), ids.get("program_id")
        scopes = {}
        for ev in events:
            op, tf_op, pid = None, None, None
            for g, v in _fields(buf, *ev):
                if g == 2:
                    op = _text(buf, v)
                elif g == 5:
                    stat = dict(_fields(buf, *v))
                    sid = stat.get(1)
                    if sid == tf_id:
                        tf_op = (_text(buf, stat[5]) if 5 in stat
                                 else stat_names.get(stat.get(7)))
                    elif sid == pid_id:
                        pid = stat.get(3, stat.get(4))
            if op is not None and tf_op:
                scopes[(pid & U64 if pid is not None else None, op)] = tf_op
        out.append((name, scopes))
    return out


# -- reduction ---------------------------------------------------------------

def events(path: str, base=tr.events) -> dict:
    """``tracereduce.events`` (or ``base``) of the trace, ``annotate``d."""
    return annotate(path, base(path))


def annotate(path: str, ev: dict) -> dict:
    """``ev``, the trace's ``tracereduce.events``, with the engine's spans
    (``engine_spans``) and, on each device, its ops' ``tf_op`` paths
    (``tf_op``)."""
    from jax.profiler import ProfileData
    spans = {n: [] for n in PHASES}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in spans:
                        spans[e.name].append((e.start_ns, e.end_ns))
    ev["engine_spans"] = spans
    for dev, (_, scopes) in zip(ev["devices"], op_scopes(path)):
        dev["tf_op"] = scopes
    return ev


def scope_of(tf_op: str | None, scopes=SCOPES) -> str:
    """The innermost of ``scopes`` in a name-scope path, or
    ``unscoped``."""
    parts = (tf_op or "").split("/")
    return next((p for p in reversed(parts) if p in scopes), "unscoped")


def program_id(module: str) -> int | None:
    """``jit__decode_fn(6685304070188173580)`` -> 6685304070188173580."""
    m = re.search(r"\((\d+)\)$", module)
    return int(m.group(1)) & U64 if m else None


def innermost(spans) -> list[tuple[int, int, str]]:
    """Disjoint (start, end, name) pieces of nested (start, end, name)
    spans, each named for the innermost span holding it; time outside
    every span is left out. A span reaching past its parent is cut at
    the parent's end."""
    out, stack, t = [], [], 0
    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            _, pe, pn = stack.pop()
            if t < pe:
                out.append((t, pe, pn))
                t = pe
        if stack:
            if t < s:
                out.append((t, s, stack[-1][2]))
            e = min(e, stack[-1][1])
        stack.append((s, e, name))
        t = s
    while stack:
        _, pe, pn = stack.pop()
        if t < pe:
            out.append((t, pe, pn))
            t = pe
    return out


def reduce(ev: dict, scopes=SCOPES) -> dict | None:
    """The numbers of the engine's phases and the model's ``scopes`` in
    one traced window (``tracereduce``'s window); None where
    ``tracereduce.reduce`` gives None. A phase that recorded no span has
    no entry in ``phase_idle_s``."""
    base = tr.reduce(ev)
    if base is None:
        return None
    w0, w1 = base["window"]
    eng = {k: sorted(v) for k, v in ev.get("engine_spans", {}).items()
           if v}
    steps = base["spans"]["step"]
    step_starts = [s for s, _ in steps]
    pieces = [(max(s, w0), min(e, w1), n)
              for s, e, n in innermost([(s, e, n) for n, v in
                                        {**base["spans"], **eng}.items()
                                        for s, e in v])
              if e > w0 and s < w1]
    devs = [d for d in ev["devices"] if d["ops"]]
    phase_idle = {k: [0.0] * len(steps) for k in eng}
    idle_by_phase: dict[str, float] = {}
    scope_s: dict[str, dict[str, float]] = {}
    scoped_ops: dict[str, float] = {}
    for d in devs:
        busy = tr.merge((max(s, w0), min(e, w1)) for _, s, e in d["ops"]
                        if e > w0 and s < w1)
        idle = (w1 - w0) - sum(e - s for s, e in busy)
        for s, e, name in pieces:
            x = (e - s) - tr.overlap(busy, s, e)
            if not x:
                continue
            idle_by_phase[name] = idle_by_phase.get(name, 0.0) + x * 1e-9
            idle -= x
            i = bisect.bisect_right(step_starts, s) - 1
            if name in phase_idle and i >= 0 and s < steps[i][1]:
                phase_idle[name][i] += x * 1e-9
        if idle > 0:
            idle_by_phase["none"] = idle_by_phase.get("none", 0.0) \
                + idle * 1e-9
        mods = sorted((s, e, n) for n, s, e in d["modules"]
                      if w0 <= s < w1)
        starts = [m[0] for m in mods]
        tf_op = d.get("tf_op", {})
        for n, s, e, own in tr.self_times(d["ops"]):
            if not (w0 <= s < w1):
                continue
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][2] if i >= 0 and s < mods[i][1] else None
            owner = tr.program_name(mod) if mod else "?"
            scope = scope_of(tf_op.get((program_id(mod or ""), n)), scopes)
            per = scope_s.setdefault(owner, {})
            per[scope] = per.get(scope, 0.0) + own * 1e-9
            key = "/".join([owner] + [scope] * (scope != "unscoped")
                           + [tr.op_name(n)])
            scoped_ops[key] = scoped_ops.get(key, 0.0) + own * 1e-9
    n = len(devs)
    return {"engine_spans": eng,
            "phase_idle_s": {k: [x / n for x in v]
                             for k, v in phase_idle.items()},
            "idle_by_phase": {k: v / n for k, v in idle_by_phase.items()},
            "scope_s": {p: {k: v / n for k, v in per.items()}
                        for p, per in scope_s.items()},
            "scoped_ops": {k: v / n for k, v in scoped_ops.items()}}


def breakdown(red: dict) -> dict:
    """``tracereduce.breakdown`` by scope and by innermost span: the ten
    device ops that took most time, named ``<program>/<scope>/<op>``,
    and the idle time by the innermost span around it."""
    top = sorted(red["scoped_ops"].items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(red["idle_by_phase"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in idle]}


def split(base: dict, red: dict) -> dict:
    """Per traced step, milliseconds: ``host_<phase>_ms``, the device-idle
    time whose innermost span is that phase, and ``decode_<scope>_ms``,
    op self time in that scope per ``_decode_fn`` execution, for each of
    ``SCOPES`` (0 where it ran nothing), each other scope the reduction
    found, and ``unscoped``. A phase the trace does not hold is left out,
    and so are the scopes of a decode in which no op had one."""
    out = {}
    n_steps = len(base["spans"]["step"])
    for phase, idle in red["phase_idle_s"].items():
        if n_steps:
            out[f"host_{phase.split('.')[1]}_ms"] = sum(idle) / n_steps * 1e3
    runs = base["programs"].get("_decode_fn", [0.0, 0])[1]
    per = red["scope_s"].get("_decode_fn", {})
    if runs and set(per) - {"unscoped"}:
        for scope in dict.fromkeys(SCOPES + tuple(per) + ("unscoped",)):
            out[f"decode_{scope}_ms"] = per.get(scope, 0.0) / runs * 1e3
    return out


def main(argv=None) -> int:
    import argparse
    import json

    import run
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import phases as used        # the copy of this module that run.py uses
    seen = {}

    def kept(ev, scopes=SCOPES):
        seen["base"], seen["red"] = tr.reduce(ev), reduce(ev, scopes)
        return seen["red"]
    used.reduce = kept
    files = run.cell_files(args.workload)
    try:
        result = run.run_cell(files, args.seed, args.seconds, True,
                              chips=files["cell"]["chips"])
    except run.NoChip as e:
        print(f"phases.py: {e}", file=sys.stderr)
        return 1
    line = {"workload": args.workload, "seed": args.seed,
            "correct": result["correct"], "device": result["device"],
            "metrics": {k: v["value"] for k, v in result["metrics"].items()}}
    if seen.get("red") is not None:
        line["split"] = split(seen["base"], seen["red"])
        line["breakdown"] = breakdown(seen["red"])
        line["scope_s"] = seen["red"]["scope_s"]
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
