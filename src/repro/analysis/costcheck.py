"""Static cost certifier: budget every serving program without a timer.

The paper's ahead-of-time-analyzability premise cuts both ways: if the
execution schedule is a pure function of the input signature, then so is
its *cost*. This module derives per-program cost metrics from the traced
jaxpr (scan-trip-weighted gather counts and bytes, scatter-in-loop
counts, peak live-buffer footprint, KV-pool read traffic via a
view-tracking walk) and from the plan IR itself (level/edge/gather
counts), cross-checks the plan-derived op counts against the analytical
cost model (``core/costmodel.py`` / ``core/patterns.py`` — the two must
be the *same* arithmetic or the DSE story models a machine the kernels
don't run), and enforces declarative budgets from
``analysis/budgets.json``. A budget violation is an ordinary
:class:`~repro.analysis.rules.Finding` (rule ``cost-budget``), so it
baselines, reports and fails CI exactly like a tracelint finding — a
perf gate that needs no timer and cannot flake.

The two headline budgets:

* ``live-page-decode`` — the Pallas paged-attention decode's KV-pool
  read traffic is O(live pages), not O(max_len): the certifier traces
  the program at ``max_len`` and ``2 * max_len`` and the bytes gathered
  *from the pool* (taint-tracked from the donated pool argument range)
  must not grow. The oracle paged decode, which gathers the whole page
  table each step, fails this budget by construction — that asymmetry
  is the regression test for the fast path.
* ``swap-trace-count`` — a pad-aligned hot swap re-traces the decode
  jit zero times (``decode_jit_traces == 1`` across the swap); a
  drifted swap demonstrably fails it.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Callable, Iterator

import numpy as np
from jax.extend.core import Literal

from repro.analysis.rules import Finding
from repro.analysis.walker import LOOP_PRIMS, SCATTER_PRIMS, subjaxprs

__all__ = ["CostMetrics", "jaxpr_cost", "plan_cost",
           "crosscheck_costmodel", "load_budgets", "program_metrics",
           "growth_ratio", "swap_trace_count", "check_budgets",
           "DEFAULT_BUDGETS"]

DEFAULT_BUDGETS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "budgets.json")
_BUDGET_FORMAT = 1

# primitives that read memory through an index vector
GATHER_PRIMS = frozenset({"gather", "dynamic_slice"})
# single-operand structural transforms: the output is still "the same
# buffer" for the purposes of pool-read attribution (view tracking)
VIEW_PRIMS = frozenset({"reshape", "transpose", "convert_element_type",
                        "bitcast_convert_type", "squeeze",
                        "broadcast_in_dim", "slice", "rev", "copy",
                        "dynamic_update_slice", "copy_p", *SCATTER_PRIMS})


@dataclasses.dataclass
class CostMetrics:
    """Signature-determined costs of one traced program.

    ``*_dynamic`` / byte fields are **scan-weighted**: an equation
    inside a ``lax.scan`` of length L counts L times (nested scans
    multiply), so the numbers are per-call costs, not per-trace counts.
    ``pool_*`` fields only fill when the caller names a pool argument
    range; ``*_unguarded`` excludes equations inside ``lax.cond``
    branches (runtime-skippable work — the live-page kernel's dead-page
    loads live there).
    """
    eqns: int = 0
    eqns_dynamic: float = 0.0
    gathers: int = 0
    gathers_dynamic: float = 0.0
    gather_bytes: float = 0.0
    gather_bytes_unguarded: float = 0.0
    pool_gathers: int = 0
    pool_gather_bytes: float = 0.0
    pool_gather_bytes_unguarded: float = 0.0
    scatters: int = 0
    scatter_in_loop: int = 0
    scatter_in_loop_dynamic: float = 0.0
    while_loops: int = 0
    peak_live_bytes: int = 0

    def to_json(self) -> dict[str, float]:
        return {k: (round(v, 1) if isinstance(v, float) else v)
                for k, v in dataclasses.asdict(self).items()}


def _aval_bytes(v: Any) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return int(np.prod(shape, dtype=np.int64)) * int(
        np.dtype(dtype).itemsize)


def _inner(jaxpr: Any) -> Any:
    return jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr


def _walk(jaxpr: Any, view_in: list[bool], weight: float, in_loop: bool,
          guarded: bool, acc: CostMetrics) -> list[bool]:
    """Accumulate costs; returns which outvars are pool views."""
    j = _inner(jaxpr)
    views = {v for v, t in zip(j.invars, view_in) if t}
    for eqn in j.eqns:
        name = eqn.primitive.name
        inv = [(not isinstance(v, Literal)) and v in views
               for v in eqn.invars]
        acc.eqns += 1
        acc.eqns_dynamic += weight
        if name in GATHER_PRIMS:
            nbytes = sum(_aval_bytes(v) for v in eqn.outvars)
            acc.gathers += 1
            acc.gathers_dynamic += weight
            acc.gather_bytes += weight * nbytes
            if not guarded:
                acc.gather_bytes_unguarded += weight * nbytes
            if inv and inv[0]:
                acc.pool_gathers += 1
                acc.pool_gather_bytes += weight * nbytes
                if not guarded:
                    acc.pool_gather_bytes_unguarded += weight * nbytes
        if name in SCATTER_PRIMS:
            acc.scatters += 1
            if in_loop:
                acc.scatter_in_loop += 1
                acc.scatter_in_loop_dynamic += weight
        if name == "while":
            acc.while_loops += 1
        sub_w = weight * (int(eqn.params.get("length", 1))
                          if name == "scan" else 1)
        sub_guard = guarded or name == "cond"
        sub_loop = in_loop or name in LOOP_PRIMS
        entered = False
        for _label, sub in subjaxprs(eqn):
            entered = True
            sj = _inner(sub)
            n = len(sj.invars)
            if name == "cond":
                sub_view = inv[1:1 + n]        # invars[0] is the index
            else:                              # pjit/scan/...: positional
                sub_view = inv[:n]
            sub_view = sub_view + [False] * (n - len(sub_view))
            out_view = _walk(sub, sub_view, sub_w, sub_loop, sub_guard,
                             acc)
            for v, t in zip(eqn.outvars, out_view):
                if t:
                    views.add(v)
        # a combining scatter (add, mul, ...) carries its combiner as a
        # sub-jaxpr; its result is still the operand's buffer
        if (name in VIEW_PRIMS and inv and inv[0]
                and (not entered or name in SCATTER_PRIMS)):
            for v in eqn.outvars:
                views.add(v)
    return [(not isinstance(v, Literal)) and v in views
            for v in j.outvars]


def _peak_live_bytes(jaxpr: Any) -> int:
    """Top-level liveness scan: peak sum of live aval bytes.

    Inputs are live from the start, every var dies after its last use
    (outputs at the end) — a coarse upper-structure metric, but it is
    signature-determined and moves when someone materialises a second
    KV cache."""
    j = _inner(jaxpr)
    last_use: dict[Any, int] = {}
    n = len(j.eqns)
    for i, eqn in enumerate(j.eqns):
        for v in eqn.invars:
            if not isinstance(v, Literal):
                last_use[v] = i
    for v in j.outvars:
        if not isinstance(v, Literal):
            last_use[v] = n
    live = {v: _aval_bytes(v) for v in j.invars}
    peak = cur = sum(live.values())
    for i, eqn in enumerate(j.eqns):
        for v in eqn.outvars:
            if v not in live:
                live[v] = _aval_bytes(v)
                cur += live[v]
        peak = max(peak, cur)
        for v in list(live):
            if last_use.get(v, n) <= i:
                cur -= live.pop(v)
    return int(peak)


def jaxpr_cost(jaxpr: Any, *,
               pool_range: tuple[int, int] | None = None) -> CostMetrics:
    """Derive :class:`CostMetrics` from a (Closed)Jaxpr.

    ``pool_range`` names the ``[start, stop)`` flattened-invar range of
    the KV pool (the same range ``LintProgram.donate_expect`` carries);
    gathers whose operand is a *view* of those invars fill the
    ``pool_*`` fields.
    """
    j = _inner(jaxpr)
    n_in = len(j.invars)
    if pool_range is None:
        view_in = [False] * n_in
    else:
        start, stop = pool_range
        view_in = [start <= i < stop for i in range(n_in)]
    acc = CostMetrics()
    _walk(jaxpr, view_in, 1.0, False, False, acc)
    acc.peak_live_bytes = _peak_live_bytes(jaxpr)
    return acc


def program_metrics(prog: Any) -> CostMetrics:
    """Metrics for one :class:`~repro.analysis.rules.LintProgram`; the
    pool range comes from its ``donate_expect`` when present."""
    pool = None
    for label, (start, stop) in (prog.donate_expect or {}).items():
        pool = (start, stop)
    return jaxpr_cost(prog.jaxpr, pool_range=pool)


# ---------------------------------------------------------------------------
# Plan-IR costs + cost-model cross-check
# ---------------------------------------------------------------------------

def plan_cost(plan: Any) -> dict[str, int]:
    """Per-call costs read straight off the plan IR (host side)."""
    t, size = int(plan.t), 1 << int(plan.t)
    j = plan.k // plan.t
    r = j * size
    s, n = int(plan.bits), int(plan.n)
    step_edges = sum(int(np.asarray(st.tile).size) for st in plan.steps)
    direct_adds = int(np.asarray(plan.direct_bits).sum())
    return {
        "levels": len(plan.steps),
        "psum_rows": r,
        "step_edges": step_edges,
        "direct_lanes": int(np.asarray(plan.direct_tile).size),
        "direct_adds": direct_adds,
        "ppe_adds": step_edges + direct_adds,
        # each level is two whole-table gathers (psum + activation)
        "level_gather_rows": 2 * t * r,
        "ape_gather_rows": s * n * j,
    }


def crosscheck_costmodel(plan: Any, *, backend: str | None = None,
                         name: str = "plan") -> list[Finding]:
    """The plan IR and the analytical cost model must count the same ops.

    ``core/patterns.py``'s :func:`tile_stats` (which feeds
    ``core/costmodel.py``'s TransitiveArrayModel via the scoreboard) and
    the executable schedule are two derivations of the same quantities:

    * ``ppe_ops`` (prefix-chain adds) == schedule step edges + direct
      subset-sum adds;
    * ``ape_ops`` (output accumulations) == nonzero TransRows
      == S*N*J - zero rows.

    Disagreement means the DSE/roofline story budgets a machine the
    kernels don't run — an error finding, not a warning.
    """
    from repro.core.patterns import tile_stats
    ts = tile_stats(plan.si)
    pc = plan_cost(plan)
    out: list[Finding] = []
    ppe_model = int(np.asarray(ts.ppe_ops).sum())
    if ppe_model != pc["ppe_adds"]:
        out.append(Finding(
            rule="cost-model-agreement", severity="error", program=name,
            backend=backend, path="ppe_ops", primitive="ppe_ops",
            message=f"cost model counts {ppe_model} PPE adds but the "
            f"schedule executes {pc['ppe_adds']} ({pc['step_edges']} "
            f"step edges + {pc['direct_adds']} direct adds) — the "
            f"analytical model and the plan IR have diverged"))
        return out
    ape_model = int(np.asarray(ts.ape_ops).sum())
    s, n = int(plan.bits), int(plan.n)
    j = plan.k // plan.t
    zr = int(np.asarray(ts.zr).sum())
    if ape_model != s * n * j - zr or ape_model > s * n * j:
        out.append(Finding(
            rule="cost-model-agreement", severity="error", program=name,
            backend=backend, path="ape_ops", primitive="ape_ops",
            message=f"cost model counts {ape_model} APE accumulations "
            f"but the plan implies {s * n * j - zr} nonzero TransRows "
            f"(S*N*J={s * n * j}, zero rows={zr})"))
    return out


# ---------------------------------------------------------------------------
# Declarative budgets
# ---------------------------------------------------------------------------

def load_budgets(path: str | os.PathLike | None = None) -> dict[str, Any]:
    """Load and validate the budgets file (default: the in-tree one)."""
    path = DEFAULT_BUDGETS if path is None else path
    with open(path) as f:
        data = json.load(f)
    if not isinstance(data, dict) or data.get("format") != _BUDGET_FORMAT:
        raise ValueError(f"{path}: not a format-{_BUDGET_FORMAT} budgets "
                         f"file (got format={data.get('format')!r})")
    for i, b in enumerate(data.get("budgets", [])):
        missing = [k for k in ("name", "program", "metric", "max")
                   if k not in b]
        if missing:
            raise ValueError(f"{path}: budgets[{i}] is missing {missing}")
    return data


def growth_ratio(backend: str, program: str, metric: str, *,
                 mesh: Any = None, arch: str = "smollm-135m",
                 scales: tuple[int, int] = (16, 32)
                 ) -> tuple[float, dict[str, float]]:
    """Trace ``program`` at two ``max_len`` scales; ratio of ``metric``.

    The +1 regularisation keeps a 0 -> 0 metric (the kernel path's pool
    reads) at ratio 1.0 instead of 0/0.
    """
    from repro.analysis.programs import build_programs
    values = {}
    for ml in scales:
        progs = {p.name: p for p in build_programs(
            backend, mesh=mesh, arch=arch, max_len=ml)}
        if program not in progs:
            raise KeyError(f"backend {backend!r} builds no {program!r} "
                           f"program")
        m = program_metrics(progs[program])
        values[f"max_len={ml}"] = float(getattr(m, metric))
    lo, hi = (values[f"max_len={s}"] for s in scales)
    return (hi + 1.0) / (lo + 1.0), values


def _map_device_plans(tree: Any, fn: Callable[[Any], Any]) -> Any:
    from repro.core.engine import DevicePlan
    if isinstance(tree, DevicePlan):
        return fn(tree)
    if isinstance(tree, dict):
        return {k: _map_device_plans(v, fn) for k, v in tree.items()}
    if isinstance(tree, list):
        return [_map_device_plans(v, fn) for v in tree]
    if isinstance(tree, tuple):
        return tuple(_map_device_plans(v, fn) for v in tree)
    return tree


def swap_trace_count(*, backend: str = "engine_jit",
                     arch: str = "smollm-135m", aligned: bool = True,
                     mesh: Any = None) -> int:
    """Decode jit trace count across one hot swap (the static scenario
    behind the ``swap-trace-count`` budget).

    Builds two weight generations, serves a request on generation 0,
    stages a swap, drains a generation-1 request, and reads the
    engine's true decode trace counter. ``aligned=False`` deliberately
    widens the new generation's DevicePlans (the drift
    ``align_device_plans`` exists to prevent) — the hand-broken twin
    that must push the count to 2.
    """
    import jax
    from repro.configs import get_reduced
    from repro.core.engine import pad_device_plan
    from repro.fleet import build_generation
    from repro.launch.specs import serve_config
    from repro.models.model import Model
    from repro.serve import ServeEngine

    cfg = serve_config(get_reduced(arch).replace(n_layers=2),
                       backend=backend)
    model = Model(cfg)
    raw0 = model.init(jax.random.PRNGKey(0))
    raw1 = model.init(jax.random.PRNGKey(1234))
    gen0 = build_generation(model, raw0, gen=0, mesh=mesh)
    gen1 = build_generation(model, raw1, ref=gen0.params, gen=1,
                            mesh=mesh)
    p1 = gen1.params
    if not aligned:
        p1 = _map_device_plans(
            p1, lambda d: pad_device_plan(
                d, int(np.asarray(d.direct_idx).shape[-1]) + 4))
    eng = ServeEngine(model, gen0.params, n_slots=2, max_len=16,
                      page_size=4)
    prompt = tuple(range(1, 9))
    eng.submit(prompt, 4)
    eng.step()
    eng.step()
    eng.swap_params(p1, tag="costcheck")
    eng.submit(prompt, 4)
    while eng.queue or eng.active:
        eng.step()
    return int(eng.stats()["decode_jit_traces"])


def check_budgets(backend_names: list[str], *, mesh: Any = None,
                  budgets_path: str | os.PathLike | None = None,
                  arch: str = "smollm-135m"
                  ) -> tuple[list[dict], list[Finding]]:
    """Evaluate every budget against every applicable backend.

    A budget applies to a backend when the budget's ``backend`` key
    matches (or is absent) and the backend builds the budget's program;
    inapplicable combinations are reported as skips, never findings.
    Returns (report rows with the measured values, findings) — a
    finding per exceeded budget, rule ``cost-budget``.
    """
    from repro.analysis.programs import build_programs

    budgets = load_budgets(budgets_path)["budgets"]
    report: list[dict] = []
    findings: list[Finding] = []
    progs_cache: dict[str, dict[str, Any]] = {}

    def programs_for(bname: str) -> dict[str, Any]:
        if bname not in progs_cache:
            progs_cache[bname] = {p.name: p for p in build_programs(
                bname, mesh=mesh, arch=arch)}
        return progs_cache[bname]

    for b in budgets:
        for bname in backend_names:
            row = {"budget": b["name"], "backend": bname,
                   "program": b["program"], "metric": b["metric"],
                   "max": b["max"]}
            if b.get("backend") is not None and b["backend"] != bname:
                row["skipped"] = f"budget pinned to {b['backend']}"
                report.append(row)
                continue
            metric = b["metric"]
            if metric == "decode_jit_traces":
                if b["program"] not in programs_for(bname):
                    row["skipped"] = "backend builds no such program"
                    report.append(row)
                    continue
                value = float(swap_trace_count(
                    backend=bname, arch=arch, mesh=mesh,
                    aligned=bool(b.get("aligned", True))))
            elif metric.endswith("_growth"):
                base = metric[:-len("_growth")]
                try:
                    value, detail = growth_ratio(bname, b["program"],
                                                 base, mesh=mesh,
                                                 arch=arch)
                except KeyError:
                    row["skipped"] = "backend builds no such program"
                    report.append(row)
                    continue
                row["values"] = detail
            else:
                progs = programs_for(bname)
                if b["program"] not in progs:
                    row["skipped"] = "backend builds no such program"
                    report.append(row)
                    continue
                m = program_metrics(progs[b["program"]])
                if not hasattr(m, metric):
                    raise ValueError(
                        f"budget {b['name']!r}: unknown metric "
                        f"{metric!r} (not a CostMetrics field)")
                value = float(getattr(m, metric))
            row["value"] = value
            row["ok"] = value <= float(b["max"])
            report.append(row)
            if not row["ok"]:
                findings.append(Finding(
                    rule="cost-budget", severity="error",
                    program=b["program"], backend=bname,
                    path=metric, primitive=b["name"],
                    message=f"budget '{b['name']}' exceeded: {metric} = "
                    f"{value:g} > max {b['max']:g}"
                    + (f" — {b['note']}" if b.get("note") else "")))
    return report, findings
