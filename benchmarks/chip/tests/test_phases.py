"""The engine's phases and the model's scopes in a trace (``phases.py``).

First on hand-made events whose answers are worked out below, then on
the two traces recorded on a TPU v5e by ``record_trace.py`` (two layers
of smollm-135m served through ``ServeEngine``): ``small.xplane.pb``,
from before the engine had spans and the model scopes, and
``scoped.xplane.pb``, recorded the same way with both.
"""
from pathlib import Path

import pytest

import phases
import tracereduce as tr

HERE = Path(__file__).resolve().parent
MS = 1_000_000  # ns
DECODE = "jit(_decode_fn)/while/body/closed_call"


def hand_made():
    """One step of 10 ms: admission 1-4 (its prefill read back 2-3), the
    decode launched 4-5, read back 5-8, retired 8-9. The device runs the
    prefill 1.2-1.8 and the decode 4.5-7.5 (a linear 4.5-6.5 holding a
    nested op 5-6, then an unscoped copy)."""
    return {
        "devices": [{
            "ops": [("%fusion.1 = prefill", 1.2 * MS, 1.8 * MS),
                    ("%fusion.2 = dot", 4.5 * MS, 6.5 * MS),
                    ("%fusion.3 = inner", 5 * MS, 6 * MS),
                    ("%copy.4 = copy", 6.5 * MS, 7.5 * MS)],
            "modules": [("jit__prefill_batched_fn(9)", 1.2 * MS, 1.8 * MS),
                        ("jit__decode_fn(3)", 4.5 * MS, 7.5 * MS)],
            "tf_op": {(3, "%fusion.2 = dot"): f"{DECODE}/linear/dot_general",
                      (3, "%fusion.3 = inner"):
                          f"{DECODE}/linear/attention/convert",
                      (9, "%fusion.1 = prefill"): "jit(f)/linear/x"}}],
        "spans": {"step": [(0, 10 * MS)], "submit": [], "wait": []},
        "engine_spans": {
            "engine.admit": [(1 * MS, 4 * MS)],
            "engine.sync": [(2 * MS, 3 * MS), (5 * MS, 8 * MS)],
            "engine.launch": [(4 * MS, 5 * MS)],
            "engine.retire": [(8 * MS, 9 * MS)]}}


def test_innermost_names_each_piece_for_its_innermost_span():
    spans = [(0, 10, "step"), (1, 4, "admit"), (2, 3, "sync"),
             (4, 5, "launch"), (9, 12, "past"), (20, 21, "wait")]
    assert phases.innermost(spans) == [
        (0, 1, "step"), (1, 2, "admit"), (2, 3, "sync"), (3, 4, "admit"),
        (4, 5, "launch"), (5, 9, "step"), (9, 10, "past"), (20, 21, "wait")]


def test_reduce_hand_made():
    ev = hand_made()
    red = phases.reduce(ev)
    base = tr.reduce(ev)
    # idle: step 0-1 and 9-10; admit 1-1.2, 1.8-2, 3-4; sync 2-3 and
    # 7.5-8; launch 4-4.5; retire 8-9
    want = {"step": 2e-3, "engine.admit": 1.4e-3, "engine.sync": 1.5e-3,
            "engine.launch": 0.5e-3, "engine.retire": 1e-3}
    assert red["idle_by_phase"] == pytest.approx(want)
    assert sum(red["idle_by_phase"].values()) == pytest.approx(
        base["window_s"] - base["busy_s"])
    assert red["phase_idle_s"] == {
        k: [pytest.approx(want[k])] for k in phases.PHASES}
    # fusion.3 nests in fusion.2: 1 ms of the linear is not its own
    assert red["scope_s"] == {
        "_decode_fn": {"linear": pytest.approx(1e-3),
                       "attention": pytest.approx(1e-3),
                       "unscoped": pytest.approx(1e-3)},
        "_prefill_batched_fn": {"linear": pytest.approx(0.6e-3)}}
    assert red["scoped_ops"]["_decode_fn/attention/fusion.3"] == \
        pytest.approx(1e-3)
    assert red["scoped_ops"]["_decode_fn/copy.4"] == pytest.approx(1e-3)
    b = phases.breakdown(red)
    assert b["idle_gaps"][0] == ["step", pytest.approx(2e-3)]
    assert {k for k, _ in b["idle_gaps"]} == set(want)
    split = phases.split(base, red)
    assert split["host_sync_ms"] == pytest.approx(1.5)
    assert split["decode_kv_gather_ms"] == 0.0
    assert sum(split[f"decode_{s}_ms"] for s in
               phases.SCOPES + ("unscoped",)) == pytest.approx(3.0)


def test_reduce_without_engine_spans_or_scopes():
    """A trace of a program without spans or scopes (the harness's spans
    only) splits nothing: idle by innermost span is idle by span."""
    ev = hand_made()
    del ev["engine_spans"], ev["devices"][0]["tf_op"]
    red = phases.reduce(ev)
    assert red["phase_idle_s"] == {}
    assert red["idle_by_phase"] == pytest.approx(
        tr.reduce(ev)["idle_by_span"])
    assert phases.split(tr.reduce(ev), red) == {}


def test_scope_of():
    assert phases.scope_of(f"{DECODE}/quantize_kv/convert") == "unscoped"
    assert phases.scope_of(None) == "unscoped"
    assert phases.scope_of(f"{DECODE}/attention/linear/dot") == "linear"
    assert phases.scope_of("jit(f)/kv_gather/gather") == "kv_gather"


def test_old_trace_reduces_as_before():
    """``small.xplane.pb``: ``tracereduce`` reads what it always read,
    and this module finds no phase and no scope in it; the op metadata
    still carries the one scope the model then had."""
    ev = phases.events(str(HERE / "small.xplane.pb"))
    base = tr.reduce(ev)
    assert base == tr.reduce(tr.events(str(HERE / "small.xplane.pb")))
    red = phases.reduce(ev)
    assert red["engine_spans"] == {} and red["phase_idle_s"] == {}
    assert red["idle_by_phase"] == pytest.approx(base["idle_by_span"])
    assert {s for per in red["scope_s"].values() for s in per} == \
        {"unscoped"}
    tf_ops = ev["devices"][0]["tf_op"].values()
    assert len(tf_ops) == 417
    assert any("/quantize_kv/" in p for p in tf_ops)
    assert sum(v for per in red["scope_s"].values()
               for v in per.values()) == pytest.approx(
                   sum(base["ops"].values()))


def test_scoped_trace():
    """``scoped.xplane.pb``: the engine's phases lie inside the harness's
    ``step`` spans, and the decode's op time splits over the three
    scopes and the rest."""
    ev = phases.events(str(HERE / "scoped.xplane.pb"))
    base, red = tr.reduce(ev), phases.reduce(ev)
    steps = base["spans"]["step"]
    assert set(red["engine_spans"]) == set(phases.PHASES)
    for spans in red["engine_spans"].values():
        assert all(any(s0 <= s and e <= e0 for s0, e0 in steps)
                   for s, e in spans)
    assert all(len(v) == len(steps) for v in red["phase_idle_s"].values())
    assert sum(red["idle_by_phase"].values()) == pytest.approx(
        base["window_s"] - base["busy_s"])
    per = red["scope_s"]["_decode_fn"]
    assert all(per[s] > 0 for s in phases.SCOPES)
    own = sum(v for k, v in base["ops"].items()
              if k.startswith("_decode_fn/"))
    assert sum(per.values()) == pytest.approx(own)
    assert base["programs"]["_decode_fn"][1] == 5
