"""The reduction from a profiler trace to numbers (``tracereduce.py``).

First on hand-made events whose answers are worked out below, then on a
small trace recorded on a TPU v5e by ``record_trace.py`` (two layers of
smollm-135m served through ``ServeEngine``), kept beside this file.
"""
from pathlib import Path

import pytest

import tracereduce as tr

HERE = Path(__file__).resolve().parent
MS = 1_000_000  # ns


def test_reduce_hand_made():
    ev = {"devices": [{
        # two overlapping ops (0-3, 2-5 ms) and one more (7-8 ms)
        "ops": [("fusion.1", 0 * MS, 3 * MS), ("fusion.2", 2 * MS, 5 * MS),
                ("fusion.1", 7 * MS, 8 * MS)],
        "modules": [("jit__decode_fn(3)", 0, 5 * MS),
                    ("jit__prefill_batched_fn(9)", 7 * MS, 8 * MS)]}],
        "spans": {"step": [(0, 6 * MS), (6 * MS, 9 * MS)],
                  "submit": [], "wait": [(9 * MS, 10 * MS)]}}
    red = tr.reduce(ev)
    assert red["window_s"] == pytest.approx(10e-3)
    assert red["busy_s"] == pytest.approx(6e-3)          # 0-5 and 7-8
    assert red["programs"]["_decode_fn"] == [pytest.approx(5e-3), 1]
    assert red["programs"]["_prefill_batched_fn"][1] == 1
    # fusion.2 starts inside fusion.1: 1 ms of fusion.1 is not its own
    assert red["ops"]["_decode_fn/fusion.1"] == pytest.approx(2e-3)
    assert red["ops"]["_decode_fn/fusion.2"] == pytest.approx(3e-3)
    assert red["ops"]["_prefill_batched_fn/fusion.1"] == pytest.approx(1e-3)
    # idle: 5-6 in step 1, 6-7 in step 2, 8-9 in step 2, 9-10 waiting
    assert red["step_idle_s"] == [pytest.approx(1e-3), pytest.approx(2e-3)]
    assert red["idle_by_span"] == {"step": pytest.approx(3e-3),
                                   "wait": pytest.approx(1e-3)}
    b = tr.breakdown(red)
    assert b["idle_gaps"][0] == ["step", pytest.approx(3e-3)]
    assert b["device_ops"][0][0] == "_decode_fn/fusion.2"


def test_reduce_without_device_ops_is_none():
    assert tr.reduce({"devices": [], "spans": {"step": [(0, 1)]}}) is None


def test_reduce_recorded_v5e_trace():
    red = tr.reduce(tr.events(str(HERE / "small.xplane.pb")))
    assert red is not None
    assert 0 < red["busy_s"] < red["window_s"]
    progs = red["programs"]
    assert progs["_decode_fn"][1] == 5            # 6 tokens: 1 + 5 decodes
    assert progs["_prefill_batched_fn"][1] == 2   # buckets 32 and 64
    assert len(red["step_idle_s"]) == len(red["spans"]["step"])
    assert all(0 <= x for x in red["step_idle_s"])
    assert sum(red["idle_by_span"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
