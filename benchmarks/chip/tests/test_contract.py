"""The harness reaches a configuration's architecture only through its
reference module (``run.reference``), and a trace's phases and scopes
reach the metric files through ``ctx.trace`` (``run.trace_numbers``).

The decoder's reference gives today's weights and counts; a test-only
configuration of the ``moe`` family (``stub-moe.json``, ``stub_moe.py``),
with a pytree that is not the decoder's and ``dims`` keys beyond its
eleven, runs through ``run.run_cell`` at a CPU size with no harness file
of its own.
"""
import json
import types
from pathlib import Path

import jax
import numpy as np
import pytest

import counts
import phases
import run
import tracereduce as tr
import weights
from conftest import cut, tiny_files

HERE = Path(__file__).resolve().parent
CELLS = ["smollm-135m.chat", "chatglm3-6b.offline"]
SCOPED = str(HERE / "scoped.xplane.pb")


def metric(name: str):
    return run.module(run.HERE / "metrics" / f"{name}.py")


@pytest.mark.parametrize("cell", CELLS)
def test_decoder_reference_draws_todays_weights(cell):
    files = tiny_files(cell)
    conf = files["conf"]
    dims, bits = run.dims_of(conf), conf["program"]["serve"]["w_bits"]
    ref = run.reference(files)
    model = run.program(conf, dims, ref)
    got = run.served_params(ref, model, 2**31 + 17, dims, bits)
    want = weights.program_params(2**31 + 17, dims, bits)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("cell", CELLS)
def test_decoder_reference_counts_are_counts_py(cell):
    """``ctx.counts`` gives what ``counts.py`` gives, and so the two
    metric files that read it give the same numbers as before."""
    files = tiny_files(cell)
    dims = run.dims_of(files["conf"])
    c = run.reference(files).counts
    for rows, live in ((1, 1), (4, 1000), (32, 30000)):
        assert c.decode_step_bytes(dims, 4, rows, live) == \
            counts.decode_step_bytes(dims, 4, rows, live)
        assert c.decode_step_ops(dims, rows, live) == \
            counts.decode_step_ops(dims, rows, live)
    assert [c.prefill_ops(dims, n) for n in (1, 345, 512)] == \
        [counts.prefill_ops(dims, n) for n in (1, 345, 512)]
    steps = [types.SimpleNamespace(prompts=[300], decode_rows=3,
                                   live_positions=900),
             types.SimpleNamespace(prompts=[], decode_rows=4,
                                   live_positions=1204)]
    peak = {"hbm_bytes_per_s": 8.19e11, "int8_ops": 3.94e14}
    ctx = types.SimpleNamespace(
        dims=dims, bits=4, steps=steps, peak=peak, counts=c,
        trace={"programs": {"_decode_fn": [0.1, 2]}, "window_s": 0.5})
    least = sum(max(counts.decode_step_bytes(dims, 4, s.decode_rows,
                                             s.live_positions)
                    / peak["hbm_bytes_per_s"],
                    counts.decode_step_ops(dims, s.decode_rows,
                                           s.live_positions)
                    / peak["int8_ops"]) for s in steps)
    assert metric("decode_roofline.offline").read(ctx) == \
        least / 0.1 * 100
    ops = counts.prefill_ops(dims, 300) + sum(
        counts.decode_step_ops(dims, s.decode_rows, s.live_positions)
        for s in steps)
    assert metric("step_mfu.offline").read(ctx) == \
        ops / (0.5 * peak["int8_ops"]) * 100


def test_program_refuses_a_dims_key_the_reference_does_not_state():
    files = tiny_files("smollm-135m.chat")
    conf = files["conf"]
    dims = {**run.dims_of(conf), "kv_lora_rank": 512}
    with pytest.raises(ValueError, match="kv_lora_rank"):
        run.program(conf, dims, run.reference(files))


def stub_files() -> dict:
    """The test-only ``moe`` configuration as a chat cell, cut to the CPU
    size its own ``"tiny"`` block states."""
    return cut({
        "bench": {"end_to_end": [{"name": "itl_p95_ms", "unit": "ms"},
                                 {"name": "itl_p50_ms", "unit": "ms"},
                                 {"name": "setup_s", "unit": "s"}]},
        "cell": {"name": "stub-moe.chat", "config": "stub-moe",
                 "traffic": "chat", "chips": 1},
        "conf": json.loads((HERE / "stub-moe.json").read_text()),
        "traffic": run.load(run.HERE / "traffic" / "chat.json"),
        "limits": run.HERE / "limits" / "smollm-135m.chat.json",
        "reference": HERE / "stub_moe.py"})


def test_stub_moe_runs_through_run_cell(tiny_run):
    files = stub_files()
    conf = files["conf"]
    dims = run.dims_of(conf)
    assert dims["n_experts"] == 8 and dims["top_k"] == 2
    ref = run.reference(files)
    model = run.program(conf, dims, ref)
    params = run.served_params(ref, model, 3, dims, 4)
    assert params["blocks"]["m0"]["w_gate"].shape == (2, 8, 128, 256)
    assert "gate" not in params["blocks"]["m0"]
    res = tiny_run(files, seed=2**31 + 5, seconds=3.0)
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"itl_p95_ms", "itl_p50_ms", "setup_s"}
    assert res["checks"]["unfinished"]["value"] == 0
    assert res["checks"]["bad_length_or_id"]["value"] == 0
    assert res["checks"]["checked_tokens"]["value"] == 0
    assert res["correct"] is False


@pytest.mark.parametrize("key,value", [("n_experts", 7), ("top_k", 3)])
def test_stub_moe_wrong_dims_value_is_named(key, value):
    files = stub_files()
    dims = {**run.dims_of(files["conf"]), key: value}
    with pytest.raises(ValueError, match=key):
        run.program(files["conf"], dims, run.reference(files))


def test_trace_numbers_keep_tracereduce_and_add_phases_and_scopes():
    got = run.trace_numbers(SCOPED, {})
    base = tr.reduce(tr.events(SCOPED))
    red = phases.reduce(phases.events(SCOPED))
    assert {k: got[k] for k in base} == base
    assert set(got) - set(base) == {"scope_s", "phase_idle_s",
                                    "idle_by_phase"}
    for k in ("scope_s", "phase_idle_s", "idle_by_phase"):
        assert got[k] == red[k]


def test_configuration_scopes_reach_scope_s():
    """``quantize_kv``, a scope the model names but ``phases.SCOPES``
    leaves unscoped, shows up once a configuration lists it; the decode's
    op time is the same in total."""
    plain = run.trace_numbers(SCOPED, {})["scope_s"]["_decode_fn"]
    got = run.trace_numbers(SCOPED, {"scopes": ["quantize_kv"]})
    per = got["scope_s"]["_decode_fn"]
    assert "quantize_kv" not in plain and per["quantize_kv"] > 0
    assert per["unscoped"] == pytest.approx(
        plain["unscoped"] - per["quantize_kv"])
    assert sum(per.values()) == pytest.approx(sum(plain.values()))
    split = phases.split(got, got)
    assert split["decode_quantize_kv_ms"] > 0


def test_scope_reader_failure_leaves_only_the_new_keys_out(monkeypatch,
                                                           capsys):
    def broken(path):
        raise ValueError("xplane: wire type 7 at byte 0")
    monkeypatch.setattr(phases, "op_scopes", broken)
    got = run.trace_numbers(SCOPED, {})
    assert got == tr.reduce(tr.events(SCOPED))
    assert "phases and scopes not read" in capsys.readouterr().err


@pytest.mark.parametrize("suffix", ["chat", "offline"])
def test_phase_and_scope_metrics_read_the_recorded_trace(suffix):
    """The sixteen readers on ``scoped.xplane.pb``: the four decode
    scopes sum to ``decode_step_ms``, the four phases to at most
    ``engine_host_ms``; without phases and scopes they read nothing."""
    ctx = types.SimpleNamespace(trace=run.trace_numbers(SCOPED, {}))
    host = {p: metric(f"host_{p}_ms.{suffix}").read(ctx)
            for p in ("admit", "launch", "sync", "retire")}
    dec = {s: metric(f"decode_{s}_ms.{suffix}").read(ctx)
           for s in ("linear", "kv_gather", "attention", "unscoped")}
    assert all(v > 0 for v in host.values()), host
    assert all(v > 0 for v in dec.values()), dec
    step = metric(f"decode_step_ms.{suffix}").read(ctx)
    assert sum(dec.values()) <= step
    assert sum(dec.values()) == pytest.approx(step, rel=0.02)
    eng = metric(f"engine_host_ms.{suffix}").read(ctx)
    assert sum(host.values()) <= eng * (1 + 1e-9)
    bare = types.SimpleNamespace(trace=tr.reduce(tr.events(SCOPED)))
    for name in [f"host_{p}_ms" for p in host] + \
            [f"decode_{s}_ms" for s in dec]:
        assert metric(f"{name}.{suffix}").read(bare) is None
        assert metric(f"{name}.{suffix}").read(
            types.SimpleNamespace(trace=None)) is None
