"""Shared set-up of the benchmark's own tests (CPU, interpret mode).

Run from the repository root:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

``tiny_run`` drives a whole run of a cell through ``run.run_cell`` with
the configuration cut to a CPU-sized model (2 layers, d_model 128), the
chip check answered with the CPU and no persistent compile cache;
everything else is the cell's own: its mix, its limits, the harness, the
engine, the reference. A configuration states its own cut in an
optional ``"tiny"`` block; without one ``TINY`` applies.
"""
from __future__ import annotations

import copy
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[2] / "src")]

import pytest                                          # noqa: E402

TINY = {"n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 32, "d_ff": 256, "vocab": 512}
ENGINE = {"n_slots": 4, "page_size": 16, "max_len": 2048}


def tiny_files(cell: str) -> dict:
    import run
    return cut(run.cell_files(cell))


def cut(files: dict) -> dict:
    """``files`` at a CPU size. The configuration's ``"tiny"`` block, or
    else ``TINY``, gives ``dims`` (each set where the configuration's
    ``dims`` takes it from: its own key or the value in place), program
    ``overrides`` and the ``engine``; an open-loop mix runs at 3
    requests/s."""
    conf = copy.deepcopy(files["conf"])
    tiny = conf.get("tiny", {"dims": TINY, "overrides": TINY,
                             "engine": ENGINE})
    for k, v in tiny["dims"].items():
        key = conf["dims"][k]
        if isinstance(key, str):
            conf[key] = v
        else:
            conf["dims"][k] = v
    conf["program"]["overrides"].update(tiny["overrides"])
    conf["engine"] = tiny["engine"]
    files = {**files, "conf": conf}
    if "rate_per_s" in files["traffic"]:
        files["traffic"] = {**files["traffic"], "rate_per_s": 3.0}
    return files


@pytest.fixture
def tiny_run(monkeypatch):
    import jax
    import run
    cpu = jax.devices()[0]
    monkeypatch.setattr(run, "devices", lambda chips: {
        "platform": cpu.platform, "kind": cpu.device_kind, "count": 1})
    monkeypatch.setattr(run, "compile_cache", lambda: None)

    def go(cell, seed: int = 5, seconds: float = 4.0, **kw) -> dict:
        """``cell``: a cell's name, or its ``files`` already cut."""
        files = tiny_files(cell) if isinstance(cell, str) else cell
        return run.run_cell(files, seed, seconds, False, **kw)
    return go
