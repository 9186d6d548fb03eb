"""The control, the reference one precision step below the served one,
comes out not correct through the cell's own checks; the program on the
same prompts comes out correct.

The served configuration rounds linear inputs and the K/V cache to 8
bits; the control computes the float32 reference with both at 4 bits
and reads, at every served position, the gap of the token it ranks
first. Here at a CPU size (``conftest.tiny_run``); the readings on the
chip at the cells' own sizes are in PERF.md.
"""
import pytest


@pytest.mark.parametrize("cell", ["smollm-135m.chat",
                                  "chatglm3-6b.offline"])
def test_control_fails_program_passes(tiny_run, cell):
    res = tiny_run(cell, control_bits=4)
    assert res["correct"], res["checks"]
    assert res["control"]["correct"] is False, res["control"]
    assert res["control"]["checked_tokens"] == \
        res["checks"]["checked_tokens"]["value"]
