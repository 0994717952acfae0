"""The precision control comes out as not correct through the harness.

On the chip, ``control.py`` reads each cell's control (named in
``limits/<cell>.json``) at the cell's own size on many seeds, and the limits
file keeps those readings.  Here, at a size a test run holds on the CPU,
the harness's own run puts the control in the program's place and judges it
like the served tokens.  The tiny program serves in float32, and at its size
the top-A entries stand so far apart that int8 seldom moves one past the
A-th: int4 does.
"""
import pytest

import tiny
from chipbench import spec

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


def test_control_is_not_correct(tmp_path):
    res = tiny.run(tmp_path, control="int4")
    assert res["sound"]["correct"]
    assert not res["correct"] and res["failed"] > 0
    assert list(res)[-1] == "compared"
    assert res["compared"]["top_a_gap"]["value"] > tiny.LIMIT


@pytest.mark.parametrize("name", CELLS)
def test_limits_sit_between_their_readings(name):
    """Each limit lies above the sound runs' largest reading and below the
    control's smallest, with the control at least three times the sound."""
    limits = spec.workload(name).limits
    assert set(limits) == {"top_a_gap"}
    for lim in limits.values():
        assert lim["lower"] < lim["limit"] < lim["upper"]
        assert lim["upper"] >= 3 * lim["lower"]
        assert lim["control"] in ("int8", "fp8", "int4")
