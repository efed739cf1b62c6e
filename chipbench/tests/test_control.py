"""The control comes out not correct under each cell's limits: the
reference's scan in the program's place at ``Precision.HIGH``, spelled
out as three bf16 passes (``_dot_high`` of the reference) so that it
computes on the CPU as on the chip.  ``control.readings`` at the
rehearsal size, over every query of the pool.

    python -m pytest chipbench/tests
"""
import pytest

import control
import run

CELLS = ["messi-rw-b16-k10", "parisplus-noise5-b1-k1"]


@pytest.mark.parametrize("seed", [3000000021, 17, 2 ** 31 + 5])
@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_the_limits(workload, seed):
    c = run.load_cell(workload, rehearse=True)
    r = control.readings(c, seed)
    ctrl, lim = r["control"], c.limits
    assert r["answers"] == c.traffic["pool"]
    assert any(ctrl[n] > lim[n] for n in lim), r
