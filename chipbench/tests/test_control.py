"""The control comes out not correct under each cell's limits: the
reference's scan in the program's place at ``Precision.HIGH``, spelled
out as three bf16 passes (``_dot_high`` of the reference) so that it
computes on the CPU as on the chip.  ``control.readings`` at the
rehearsal size, over every query of the pool, for every cell of
BENCHMARK.json; a cell of several chips runs ``control.py --rehearse``
in a process of its own with that many CPU devices.

    python -m pytest chipbench/tests
"""
import pytest

import cell_runs
import control
import run
from cell_runs import CELLS, CHIPS


@pytest.mark.parametrize("seed", [3000000021, 17, 2 ** 31 + 5])
@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_the_limits(workload, seed):
    c = run.load_cell(workload, rehearse=True)
    if CHIPS[workload] == 1:
        r = control.readings(c, seed)
    else:
        r = cell_runs.apart(["chipbench/control.py", "--workload", workload,
                             "--seeds", str(seed), "--rehearse"],
                            CHIPS[workload])
    ctrl, lim = r["control"], c.limits
    assert r["answers"] == c.traffic["pool"]
    assert any(ctrl[n] > lim[n] for n in lim), r
