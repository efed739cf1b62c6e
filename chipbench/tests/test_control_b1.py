"""The control comes out not correct under the limits of the cell of
single in-memory queries (``messi-rw-b1-k1``), which it shares with the
in-memory batch cell, at the rehearsal size (as ``test_control.py`` for
the other cells).

    python -m pytest chipbench/tests
"""
import pytest

import control
import run


@pytest.mark.parametrize("seed", [3000000021, 17, 2 ** 31 + 5])
def test_control_fails_the_b1_limits(seed):
    c = run.load_cell("messi-rw-b1-k1", rehearse=True)
    r = control.readings(c, seed)
    assert r["answers"] == c.traffic["pool"]
    assert any(r["control"][n] > c.limits[n] for n in c.limits), r
