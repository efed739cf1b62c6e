"""A run with the timed path broken underneath comes out not correct.

Each test drives the whole of a rehearsal run (``run.main`` with
``--rehearse``: the CPU, the configuration's rehearsal size, the same
paths, traffic and reference) with one answer altered where the served
path produces it (``Served.search`` of the cell's
``paths/<placement>.py``), and reads ``correct`` from the result line.
The cells are BENCHMARK.json's; one of several chips runs in a process
of its own with that many CPU devices (``cell_runs.result``).

    python -m pytest chipbench/tests
"""
import pytest

import cell_runs
from cell_runs import CELLS


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    out, _ = cell_runs.result(workload)
    assert out["correct"] is True and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", ["wrong_id", "far_distance"])
@pytest.mark.parametrize("workload", CELLS)
def test_altered_answer_is_not_correct(workload, fault):
    """The served path's search returns one altered answer: the first
    request of the window."""
    c = cell_runs.run.load_cell(workload, rehearse=True)
    out, calls = cell_runs.result(workload, fault)
    assert calls > c.traffic["warmup_requests"] + 1
    assert out["correct"] is False


def test_altered_answer_in_a_process_of_its_own():
    """The way a cell of several chips is driven: a process of its own
    with the cell's devices, the fault planted there."""
    workload = CELLS[0]
    c = cell_runs.run.load_cell(workload, rehearse=True)
    out, calls = cell_runs.result(workload, "far_distance",
                                  own_process=True)
    assert calls > c.traffic["warmup_requests"] + 1
    assert out["correct"] is False
    assert out["checks"]["gap_max"]["value"] > out["checks"]["gap_max"][
        "limit"]
