"""A run with the timed path broken underneath comes out not correct.

Each test drives the whole of a rehearsal run (``run.main`` with
``--rehearse``: the CPU, the configuration's rehearsal size, the same
paths, traffic and reference) with one answer altered where the
program produces it, and reads ``correct`` from the result line.

    python -m pytest chipbench/tests
"""
import json

import numpy as np
import pytest

import run

CELLS = ["messi-rw-b16-k10", "parisplus-noise5-b1-k1"]


def _result(capsys, workload):
    assert run.main(["--workload", workload, "--seed", "3000000019",
                     "--seconds", "1", "--rehearse"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _alter(fault):
    """-> f(dist, idx) applied to the first answer of the window."""
    def wrong_id(dist, idx):
        idx = np.array(idx)
        idx[0, -1] = (idx[0, -1] + 1) % 1000
        return dist, idx

    def far_distance(dist, idx):
        dist = np.array(dist)
        dist[0, 0] = np.sqrt(dist[0, 0] ** 2 + 0.01)
        return dist, idx

    return {"wrong_id": wrong_id, "far_distance": far_distance}[fault]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(capsys, workload):
    out = _result(capsys, workload)
    assert out["correct"] is True and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", ["wrong_id", "far_distance"])
@pytest.mark.parametrize("workload", CELLS)
def test_altered_answer_is_not_correct(capsys, monkeypatch, workload,
                                       fault):
    """The program's search entry returns one altered answer: the first
    request of the window (the calls before it are the warm-up's)."""
    import jax.numpy as jnp
    from repro import core, storage

    alter = _alter(fault)
    c = run.load_cell(workload, rehearse=True)
    calls = [0]

    def wrap(search):
        def altered(*a, **kw):
            res = search(*a, **kw)
            calls[0] += 1
            if calls[0] != c.traffic["warmup_requests"] + 1:
                return res
            dist, idx = alter(np.asarray(res.dist), np.asarray(res.idx))
            return res._replace(dist=jnp.asarray(dist),
                                idx=jnp.asarray(idx))
        return altered

    if c.cfg["placement"] == "hbm":
        monkeypatch.setattr(core, "search", wrap(core.search))
    else:
        monkeypatch.setattr(storage.SearchSession, "search",
                            wrap(storage.SearchSession.search))
    out = _result(capsys, workload)
    assert calls[0] > c.traffic["warmup_requests"] + 1
    assert out["correct"] is False
