#!/usr/bin/env python3
"""Rehearsal runs of the cells of BENCHMARK.json for the chipbench tests.

A cell of one chip rehearses in the test's own process.  A cell of
several chips rehearses in a process of its own, whose JAX starts on
the CPU with as many devices as the cell asks for chips (``apart``): a
process's device count is fixed when JAX first starts, and the tests'
process has one device.

    python3 chipbench/tests/cell_runs.py --workload <cell> [--fault <name>]

drives one rehearsal run of the cell (``drive``) in this process and
prints ``{"result": <the run's result line>, "calls": <searches>}``.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import run  # noqa: E402

BENCH = json.loads((run.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
CHIPS = {w["name"]: w["chips"] for w in BENCH["workloads"]}
SEED = 3000000019


def apart(argv: list[str], chips: int, timeout: int = 600) -> dict:
    """Run ``python3 <argv>`` with JAX on ``chips`` CPU devices -> the
    first JSON line of its standard output."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    r = subprocess.run([sys.executable, *argv], capture_output=True,
                       text=True, env=env, timeout=timeout, cwd=run.ROOT)
    if r.returncode != 0:
        raise RuntimeError(f"{argv} exited {r.returncode}:\n{r.stdout}\n"
                           f"{r.stderr[-4000:]}")
    return json.loads(next(line for line in r.stdout.splitlines()
                           if line.startswith("{")))


def _alter(fault: str):
    """-> f(dist, idx), applied to the first answer of the window."""
    def wrong_id(dist, idx):
        idx = np.array(idx)
        idx[0, -1] = (idx[0, -1] + 1) % 1000
        return dist, idx

    def far_distance(dist, idx):
        dist = np.array(dist)
        dist[0, 0] = np.sqrt(dist[0, 0] ** 2 + 0.01)
        return dist, idx

    return {"wrong_id": wrong_id, "far_distance": far_distance}[fault]


def drive(workload: str, fault: str | None = None) -> tuple[dict, int]:
    """One rehearsal run of the cell in this process (``run.main
    --rehearse``).  With ``fault``, the served path's search (``Served
    .search`` of ``paths/<placement>.py``) returns the first request of
    the window (the calls before it are the warm-up's) with its answer
    altered.  -> (the result line, the searches made)."""
    c = run.load_cell(workload, rehearse=True)
    served = run.parts.load("paths", c.cfg["placement"]).Served
    search, calls = served.search, [0]

    def counted(self, q):
        res = search(self, q)
        calls[0] += 1
        if fault is None or calls[0] != c.traffic["warmup_requests"] + 1:
            return res
        import jax.numpy as jnp
        dist, idx = _alter(fault)(np.asarray(res.dist), np.asarray(res.idx))
        return res._replace(dist=jnp.asarray(dist), idx=jnp.asarray(idx))

    out = io.StringIO()
    served.search = counted
    try:
        with contextlib.redirect_stdout(out):
            rc = run.main(["--workload", workload, "--seed", str(SEED),
                           "--seconds", "1", "--rehearse"])
    finally:
        served.search = search
    if rc != 0:
        raise RuntimeError(f"{workload}: the rehearsal exited {rc}")
    return json.loads(out.getvalue().strip().splitlines()[-1]), calls[0]


def result(workload: str, fault: str | None = None, *,
           own_process: bool = False) -> tuple[dict, int]:
    """``drive`` here for a cell of one chip, in a process of its own
    with the cell's devices for a cell of more (or where
    ``own_process``)."""
    if CHIPS[workload] == 1 and not own_process:
        return drive(workload, fault)
    argv = [__file__, "--workload", workload]
    out = apart(argv + (["--fault", fault] if fault else []),
                CHIPS[workload])
    return out["result"], out["calls"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", choices=("wrong_id", "far_distance"))
    args = ap.parse_args(argv)
    res, calls = drive(args.workload, args.fault)
    print(json.dumps({"result": res, "calls": calls}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
