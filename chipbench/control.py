#!/usr/bin/env python3
"""The control's readings, the upper ones that the limits of
``limits/<cell>.json`` are set from.

    python3 chipbench/control.py --workload <cell> --seeds 11,12,13

For each seed, in one process: the collection and the pool of queries
that a run of that seed makes, then the control in the program's place
over every query of the pool (as many as a run compares, or more): the
configuration's reference at the step below its stated precision
(``control_answers``; for the float32 at HIGHEST of these
configurations, ``Precision.HIGH``).  Each line of standard output is
one JSON record; the last one gives the smallest reading of each
number over the seeds.  The program's own readings, the lower ones,
are the ``checks`` of the benchmark's runs.  The benchmark's own runs
never run this.  The collection is made as one row-contiguous shard on
each of the cell's chips, as the benchmark's check makes it.
``--rehearse`` runs it on the CPU at the rehearsal size, with as many
CPU devices as the cell asks for chips.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run


def readings(c, seed: int) -> dict:
    import jax

    import gen
    x = gen.collection_shards(c.cfg, seed, jax.devices()[:c.cell["chips"]])
    ref = run.parts.load("references", c.cfg["reference"])
    pool = gen.queries(c.cfg, c.traffic, seed, c.traffic["pool"],
                       lambda ids: ref.take(x, ids))
    k = c.traffic["k"]
    rows = np.arange(len(pool))
    dist, idx = ref.control_answers(x, pool, rows, k)
    checks = ref.compare(x, pool, rows, dist, idx, k, c.limits)
    return {"seed": seed, "answers": int(len(rows)),
            "control": {name: ch["value"] for name, ch in checks.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, three or more")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    c = run.load_cell(args.workload, args.rehearse)
    chips = c.cell["chips"]
    if args.rehearse:
        run.rehearsal_env(chips)
    import jax
    devices = jax.devices()
    if len(devices) < chips:
        return run.fail(f"the cell needs {chips} devices; JAX found "
                        f"{len(devices)}")
    if not args.rehearse:
        if devices[0].platform != "tpu":
            return run.fail("control readings are taken on a TPU")
        run.enable_compile_cache()
    recs = []
    for seed in (int(s) for s in args.seeds.split(",")):
        recs.append(readings(c, seed))
        print(json.dumps(recs[-1]), flush=True)
    names = recs[0]["control"].keys()
    print(json.dumps({
        "workload": args.workload, "seeds": len(recs),
        "control_min": {n: min(r["control"][n] for r in recs)
                        for n in names}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
