"""Shared benchmark utilities: timing, table/JSON output, and the
``BenchRunner`` CLI harness every ``bench_*`` driver builds on."""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Callable

import jax
import numpy as np

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "bench")


def csv_ints(s: str) -> tuple[int, ...]:
    """argparse type for comma-separated int sweeps, e.g. --k 1,5,32."""
    return tuple(int(x) for x in s.split(","))


def csv_strs(s: str) -> tuple[str, ...]:
    return tuple(s.split(","))


class BenchRunner:
    """The per-driver CLI boilerplate, hoisted: argparse construction,
    the ``--out`` JSON artifact emission (``BENCH_*.json`` in CI), and
    the exit-code contract — previously copy-pasted across the seven
    ``bench_*`` drivers.

    >>> def main(argv=None):
    ...     return (BenchRunner(__doc__)
    ...             .arg("--sizes", type=csv_ints, default=(50_000,))
    ...             .main(lambda a: run(sizes=a.sizes), argv))
    """

    def __init__(self, description: str | None = None):
        self.ap = argparse.ArgumentParser(
            description=description,
            formatter_class=argparse.RawDescriptionHelpFormatter)
        self.ap.add_argument(
            "--out", default=None,
            help="also write rows to this JSON path "
                 "(e.g. BENCH_query.json for the CI artifact)")

    def arg(self, *args, **kw) -> "BenchRunner":
        self.ap.add_argument(*args, **kw)
        return self

    def main(self, run: Callable[[argparse.Namespace], list[dict]],
             argv=None) -> int:
        args = self.ap.parse_args(argv)
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        rows = run(args)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(rows, f, indent=1)
            print(f"wrote {args.out}")
        return 0


def timeit(fn: Callable, *args, warmup: int = 1, iters: int = 3,
           **kw) -> tuple[float, object]:
    """Median wall time (s) of ``fn(*args)`` with block_until_ready."""
    out = None
    for _ in range(warmup):
        out = fn(*args, **kw)
        jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)), out


def write_rows(name: str, rows: list[dict]) -> str:
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(rows, f, indent=1)
    return path


def print_table(title: str, rows: list[dict], cols: list[str]) -> None:
    print(f"\n== {title} ==")
    widths = {c: max(len(c), *(len(_fmt(r.get(c))) for r in rows))
              for c in cols}
    print("  ".join(c.ljust(widths[c]) for c in cols))
    for r in rows:
        print("  ".join(_fmt(r.get(c)).ljust(widths[c]) for c in cols))


def _fmt(v) -> str:
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)
