"""Scaling benchmarks — the paper's #cores axis mapped to mesh devices.

Runs build + query on 1/2/4/8 fake CPU devices in subprocesses (device
count is fixed at jax init); it refuses to start where JAX's backend is
not the CPU, since a parent holding a chip locks its children out.  One
physical core backs all fake devices, so WALL TIME cannot drop; what the
bench verifies and reports is
  * exactness under sharding (answers == oracle at every device count),
  * work partitioning (per-shard refined-series counts, max/mean skew —
    the paper's load-balancing concern),
  * communication volume independence (BSF protocol bytes per query).
The projection to real chips is the roofline table (EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import BenchRunner, csv_ints, print_table, write_rows

_PAYLOAD = r"""
import json, time
import jax, jax.numpy as jnp, numpy as np
from repro.core import distributed, engine, ucr
from repro.data import make_dataset

n_dev = __NDEV__
mesh = jax.make_mesh((n_dev,), ("data",))
raw = make_dataset("synthetic", 131072, 256)
rng = np.random.default_rng(0)
qs = jnp.asarray(raw[rng.choice(len(raw), 8, replace=False)]
                 + 0.05 * rng.standard_normal((8, 256)).astype(np.float32))

t0 = time.perf_counter()
sidx = distributed.build_sharded(jnp.asarray(raw), mesh, capacity=1024)
jax.block_until_ready(sidx.raw)
t_build = time.perf_counter() - t0

res = distributed.search_sharded(sidx, qs, mesh)
jax.block_until_ready(res.dist)
t0 = time.perf_counter()
res = distributed.search_sharded(sidx, qs, mesh)
jax.block_until_ready(res.dist)
t_query = time.perf_counter() - t0

oracle = ucr.search_scan(jnp.asarray(raw), qs)
exact = bool(np.allclose(res.dist, oracle.dist, rtol=1e-3, atol=1e-3))

# metric axis under sharding (ROADMAP: distributed DTW / cosine) — a
# smaller dataset keeps the banded DP affordable on fake CPU devices;
# exactness vs the scan oracles is pinned in tests/test_distributed.py
raw2 = np.ascontiguousarray(raw[:8192, :128])
qs2 = jnp.asarray(raw2[rng.choice(len(raw2), 8, replace=False)]
                  + 0.05 * rng.standard_normal((8, 128)).astype(np.float32))
sidx2 = distributed.build_sharded(jnp.asarray(raw2), mesh, capacity=512)

def timed(fn):
    r = fn(); jax.block_until_ready(r.dist)          # compile + warm
    t0 = time.perf_counter()
    r = fn(); jax.block_until_ready(r.dist)
    return time.perf_counter() - t0, r

t_dtw, res_dtw = timed(lambda: distributed.search_sharded(
    sidx2, qs2, mesh, metric=engine.DTW(r=6)))
vecs = engine.prep_vectors(jnp.asarray(raw2))
sidx_v = distributed.build_sharded(vecs, mesh, capacity=512,
                                   normalize=False)
t_cos, res_cos = timed(lambda: distributed.search_sharded(
    sidx_v, qs2, mesh, metric=engine.Cosine()))
cos_oracle = ucr.search_scan(vecs, engine.prep_vectors(qs2),
                             normalize=False)
exact_cos = bool(np.array_equal(np.asarray(res_cos.idx),
                                np.asarray(cos_oracle.idx)))

print(json.dumps({
    "n_dev": n_dev, "build_s": t_build, "query_s": t_query,
    "exact": exact,
    "refined_total": int(np.sum(np.asarray(res.stats.series_refined))),
    "iters_max": int(np.asarray(res.stats.iters)),
    "query_s_dtw": t_dtw, "query_s_cos": t_cos, "exact_cos": exact_cos,
}))
"""


def run(device_counts=(1, 2, 4, 8)) -> list[dict]:
    import jax
    if jax.default_backend() != "cpu":
        # the children below need the accelerator this process now holds
        # (one process per chip), and fake host devices would measure
        # nothing there anyway
        raise SystemExit(
            f"bench_scaling runs the sharded protocol on fake CPU devices "
            f"in child processes; this process holds the "
            f"{jax.default_backend()!r} backend, so it refuses to run. "
            "On a chip host, measure search_sharded in one process over "
            "jax.devices().")
    rows = []
    for n in device_counts:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
        env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                         "src")
        r = subprocess.run([sys.executable, "-c",
                            _PAYLOAD.replace("__NDEV__", str(n))],
                           capture_output=True, text=True, timeout=900,
                           env=env)
        if r.returncode != 0:
            raise RuntimeError(r.stderr[-2000:])
        rows.append(json.loads(r.stdout.strip().splitlines()[-1]))
        assert rows[-1]["exact"], f"sharded search inexact at {n} devices"
        assert rows[-1]["exact_cos"], f"sharded cosine inexact at {n} devices"
    print_table("scaling (Fig. 4/5/8/9 axis)", rows,
                ["n_dev", "build_s", "query_s", "query_s_dtw", "query_s_cos",
                 "exact", "refined_total", "iters_max"])
    write_rows("scaling", rows)
    return rows


def main(argv=None) -> int:
    return (BenchRunner(__doc__)
            .arg("--devices", type=csv_ints, default=(1, 2, 4, 8))
            .main(lambda a: run(device_counts=a.devices), argv))


if __name__ == "__main__":
    import sys
    sys.exit(main())
