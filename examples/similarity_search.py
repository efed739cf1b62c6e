"""Full similarity-search tour: the three systems of the paper, streaming
(ParIS+) ingestion, anytime answers, and the DTW extension.

    PYTHONPATH=src python examples/similarity_search.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.core as core
from repro.core import dtw
from repro.core.paris import search_paris
from repro.core.ucr import search_scan
from repro.data import make_dataset
from repro.data.loader import build_streaming


def main():
    n = 60_000
    raw_np = make_dataset("seismic", n, 256)
    raw = jnp.asarray(raw_np)
    rng = np.random.default_rng(0)
    qs = jnp.asarray(raw_np[rng.choice(n, 8, replace=False)]
                     + 0.05 * rng.standard_normal((8, 256)).astype(np.float32))

    # -- ParIS+-style streaming build (ingest/compute overlap) -------------
    t0 = time.perf_counter()
    index = build_streaming(raw_np, chunk=1 << 15, capacity=1024)
    jax.block_until_ready(index.raw)
    print(f"streaming build (ParIS+ overlap): {time.perf_counter()-t0:.2f}s "
          f"for {n} series")

    # -- the three query systems -------------------------------------------
    from repro.core.search import search_block_major
    for name, fn in [("UCR-Suite-p", lambda: search_scan(raw, qs)),
                     ("ParIS", lambda: search_paris(index, qs)),
                     ("MESSI (paper)", lambda: core.search(index, qs)),
                     ("MESSI (block-major)",
                      lambda: search_block_major(index, qs))]:
        res = fn()
        jax.block_until_ready(res.dist)
        t0 = time.perf_counter()
        res = fn()
        jax.block_until_ready(res.dist)
        dt = (time.perf_counter() - t0) / 8 * 1e3
        print(f"{name:20s} {dt:8.2f} ms/query   "
              f"refined {float(np.mean(np.asarray(res.stats.series_refined))):9.0f}"
              f" series/query")

    # -- k-NN result lists (same frontier machinery, any k) -----------------
    res_k = core.search(index, qs, k=5)
    print("top-5 ids for query 0:",
          [int(i) for i in np.asarray(res_k.idx[0])],
          "dists", [round(float(d), 3) for d in np.asarray(res_k.dist[0])])

    # -- anytime mode (straggler mitigation / deadline) ---------------------
    exact = core.search(index, qs)
    rough = core.search(index, qs, deadline_blocks=4)
    gap = np.asarray(rough.dist) / np.asarray(exact.dist) - 1
    print(f"anytime (4-block deadline): distance gap vs exact "
          f"mean {100*gap.mean():.2f}% max {100*gap.max():.2f}%")

    # -- DTW on the same index (paper SV) -----------------------------------
    res_d = dtw.search_dtw(index, qs[:2], r=6)
    print("DTW 1-NN (same index, banded):",
          [int(i) for i in np.asarray(res_d.idx[:, 0])])


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
