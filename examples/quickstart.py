"""Quickstart: build a MESSI index and answer exact 1-NN queries.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax.numpy as jnp
import numpy as np

import repro.core as core
from repro.core.ucr import search_scan
from repro.data import random_walk


def main():
    # 100k random-walk series of 256 points (the paper's Synthetic recipe)
    raw = jnp.asarray(random_walk(100_000, 256, seed=0))
    queries = jnp.asarray(random_walk(10, 256, seed=1))

    print("building MESSI block index ...")
    index = core.build(raw, capacity=1024)
    print(f"  {index.n_blocks} blocks x {index.capacity} series")

    print("searching (exact 1-NN) ...")
    res = core.search(index, queries)  # (Q, 1) results; pass k= for more
    for i in range(10):
        print(f"  query {i}: nn={int(res.idx[i, 0]):6d} "
              f"dist={float(res.dist[i, 0]):8.4f} "
              f"refined {int(res.stats.series_refined[i])} / 100000 series")

    # cross-check against the brute-force oracle
    oracle = search_scan(raw, queries)
    assert np.array_equal(np.asarray(res.idx), np.asarray(oracle.idx))
    print("verified: answers identical to the full scan, "
          f"{100_000 / float(np.mean(np.asarray(res.stats.series_refined))):.0f}x "
          "less real-distance work")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
