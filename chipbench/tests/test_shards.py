"""The check's collection held over four devices: ``gen.collection_shards``
and the reference over its shards, against the one-device collection.

One process of its own with four CPU devices (``cell_runs.apart``),
at the rehearsal size of ``messi-rw-b16-k10`` (16,384 series, a pool
of 64 random walks, k=10): the four shards are made, the oracle runs
over them and over the one buffer, and ``distributed.search_sharded``
answers over an index that ``build_sharded`` made from the same shards.

    python -m pytest chipbench/tests
"""
import pytest

import cell_runs

_FOUR = """
import json, sys
sys.path[:0] = ["chipbench", "src"]
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import gen, run
from repro.core import distributed

c = run.load_cell("messi-rw-b16-k10", rehearse=True)
cfg, k, seed = c.cfg, c.traffic["k"], 3000000023
ref = run.parts.load("references", cfg["reference"])
devices = jax.devices()
whole = gen.collection(cfg, seed)
shards = gen.collection_shards(cfg, seed, devices)
pool = gen.queries(cfg, c.traffic, seed, c.traffic["pool"])
one, four = ref.Oracle([whole], pool, k), ref.Oracle(shards, pool, k)
out = {
    "devices": [str(d) for s in shards for d in s.devices()],
    "expected": [str(d) for d in devices],
    "bitwise": bool(np.array_equal(np.concatenate(shards), whole)),
    "same_ids": bool(np.array_equal(one.ids, four.ids)),
    "same_d2": bool(np.array_equal(one.d2, four.d2)),
}
mesh = jax.make_mesh((4,), ("data",), devices=devices)
x = jax.make_array_from_single_device_arrays(
    whole.shape, NamedSharding(mesh, P("data")), shards)
index = distributed.build_sharded(x, mesh, w=cfg["w"], card=cfg["card"],
                                  capacity=cfg["capacity"])
search = jax.jit(lambda i, q: distributed.search_sharded(
    i, q, mesh, k=k, schedule="query_major"))
res = [search(index, jnp.asarray(pool[s:s + 16]))
       for s in range(0, len(pool), 16)]
dist = np.concatenate([np.asarray(r.dist) for r in res])
idx = np.concatenate([np.asarray(r.idx) for r in res])
rows = np.arange(len(pool))

def checks(d, i):
    return ref.compare(shards, pool, rows, d, i, k, c.limits)

wrong = idx.copy()
wrong[0, -1] = next(j for j in range(len(whole)) if j not in idx[0])
far = dist.copy()
far[0, 0] = np.sqrt(far[0, 0] ** 2 + 0.01)
out.update(sound=checks(dist, idx), wrong_id=checks(dist, wrong),
           far_distance=checks(far, idx))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four():
    return cell_runs.apart(["-c", _FOUR], 4)


def test_four_shards_concatenate_to_the_collection(four):
    assert four["devices"] == four["expected"]
    assert four["bitwise"]


def test_oracle_over_four_shards_is_the_one_buffer_oracle(four):
    assert four["same_ids"] and four["same_d2"]


def test_compare_over_four_shards_holds_search_sharded(four):
    passed = lambda checks: all(ch["value"] <= ch["limit"]
                                for ch in checks.values())
    assert passed(four["sound"]), four["sound"]
    assert four["wrong_id"]["rank_misses"]["value"] > 0
    far = four["far_distance"]["gap_max"]
    assert far["value"] > far["limit"]
