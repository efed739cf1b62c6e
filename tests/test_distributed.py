"""Multi-device behaviour: sharded index build/search, elastic reshard,
MoE EP == local, seq-sharded flash decode, int8 DDP compression.

Each test runs in a fresh subprocess with 8 fake CPU devices (the device
count must be fixed before jax initializes, and the main pytest process
must keep seeing 1 device per the assignment rules)."""
import pytest

from conftest import run_subprocess


def test_sharded_build_and_search_exact():
    run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import distributed, ucr
mesh = jax.make_mesh((4, 2), ("data", "model"))
rng = np.random.default_rng(1)
raw = np.cumsum(rng.standard_normal((4096, 128)).astype(np.float32), axis=1)
qs = np.cumsum(rng.standard_normal((8, 128)).astype(np.float32), axis=1)
sidx = distributed.build_sharded(jnp.asarray(raw), mesh, capacity=128)
res = distributed.search_sharded(sidx, jnp.asarray(qs), mesh)
want = ucr.search_scan(jnp.asarray(raw), jnp.asarray(qs))
assert np.allclose(res.dist, want.dist, rtol=1e-4, atol=1e-4)
assert np.array_equal(np.asarray(res.idx), np.asarray(want.idx))
res2 = distributed.search_sharded_scan(jnp.asarray(raw), jnp.asarray(qs), mesh)
assert np.allclose(res2.dist, want.dist, rtol=1e-4, atol=1e-4)
# k-NN: the two-round protocol agrees with the single-host oracle for k > 1
res_k = distributed.search_sharded(sidx, jnp.asarray(qs), mesh, k=8)
want_k = ucr.search_scan(jnp.asarray(raw), jnp.asarray(qs), k=8)
assert res_k.idx.shape == (8, 8)
assert np.array_equal(np.asarray(res_k.idx), np.asarray(want_k.idx))
assert np.allclose(res_k.dist, want_k.dist, rtol=1e-4, atol=1e-4)
print("OK")
""")


def test_index_checkpoint_elastic_reshard_8_to_4():
    run_subprocess("""
import tempfile, jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import distributed, ucr
from repro.train import Checkpointer
rng = np.random.default_rng(2)
raw = np.cumsum(rng.standard_normal((2048, 128)).astype(np.float32), axis=1)
qs = np.cumsum(rng.standard_normal((4, 128)).astype(np.float32), axis=1)

mesh8 = jax.make_mesh((8,), ("data",))
sidx = distributed.build_sharded(jnp.asarray(raw), mesh8, capacity=64)
with tempfile.TemporaryDirectory() as d:
    ck = Checkpointer(d, async_writes=False)
    ck.save(0, {"idx": sidx})
    # restore onto HALF the devices (elastic rescale) — same answers
    mesh4 = jax.make_mesh((4,), ("data",), devices=jax.devices()[:4])
    tmpl = {"idx": jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), sidx)}
    specs = distributed.index_pspecs(mesh4, like=sidx)
    sh = {"idx": jax.tree.map(lambda s: NamedSharding(mesh4, s), specs,
          is_leaf=lambda x: isinstance(x, P))}
    back = ck.restore(tmpl, shardings=sh)["idx"]
    res = distributed.search_sharded(back, jnp.asarray(qs), mesh4)
    want = ucr.search_scan(jnp.asarray(raw), jnp.asarray(qs))
    assert np.allclose(res.dist, want.dist, rtol=1e-4, atol=1e-4)
    assert np.array_equal(np.asarray(res.idx), np.asarray(want.idx))
print("OK")
""")


def test_moe_ep_equals_local():
    run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.models import moe, common
mesh = jax.make_mesh((2, 4), ("data", "model"))
key = jax.random.PRNGKey(0)
class C: n_layers=1; d_model=32; d_ff=64; n_experts=8
p = jax.tree.map(lambda a: a[0], common.build_params(moe.param_specs(C), key))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((4, 16, 32)).astype(np.float32))
y_ep, aux_ep = jax.jit(lambda x: moe.moe_ffn_ep(
    x, p, top_k=2, capacity_factor=8.0, act=jax.nn.silu,
    mesh=mesh, data_axes=("data",)))(x)
# local reference: same capacity semantics PER SHARD -> use per-shard halves
y0, _ = moe.moe_ffn_local(x[:2].reshape(-1, 32), p, top_k=2,
                          capacity_factor=8.0, act=jax.nn.silu)
y1, _ = moe.moe_ffn_local(x[2:].reshape(-1, 32), p, top_k=2,
                          capacity_factor=8.0, act=jax.nn.silu)
want = jnp.concatenate([y0.reshape(2, 16, 32), y1.reshape(2, 16, 32)])
assert np.allclose(np.asarray(y_ep), np.asarray(want), rtol=2e-3, atol=2e-3), \
    np.max(np.abs(np.asarray(y_ep) - np.asarray(want)))
assert float(aux_ep.dropped_frac) == 0.0
print("OK")
""")


def test_seqsharded_flash_decode_equals_local():
    run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.models import attention
mesh = jax.make_mesh((8,), ("data",))
rng = np.random.default_rng(0)
B, S, H, KVH, hd = 1, 512, 4, 2, 16
q = jnp.asarray(rng.standard_normal((B, 1, H, hd)).astype(np.float32))
kn = jnp.asarray(rng.standard_normal((B, 1, KVH, hd)).astype(np.float32))
vn = jnp.asarray(rng.standard_normal((B, 1, KVH, hd)).astype(np.float32))
k = jnp.asarray(rng.standard_normal((B, S, KVH, hd)).astype(np.float32))
v = jnp.asarray(rng.standard_normal((B, S, KVH, hd)).astype(np.float32))
pos = jnp.asarray(300)
got, kc, vc = jax.jit(lambda q, kn, vn, k, v: attention.decode_attend_seqsharded(
    q, kn, vn, k, v, pos, mesh=mesh, axes=("data",), chunk=64))(q, kn, vn, k, v)
k2, v2 = attention.cache_update(k, v, kn, vn, pos)
want = attention.decode_attend(q, k2, v2, pos, chunk=64)
assert np.allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)
assert np.allclose(np.asarray(kc), np.asarray(k2))  # write landed correctly
assert np.allclose(np.asarray(vc), np.asarray(v2))
print("OK")
""")


def test_ddp_int8_allreduce_mean():
    run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.train import compression
mesh = jax.make_mesh((8,), ("data",))
rng = np.random.default_rng(0)
g = jnp.asarray(rng.standard_normal((8, 64, 32)).astype(np.float32))
err = jnp.zeros_like(g)
mean, new_err = compression.ddp_allreduce_int8(
    {"w": g}, {"w": err}, mesh, ("data",))
want = np.mean(np.asarray(g), axis=0)
got = np.asarray(mean["w"])
# int8 quantization error is bounded by scale/2 per shard
scale = np.abs(np.asarray(g)).max(axis=(1, 2), keepdims=True) / 127
tol = float(scale.mean()) * 0.6
assert np.abs(got - want).max() < tol, (np.abs(got - want).max(), tol)
print("OK")
""")


def test_multidevice_train_step_runs():
    run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import common, transformer as T
from repro.train import make_train_step, opt_init
from repro.launch.mesh import make_mesh
from repro.launch.specs import param_pspecs
mesh = make_mesh((4, 2), ("data", "model"))
cfg = get_config("granite-moe-1b-a400m", smoke=True)
key = jax.random.PRNGKey(0)
params = common.build_params(T.param_specs(cfg), key)
pp = param_pspecs(cfg, mesh, ("data",))
params = jax.device_put(params, jax.tree.map(
    lambda s: NamedSharding(mesh, s), pp,
    is_leaf=lambda x: isinstance(x, P)))
opt = opt_init(cfg.optimizer, params)
step = jax.jit(make_train_step(cfg, mesh=mesh, data_axes=("data",),
                               microbatch=1))
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 64)),
                               dtype=jnp.int32)}
losses = []
for _ in range(4):
    params, opt, m = step(params, opt, batch)
    losses.append(float(m["loss"]))
assert losses[-1] < losses[0]
assert int(m["skipped"]) == 0
print("OK", losses)
""")


def test_sharded_search_bit_identical_to_noreuse_protocol():
    """Round-1 reuse regression: threading the prepared state into
    ``engine.run`` must answer bit-for-bit — dist, idx, AND stats —
    what the PR-4 wrapper (round 2 recomputing ``engine.prepare``)
    answers."""
    run_subprocess("""
import functools, jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import distributed, engine
from repro.core.search import SearchResult, SearchStats
mesh = jax.make_mesh((8,), ("data",))
rng = np.random.default_rng(17)
raw = np.cumsum(rng.standard_normal((2048, 128)).astype(np.float32), axis=1)
qs = jnp.asarray(np.cumsum(
    rng.standard_normal((5, 128)).astype(np.float32), axis=1))
sidx = distributed.build_sharded(jnp.asarray(raw), mesh, capacity=64)

k = 5
m = engine.ED()
plan = engine.QueryPlan(metric=m, schedule="block_major", k=k)
ax = ("data",)

def _search_noreuse(local_index, q):
    # the PR-4 protocol body: round 2 re-prepares instead of resuming
    prep = engine.prepare(m, local_index, q, k)
    thr_g = jax.lax.pmin(prep.front.threshold(), ax)
    res = engine.run(local_index, q, plan, initial_threshold=thr_g)
    dist_g, idx_g = distributed._merge_shards(res, ax)
    stats = SearchStats(
        blocks_visited=jax.lax.psum(res.stats.blocks_visited, ax),
        series_refined=jax.lax.psum(res.stats.series_refined, ax),
        lb_series=jax.lax.psum(res.stats.lb_series, ax),
        iters=jax.lax.pmax(res.stats.iters, ax))
    return SearchResult(dist=dist_g, idx=idx_g, stats=stats)

specs = distributed.index_pspecs(mesh, like=sidx)
out = SearchResult(dist=P(None), idx=P(None),
                   stats=SearchStats(blocks_visited=P(None),
                                     series_refined=P(None),
                                     lb_series=P(None), iters=P()))
old = jax.shard_map(_search_noreuse, mesh=mesh, in_specs=(specs, P(None)),
                    out_specs=out, check_vma=False)(sidx, qs)
new = distributed.search_sharded(sidx, qs, mesh, k=k)
assert np.array_equal(np.asarray(new.idx), np.asarray(old.idx))
assert np.array_equal(np.asarray(new.dist), np.asarray(old.dist))
for g, w in zip(new.stats, old.stats):
    assert np.array_equal(np.asarray(g), np.asarray(w))
print("OK")
""")


def test_sharded_dtw_exact_vs_scan_oracle():
    """ROADMAP cell: ``search_sharded(..., metric=DTW(r))`` — exact vs a
    brute-force banded-DTW scan, under shard_map, k in {1, 5, 32}."""
    run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import distributed, engine, isax
from repro.core import frontier as frontier_lib
mesh = jax.make_mesh((8,), ("data",))
rng = np.random.default_rng(19)
raw = np.cumsum(rng.standard_normal((1024, 64)).astype(np.float32), axis=1)
qs = jnp.asarray(raw[rng.choice(1024, 4, replace=False)]
                 + 0.1 * rng.standard_normal((4, 64)).astype(np.float32))
sidx = distributed.build_sharded(jnp.asarray(raw), mesh, capacity=32)
r = 4
x = isax.znorm(jnp.asarray(raw))
q = isax.znorm(qs)
d = engine.dtw_band(q[:, None, :], x[None, :, :], r)       # (Q, N) squared
ids = jnp.broadcast_to(jnp.arange(1024, dtype=jnp.int32)[None], d.shape)
for k in (1, 5, 32):
    want = frontier_lib.init(q.shape[0], k).insert(d, ids)
    res = distributed.search_sharded(sidx, qs, mesh, k=k,
                                     metric=engine.DTW(r=r))
    assert np.array_equal(np.asarray(res.idx), np.asarray(want.ids)), k
    assert np.allclose(np.asarray(res.dist),
                       np.sqrt(np.asarray(want.dists)),
                       rtol=1e-4, atol=1e-4), k
print("OK")
""")


def test_sharded_cosine_exact_vs_scan_oracle():
    """ROADMAP cell: ``search_sharded(..., metric=Cosine())`` over a
    sharded vector index built with normalize=False — exact vs the
    brute-force scan on prepped embeddings, k in {1, 5, 32}."""
    run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import distributed, engine, ucr
mesh = jax.make_mesh((8,), ("data",))
rng = np.random.default_rng(23)
embs = jnp.asarray(rng.standard_normal((1024, 64)).astype(np.float32))
qs = jnp.asarray(rng.standard_normal((4, 64)).astype(np.float32))
prepped = engine.prep_vectors(embs)
sidx = distributed.build_sharded(prepped, mesh, capacity=32,
                                 normalize=False)
for k in (1, 5, 32):
    res = distributed.search_sharded(sidx, qs, mesh, k=k,
                                     metric=engine.Cosine())
    want = ucr.search_scan(prepped, engine.prep_vectors(qs), k=k,
                           normalize=False)
    assert np.array_equal(np.asarray(res.idx), np.asarray(want.idx)), k
    assert np.allclose(np.asarray(res.dist), np.asarray(want.dist),
                       rtol=1e-4, atol=1e-4), k
print("OK")
""")


def test_anytime_deadline_under_shards():
    run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import distributed, ucr
mesh = jax.make_mesh((8,), ("data",))
rng = np.random.default_rng(5)
raw = np.cumsum(rng.standard_normal((4096, 128)).astype(np.float32), axis=1)
qs = np.cumsum(rng.standard_normal((4, 128)).astype(np.float32), axis=1)
sidx = distributed.build_sharded(jnp.asarray(raw), mesh, capacity=32)
exact = distributed.search_sharded(sidx, jnp.asarray(qs), mesh)
rough = distributed.search_sharded(sidx, jnp.asarray(qs), mesh,
                                   deadline_blocks=2)
assert (np.asarray(rough.dist) >= np.asarray(exact.dist) - 1e-5).all()
print("OK")
""")
