"""Compile every Pallas kernel of the search path for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached, and refuses what the chip would refuse
(unaligned blocks, layouts Mosaic cannot lower, too much VMEM).  The
kernels are called with ``interpret=False`` directly, because the ops
dispatch takes the jnp oracle on a CPU backend.  Shapes are the chip
smoke's widths: n=256, w=16, C=512/1024, Q=16, k=10; the gathered
refine also at the one-query cell's Q=1, k=1, each over K=4 blocks of
an index of 2^22 series.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.batch_l2 import batch_l2
from repro.kernels.block_topk import block_topk
from repro.kernels.dtw_band import dtw_band_panel
from repro.kernels.fused_refine import fused_panel_topk, gathered_panel_topk
from repro.kernels.isax_summarize import isax_summarize
from repro.kernels.lb_scan import lb_scan

N, W, Q, K = 256, 16, 16, 10


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _gathered_shapes(q, c, kb=4):
    """q queries, each against kb blocks of an index of 2^22 series."""
    b = (1 << 22) // c
    return [((q, N), jnp.float32), ((q, W), jnp.float32),
            ((b, c, N), jnp.float32), ((b, W, c), jnp.float32),
            ((b, W, c), jnp.float32), ((b, c), jnp.int32),
            ((q, kb), jnp.int32), ((q, kb), jnp.bool_), ((q,), jnp.float32),
            ((q,), jnp.float32)]


def _cases(c):
    """name -> (kernel with its static arguments, argument shapes)."""
    f32, i32 = jnp.float32, jnp.int32
    return {
        "isax_summarize": (
            functools.partial(isax_summarize, w=W, card=256,
                              normalize=False),
            [((8 * c, N), f32)]),
        "isax_summarize_znorm": (
            functools.partial(isax_summarize, w=W, card=256),
            [((8 * c, N), f32)]),
        "lb_scan": (                # envelopes of 2^22 series in blocks
            functools.partial(lb_scan, n=N),
            [((Q, W), f32), ((W, (1 << 22) // c), f32),
             ((W, (1 << 22) // c), f32)]),
        "batch_l2": (batch_l2, [((Q, N), f32), ((c, N), f32)]),
        "block_topk": (
            functools.partial(block_topk, k=K),
            [((Q, 4 * c), f32), ((Q, 4 * c), i32)]),
        "fused_panel_topk": (
            functools.partial(fused_panel_topk, k=K, n=N),
            [((Q, N), f32), ((Q, W), f32), ((c, N), f32), ((W, c), f32),
             ((W, c), f32), ((c,), i32), ((Q,), f32)]),
        "gathered_panel_topk": (
            functools.partial(gathered_panel_topk, k=K, n=N),
            _gathered_shapes(Q, c)),
        "gathered_panel_topk_q1": (
            functools.partial(gathered_panel_topk, k=1, n=N),
            _gathered_shapes(1, c)),
        "dtw_band_panel_shared": (
            functools.partial(dtw_band_panel, r=N // 10),
            [((Q, N), f32), ((c, N), f32)]),
        "dtw_band_panel_gathered": (
            functools.partial(dtw_band_panel, r=N // 10),
            [((Q, N), f32), ((Q, 4 * c, N), f32)]),
    }


@pytest.mark.parametrize("capacity", [512, 1024])
@pytest.mark.parametrize("name", sorted(_cases(512)))
def test_kernel_compiles_for_v5e(one_chip, name, capacity):
    fn, shapes = _cases(capacity)[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(functools.partial(fn, interpret=False)) \
        .lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_query_major_run_holds_no_gathered_copy(one_chip):
    """``engine.run`` on a query-major ED plan, compiled for the v5e with
    the Pallas kernels: no (Q, K, C, n) copy of the visited blocks exists
    in the program.  The same plan without the LB filter still gathers,
    which shows the check can see such a buffer."""
    from repro.core import engine
    from repro.core.index import BlockIndex
    from repro.kernels import ops
    c, b = 512, (1 << 22) // 512
    f32, i32 = jnp.float32, jnp.int32
    s = lambda shape, d=f32: jax.ShapeDtypeStruct(shape, d,
                                                  sharding=one_chip)
    index = BlockIndex(raw=s((b, c, N)), slo=s((b, W, c)), shi=s((b, W, c)),
                       elo=s((W, b)), ehi=s((W, b)), ids=s((b, c), i32),
                       n=N, w=W, card=256, capacity=c, n_real=b * c)
    copy = (f"f32[{Q},4,{c},{N}]", f"f32[{Q * 4},{c},{N}]")

    def hlo(metric):
        plan = engine.QueryPlan(metric=metric, schedule="query_major", k=K)
        with ops.kernel_mode("pallas"):
            return engine.run.lower(index, s((Q, N)), plan).compile() \
                .as_text()

    fused = hlo(engine.ED())
    assert "gathered_panel_topk" in fused
    assert not any(t in fused for t in copy)
    assert any(t in hlo(engine.ED(lb_filter=False)) for t in copy)
