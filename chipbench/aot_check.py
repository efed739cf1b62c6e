#!/usr/bin/env python3
"""Compile a cell's programs for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python3 chipbench/aot_check.py --workload <cell>

Compiles, at the cell's own sizes and with the program's Pallas kernels
(``REPRO_KERNEL_MODE=pallas``, set here before the program is imported,
since on a CPU backend its dispatch would pick the jnp oracles): the
benchmark's generator chunk, the served path's programs (``core.build``
and ``engine.run`` for ``hbm``; the block-LB kernel and the cached
walk's refine step for ``disk``; for a cell of several chips, the
two-round protocol's ``distributed.build_sharded`` and
``search_sharded`` jitted over a mesh of that many described chips,
``N / chips`` series a chip) and the reference's scan of one chip's
shard at both precisions.  Prints each program's compile seconds and
memory on each device it runs on.
What the chip's compiler refuses fails here, at no chip time; a compile
that passes is not a run, and says nothing of results or times.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["REPRO_KERNEL_MODE"] = "pallas"

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    c = run.load_cell(args.workload)
    sys.path.insert(0, str(run.ROOT / "src"))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    import gen
    from repro import core
    from repro.core import distributed, engine

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chips = c.cell["chips"]
    one = SingleDeviceSharding(topo.devices[0])
    cfg, traffic = c.cfg, c.traffic
    n_series, n = cfg["n_series"], cfg["length"]
    shard_n = n_series // chips
    q_n, k = traffic["batch"], traffic["k"]

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def compile_(name, fn, *a, **kw):
        t0 = time.perf_counter()
        compiled = fn.lower(*a, **kw).compile()
        mem = compiled.memory_analysis()
        need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        kernels = compiled.as_text().count("tpu_custom_call")
        devices = {d for sh in jax.tree.leaves(compiled.input_shardings)
                   for d in sh.device_set}
        on = f" on each of {len(devices)} devices" if len(devices) > 1 else ""
        print(f"{name}: compiled in {time.perf_counter() - t0:.1f} s, "
              f"{need / 2 ** 30:.3f} GiB{on}, {kernels} Pallas call sites",
              flush=True)

    dataset = run.parts.load("datasets", cfg["dataset"])
    reference = run.parts.load("references", cfg["reference"])
    compile_(f"datasets/{cfg['dataset']}.chunk", dataset.chunk,
             jax.random.key(0), jnp.int32(0),
             rows=min(gen.CHUNK, n_series), length=n)
    kw = dict(w=cfg["w"], card=cfg["card"], capacity=cfg["capacity"])
    x = spec((n_series, n))
    if chips > 1:
        mesh = Mesh(np.array(topo.devices[:chips]), ("data",))

        def on_mesh(shape, spec_, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dtype,
                                        sharding=NamedSharding(mesh, spec_))

        build = jax.jit(lambda a: distributed.build_sharded(a, mesh, **kw))
        rows = on_mesh((n_series, n), P("data"))
        compile_("distributed.build_sharded", build, rows)
        shapes = jax.eval_shape(build, rows)
        index = jax.tree.map(lambda s, p: on_mesh(s.shape, p, s.dtype),
                             shapes,
                             distributed.index_pspecs(mesh, like=shapes))
        search = jax.jit(lambda i, q: distributed.search_sharded(
            i, q, mesh, k=k, schedule="query_major"))
        compile_("distributed.search_sharded", search, index,
                 on_mesh((q_n, n), P()))
    elif cfg["placement"] == "hbm":
        compile_("core.build", core.build, x, **kw)
        index = jax.eval_shape(lambda a: core.build(a, **kw), x)
        index = jax.tree.map(lambda s: spec(s.shape, s.dtype), index)
        plan = engine.QueryPlan(metric=engine.ED(), schedule="query_major",
                                k=k)
        compile_("engine.run (core.search)", engine.run, index,
                 spec((q_n, n)), plan)
    else:
        from repro.kernels import ops
        cap, b = cfg["capacity"], n_series // cfg["capacity"]
        compile_("ops.lb_scan_planar", jax.jit(
            lambda qp, lo, hi: ops.lb_scan_planar(qp, lo, hi, n=n)),
            spec((q_n, cfg["w"])), spec((cfg["w"], b)),
            spec((cfg["w"], b)))
        qs = engine.QueryState(q=spec((q_n, n)), aux=(spec((q_n, cfg["w"])),))
        from repro.core import frontier
        front = jax.eval_shape(lambda: frontier.init(q_n, k))
        stats = jax.eval_shape(lambda: frontier.stats_init(q_n))
        as_spec = lambda t: jax.tree.map(lambda s: spec(s.shape, s.dtype), t)
        compile_("engine._cached_refine_step", engine._cached_refine_step,
                 engine.ED(), qs, as_spec(front), as_spec(stats),
                 spec((cap, n)), spec((cap,), jnp.int32),
                 spec((cfg["w"], cap)), spec((cfg["w"], cap)),
                 spec((q_n,)), None, n=n, w=cfg["w"])
    tile = min(256, traffic["pool"])
    for prec in ("highest", "high"):
        compile_(f"reference scan ({prec})", reference._scan_tile,
                 spec((shard_n, n)), spec((tile, n)),
                 m=k + reference.MARGIN, precision=prec,
                 chunk=min(1 << 17, shard_n))
    return 0


if __name__ == "__main__":
    sys.exit(main())
