#!/usr/bin/env python3
"""Chip benchmark of exact k-NN search: one cell of BENCHMARK.json per run.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 chipbench/run.py --workload <cell> --seed <n> --rehearse

A cell pairs a configuration (``configs/<name>.json``: the deployment,
its sizes, its placement, dataset and reference) with a traffic mix
(``traffic/<name>.json``: the query generator, batch, k).  Each of
these names a part found by that name (``parts.py``): the served path
``paths/<placement>.py``, the generators ``datasets/<dataset>.py`` and
``queries/<queries>.py``, the reference ``references/<reference>.py``;
every per-layer metric has a reader of its own (``metrics/<name>.py``).
So a new configuration, mix or metric is new files plus entries in
BENCHMARK.json.  A metric named ``<name>.<part>`` (one quantity split
by the end-to-end metric it moves, as ``device_idle_share.disk``) is
read by ``metrics/<name>.py``, and an end-to-end ``<name>.<part>`` is
the run's ``<name>``.

A run makes its collection and its pool of queries on the device from
``--seed``, at the sizes the configuration and the mix fix: another
seed is another collection and other queries of the same shape.  It
builds the index through the program's own entry points, warms every
shape the cell sends, then drives a closed loop with one client for
``--seconds``:
the next request is sent when the previous answer is on the host, and
each request is timed from its send.  With ``--trace 1`` the loop runs
under the profiler for the mix's ``trace_seconds`` instead and the run
reports the per-layer metrics.  After the window the program's state
is freed, the collection is made again, and every answer
of the window is held to the configuration's plain reference; the
numbers compared are printed with their limits as the last lines of
standard error and under ``checks``, the last key of the result line.

It runs only on a TPU with the chips the cell asks for, and exits 2
with no result otherwise.  The reference remakes the collection as one
row-contiguous shard on each of those chips and scans it shard by shard.
``--rehearse`` runs the same path on the CPU at the configuration's
rehearsal size, with as many CPU devices as the cell asks for chips,
and prints counts and checks but no metric and no device.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
WORK = ROOT / ".chipbench_work"
sys.path.insert(0, str(HERE))

import parts  # noqa: E402

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def load_cell(workload: str, rehearse: bool = False) -> SimpleNamespace:
    """The cell's entries and files, found by the names in BENCHMARK.json."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((ROOT / entry["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    if rehearse:
        cfg = {**cfg, **cfg.get("rehearsal", {})}
        traffic = {**traffic, **traffic.get("rehearsal", {})}
    limits = json.loads((HERE / "limits" / f"{workload}.json").read_text())
    return SimpleNamespace(bench=bench, cell=cell, cfg=cfg, traffic=traffic,
                           limits=limits["limits"])


def cell_metrics(bench: dict, workload: str, kind: str) -> list[dict]:
    """The end-to-end or per-layer metrics this cell reports."""
    out = []
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench[kind]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end":
            out.append(m)
        else:
            moved = e2e[m["moves"]]
            if "workloads" not in moved or workload in moved["workloads"]:
                out.append(m)
    return out


class CompileCount:
    """Programs compiled (not loaded from the persistent cache) while the
    block runs, by JAX's own compile events."""

    def __enter__(self):
        import jax
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event, secs, **_):
        if event == _BACKEND_COMPILE:
            self.programs += 1

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def setup(c: SimpleNamespace, seed: int) -> SimpleNamespace:
    """Build the served path over the collection of ``seed``; ->
    ``served`` with its pool of queries, drawn from ``seed`` too."""
    import gen
    traffic, cfg = c.traffic, c.cfg
    batch = traffic["batch"]
    ctx = SimpleNamespace(
        cfg=cfg, traffic=traffic, seed=seed, workdir=WORK,
        make_pool=lambda rows_of: gen.queries(
            cfg, traffic, seed, traffic["pool"], rows_of),
        make_warmup=lambda rows_of: gen.queries(
            cfg, traffic, seed, batch * traffic["warmup_requests"], rows_of,
            warmup=True))
    served = parts.load("paths", cfg["placement"]).Served(ctx)
    for s in range(0, len(served.warm), batch):
        served.pull(served.search(served.warm[s:s + batch]))
    return served


def closed_loop(served, traffic: dict, seconds: float) -> SimpleNamespace:
    """One client: send, wait for the answer on the host, send the next.
    Batches walk the pool in order (wrapping).  The window runs from the
    first send to the landing of the last request sent before
    ``seconds`` had passed."""
    import jax
    ann = jax.profiler.TraceAnnotation
    pool, batch = served.pool, traffic["batch"]
    recs, failed = [], 0
    i = 0
    with ann("window"):
        t_first = time.perf_counter()
        t_end = t_first + seconds
        t_last = t_first
        while True:
            with ann("next_batch"):
                rows = (np.arange(batch) + i * batch) % len(pool)
                q = pool[rows]
            t0 = time.perf_counter()
            if t0 >= t_end:
                break
            i += 1
            try:
                with ann("request"):
                    res = served.search(q)
                with ann("result_pull"):
                    dist, idx, counters = served.pull(res)
            except Exception:            # a failed request is counted
                traceback.print_exc()
                failed += 1
                t_last = time.perf_counter()
                continue
            t_last = time.perf_counter()
            recs.append(SimpleNamespace(rows=rows, dist=dist, idx=idx,
                                        counters=counters,
                                        latency_s=t_last - t0))
    return SimpleNamespace(recs=recs, attempted=i, failed=failed,
                           t_first=t_first, window_s=t_last - t_first,
                           queries=sum(len(r.rows) for r in recs))


def check(c: SimpleNamespace, seed: int, pool: np.ndarray, recs: list,
          devices: list) -> dict:
    """Hold the window's answers to the configuration's reference over
    the collection of ``seed``, made again as one shard on each of the
    cell's ``devices``."""
    import gen
    x = gen.collection_shards(c.cfg, seed, devices)
    rows = np.concatenate([r.rows for r in recs])
    return parts.load("references", c.cfg["reference"]).compare(
        x, pool, rows, np.concatenate([r.dist for r in recs]),
        np.concatenate([r.idx for r in recs]), c.traffic["k"], c.limits)


def passed(checks: dict) -> bool:
    return all(ch["value"] <= ch["limit"] for ch in checks.values())


def _percentile_95(values: list[float]) -> float:
    return float(np.percentile(np.asarray(values), 95))


def end_to_end(loop, build_s: float, setup_s: float, peak: int) -> dict:
    lat = [r.latency_s for r in loop.recs]
    return {
        "queries_per_s": loop.queries / loop.window_s,
        "latency_p95_ms": _percentile_95(lat) * 1e3 if lat else None,
        "index_build_s": build_s,
        "peak_hbm_gib": peak / 2 ** 30,
        "setup_s": setup_s,
    }


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), every program kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    Path(path).mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def rehearsal_env(chips: int) -> None:
    """JAX on the CPU with ``chips`` devices, as a rehearsal of a cell
    of that many chips runs.  Takes effect only where JAX has not yet
    started in this process."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    count = "--xla_force_host_platform_device_count="
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith(count)]
    os.environ["XLA_FLAGS"] = " ".join(flags + [f"{count}{chips}"])


def fail(msg: str) -> int:
    print(f"chipbench: {msg}", file=sys.stderr)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, rehearsal size: counts and checks, no metric")
    args = ap.parse_args(argv)
    try:
        c = load_cell(args.workload, args.rehearse)
    except (OSError, KeyError, ValueError) as e:
        return fail(f"cannot read the cell's files: {e!r}")
    chips = c.cell["chips"]
    if args.rehearse:
        rehearsal_env(chips)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro  # noqa: F401  the system under test
    except ImportError as e:
        return fail(f"cannot import the program from {ROOT / 'src'}: {e}")
    import jax

    devices = jax.devices()
    if args.rehearse:
        if devices[0].platform != "cpu" or len(devices) < chips:
            return fail(f"a rehearsal runs on {chips} CPU device(s); JAX "
                        f"found {len(devices)} {devices[0].platform} "
                        f"device(s)")
        peaks = None
    else:
        if devices[0].platform != "tpu" or len(devices) < chips:
            return fail(f"needs {chips} TPU chip(s); JAX found "
                        f"{len(devices)} {devices[0].platform} device(s)")
        kind = devices[0].device_kind
        all_peaks = json.loads((HERE / "peaks.json").read_text())
        if kind not in all_peaks:
            return fail(f"no peaks for device kind {kind!r} in peaks.json")
        peaks = all_peaks[kind]
        enable_compile_cache()
    WORK.mkdir(exist_ok=True)

    served = setup(c, args.seed)
    seconds = (min(args.seconds, c.traffic["trace_seconds"]) if args.trace
               else args.seconds)
    trace_dir = WORK / "trace"
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # host spans only, no Python calls
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    with CompileCount() as compiles:
        loop = closed_loop(served, c.traffic, seconds)
    setup_s = loop.t_first - T_START
    if args.trace:
        jax.profiler.stop_trace()
    used = devices[:chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    pool, build_s = served.pool, served.build_s
    served.close()
    del served
    gc.collect()

    trace = None
    if args.trace:
        import trace_reduce
        xplanes = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
        trace = trace_reduce.Trace(trace_reduce.read_xplane(xplanes[-1]))
        shutil.rmtree(trace_dir, ignore_errors=True)

    checks = check(c, args.seed, pool, loop.recs, used) if loop.recs else {}
    correct = bool(loop.recs) and loop.failed == 0 and passed(checks)
    counts = {name: sum(r.counters.get(name, 0) for r in loop.recs)
              / max(loop.queries, 1)
              for name in sorted({k for r in loop.recs for k in r.counters})}
    print(f"chipbench: {args.workload} seed {args.seed}: {loop.attempted} "
          f"requests, {loop.queries} queries in {loop.window_s:.3f} s, "
          f"{compiles.programs} programs compiled in the window, "
          f"per query {counts}", file=sys.stderr)
    for name, ch in checks.items():
        print(f"check {name} = {ch['value']!r} (limit {ch['limit']!r})",
              file=sys.stderr)

    out = {"correct": correct, "attempted": loop.attempted,
           "failed": loop.failed}
    if args.rehearse:
        out.update(rehearsal=True, per_query=counts, checks=checks)
        print(json.dumps(out))
        return 0
    metrics = {}
    if args.trace:
        run = SimpleNamespace(cell=c.cell, cfg=c.cfg, traffic=c.traffic,
                              loop=loop, trace=trace, peaks=peaks)
        for m in cell_metrics(c.bench, args.workload, "per_layer"):
            value = parts.load("metrics", m["name"].split(".")[0]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = end_to_end(loop, build_s, setup_s, peak)
        for m in cell_metrics(c.bench, args.workload, "end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"].split(".")[0]],
                                  "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out.update(metrics=metrics, device=device)
    if trace is not None:
        device.update(busy_s=trace.busy_s, window_s=trace.window_s)
        out["breakdown"] = trace.breakdown()
    out["checks"] = checks
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
