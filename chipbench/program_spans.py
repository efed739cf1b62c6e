"""Read the program's own host spans out of a reduced trace.

The served paths record spans with ``jax.profiler.TraceAnnotation``
(``engine.run_cached``, ``storage.BlockCache``, ``storage.SearchSession``,
``core.search``) into the same trace as the device's ops, on one clock.
A trace's host lines all carry the process's name, so the spans are told
apart by name, not by thread: ``CLIENT`` spans run on the thread that
calls ``search``, nested in the benchmark's ``request``; the block
cache's reader threads record ``cache.read_file`` and ``cache.upload``,
which overlap the client's.

* ``of(trace, name)`` — the spans of one name that start in the window,
  each with its self time: its duration less the client spans nested
  in it (``walk.dispatch`` less its ``cache.wait``).  A reader span's
  self time is its duration.
* ``gap_names(trace)`` — every idle gap of the device in the window,
  named by the innermost span open at its middle, out of the
  benchmark's host spans (``trace_reduce.HOST_SPANS``) and ``CLIENT``.
  A reader span never names a gap.
* ``request_coverage(trace)`` — the share of the ``request`` time that
  client spans cover.

Where the program records no such span, each returns nothing to read.
"""
from __future__ import annotations

import bisect

import trace_reduce as tr

CLIENT = ("walk.prep", "walk.stage_a", "walk.schedule", "walk.scan",
          "walk.dispatch", "cache.wait", "walk.sync", "walk.settle",
          "engine.dispatch")
OUTSIDE = "outside_spans"


def _host(trace: tr.Trace, names) -> list[tr.Event]:
    return [e for e in trace.events
            if e.name in names and not tr.DEVICE_PLANE.match(e.plane)]


def of(trace: tr.Trace, name: str) -> list[tuple[tr.Event, float]]:
    """(span, self ns) for every span ``name`` that starts in the window."""
    if name in CLIENT:
        selfs = tr._self_times(_host(trace, CLIENT), float("-inf"),
                               float("inf"))
    else:
        selfs = [(e, e.dur_ns) for e in _host(trace, (name,))]
    return [(e, s) for e, s in selfs
            if e.name == name and trace.t0 <= e.start_ns < trace.t1]


def mean_ms(trace: tr.Trace, name: str) -> float | None:
    """Mean self time in ms of the window's spans ``name``; None if none."""
    got = of(trace, name)
    return 1e-6 * sum(s for _, s in got) / len(got) if got else None


def has_client_spans(trace: tr.Trace) -> bool:
    return bool(_host(trace, CLIENT))


def _innermost(spans: list[tr.Event]) -> tuple[list[float], list[str]]:
    """(times, names): from times[i] to times[i + 1] the innermost of the
    properly nested ``spans`` open is names[i]."""
    times, names, stack = [], [], []

    def close_until(t):
        while stack and stack[-1].end_ns <= t:
            top = stack.pop()
            times.append(top.end_ns)
            names.append(stack[-1].name if stack else OUTSIDE)

    for e in sorted(spans, key=lambda e: (e.start_ns, -e.dur_ns)):
        close_until(e.start_ns)
        stack.append(e)
        times.append(e.start_ns)
        names.append(e.name)
    close_until(float("inf"))
    return times, names


def gap_names(trace: tr.Trace) -> list[tuple[str, float]]:
    """(name, seconds) of every idle gap of each device in the window."""
    times, names = _innermost(_host(trace, tr.HOST_SPANS + CLIENT))
    out = []
    for iv in trace.busy.values():
        edges = [trace.t0] + [x for s, e in iv for x in (s, e)] + [trace.t1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                i = bisect.bisect_right(times, (s + e) / 2) - 1
                out.append((names[i] if i >= 0 else OUTSIDE, (e - s) * 1e-9))
    return out


def request_coverage(trace: tr.Trace) -> float | None:
    """Share of the window's ``request`` time inside client spans."""
    reqs = [e for e in _host(trace, ("request",))
            if trace.t0 <= e.start_ns < trace.t1]
    total = sum(e.dur_ns for e in reqs)
    if not total:
        return None
    covered = 0.0
    for s, e in tr._union_ns([(e.start_ns, e.end_ns)
                              for e in _host(trace, CLIENT)]):
        for r in reqs:
            covered += max(0.0, min(e, r.end_ns) - max(s, r.start_ns))
    return covered / total
