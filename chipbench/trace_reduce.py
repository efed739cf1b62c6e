"""Reduce a profiler trace to the device numbers the benchmark reports.

A trace is read into flat ``Event`` records (``read_xplane``; the
self-check reads the same records from a recorded JSON file).  From
them:

* ``busy_s`` — per device, the union of the intervals in which an
  operation ran (the "XLA Ops" line of each ``/device:TPU:<i>`` plane),
  clipped to the traced window, averaged over the devices;
* ``window_s`` — the length of the benchmark's own ``window`` host
  annotation, which spans the traced part of the closed loop;
* ``kernel_events(name)`` — the device executions of one Pallas kernel.
  On a v5e each op event is named by its HLO instruction
  (``%block_topk.2 = (f32[16,10]...) custom-call(f32[16,2048]... ),
  custom_call_target="tpu_custom_call", ...``), and a Pallas call's
  instruction takes the name of the jitted wrapper it is called
  through, so a kernel's events are the TPU custom calls named after
  it; ``operand_shapes`` reads their operand shapes from the same text;
* ``breakdown`` — the device operations that took most self time (an
  op's time less that of the ops nested in it: a ``while`` holds its
  body's ops), and the longest idle gaps of the device, each named by
  the innermost host annotation of the benchmark (``request``,
  ``result_pull``, ``next_batch``) open at the gap's middle.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW = "window"
HOST_SPANS = ("request", "result_pull", "next_batch")
_SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: dict

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def read_xplane(path: str | Path) -> list[Event]:
    """Every event of the device planes' op lines and of the host plane."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        is_dev = DEVICE_PLANE.match(plane.name) is not None
        if not is_dev and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if is_dev and line.name != OPS_LINE:
                continue
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns),
                                 {}))
    return out


def save_events(events: list[Event], path: str | Path) -> None:
    """Events as gzipped JSON (a recorded trace for the self-check)."""
    with gzip.open(path, "wt") as f:
        json.dump([dataclasses.asdict(e) for e in events], f)


def load_events(path: str | Path) -> list[Event]:
    with gzip.open(path, "rt") as f:
        return [Event(**e) for e in json.load(f)]


def _union_ns(intervals: list[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def operand_shapes(ev: Event) -> list[tuple[int, ...]]:
    """Shapes of the operands of a device op, read from the HLO text the
    trace names it by (``%x = <result> op(<operands>), ...``); [] where
    the name holds none."""
    text = ev.name
    if "=" not in text:
        return []
    rest = text[text.index("=") + 1:].lstrip()
    if rest.startswith("("):                 # a tuple result: skip it
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[-1]
    if "(" not in rest:
        return []
    args = rest[rest.index("(") + 1:]
    depth = 1
    for i, ch in enumerate(args):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0:
            args = args[:i]
            break
    return [tuple(int(d) for d in dims.split(",") if d)
            for _, dims in _SHAPE.findall(args)]


class Trace:
    def __init__(self, events: list[Event]):
        self.events = events
        wins = [e for e in events if e.name == WINDOW
                and not DEVICE_PLANE.match(e.plane)]
        if not wins:
            raise ValueError("trace holds no 'window' host annotation")
        win = max(wins, key=lambda e: e.dur_ns)
        self.t0, self.t1 = win.start_ns, win.end_ns
        self.window_s = (self.t1 - self.t0) * 1e-9
        self.ops: dict[str, list[Event]] = {}
        for e in events:
            if (DEVICE_PLANE.match(e.plane) and e.line == OPS_LINE
                    and e.end_ns > self.t0 and e.start_ns < self.t1):
                self.ops.setdefault(e.plane, []).append(e)
        self.busy: dict[str, list[list[float]]] = {
            plane: _union_ns([(max(e.start_ns, self.t0),
                               min(e.end_ns, self.t1)) for e in evs])
            for plane, evs in self.ops.items()}
        self.host = [e for e in events if e.name in HOST_SPANS
                     and not DEVICE_PLANE.match(e.plane)]

    @property
    def devices(self) -> int:
        return len(self.busy)

    @property
    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the devices."""
        if not self.busy:
            return 0.0
        tot = sum(e - s for iv in self.busy.values() for s, e in iv)
        return tot * 1e-9 / len(self.busy)

    def kernel_events(self, kernel: str) -> list[Event]:
        return [e for evs in self.ops.values() for e in evs
                if _kernel_of(e) == kernel]

    def _host_at(self, t: float) -> str:
        best = None
        for e in self.host:
            if e.start_ns <= t <= e.end_ns and (
                    best is None or e.dur_ns < best.dur_ns):
                best = e
        return best.name if best else "outside_spans"

    def breakdown(self, top: int = 10) -> dict:
        by_op: dict[str, float] = {}
        for evs in self.ops.values():
            for e, self_ns in _self_times(evs, self.t0, self.t1):
                label = _op_label(e)
                by_op[label] = by_op.get(label, 0.0) + self_ns
        n_dev = max(1, len(self.ops))
        device_ops = sorted(((k, v * 1e-9 / n_dev) for k, v in by_op.items()),
                            key=lambda kv: -kv[1])[:top]
        gaps = []
        for iv in self.busy.values():
            edges = [self.t0] + [x for s, e in iv for x in (s, e)] + [self.t1]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    gaps.append((e - s, s))
        gaps.sort(reverse=True)
        idle = [[self._host_at(s + d / 2), d * 1e-9] for d, s in gaps[:top]]
        return {"device_ops": [[k, v] for k, v in device_ops],
                "idle_gaps": idle}


_INSTR = re.compile(r"^%([A-Za-z_][\w\-]*?)(?:\.\d+)* = ")


def _kernel_of(e: Event) -> str | None:
    """The kernel a TPU custom call runs, by its instruction's name."""
    if 'custom_call_target="tpu_custom_call"' not in e.name:
        return None
    m = _INSTR.match(e.name)
    return m.group(1) if m else None


def _op_label(e: Event) -> str:
    """A stable name for a device op: ``kernel:<name>`` for a Pallas
    call, else the instruction's name without its number and its result
    shape (``fusion f32[64,512,256]``)."""
    kernel = _kernel_of(e)
    if kernel:
        return f"kernel:{kernel}"
    m = _INSTR.match(e.name)
    if not m:
        return e.name[:80]
    result = e.name[m.end():].split(" ", 1)[0]
    shape = _SHAPE.match(result)
    return f"{m.group(1)} {shape.group(0)}" if shape else m.group(1)


def _self_times(evs: list[Event], t0: float, t1: float):
    """(event, self ns within [t0, t1]) for ops on one line, where an op
    nested in another (same line, inside its interval) is its child."""
    order = sorted(evs, key=lambda e: (e.start_ns, -e.dur_ns))
    selfs = {}
    stack: list[Event] = []
    for e in order:
        while stack and stack[-1].end_ns <= e.start_ns:
            stack.pop()
        span = max(0.0, min(e.end_ns, t1) - max(e.start_ns, t0))
        selfs[id(e)] = selfs.get(id(e), 0.0) + span
        if stack and e.end_ns <= stack[-1].end_ns:
            selfs[id(stack[-1])] -= span
        stack.append(e)
    return [(e, max(0.0, selfs[id(e)])) for e in order]
