"""``program_spans`` and the readers of the program's span metrics on a
hand-made trace whose answers are known, and on a trace recorded on a
v5e (``data/v5e_disk_request.json.gz``: one ``SearchSession.search``
request of one member-plus-noise query, k=1, over 2^21 series on disk,
and the pull of its answer, inside a ``window`` span).

    python -m pytest chipbench/tests
"""
from pathlib import Path
from types import SimpleNamespace

import pytest

import parts
import program_spans as ps
import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
DEV, HOST = "/device:TPU:0", "/host:CPU"


def _ev(plane, name, start, dur):
    line = tr.OPS_LINE if plane == DEV else "python"
    return tr.Event(plane, line, name, float(start), float(dur), {})


def _op(start, dur=100):
    return _ev(DEV, "%fusion.1 = f32[1,1]{1,0} fusion(...)", start, dur)


def _disk_request():
    """One disk request: stage A refines one block after waiting for its
    read, the walk refines one more; two reads on a reader thread."""
    return [
        _ev(HOST, "window", 0, 10000),
        _ev(HOST, "walk.sync", -500, 400),          # before the window
        _ev(HOST, "request", 0, 9000),
        _ev(HOST, "walk.prep", 100, 500),
        _ev(HOST, "walk.stage_a", 600, 2000),
        _ev(HOST, "walk.dispatch", 700, 1800),
        _ev(HOST, "cache.wait", 800, 1000),
        _ev(HOST, "walk.schedule", 2600, 200),
        _ev(HOST, "walk.sync", 2800, 1000),
        _ev(HOST, "walk.scan", 3800, 100),
        _ev(HOST, "walk.dispatch", 3900, 1000),
        _ev(HOST, "walk.sync", 4900, 2000),
        _ev(HOST, "walk.settle", 6900, 100),
        _ev(HOST, "result_pull", 9000, 500),
        # the reader thread: spans overlapping the client's
        _ev(HOST, "cache.read_file", 750, 950),
        _ev(HOST, "cache.upload", 1700, 90),
        _ev(HOST, "cache.read_file", 3000, 2000),
        _ev(HOST, "cache.upload", 5000, 90),
        # device: gaps [0,500] [600,2400] [2500,2850] [2900,4800]
        # [4900,5000] [5100,9100] [9200,10000]
        _op(500), _op(2400), _op(2850, 50), _op(4800), _op(5000), _op(9100),
    ]


def _read(metric, events, queries=1):
    run = SimpleNamespace(trace=tr.Trace(events),
                          loop=SimpleNamespace(queries=queries))
    return parts.load("metrics", metric).read(run)


def test_self_time_takes_out_nested_client_spans_only():
    t = tr.Trace(_disk_request())
    assert [s for _, s in ps.of(t, "walk.dispatch")] == [800.0, 1000.0]
    assert [s for _, s in ps.of(t, "walk.stage_a")] == [200.0]
    # the first walk.sync starts before the window
    assert [s for _, s in ps.of(t, "walk.sync")] == [1000.0, 2000.0]
    # client spans inside a read's interval run on another thread
    assert [s for _, s in ps.of(t, "cache.read_file")] == [950.0, 2000.0]
    assert ps.mean_ms(t, "walk.dispatch") == pytest.approx(0.9e-3)
    assert ps.mean_ms(t, "engine.dispatch") is None


def test_gaps_are_named_by_the_innermost_client_span():
    gaps = ps.gap_names(tr.Trace(_disk_request()))
    assert gaps == [
        ("walk.prep", pytest.approx(500e-9)),
        ("cache.wait", pytest.approx(1800e-9)),
        ("walk.schedule", pytest.approx(350e-9)),
        # the read on the reader thread also spans this gap's middle
        ("walk.scan", pytest.approx(1900e-9)),
        ("walk.sync", pytest.approx(100e-9)),
        ("request", pytest.approx(4000e-9)),
        (ps.OUTSIDE, pytest.approx(800e-9)),
    ]


def test_a_reader_span_never_names_a_gap():
    events = [_ev(HOST, "window", 0, 1000), _ev(HOST, "request", 0, 1000),
              _ev(HOST, "cache.read_file", 100, 800), _op(0), _op(900)]
    assert ps.gap_names(tr.Trace(events)) == [
        ("request", pytest.approx(800e-9))]


def test_request_coverage():
    t = tr.Trace(_disk_request())
    # client spans cover [100, 7000] of the request's [0, 9000]
    assert ps.request_coverage(t) == pytest.approx(6900 / 9000)


def test_disk_readers_on_known_events():
    ev = _disk_request()
    assert _read("read_wait_ms_per_query", ev, queries=2) == pytest.approx(
        0.5e-3)
    assert _read("block_file_read_ms", ev) == pytest.approx(1.475e-3)
    assert _read("block_upload_ms", ev) == pytest.approx(0.09e-3)
    assert _read("refine_dispatch_ms", ev) == pytest.approx(0.9e-3)
    assert _read("threshold_sync_ms", ev) == pytest.approx(1.5e-3)
    assert _read("unattributed_idle_share", ev) == pytest.approx(
        100 * 4800 / 9450)


def test_search_dispatch_ms_on_known_events():
    ev = [_ev(HOST, "window", 0, 1000), _ev(HOST, "request", 0, 800),
          _ev(HOST, "engine.dispatch", 10, 50),
          _ev(HOST, "engine.dispatch", 400, 100), _op(100)]
    assert _read("search_dispatch_ms", ev) == pytest.approx(0.075e-3)


@pytest.mark.parametrize("metric", [
    "read_wait_ms_per_query", "block_file_read_ms", "block_upload_ms",
    "refine_dispatch_ms", "threshold_sync_ms", "search_dispatch_ms",
    "unattributed_idle_share"])
def test_no_program_spans_read_nothing(metric):
    """A program that records no spans (or an untraced run) gives each
    reader nothing to read, and none raises."""
    ev = [e for e in _disk_request()
          if e.name in ("window", "request", "result_pull")
          or e.plane == DEV]
    assert _read(metric, ev) is None
    run = SimpleNamespace(trace=None, loop=SimpleNamespace(queries=1))
    assert parts.load("metrics", metric).read(run) is None


def test_recorded_v5e_disk_request():
    t = tr.Trace(tr.load_events(DATA / "v5e_disk_request.json.gz"))
    assert t.devices == 1
    assert ps.request_coverage(t) >= 0.9
    n = {name: len(ps.of(t, name)) for name in ps.CLIENT}
    # one stage-A dispatch, then the walk's: a sync after each, plus the
    # first; a survivor scan before each, plus the last, empty one
    assert n["walk.sync"] == n["walk.dispatch"] == n["walk.scan"] > 1
    assert n["walk.prep"] == n["walk.settle"] == 1
    gaps = ps.gap_names(t)
    idle = sum(s for _, s in gaps)
    assert idle == pytest.approx(t.window_s - t.busy_s)
    bare = sum(s for name, s in gaps if name in ("request", ps.OUTSIDE))
    assert bare <= 0.1 * idle
    assert {name for name, _ in gaps} <= set(ps.CLIENT) | {"request"}
    for metric in ("refine_dispatch_ms", "threshold_sync_ms",
                   "block_file_read_ms", "block_upload_ms",
                   "read_wait_ms_per_query", "unattributed_idle_share"):
        assert _read(metric, t.events) > 0, metric
