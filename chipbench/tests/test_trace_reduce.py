"""``trace_reduce`` on a hand-made trace whose answers are known, and on
a small trace recorded on a v5e (``data/v5e_messi_b1_request.json.gz``:
one ``core.search`` request of one query, k=1, over 2^20 random walks,
and the pull of its answer, inside a ``window`` span).

    python -m pytest chipbench/tests
"""
from pathlib import Path

import pytest

import kernel_cost
import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
DEV, HOST = "/device:TPU:0", "/host:CPU"
TOPK = ('%block_topk.2 = (f32[16,10]{1,0:T(8,128)S(1)}, s32[16,10]{1,0:'
        'T(8,128)S(1)}) custom-call(f32[16,2048]{1,0:T(8,128)S(1)} %sel, '
        's32[16,2048]{1,0:T(8,128)S(1)} %ids), custom_call_target='
        '"tpu_custom_call", operand_layout_constraints={f32[16,2048]{1,0}, '
        's32[16,2048]{1,0}}')
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _ev(plane, name, start, dur):
    line = tr.OPS_LINE if plane == DEV else "python"
    return tr.Event(plane, line, name, float(start), float(dur), {})


def _hand_made():
    return [
        _ev(HOST, "window", 100, 1000),
        _ev(HOST, "request", 100, 500),
        _ev(HOST, "result_pull", 600, 100),
        _ev(HOST, "next_batch", 700, 50),
        _ev(HOST, "request", 750, 350),
        # a while op holding two overlapping fusions, an op that starts
        # before the window, a kernel, and an op after the window
        _ev(DEV, "%while.1 = (f32[16,10]{1,0}) while(...)", 150, 150),
        _ev(DEV, "%fusion.1 = f32[64,512,256]{2,1,0} fusion(...)", 150, 100),
        _ev(DEV, "%fusion.2 = f32[64,512,256]{2,1,0} fusion(...)", 250, 50),
        _ev(DEV, "%copy.3 = f32[16,10]{1,0} copy(f32[16,10]{1,0} %a)", 50, 60),
        _ev(DEV, TOPK, 800, 200),
        _ev(DEV, "%fusion.9 = f32[16]{0} fusion(...)", 2000, 10),
    ]


def test_busy_idle_and_kernels():
    t = tr.Trace(_hand_made())
    assert t.window_s == pytest.approx(1000e-9)
    # union: [100,110] + [150,300] + [800,1000] = 10 + 150 + 200
    assert t.busy_s == pytest.approx(360e-9)
    ks = t.kernel_events("block_topk")
    assert len(ks) == 1
    assert tr.operand_shapes(ks[0]) == [(16, 2048), (16, 2048)]
    assert t.kernel_events("lb_scan") == []


def test_breakdown_takes_self_time_and_names_gaps():
    b = tr.Trace(_hand_made()).breakdown()
    ops = dict(b["device_ops"])
    assert ops["kernel:block_topk"] == pytest.approx(200e-9)
    assert ops["fusion f32[64,512,256]"] == pytest.approx(150e-9)
    assert ops.get("while", 0.0) == 0.0        # its body covers it all
    assert ops["copy f32[16,10]"] == pytest.approx(10e-9)
    gaps = b["idle_gaps"]
    # gaps [110,150] and [300,800]: both middles fall in the first request
    assert gaps[0] == ["request", pytest.approx(500e-9)]
    assert sum(g[1] for g in gaps) == pytest.approx(640e-9)


def test_roofline_share_of_hand_made_kernel():
    t = tr.Trace(_hand_made())
    share = kernel_cost.roofline_share(
        t.kernel_events("block_topk"),
        lambda e: kernel_cost.block_topk(*tr.operand_shapes(e)[0], 10),
        PEAKS)
    ops, nbytes = kernel_cost.block_topk(16, 2048, 10)
    want = max(ops / 197e12, nbytes / 819e9) / 200e-9 * 100
    assert share == pytest.approx(want)


def test_a_trace_without_a_window_is_refused():
    with pytest.raises(ValueError):
        tr.Trace([e for e in _hand_made() if e.name != "window"])


def test_recorded_v5e_request():
    events = tr.load_events(DATA / "v5e_messi_b1_request.json.gz")
    t = tr.Trace(events)
    assert t.devices == 1
    assert 0 < t.busy_s < t.window_s
    topk = t.kernel_events("block_topk")
    lb = t.kernel_events("lb_scan")
    assert len(topk) > 100 and len(lb) == 1
    assert {tuple(tr.operand_shapes(e)[0]) for e in topk} <= {
        (1, 512), (1, 2048)}
    assert tr.operand_shapes(lb[0]) == [(1, 16), (16, 2048), (16, 2048)]
    b = t.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert ("kernel:block_topk" in dict(b["device_ops"]))
    self_total = sum(v for _, v in b["device_ops"])
    assert self_total <= t.busy_s * (1 + 1e-9)
    share = kernel_cost.roofline_share(
        topk, lambda e: kernel_cost.block_topk(*tr.operand_shapes(e)[0], 1),
        {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert 0 < share < 100
