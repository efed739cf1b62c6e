"""Operations and bytes of one call of each kernel, from its call shapes.

The least time a call can take on a chip is the larger of operations
over the peak operation rate and bytes over the peak HBM bandwidth
(``least_time``); a kernel's roofline share is that least time over
the device time its trace events took.  What is counted:

* operations the algorithm needs for the real rows: padding of the
  query axis to the kernel's 8-row tile (and of the candidate axis to
  its lane tile) is work the kernel does but the call does not need,
  and is not counted;
* every vector operation (compare, select, min, add, multiply) counts
  as one operation against the bf16 MXU peak, the only operation peak
  the table has.  The v5e's vector unit peaks lower, so these shares
  are lower bounds of the share of the true roofline;
* a float32 dot at ``Precision.HIGHEST`` counts its 2 m n k useful
  operations, not the six bf16 passes the MXU makes for it;
* bytes: every input read once and every output written once, at the
  real shapes.

``block_topk`` extracts k minima: per extraction and lane a min, a
compare, a key select, a key min, two kill compares and two selects
(8 operations), plus the same over the 2k-wide merge with the running
best.  ``lb_scan`` takes per query, series and segment two
subtractions, two maxes, a square and an add (6).
"""
from __future__ import annotations

F32 = I32 = 4
SELECT_OPS = 8         # per lane and extraction of block_topk's select
LB_OPS = 6             # per query, series and segment of the MINDIST


def block_topk(q: int, c: int, k: int) -> tuple[float, float]:
    """d (q, c) f32 and ids (q, c) i32 -> top-k (q, k) pairs."""
    ops = SELECT_OPS * k * q * (c + 2 * k)
    return ops, q * c * (F32 + I32) + q * k * (F32 + I32)


def fused_panel_topk(q: int, c: int, n: int, w: int, k: int
                     ) -> tuple[float, float]:
    """q (q, n), q_paa (q, w), thr (q,), block (c, n), lo/hi (w, c),
    ids (c,) -> (q, k) pairs and (q,) live counts."""
    ops = (2 * q * c * n          # cross term on the MXU
           + 2 * (q + c) * n      # the two squared norms
           + 3 * q * c            # qq + xx - 2 cross, clamp
           + LB_OPS * q * c * w   # per-series MINDIST
           + 2 * q * c            # live mask
           + SELECT_OPS * k * q * (c + 2 * k))
    nbytes = (c * n * F32 + 2 * w * c * F32 + c * I32
              + q * (n + w + 1) * F32
              + q * k * (F32 + I32) + q * I32)
    return ops, nbytes


def lb_scan(q: int, m: int, w: int) -> tuple[float, float]:
    """q_paa (q, w), lo/hi (w, m) -> (q, m) squared lower bounds."""
    return LB_OPS * q * m * w, (2 * w * m + q * w + q * m) * F32


def least_time(ops: float, nbytes: float, peaks: dict
               ) -> tuple[float, str]:
    """-> (seconds, the bound that sets them: "compute" or "memory")."""
    t_ops = ops / peaks["flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def roofline_share(events, cost, peaks: dict) -> float | None:
    """Percent of the roofline over a kernel's trace events: the sum of
    each call's least time over the sum of its device time.  ``cost(ev)``
    gives a call's (operations, bytes), or None where its shapes cannot
    be read; -> None when no event can be costed."""
    least = took = 0.0
    for ev in events:
        c = cost(ev)
        if c is None:
            continue
        least += least_time(*c, peaks)[0]
        took += ev.dur_ns * 1e-9
    return 100.0 * least / took if took > 0 else None
