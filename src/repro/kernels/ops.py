"""Jitted dispatch layer over the Pallas kernels and their jnp oracles.

All core/ code calls these wrappers, never the kernels directly.  Dispatch:

  * mode="auto"      : compiled Pallas on TPU, jnp oracle elsewhere (XLA:CPU
                       compiles the oracle well; interpret-mode Pallas is for
                       correctness, not speed).
  * mode="ref"       : always the pure-jnp oracle.
  * mode="interpret" : Pallas kernels in interpret mode (CPU correctness runs;
                       the tests also call kernels directly with sweeps).
  * mode="pallas"    : compiled Pallas unconditionally (real TPU runs).

The starting mode comes from the ``REPRO_KERNEL_MODE`` environment
variable (validated at import time against the same set) so CI jobs and
benchmark runs can select ref/interpret/pallas without code edits;
``set_mode`` still overrides it at runtime.

Mode is read at TRACE time, so any jitted function that calls these
wrappers bakes the current mode into its cache entries.  Callers that
jit over the dispatch register those functions with
``register_dispatch_cache``; ``set_mode`` clears every registered cache
whenever the mode actually changes, and the ``kernel_mode`` context
manager scopes a set/restore pair for tests and benchmarks.
"""
from __future__ import annotations

import contextlib
import os

# -- jit-cache registry -----------------------------------------------------
# Defined BEFORE the kernel imports: importing this module pulls in
# repro.core (via ref -> isax), whose engine module registers its jitted
# entry points at import time against this partially-initialized module.
#
# Jitted functions whose traces capture the dispatch mode.  set_mode
# clears these on every mode change; without this, a function traced
# under the old mode keeps running the old kernels (mode-sweep tests
# would silently compare a kernel against itself).
_DISPATCH_CACHES: list = []


def register_dispatch_cache(fn) -> None:
    """Register a jitted function whose trace bakes in the kernel mode."""
    _DISPATCH_CACHES.append(fn)


def clear_dispatch_caches() -> None:
    for fn in _DISPATCH_CACHES:
        fn.clear_cache()


import jax

from repro.kernels import ref
from repro.kernels.batch_l2 import batch_l2 as _batch_l2_kernel
from repro.kernels.block_topk import block_topk as _block_topk_kernel
from repro.kernels.dtw_band import dtw_band_panel as _dtw_band_kernel
from repro.kernels.fused_refine import (
    fused_panel_topk as _fused_refine_kernel,
    gathered_panel_topk as _gathered_refine_kernel,
)
from repro.kernels.isax_summarize import isax_summarize as _summ_kernel
from repro.kernels.lb_scan import lb_scan as _lb_kernel

_ENV_VAR = "REPRO_KERNEL_MODE"
_VALID = ("auto", "ref", "interpret", "pallas")


def _mode_from_env() -> str:
    mode = os.environ.get(_ENV_VAR, "auto")
    if mode not in _VALID:
        raise ValueError(
            f"{_ENV_VAR}={mode!r} is not a valid kernel mode; "
            f"choose one of {_VALID}")
    return mode


_MODE = _mode_from_env()


def set_mode(mode: str) -> None:
    global _MODE
    if mode not in _VALID:
        raise ValueError(f"mode must be one of {_VALID}")
    if mode != _MODE:
        _MODE = mode
        clear_dispatch_caches()


def get_mode() -> str:
    return _MODE


@contextlib.contextmanager
def kernel_mode(mode: str):
    """Scoped mode switch: sets ``mode`` (clearing registered jit caches)
    and restores the previous mode — clearing again — on exit, even on
    exceptions."""
    old = _MODE
    set_mode(mode)
    try:
        yield
    finally:
        set_mode(old)


def _use_pallas() -> tuple[bool, bool]:
    """-> (use_pallas_kernel, interpret_flag)."""
    if _MODE == "ref":
        return False, False
    if _MODE == "interpret":
        return True, True
    if _MODE == "pallas":
        return True, False
    # auto
    platform = jax.default_backend()
    return (platform == "tpu"), False


def summarize(x: jax.Array, *, w: int, card: int, normalize: bool = True
              ) -> tuple[jax.Array, jax.Array]:
    """(N, n) -> (paa (N, w), sax (N, w) int32)."""
    use, interp = _use_pallas()
    if use:
        return _summ_kernel(x, w=w, card=card, normalize=normalize,
                            interpret=interp)
    return ref.isax_summarize_ref(x, w=w, card=card,
                                  normalize=normalize)


def lb_scan_planar(q_paa: jax.Array, lo: jax.Array, hi: jax.Array, *, n: int
                   ) -> jax.Array:
    """q_paa (Q, w); lo/hi (w, N) -> (Q, N) squared lower bounds."""
    use, interp = _use_pallas()
    if use:
        return _lb_kernel(q_paa, lo, hi, n=n, interpret=interp)
    return ref.lb_scan_ref(q_paa, lo, hi, n=n)


def batch_l2(q: jax.Array, x: jax.Array) -> jax.Array:
    """q (Q, n), x (N, n) -> (Q, N) squared distances."""
    use, interp = _use_pallas()
    if use:
        return _batch_l2_kernel(q, x, interpret=interp)
    return ref.batch_l2_ref(q, x)


def block_topk(d: jax.Array, ids: jax.Array, k: int
               ) -> tuple[jax.Array, jax.Array]:
    """(dist, id)-lexicographic top-k of a masked panel.

    d (Q, C) f32, ids (Q, C) int32 -> (sel_d (Q, k), sel_id (Q, k)).
    Contract: within a row ids >= 0 are distinct and every lane with
    id < 0 carries d == INF (the engine masks both before calling).
    """
    use, interp = _use_pallas()
    if use and k <= d.shape[-1]:
        return _block_topk_kernel(d, ids, k=k, interpret=interp)
    return ref.block_topk_ref(d, ids, k)


def fused_panel_topk(q: jax.Array, q_paa: jax.Array, block: jax.Array,
                     lo: jax.Array, hi: jax.Array, ids: jax.Array,
                     thr: jax.Array, *, k: int, n: int
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused LB + distance + select over one raw block.

    q (Q, n), q_paa (Q, w), block (C, n), lo/hi (w, C) planar bounds,
    ids (C,) int32, thr (Q,) effective bound (-inf disables a query)
    -> (sel_d (Q, k), sel_id (Q, k), n_live (Q,) int32).
    """
    use, interp = _use_pallas()
    if use and k <= block.shape[0]:
        return _fused_refine_kernel(q, q_paa, block, lo, hi, ids, thr,
                                    k=k, n=n, interpret=interp)
    return ref.fused_panel_topk_ref(q, q_paa, block, lo, hi, ids, thr,
                                    k=k, n=n)


def gathered_panel_topk(q: jax.Array, q_paa: jax.Array, raw: jax.Array,
                        slo: jax.Array, shi: jax.Array, ids: jax.Array,
                        idxs: jax.Array, active: jax.Array, thr: jax.Array,
                        bound: jax.Array, *, k: int, n: int
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused LB + distance + select of each query against its own K blocks
    of the index, read in place (the query-major refine).

    q (Q, n), q_paa (Q, w); raw (B, C, n), slo/shi (B, w, C), ids (B, C);
    idxs (Q, K) block ids, active (Q, K) bool, thr (Q,) pruning bound,
    bound (Q,) the largest distance selected
    -> (sel_d (Q, k), sel_id (Q, k), n_live (Q,) int32).
    The kernel copies whole blocks from HBM, so it takes lane-aligned
    blocks (C and n multiples of 128) and leaves others to the oracle.
    """
    use, interp = _use_pallas()
    _, c, n_len = raw.shape
    if use and k <= c and c % 128 == 0 and n_len % 128 == 0:
        return _gathered_refine_kernel(q, q_paa, raw, slo, shi, ids, idxs,
                                       active, thr, bound, k=k, n=n,
                                       interpret=interp)
    return ref.gathered_panel_topk_ref(q, q_paa, raw, slo, shi, ids, idxs,
                                       active, thr, bound, k=k, n=n)


def dtw_panel(q: jax.Array, x: jax.Array, *, r: int) -> jax.Array:
    """Banded squared-DTW panel. q (Q, n); x (C, n) shared -> (Q, C), or
    x (Q, M, n) gathered -> (Q, M)."""
    use, interp = _use_pallas()
    if use:
        return _dtw_band_kernel(q, x, r=r, interpret=interp)
    return ref.dtw_band_panel_ref(q, x, r=r)
