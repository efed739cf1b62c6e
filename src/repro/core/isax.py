"""iSAX representation: PAA, symbols, region bounds, and lower-bound distances.

Faithful to Shieh & Keogh's iSAX as used by ParIS/ParIS+/MESSI:
  * series are z-normalized,
  * PAA with ``w`` equal-length segments (paper fixes w=16),
  * symbols drawn from equiprobable N(0,1) regions (cardinality 256 = 8 bits),
  * MINDIST lower bound:  LB(q, S)^2 = (n/w) * sum_seg max(0, lo-q, q-hi)^2,
    which never exceeds the true Euclidean distance (no false dismissals).

TPU adaptation (see DESIGN.md §2): alongside the packed symbols we keep the
*decompressed region envelope* ``bounds[..., 2]`` (the breakpoint interval of
each symbol) so the lower-bound kernels are pure VPU arithmetic with no
gathers.  Region sentinels are large-but-finite so f32 arithmetic stays
inf/nan-free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from scipy.stats import norm

# Paper-fixed defaults.
W = 16          # number of PAA segments ("w is fixed to 16 in this paper")
CARD = 256      # per-segment cardinality (8 bits), as in the ParIS/MESSI SAX array
SENTINEL = 1.0e9  # finite stand-in for +/- infinity region edges


@functools.lru_cache(maxsize=None)
def breakpoints(card: int = CARD) -> np.ndarray:
    """The card-1 equiprobable N(0,1) breakpoints, ascending. float32."""
    qs = np.arange(1, card) / card
    return norm.ppf(qs).astype(np.float32)


@functools.lru_cache(maxsize=None)
def region_tables(card: int = CARD) -> tuple[np.ndarray, np.ndarray]:
    """(lo, hi) value tables indexed by symbol; edges use finite sentinels."""
    bps = breakpoints(card)
    lo = np.concatenate([[-SENTINEL], bps]).astype(np.float32)   # lo[s] = bps[s-1]
    hi = np.concatenate([bps, [SENTINEL]]).astype(np.float32)    # hi[s] = bps[s]
    return lo, hi


def znorm(x: jax.Array, eps: float = 1e-8) -> jax.Array:
    """Z-normalize each series along the last axis (standard in this literature)."""
    mu = jnp.mean(x, axis=-1, keepdims=True)
    sd = jnp.std(x, axis=-1, keepdims=True)
    return (x - mu) / jnp.maximum(sd, eps)


def paa(x: jax.Array, w: int = W) -> jax.Array:
    """Piecewise Aggregate Approximation: mean over n/w windows. (..., n) -> (..., w)."""
    n = x.shape[-1]
    if n % w:
        raise ValueError(f"series length {n} not divisible by w={w}")
    return jnp.mean(x.reshape(*x.shape[:-1], w, n // w), axis=-1)


def sax_from_paa(paa_vals: jax.Array, card: int = CARD) -> jax.Array:
    """Quantize PAA values into symbols [0, card) by counting breakpoints below.

    Equivalent to searchsorted into the ascending breakpoint list; implemented
    as a broadcast-compare + sum, which is the VPU-friendly form the Pallas
    kernel mirrors.
    """
    bps = jnp.asarray(breakpoints(card))
    return jnp.sum(paa_vals[..., None] >= bps, axis=-1).astype(jnp.int32)


def bounds_from_sax(sax, card: int = CARD, *, xp=jnp):
    """Decompress symbols into their region [lo, hi]. (..., w) -> (..., w, 2).

    ``xp`` is the array namespace: jnp (default) for the device builders,
    np for the host side of the out-of-core build pipeline
    (storage/pipeline/) — one definition of the symbol→region decode for
    both, same table lookup, bit-identical f32 values.
    """
    lo_t, hi_t = region_tables(card)
    lo = xp.asarray(lo_t)[sax]
    hi = xp.asarray(hi_t)[sax]
    return xp.stack([lo, hi], axis=-1)


def summarize(x: jax.Array, w: int = W, card: int = CARD,
              normalize: bool = True) -> tuple[jax.Array, jax.Array, jax.Array]:
    """znorm -> (paa, sax, bounds) for a batch of series (..., n)."""
    if normalize:
        x = znorm(x)
    p = paa(x, w)
    s = sax_from_paa(p, card)
    return p, s, bounds_from_sax(s, card)


def mindist_paa_bounds_sq(q_paa: jax.Array, bounds: jax.Array, n: int) -> jax.Array:
    """Squared MINDIST between query PAA (..., w) and region bounds (..., w, 2).

    Broadcasts over leading dims. Returns squared lower bound of the Euclidean
    distance between the query and ANY series whose PAA lies in the bounds.
    """
    lo = bounds[..., 0]
    hi = bounds[..., 1]
    d = jnp.maximum(jnp.maximum(lo - q_paa, q_paa - hi), 0.0)
    w = q_paa.shape[-1]
    return (n / w) * jnp.sum(d * d, axis=-1)


def paa_lb_sq(q_paa: jax.Array, s_paa: jax.Array, n: int) -> jax.Array:
    """Squared PAA lower bound (n/w)*||q_paa - s_paa||^2 (tighter than MINDIST)."""
    w = q_paa.shape[-1]
    d = q_paa - s_paa
    return (n / w) * jnp.sum(d * d, axis=-1)


# ---------------------------------------------------------------------------
# iSAX word ordering.  MESSI partitions series into root subtrees keyed by the
# first bit of every segment; deeper tree levels refine one segment's
# cardinality at a time.  The TPU-native equivalent is a single sort by the
# *bit-interleaved* iSAX word (MSB of every segment first, then the next bit,
# ...), which clusters exactly like a breadth-first iSAX tree: the top w bits
# reproduce the root partition, each further w-bit group is one refinement
# level.  See DESIGN.md §2/§4.
# ---------------------------------------------------------------------------

def interleaved_keys(sax: jax.Array, w: int = W, bits: int = 8) -> tuple[jax.Array, ...]:
    """Pack the bit-interleaved iSAX word of each series into uint32 sort keys.

    sax: (..., w) int32 symbols (bits-wide). Returns ceil(w*bits/32) uint32
    keys, most-significant key first.
    """
    if w > 32:
        raise ValueError("w > 32 unsupported")
    per_key = max(1, 32 // w)           # bit-levels per uint32 key
    keys = []
    for k0 in range(0, bits, per_key):
        key = jnp.zeros(sax.shape[:-1], dtype=jnp.uint32)
        for j in range(min(per_key, bits - k0)):
            level = k0 + j              # bit level (0 = MSB)
            bit = (sax >> (bits - 1 - level)) & 1
            for seg in range(w):
                shift = (min(per_key, bits - k0) - 1 - j) * w + (w - 1 - seg)
                key = key | (bit[..., seg].astype(jnp.uint32) << shift)
        keys.append(key)
    return tuple(keys)


def sort_order(sax: jax.Array, w: int = W, bits: int = 8) -> jax.Array:
    """Permutation sorting series by their bit-interleaved iSAX word.

    Ties keep input order, as ``jnp.lexsort`` would.  Built as one stable
    single-key sort per key, least significant first (LSD): the same
    permutation, but a sort whose comparator spans all four keys took
    about four minutes in the TPU compiler (v5e target) against about half
    a minute for these passes.
    """
    keys = interleaved_keys(sax, w, bits)
    order = jnp.arange(sax.shape[0], dtype=jnp.int32)
    for key in reversed(keys):
        _, order = jax.lax.sort((key[order], order), num_keys=1,
                                is_stable=True)
    return order
