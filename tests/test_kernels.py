"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs the pure-jnp
oracles in kernels/ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import isax
from repro.kernels import ref
from repro.kernels.batch_l2 import batch_l2
from repro.kernels.isax_summarize import isax_summarize
from repro.kernels.lb_scan import lb_scan

RNG = np.random.default_rng(42)


def series(n, length, dtype=np.float32):
    return jnp.asarray(
        np.cumsum(RNG.standard_normal((n, length)), axis=1).astype(dtype))


def expanded_l2_tol(n, norms_sq):
    """Worst-case gap between two f32 evaluations of the expanded-form
    squared distance ||q||^2 + ||x||^2 - 2 q.x that sum in different
    orders (DESIGN.md §8): each lies within (2 gamma_n + 4u)(qq + xx)
    of the exact value.  ``norms_sq`` is qq + xx."""
    u = 2.0 ** -24
    gamma = n * u / (1 - n * u)
    return (4 * gamma + 8 * u) * norms_sq


@pytest.mark.parametrize("n,length", [(8, 64), (100, 128), (256, 256),
                                      (1000, 512), (37, 96)])
@pytest.mark.parametrize("w", [8, 16, 32])
def test_summarize_sweep(n, length, w):
    if length % w:
        pytest.skip("length % w != 0")
    x = series(n, length)
    paa_k, sax_k = isax_summarize(x, w=w, card=256, interpret=True)
    xn = isax.znorm(x)
    paa_r, sax_r = ref.paa_sax_ref(xn, w, 256)
    np.testing.assert_allclose(np.asarray(paa_k), np.asarray(paa_r),
                               rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(sax_k), np.asarray(sax_r))


@pytest.mark.parametrize("card", [4, 16, 64, 256])
def test_summarize_cardinalities(card):
    x = series(64, 128)
    _, sax_k = isax_summarize(x, w=16, card=card, interpret=True)
    xn = isax.znorm(x)
    _, sax_r = ref.paa_sax_ref(xn, 16, card)
    assert np.array_equal(np.asarray(sax_k), np.asarray(sax_r))
    assert int(jnp.max(sax_k)) < card and int(jnp.min(sax_k)) >= 0


@pytest.mark.parametrize("q,n", [(1, 128), (8, 512), (16, 1000), (5, 2048),
                                 (64, 64)])
@pytest.mark.parametrize("w", [8, 16])
def test_lb_scan_sweep(q, n, w):
    x = series(n, 128)
    qs = series(q, 128)
    _, sax, bounds = isax.summarize(x, w=w)
    q_paa = isax.paa(isax.znorm(qs), w)
    lo = bounds[..., 0].T
    hi = bounds[..., 1].T
    got = lb_scan(q_paa, lo, hi, n=128, interpret=True)
    want = ref.lb_series_ref(q_paa, bounds, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("tile_q,tile_n", [(2, 128), (8, 512), (16, 256)])
def test_lb_scan_tilings(tile_q, tile_n):
    x = series(300, 128)
    qs = series(7, 128)
    _, _, bounds = isax.summarize(x)
    q_paa = isax.paa(isax.znorm(qs), 16)
    got = lb_scan(q_paa, bounds[..., 0].T, bounds[..., 1].T, n=128,
                  tile_q=tile_q, tile_n=tile_n, interpret=True)
    want = ref.lb_series_ref(q_paa, bounds, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("q,n,length", [(4, 128, 64), (16, 512, 256),
                                        (3, 100, 128), (128, 128, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_batch_l2_sweep(q, n, length, dtype):
    x = series(n, length).astype(dtype)
    qs = series(q, length).astype(dtype)
    got = batch_l2(qs, x, interpret=True)
    want = ref.batch_l2_exact_ref(qs.astype(jnp.float32),
                                  x.astype(jnp.float32))
    tol = 1e-3 if dtype == jnp.float32 else 0.3
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * np.max(np.asarray(want)))


def test_batch_l2_identity_zero():
    x = series(32, 128)
    d = batch_l2(x[:4], x, interpret=True)
    for i in range(4):
        assert float(d[i, i]) <= 1e-2
        assert int(jnp.argmin(d[i])) == i


@pytest.mark.parametrize("b,s,d,n", [(1, 16, 8, 4), (2, 32, 100, 16),
                                     (1, 64, 128, 8)])
def test_ssm_scan_kernel_vs_ref(b, s, d, n):
    from repro.kernels.ssm_scan import ssm_scan
    mk = lambda *sh: jnp.asarray(
        RNG.standard_normal(sh).astype(np.float32) * 0.5)
    xc, dt = mk(b, s, d), jnp.abs(mk(b, s, d)) * 0.2
    bm, cm = mk(b, s, n), mk(b, s, n)
    a_log = -jnp.abs(mk(d, n)) - 0.1
    got = ssm_scan(xc, dt, bm, cm, a_log, tile_d=32, interpret=True)
    want = ref.ssm_scan_ref(xc, dt, bm, cm, a_log)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_ssm_scan_matches_mamba_layer_math():
    """The kernel's recurrence == models/mamba's (with matching coeffs)."""
    from repro.kernels.ssm_scan import ssm_scan
    from repro.models import mamba, common as C

    class Cfg:
        n_layers = 1
        d_model = 32
        ssm_state = 8
        ssm_conv = 4
    p = jax.tree.map(lambda a: a[0],
                     C.build_params(mamba.param_specs(Cfg, 48),
                                    jax.random.PRNGKey(1)))
    x = jnp.asarray(RNG.standard_normal((2, 24, 32)).astype(np.float32) * .2)
    xz = jnp.einsum("bsd,de->bse", x, p["w_in"])
    xi = xz[..., :48]
    xc = jax.nn.silu(mamba._conv_causal(
        xi, p["conv"], jnp.zeros((2, 3, 48), x.dtype)))
    dt = jax.nn.softplus(xc * p["w_dt"][..., 0] + p["dt_bias"])
    bm = jnp.einsum("bsd,dn->bsn", xc, p["w_b"])
    cm = jnp.einsum("bsd,dn->bsn", xc, p["w_c"])
    a_log = -jnp.exp(p["a_log"].astype(jnp.float32))
    y_k = ssm_scan(xc, dt, bm, cm, a_log, tile_d=16, interpret=True)
    # reference: h-scan part of mamba (before D-skip/gate/out-proj)
    a, bb, ct = mamba._ssm_coeffs(xc, p)
    hs, _ = mamba._chunk_scan(a, bb, jnp.zeros((2, 48, 8), jnp.float32))
    y_r = jnp.einsum("bsdn,bsn->bsd", hs, ct.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# ops dispatch: the REPRO_KERNEL_MODE environment variable
# ---------------------------------------------------------------------------

def _mode_subprocess(mode):
    """Fresh interpreter importing repro.kernels.ops under the env var."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("REPRO_KERNEL_MODE", None)
    if mode is not None:
        env["REPRO_KERNEL_MODE"] = mode
    code = "from repro.kernels import ops; print(ops.get_mode())"
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=120)


@pytest.mark.parametrize("mode", ["ref", "interpret", "pallas", "auto"])
def test_kernel_mode_env_var_selects_mode(mode):
    r = _mode_subprocess(mode)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == mode


def test_kernel_mode_env_var_defaults_to_auto():
    r = _mode_subprocess(None)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == "auto"


def test_kernel_mode_env_var_rejects_junk_at_import():
    r = _mode_subprocess("jit-harder")
    assert r.returncode != 0, "junk mode must fail the import loudly"
    assert "REPRO_KERNEL_MODE" in r.stderr and "jit-harder" in r.stderr
    assert "auto" in r.stderr        # the error names the valid choices


def test_mode_ref_and_interpret_agree_through_dispatch():
    """Both dispatch paths of the ops layer on the same inputs: the jnp
    oracle (ref) vs interpret-mode Pallas — the per-kernel sweeps above
    call the kernels directly; this exercises ops.* dispatch itself."""
    from repro.kernels import ops
    x = series(96, 128)
    qs = series(4, 128)
    old = ops.get_mode()
    try:
        ops.set_mode("ref")
        paa_r, sax_r = ops.summarize(x, w=16, card=64)
        _, _, bounds = isax.summarize(x)
        q_paa = isax.paa(isax.znorm(qs), 16)
        lb_r = ops.lb_scan_planar(q_paa, bounds[..., 0].T, bounds[..., 1].T,
                                  n=128)
        d_r = ops.batch_l2(isax.znorm(qs), isax.znorm(x))
        ops.set_mode("interpret")
        paa_i, sax_i = ops.summarize(x, w=16, card=64)
        lb_i = ops.lb_scan_planar(q_paa, bounds[..., 0].T, bounds[..., 1].T,
                                  n=128)
        d_i = ops.batch_l2(isax.znorm(qs), isax.znorm(x))
    finally:
        ops.set_mode(old)
    assert np.array_equal(np.asarray(sax_r), np.asarray(sax_i))
    np.testing.assert_allclose(np.asarray(paa_r), np.asarray(paa_i),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lb_r), np.asarray(lb_i),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(d_r), np.asarray(d_i),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# block-local top-k select (kernels/block_topk.py)
# ---------------------------------------------------------------------------

INF = float(jnp.finfo(jnp.float32).max)


def masked_panel(q, c, frac_dead=0.3, quantize=None):
    """A (d, ids) panel under the engine's masking contract: distinct
    ids >= 0 on live lanes, (INF, -1) on dead ones."""
    d = np.abs(RNG.standard_normal((q, c))).astype(np.float32)
    if quantize:
        d = np.round(d * quantize).astype(np.float32) / quantize  # ties
    ids = np.tile(np.arange(c, dtype=np.int32), (q, 1))
    dead = RNG.random((q, c)) < frac_dead
    d[dead] = INF
    ids[dead] = -1
    return jnp.asarray(d), jnp.asarray(ids)


def frontier_oracle(d, ids, k):
    """topk via core.frontier's own lexsort (the tie-break contract)."""
    from repro.core import frontier as frontier_lib
    sd, si = frontier_lib._topk_by_dist_id(d, ids, k)
    return sd, jnp.where(sd < INF, si, -1)


@pytest.mark.parametrize("q,c", [(1, 128), (3, 37), (8, 256), (16, 1000)])
@pytest.mark.parametrize("k", [1, 5, 32])
def test_block_topk_sweep(q, c, k):
    from repro.kernels.block_topk import block_topk
    if k > c:
        pytest.skip("k > C is the ref-fallback path (tested separately)")
    d, ids = masked_panel(q, c)
    gd, gi = block_topk(d, ids, k=k, interpret=True)
    wd, wi = ref.block_topk_ref(d, ids, k)
    assert np.array_equal(np.asarray(gd), np.asarray(wd))
    assert np.array_equal(np.asarray(gi), np.asarray(wi))
    fd, fi = frontier_oracle(d, ids, k)
    assert np.array_equal(np.asarray(gd), np.asarray(fd))
    assert np.array_equal(np.asarray(gi), np.asarray(fi))


@pytest.mark.parametrize("tile_q,tile_c", [(1, 128), (4, 128), (8, 256),
                                           (16, 1024)])
def test_block_topk_tilings(tile_q, tile_c):
    from repro.kernels.block_topk import block_topk
    d, ids = masked_panel(7, 300)
    gd, gi = block_topk(d, ids, k=5, tile_q=tile_q, tile_c=tile_c,
                        interpret=True)
    wd, wi = ref.block_topk_ref(d, ids, 5)
    assert np.array_equal(np.asarray(gd), np.asarray(wd))
    assert np.array_equal(np.asarray(gi), np.asarray(wi))


def test_block_topk_tie_break_toward_smaller_id():
    """Quantized distances force exact ties: (dist, id)-lex order must
    match the frontier's lexsort bit-for-bit."""
    from repro.kernels.block_topk import block_topk
    d, ids = masked_panel(5, 400, quantize=4)     # ~4 distinct values
    for k in (1, 8):
        gd, gi = block_topk(d, ids, k=k, interpret=True)
        fd, fi = frontier_oracle(d, ids, k)
        assert np.array_equal(np.asarray(gd), np.asarray(fd))
        assert np.array_equal(np.asarray(gi), np.asarray(fi))


def test_block_topk_all_dead_rows():
    from repro.kernels.block_topk import block_topk
    d, ids = masked_panel(4, 200, frac_dead=1.0)
    gd, gi = block_topk(d, ids, k=6, interpret=True)
    assert np.all(np.asarray(gd) == INF)
    assert np.all(np.asarray(gi) == -1)


def test_block_topk_k_exceeds_candidates():
    """ops dispatch falls back to the padded oracle when k > C."""
    from repro.kernels import ops
    d, ids = masked_panel(3, 8, frac_dead=0.0)
    with ops.kernel_mode("interpret"):
        gd, gi = ops.block_topk(d, ids, 32)
    wd, wi = ref.block_topk_ref(d, ids, 32)
    assert gd.shape == (3, 32)
    assert np.array_equal(np.asarray(gd), np.asarray(wd))
    assert np.array_equal(np.asarray(gi), np.asarray(wi))
    assert np.all(np.asarray(gd[:, 8:]) == INF)
    assert np.all(np.asarray(gi[:, 8:]) == -1)


# ---------------------------------------------------------------------------
# fused LB + distance + select (kernels/fused_refine.py)
# ---------------------------------------------------------------------------

def _fused_inputs(q, c, n, w=16, thr_val=50.0, inactive=()):
    x = series(c, n)
    qs = series(q, n)
    xn, qn = isax.znorm(x), isax.znorm(qs)
    _, _, bounds = isax.summarize(xn, w=w)
    q_paa = isax.paa(qn, w)
    thr = np.full((q,), thr_val, np.float32)
    for i in inactive:
        thr[i] = -np.inf                  # the folded ``active`` mask
    return (qn, q_paa, xn, bounds[..., 0].T, bounds[..., 1].T,
            jnp.arange(c, dtype=jnp.int32), jnp.asarray(thr))


@pytest.mark.parametrize("q,c,n", [(1, 130, 64), (5, 150, 128), (8, 256, 128),
                                   (3, 300, 96)])
@pytest.mark.parametrize("k", [1, 5])
def test_fused_refine_sweep(q, c, n, k):
    """Seeded float data: ids and live counts integer-exact, distances
    match the unfused oracle to float tolerance for any tiling."""
    from repro.kernels.fused_refine import fused_panel_topk
    args = _fused_inputs(q, c, n, inactive=(0,) if q > 2 else ())
    gd, gi, gn = fused_panel_topk(*args, k=k, n=n, interpret=True)
    wd, wi, wn = ref.fused_panel_topk_ref(*args, k=k, n=n)
    assert np.array_equal(np.asarray(gi), np.asarray(wi))
    assert np.array_equal(np.asarray(gn), np.asarray(wn))
    np.testing.assert_allclose(np.asarray(gd), np.asarray(wd),
                               rtol=1e-5, atol=1e-5)


def test_fused_refine_bitwise_at_engine_tiling():
    """At the default (batch_l2-mirroring) tile sizes: ids and live
    counts agree bit-for-bit with the oracle, and the selected squared
    distances within the expanded form's worst-case f32 bound (the
    summation order of a dot is the compiler's choice, so the distance
    bits are not part of the contract — DESIGN.md §8)."""
    from repro.kernels.fused_refine import fused_panel_topk
    args = _fused_inputs(5, 150, 128)
    gd, gi, gn = fused_panel_topk(*args, k=5, n=128, interpret=True)
    wd, wi, wn = ref.fused_panel_topk_ref(*args, k=5, n=128)
    assert np.array_equal(np.asarray(gi), np.asarray(wi))
    assert np.array_equal(np.asarray(gn), np.asarray(wn))
    # z-normed operands: ||q||^2 = ||x||^2 = n
    np.testing.assert_allclose(np.asarray(gd), np.asarray(wd), rtol=0,
                               atol=expanded_l2_tol(128, 2 * 128))


@pytest.mark.parametrize("tile_q,tile_c", [(8, 128), (128, 256), (4, 512)])
def test_fused_refine_tilings(tile_q, tile_c):
    from repro.kernels.fused_refine import fused_panel_topk
    args = _fused_inputs(6, 330, 64)
    gd, gi, gn = fused_panel_topk(*args, k=3, n=64, tile_q=tile_q,
                                  tile_c=tile_c, interpret=True)
    wd, wi, wn = ref.fused_panel_topk_ref(*args, k=3, n=64)
    assert np.array_equal(np.asarray(gi), np.asarray(wi))
    assert np.array_equal(np.asarray(gn), np.asarray(wn))
    np.testing.assert_allclose(np.asarray(gd), np.asarray(wd),
                               rtol=1e-5, atol=1e-5)


def test_fused_refine_all_pruned_and_inactive():
    """thr = 0 prunes every lane (lb >= 0 always); -inf rows are inactive
    queries.  Everything comes back (INF, -1) with zero live lanes —
    exactly what the engine's unfused path inserted."""
    from repro.kernels.fused_refine import fused_panel_topk
    args = list(_fused_inputs(4, 140, 64, thr_val=0.0, inactive=(2,)))
    gd, gi, gn = fused_panel_topk(*args, k=4, n=64, interpret=True)
    assert np.all(np.asarray(gd) == INF)
    assert np.all(np.asarray(gi) == -1)
    assert np.all(np.asarray(gn) == 0)


def test_fused_refine_padding_lanes_ignored():
    """ids < 0 lanes (block padding) never surface, even with huge thr."""
    from repro.kernels.fused_refine import fused_panel_topk
    args = list(_fused_inputs(3, 100, 64, thr_val=INF))
    ids = np.asarray(args[5]).copy()
    ids[60:] = -1                                 # pad tail of the block
    args[5] = jnp.asarray(ids)
    gd, gi, gn = fused_panel_topk(*args, k=8, n=64, interpret=True)
    wd, wi, wn = ref.fused_panel_topk_ref(*args, k=8, n=64)
    assert np.array_equal(np.asarray(gi), np.asarray(wi))
    assert np.array_equal(np.asarray(gn), np.asarray(wn))
    assert np.all(np.asarray(gi) < 60)
    assert np.all(np.asarray(gn) == 60)


# ---------------------------------------------------------------------------
# gathered refine: each query against its own K blocks, read in place
# ---------------------------------------------------------------------------

def _gathered_inputs(q, kb, k, b=12, c=128, n=128, w=16, thr_val=100.0,
                     seed=0):
    """A (b, c, n) index with shuffled ids, q z-normed queries, each with
    kb distinct blocks of which about 70% are active.  b = 12 puts four
    blocks past the id table's last whole 8-row tile."""
    rng = np.random.default_rng(seed)
    x = isax.znorm(series(b * c, n))
    _, _, bounds = isax.summarize(x, w=w)                 # (b*c, w, 2)
    planar = lambda a: a.reshape(b, c, w).transpose(0, 2, 1)
    qs = isax.znorm(series(q, n))
    idxs = np.stack([rng.choice(b, kb, replace=False) for _ in range(q)])
    return dict(
        q=qs, q_paa=isax.paa(qs, w), raw=x.reshape(b, c, n),
        slo=planar(bounds[..., 0]), shi=planar(bounds[..., 1]),
        ids=rng.permutation(b * c).reshape(b, c).astype(np.int32),
        idxs=idxs.astype(np.int32), active=rng.random((q, kb)) < 0.7,
        thr=np.full((q,), thr_val, np.float32),
        bound=np.full((q,), INF, np.float32))


def _check_gathered(args, k, n=128):
    """Ids and live counts exact; distances within the expanded form's
    worst-case f32 bound (z-normed operands: ||q||^2 = ||x||^2 = n)."""
    from repro.kernels.fused_refine import gathered_panel_topk
    args = {a: jnp.asarray(v) for a, v in args.items()}
    gd, gi, gn = gathered_panel_topk(**args, k=k, n=n, interpret=True)
    wd, wi, wn = ref.gathered_panel_topk_ref(**args, k=k, n=n)
    assert np.array_equal(np.asarray(gi), np.asarray(wi))
    assert np.array_equal(np.asarray(gn), np.asarray(wn))
    live = np.asarray(wi) >= 0
    assert np.array_equal(np.asarray(gd)[~live], np.asarray(wd)[~live])
    np.testing.assert_allclose(np.asarray(gd)[live], np.asarray(wd)[live],
                               rtol=0, atol=expanded_l2_tol(n, 2 * n))
    return gd, gi, gn


@pytest.mark.parametrize("q", [1, 3, 16])
@pytest.mark.parametrize("kb", [1, 4])
@pytest.mark.parametrize("k", [1, 10])
def test_gathered_refine_sweep(q, kb, k):
    args = _gathered_inputs(q, kb, k, seed=q * 100 + kb * 10 + k)
    args["active"][0, 0] = True                 # at least one step runs
    _check_gathered(args, k)


def _edge_inactive_rows(a):
    a["active"][0] = False
    a["active"][2] = False


def _edge_all_pruned(a):
    a["thr"][:] = 0.0                           # lb >= 0 prunes every lane


def _edge_pad_lanes(a):
    a["ids"][a["idxs"][0, 0], 80:] = -1         # a block's padded tail
    a["raw"] = np.asarray(a["raw"]).copy()
    a["raw"][a["idxs"][0, 0], 80:] = 1.0e4      # RAW_PAD
    a["active"][0, 0] = True
    a["thr"][:] = INF


def _edge_ties(a):
    """One block of copies of a series near query 1: its ten lanes in the
    top-10 tie, and the tie goes to the smaller ids."""
    b0 = a["idxs"][1, 0]
    raw = np.asarray(a["raw"]).copy()
    raw[b0] = np.asarray(a["q"])[1] + 0.01
    a["raw"] = raw
    a["active"][1, 0] = True
    a["thr"][:] = INF


def _edge_neg_inf_thr(a):
    a["active"][1] = True
    a["thr"][1] = -np.inf


def _edge_bound(a):
    """Only distances within ``bound`` (the frontier's k-th) are selected,
    here halfway between each row's 5th and 6th; the live count still
    counts every live lane."""
    a["thr"][:] = INF
    a["active"][:] = True
    sd, _, _ = ref.gathered_panel_topk_ref(
        **{n: jnp.asarray(v) for n, v in a.items()}, k=10, n=128)
    a["bound"][:] = (np.asarray(sd)[:, 4] + np.asarray(sd)[:, 5]) / 2
    a["bound"][3] = 0.0


@pytest.mark.parametrize("edge", [_edge_inactive_rows, _edge_all_pruned,
                                  _edge_pad_lanes, _edge_ties,
                                  _edge_neg_inf_thr, _edge_bound],
                         ids=lambda f: f.__name__[6:])
def test_gathered_refine_edges(edge):
    args = _gathered_inputs(4, 4, 10, seed=7)
    edge(args)
    gd, gi, gn = _check_gathered(args, 10)
    gi, gn = np.asarray(gi), np.asarray(gn)
    if edge is _edge_inactive_rows:
        assert np.all(gi[[0, 2]] == -1) and np.all(gn[[0, 2]] == 0)
    elif edge is _edge_all_pruned:
        assert np.all(gi == -1) and np.all(gn == 0)
        assert np.all(np.asarray(gd) == INF)
    elif edge is _edge_pad_lanes:
        pad = set(args["ids"][args["idxs"][0, 0], 80:].tolist())
        assert not pad & set(gi[0].tolist())
    elif edge is _edge_ties:
        block_ids = np.sort(args["ids"][args["idxs"][1, 0]])
        assert np.array_equal(gi[1], block_ids[:10])
    elif edge is _edge_neg_inf_thr:
        assert np.all(gi[1] == -1) and gn[1] == 0
    elif edge is _edge_bound:
        gd = np.asarray(gd)
        assert np.all((gi[:3, :5] >= 0) & (gi[:3, 5:] == -1))
        assert np.all(gi[3] == -1) and gn[3] == 4 * 128


# ---------------------------------------------------------------------------
# banded-DTW wavefront (kernels/dtw_band.py)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("q,c,n", [(1, 130, 32), (4, 150, 64), (6, 256, 64)])
@pytest.mark.parametrize("r", [2, 7])
def test_dtw_band_panel_shared_bitwise(q, c, n, r):
    """Purely elementwise wavefront: kernel == lax.scan oracle
    BIT-FOR-BIT, shared-panel form."""
    from repro.kernels.dtw_band import dtw_band_panel
    x = isax.znorm(series(c, n))
    qs = isax.znorm(series(q, n))
    got = dtw_band_panel(qs, x, r=r, interpret=True)
    want = ref.dtw_band_ref(qs[:, None, :], x[None], r)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m", [40, 128, 300])
def test_dtw_band_panel_gathered_bitwise(m):
    from repro.kernels.dtw_band import dtw_band_panel
    xg = isax.znorm(series(3 * m, 48)).reshape(3, m, 48)
    qs = isax.znorm(series(3, 48))
    got = dtw_band_panel(qs, xg, r=5, interpret=True)
    want = ref.dtw_band_ref(qs[:, None, :], xg, 5)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("tile_m", [128, 256, 512])
def test_dtw_band_panel_tilings(tile_m):
    from repro.kernels.dtw_band import dtw_band_panel
    x = isax.znorm(series(333, 32))
    qs = isax.znorm(series(2, 32))
    got = dtw_band_panel(qs, x, r=4, tile_m=tile_m, interpret=True)
    want = ref.dtw_band_ref(qs[:, None, :], x[None], 4)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_dtw_band_panel_zero_to_self():
    from repro.kernels.dtw_band import dtw_band_panel
    x = isax.znorm(series(16, 64))
    d = dtw_band_panel(x[:4], x, r=5, interpret=True)
    for i in range(4):
        assert float(d[i, i]) < 1e-6
        assert int(jnp.argmin(d[i])) == i


# ---------------------------------------------------------------------------
# kernel_mode: scoped dispatch with jit-cache invalidation
# ---------------------------------------------------------------------------

def test_kernel_mode_sets_and_restores():
    from repro.kernels import ops
    old = ops.get_mode()
    with ops.kernel_mode("ref"):
        assert ops.get_mode() == "ref"
        with ops.kernel_mode("interpret"):
            assert ops.get_mode() == "interpret"
        assert ops.get_mode() == "ref"
    assert ops.get_mode() == old


def test_kernel_mode_restores_on_exception():
    from repro.kernels import ops
    old = ops.get_mode()
    with pytest.raises(RuntimeError):
        with ops.kernel_mode("ref"):
            raise RuntimeError("boom")
    assert ops.get_mode() == old


def test_kernel_mode_clears_registered_jit_caches(monkeypatch):
    """The regression the context manager exists for: a jitted caller
    traced under one mode must NOT keep serving the stale kernel after
    the mode changes — set_mode without a cache clear would silently
    compare a kernel against itself in every mode-sweep test."""
    from repro.kernels import ops
    calls = []
    real = ops._batch_l2_kernel

    def spy(q, x, **kw):
        calls.append(kw)
        return real(q, x, **kw)

    monkeypatch.setattr(ops, "_batch_l2_kernel", spy)

    @jax.jit
    def f(q, x):
        return ops.batch_l2(q, x)

    ops.register_dispatch_cache(f)
    try:
        q, x = series(2, 64), series(16, 64)
        with ops.kernel_mode("ref"):
            f(q, x)
            assert not calls          # oracle path traced in
            with ops.kernel_mode("interpret"):
                f(q, x)               # stale cache would skip the kernel
            assert len(calls) == 1 and calls[0]["interpret"] is True
            f(q, x)                   # back under ref: retraced again
            assert len(calls) == 1
    finally:
        ops._DISPATCH_CACHES.remove(f)


# ---------------------------------------------------------------------------
# engine cell matrix: ref vs interpret through the full drivers
# ---------------------------------------------------------------------------

def _cell_fixtures():
    import repro.core as core
    from repro.core import vector
    from repro.data import random_walk
    raw = jnp.asarray(random_walk(192, 64, seed=21))
    rng = np.random.default_rng(22)
    qs = jnp.asarray(np.asarray(raw[:4])
                     + 0.05 * rng.standard_normal((4, 64)).astype(np.float32))
    idx = core.build(raw, capacity=32)
    fidx = core.build_flat(raw)
    embs = jnp.asarray(rng.standard_normal((256, 64)).astype(np.float32))
    vidx = vector.build_vector_index(embs, capacity=32)
    vq = embs[:4] + 0.01
    return dict(raw=raw, qs=qs, idx=idx, fidx=fidx, vidx=vidx, vq=vq)


_CELLS = {
    "ed_query_major": lambda c, core, D, vector:
        core.search(c["idx"], c["qs"], k=5),
    "ed_block_major": lambda c, core, D, vector:
        core.search_block_major(c["idx"], c["qs"], k=5),
    "ed_paris_flat": lambda c, core, D, vector:
        core.search_paris(c["idx"], c["qs"], k=5, chunk=64),
    "ed_ucr_scan": lambda c, core, D, vector:
        core.search_scan(c["raw"], c["qs"], k=5, chunk=64),
    "dtw_query_major": lambda c, core, D, vector:
        D.search_dtw(c["idx"], c["qs"], r=4, k=5),
    "dtw_flat": lambda c, core, D, vector:
        D.search_dtw_flat(c["fidx"], c["qs"], r=4, k=5, chunk=64),
    "cosine_query_major": lambda c, core, D, vector:
        vector.search_vectors(c["vidx"], c["vq"], k=5),
}


@pytest.fixture(scope="module")
def cells():
    return _cell_fixtures()


@pytest.mark.parametrize("cell", sorted(_CELLS))
def test_engine_cells_ref_vs_interpret(cells, cell):
    """The same public driver under both dispatch modes: identical
    neighbour ids and work stats; DTW distances to float tolerance,
    expanded-form (ED/cosine) distances within its worst-case f32 bound
    (DESIGN.md §8)."""
    from repro.core import dtw as D
    from repro.core import vector
    import repro.core as core
    from repro.kernels import ops
    run = _CELLS[cell]
    with ops.kernel_mode("ref"):
        want = run(cells, core, D, vector)
    with ops.kernel_mode("interpret"):
        got = run(cells, core, D, vector)
    assert np.array_equal(np.asarray(got.idx), np.asarray(want.idx))
    if cell.startswith("dtw"):
        np.testing.assert_allclose(np.asarray(got.dist),
                                   np.asarray(want.dist),
                                   rtol=1e-5, atol=1e-5)
    else:
        # results are sqrt'd: compare squares, plus the sqrt's rounding;
        # z-normed series and sqrt(d)-scaled unit vectors both have
        # squared norm 64 here
        g2 = np.asarray(got.dist, np.float64) ** 2
        w2 = np.asarray(want.dist, np.float64) ** 2
        tol = expanded_l2_tol(64, 2 * 64) + 4 * 2.0 ** -24 * w2
        assert np.all(np.abs(g2 - w2) <= tol), (cell, np.abs(g2 - w2).max())
    for g, w in zip(got.stats, want.stats):
        assert np.array_equal(np.asarray(g), np.asarray(w)), cell


def test_refine_insert_width_is_k_not_capacity(monkeypatch):
    """The tentpole's frontier claim, proven on the live drivers: every
    insert during a search carries exactly k pre-selected candidates —
    the merge sorts K + k = 2k elements — never the C-wide panel."""
    import repro.core as core
    from repro.core import frontier as frontier_lib
    from repro.data import random_walk
    from repro.kernels import ops
    widths = []
    real = frontier_lib.insert_batch

    def spy(f, d, ids, **kw):
        widths.append(d.shape[-1])
        return real(f, d, ids, **kw)

    monkeypatch.setattr(frontier_lib, "insert_batch", spy)
    ops.clear_dispatch_caches()     # force retrace so the spy is seen
    try:
        raw = jnp.asarray(random_walk(128, 64, seed=30))
        idx = core.build(raw, capacity=32)
        k = 4
        for drv in (core.search_block_major, core.search):
            widths.clear()
            drv(idx, raw[:3], k=k)
            assert widths, "no inserts traced"
            assert max(widths) == k, (drv.__name__, widths)
        widths.clear()
        core.search_paris(idx, raw[:3], k=k, chunk=64)
        assert widths and max(widths) == k
    finally:
        ops.clear_dispatch_caches()  # drop spy-traced entries
