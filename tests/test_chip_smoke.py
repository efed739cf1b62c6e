"""``chip_smoke.py``'s phases and checks, rehearsed on the CPU at a tiny
size with the Pallas kernels in interpret mode.  The test steers the
phases from outside; the script's ``main`` runs only on a TPU."""
import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

from conftest import run_subprocess

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def data(smoke):
    return smoke.make_data(1536, 4, seed=5, n=64)


def test_main_refuses_without_tpu(smoke, capsys, monkeypatch, tmp_path):
    """On a CPU backend the script fails loudly, with no device record."""
    # with the variable set, main() leaves this process's cache alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert smoke.main([]) == 2
    out, err = capsys.readouterr()
    assert "no TPU found" in err
    assert '"ok"' not in out


def test_oracle_matches_direct_scan(smoke, data):
    raw, batches = data
    d, i = smoke.oracle_knn(raw, batches[0], 5, chunk=500)
    x, q = smoke.znorm64(raw), smoke.znorm64(batches[0])
    full = np.sum((q[:, None, :] - x[None, :, :]) ** 2, axis=-1)
    want = np.argsort(full, axis=1, kind="stable")[:, :5]
    assert np.array_equal(i, want)
    np.testing.assert_allclose(d, np.take_along_axis(full, want, 1),
                               rtol=1e-12, atol=1e-9)


def test_check_rejects_wrong_answers(smoke, data):
    raw, batches = data
    oracle = smoke.oracle_knn(raw, batches[0], 5)
    dist = np.sqrt(oracle[0]).astype(np.float32)
    idx = oracle[1].astype(np.int32)
    assert smoke.check_knn("exact", raw, batches[0], dist, idx, oracle, 5)
    far = np.argmax(np.sum((smoke.znorm64(raw)
                            - smoke.znorm64(batches[0])[0]) ** 2, axis=1))
    bad = idx.copy()
    bad[0, 1] = far                          # a far series at rank 1
    assert not smoke.check_knn("id", raw, batches[0], dist, bad, oracle, 5)
    off = dist.copy()
    off[0, 0] += 0.05                        # a distance off by 0.05
    assert not smoke.check_knn("dist", raw, batches[0], off, idx, oracle, 5)


def test_in_memory_phase(smoke, data):
    from repro.kernels import ops
    raw, batches = data
    with ops.kernel_mode("interpret"):
        out = smoke.phase_in_memory(raw, batches[0], ks=(1, 5), capacity=64)
    oracle = smoke.oracle_knn(raw, batches[0], 5)
    for k, (d, i) in out["results"].items():
        assert smoke.check_knn(f"k={k}", raw, batches[0], d, i, oracle, k)
    assert out["n_blocks"] == len(raw) // 64


def test_on_disk_phase(smoke, data, tmp_path):
    from repro.kernels import ops
    raw, batches = data
    with ops.kernel_mode("interpret"):
        out = smoke.phase_on_disk(raw, batches[1], batches[2], tmp_path,
                                  k=5, capacity=64, workers=2)
    assert out["cache_blocks"] <= out["n_blocks"] // 8
    for name, q in (("search", batches[1]), ("submit", batches[2])):
        assert out["io"][name]["blocks_fetched"] > out["cache_blocks"]
        d, i = out["results"][name]
        assert smoke.check_knn(name, raw, q, d, i,
                               smoke.oracle_knn(raw, q, 5), 5)


def test_dtw_oracle_matches_full_dp(smoke, data):
    """The banded float64 DP against the textbook full-matrix DP."""
    raw, batches = data
    r = 6
    got = smoke.oracle_dtw(raw[:50], batches[0], r)
    q, x = smoke.znorm64(batches[0]), smoke.znorm64(raw[:50])
    n = x.shape[1]
    for qi, xi in ((0, 0), (1, 17), (3, 49)):
        dp = np.full((n + 1, n + 1), np.inf)
        dp[0, 0] = 0.0
        for i in range(1, n + 1):
            for j in range(max(1, i - r), min(n, i + r) + 1):
                dp[i, j] = (q[qi, i - 1] - x[xi, j - 1]) ** 2 + min(
                    dp[i - 1, j], dp[i, j - 1], dp[i - 1, j - 1])
        assert got[qi, xi] == pytest.approx(dp[n, n], rel=1e-12)


def test_dtw_phase(smoke, data):
    from repro.kernels import ops
    raw, batches = data
    with ops.kernel_mode("interpret"):
        out = smoke.phase_dtw(raw, batches[0], k=5, capacity=64)
    assert out["r"] == raw.shape[1] // 10
    all_d2 = smoke.oracle_dtw(raw, batches[0], out["r"])
    oracle = smoke.top_k(all_d2, 5)
    d, i = out["results"]
    assert smoke.check_knn("dtw", raw, batches[0], d, i, oracle, 5,
                           all_d2=all_d2, r=out["r"])
    off = d.copy()
    off[0, 0] += 0.05                        # a distance off by 0.05
    assert not smoke.check_knn("dtw off", raw, batches[0], off, i, oracle,
                               5, all_d2=all_d2, r=out["r"])


def test_four_chip_phase_on_fake_devices():
    """The --four-chips phase on four CPU devices (interpret mode): every
    device holds its own shard, and the answer matches the oracle."""
    out = run_subprocess(f"""
import importlib.util, json, sys
import numpy as np
from repro.kernels import ops
spec = importlib.util.spec_from_file_location("chip_smoke",
                                              {str(ROOT / "chip_smoke.py")!r})
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
raw, batches = smoke.make_data(2048, 4, seed=9, n=64)
with ops.kernel_mode("interpret"):
    out = smoke.phase_four_chips(raw, batches[0], k=5, capacity=64)
d, i = out["results"]
ok = smoke.check_knn("four", raw, batches[0], d, i,
                     smoke.oracle_knn(raw, batches[0], 5), 5)
print(json.dumps({{"ok": bool(ok), "shards": len(out["shards"])}}))
""", devices=4)
    got = json.loads(out.strip().splitlines()[-1])
    assert got == {"ok": True, "shards": 4}
