"""BENCHMARK.json and the files it names hold together: every name is
found by the harness, and every cell reports what the contract asks.

    python -m pytest chipbench/tests
"""
import json
import re
from types import SimpleNamespace

import pytest

import run

BENCH = json.loads((run.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
             + [w["traffic"] for w in BENCH["workloads"]])
    assert all(NAME.match(n) for n in names), names
    for kind in ("end_to_end", "per_layer"):
        for m in BENCH[kind]:
            assert m["better"] in ("lower", "higher")
            assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
    assert len({m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}
               ) == len(BENCH["end_to_end"]) + len(BENCH["per_layer"])


@pytest.mark.parametrize("workload", CELLS)
def test_cell_files_and_metrics(workload):
    c = run.load_cell(workload)
    assert (run.HERE / "paths" / f"{c.cfg['placement']}.py").exists()
    e2e = run.cell_metrics(BENCH, workload, "end_to_end")
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    values = run.end_to_end(
        SimpleNamespace(queries=10, window_s=1.0,
                        recs=[SimpleNamespace(latency_s=0.1)]),
        1.0, 2.0, 2 ** 30)
    assert all(m["name"].split(".")[0] in values for m in e2e)
    layer = run.cell_metrics(BENCH, workload, "per_layer")
    assert layer
    for m in layer:
        assert m["moves"] in names
        assert (run.HERE / "metrics" / f"{m['name'].split('.')[0]}.py"
                ).exists()


def test_limits_sit_between_readings():
    for workload in CELLS:
        lim = json.loads((run.HERE / "limits" / f"{workload}.json"
                          ).read_text())
        for name, limit in lim["limits"].items():
            r = lim.get("readings", {}).get(name)
            if r is None:
                assert limit == 0, (workload, name)
                continue
            assert r["program_max"] < limit < r["control_min"], (
                workload, name)
