"""The harness is driven by data: configurations, traffic mixes, limits
and metric readers are found by name, and bad names are refused."""

import json
from pathlib import Path

import pytest

from midasbench.spec import Bench, check_name, check_unit

ROOT = Path(__file__).resolve().parent.parent
DOC = json.loads((ROOT / "BENCHMARK.json").read_text())

CONTRACT_KEYS = {
    "command", "paths", "run_seconds", "configs", "workloads",
    "end_to_end", "per_layer",
}


def _doc(workload="new_cell", config="new_deployment", traffic="new_mix",
         metric="new_metric", unit="ms"):
    return {
        "configs": [{"name": config}],
        "workloads": [{"name": workload, "config": config,
                       "traffic": traffic, "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": metric, "unit": unit,
                       "workloads": [workload]}],
    }


def test_new_files_are_found_by_name(tmp_path):
    d = tmp_path / "bench"
    for sub in ("configs", "traffic", "limits", "metrics", "cost"):
        (d / sub).mkdir(parents=True)
    (d / "configs" / "new_deployment.json").write_text('{"sim": {"m": 3}}')
    (d / "traffic" / "new_mix.json").write_text('{"T": 7}')
    (d / "limits" / "new_cell.json").write_text('{"limits": {"x": 0}}')
    (d / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return ctx * 2\n"
    )
    (d / "cost" / "new_kernel.py").write_text(
        "def cost(n):\n    return n, 4 * n\n"
    )
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(_doc()))
    b = Bench.from_root(tmp_path)
    w = b.workload("new_cell")
    assert b.config(w["config"])["sim"]["m"] == 3
    assert b.traffic(w["traffic"])["T"] == 7
    assert b.limits("new_cell")["limits"] == {"x": 0}
    assert b.reader("new_metric").read(21) == 42
    assert b.cost("new_kernel").cost(2) == (2, 8)
    assert [m["name"] for m in b.metrics_for("per_layer", "new_cell")] == [
        "new_metric"
    ]
    with pytest.raises(FileNotFoundError):
        b.reader("absent_metric")
    with pytest.raises(ValueError, match="unknown workload"):
        b.workload("absent_cell")


@pytest.mark.parametrize(
    "field,value",
    [
        ("workload", "two words"),
        ("workload", "a,b"),
        ("config", "a/b"),
        ("traffic", "-leading"),
        ("metric", "x" * 65),
        ("metric", "café"),
        ("unit", "tokens per second"),
        ("unit", "µs"),
        ("unit", ""),
    ],
)
def test_names_and_units_outside_the_allowed_set_are_refused(field, value):
    with pytest.raises(ValueError):
        Bench(_doc(**{field: value}), ROOT / "bench")


def test_allowed_names_and_units_pass():
    assert check_name("fleet_midas.x4-b", "workload")
    assert check_unit("cell-ticks/s") and check_unit("%")


def test_benchmark_json_holds_to_the_contract():
    assert set(DOC) == CONTRACT_KEYS
    b = Bench.from_root(ROOT)
    assert DOC["command"][1].startswith(DOC["paths"][0] + "/")
    for c in DOC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert json.loads((ROOT / c["file"]).read_text())["name"] == (
            c["name"]
        )
    for w in DOC["workloads"]:
        b.config(w["config"])
        b.traffic(w["traffic"])
        assert set(b.limits(w["name"])["limits"]) == {
            "row_gap", "rows_differing",
        }
        assert b.metrics_for("end_to_end", w["name"])
        assert b.metrics_for("per_layer", w["name"])
    for mt in DOC["end_to_end"]:
        assert 0 < mt["bound"] <= 0.25
        assert mt["source"] in ("host_clock", "device_trace")
    for mt in DOC["per_layer"]:
        assert callable(b.reader(mt["name"]).read)
        assert mt["moves"] in {m["name"] for m in DOC["end_to_end"]}
