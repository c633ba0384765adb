"""The harness is driven by data: configurations, traffic mixes, limits,
references, generators and metric readers are found by name, and bad
names, and deployments the harness cannot take, are refused naming the
culprit."""

import json
from pathlib import Path

import pytest

from conftest import tiny_cell, write_bench
from midasbench import cell as cell_lib
from midasbench.spec import Bench, check_name, check_unit

ROOT = Path(__file__).resolve().parent.parent
DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
REAL_DIR = ROOT / "bench"

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
    for sub in ("configs", "traffic", "limits", "metrics", "cost",
                "reference", "traffic_gen"):
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
    (d / "reference" / "new_ref.py").write_text("FIELDS = (('a', 'b'),)\n")
    (d / "traffic_gen" / "new_gen.py").write_text(
        "def make(name, **kw):\n    return name, kw['T']\n"
    )
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(_doc()))
    b = Bench.from_root(tmp_path)
    w = b.workload("new_cell")
    assert b.config(w["config"])["sim"]["m"] == 3
    assert b.traffic(w["traffic"])["T"] == 7
    assert b.limits("new_cell")["limits"] == {"x": 0}
    assert b.reader("new_metric").read(21) == 42
    assert b.cost("new_kernel").cost(2) == (2, 8)
    assert b.reference("new_ref").FIELDS == (("a", "b"),)
    assert b.reference("new_ref") is b.reference("new_ref")
    assert b.generator("new_gen").make("x", T=5) == ("x", 5)
    assert [m["name"] for m in b.metrics_for("per_layer", "new_cell")] == [
        "new_metric"
    ]
    for lookup in (b.reader, b.reference, b.generator):
        with pytest.raises(FileNotFoundError, match="absent_module"):
            lookup("absent_module")
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
        ref = b.reference(b.config(w["config"])["reference"])
        for fn in ("deployment", "targets", "simulate"):
            assert callable(getattr(ref, fn))
        assert all(len(pair) == 2 for pair in ref.FIELDS)
        assert callable(b.generator(b.traffic(w["traffic"])["generator"]).make)
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


def _sim_config_lacks(cell):
    cell.config["sim"]["no_such_knob"] = 1
    return cell_lib.attach_program(cell)


@pytest.mark.parametrize(
    "change,error,culprit",
    [
        ({"reference": "no_such_reference"}, FileNotFoundError,
         "no_such_reference"),
        ({"generator": "no_such_generator"}, FileNotFoundError,
         "no_such_generator"),
        ({"sim": {"faults": ["proxy_crash"]}}, ValueError, "sim.faults"),
        ({"sim": {"cache_mode": "ttl_per_key"}}, ValueError,
         "sim.cache_mode='ttl_per_key'"),
        ({"policy": "chbl"}, ValueError, "policy='chbl'"),
        ({"controller": "pid"}, ValueError, "controller='pid'"),
        ({"grid_field": "op_type"}, ValueError,
         "Workload has no field op_type"),
        ({"program": _sim_config_lacks}, ValueError,
         "SimConfig has no setting no_such_knob"),
    ],
    ids=["reference", "generator", "sim_key", "sim_value", "policy",
         "controller", "grid_field", "sim_config_key"],
)
def test_a_deployment_the_harness_cannot_take_fails_naming_it(
    tmp_path, change, error, culprit
):
    cell = tiny_cell(
        "odd_cell", "paper_testbed", "midas_cache_e8", "testbed_midas",
        sim=change.get("sim"), T=8, scenarios=["bursty"], seeds_per_sweep=1,
        **{k: change[k] for k in ("generator", "policy", "controller")
           if k in change},
    )
    files = {}
    if "reference" in change:
        cell["config"]["reference"] = change["reference"]
    if "grid_field" in change:
        # gen's grids with one more field
        gen = (REAL_DIR / "traffic_gen" / "gen.py").read_text()
        files["traffic_gen/odd_gen.py"] = gen.replace(
            "    is_write: jnp.ndarray  # (T, R) bool, only where mask\n",
            "    is_write: jnp.ndarray  # (T, R) bool, only where mask\n"
            f"    {change['grid_field']}: jnp.ndarray = None\n",
        )
        cell["traffic"]["generator"] = "odd_gen"
    bench = write_bench(tmp_path, [cell], files)
    with pytest.raises(error, match=culprit):
        built = cell_lib.build(bench, "odd_cell", 2**31 + 9)
        change.get("program", cell_lib.attach_program)(built)
