"""What the benchmark's tests share: benchmark directories of tiny cells,
cut from the real cells' files so that the CPU can run them."""

import json
import shutil
from pathlib import Path

import pytest

from midasbench.spec import Bench

ROOT = Path(__file__).resolve().parent.parent
REAL = Bench.from_root(ROOT)
# the real harness files a tiny benchmark directory takes as they are
SHARED = ("metrics", "cost", "reference", "traffic_gen")


def tiny_cell(name, config, traffic, limits, *, sim=None, chips=1, **cut):
    """A cell named ``name`` from the real files ``config``, ``traffic``
    and ``limits``: ``sim`` overrides deployment settings, ``cut`` traffic
    keys.  The traffic file takes the cell's name; the configuration
    keeps its own unless ``sim`` changes it."""
    cfg = REAL.config(config)
    if sim:
        cfg["sim"].update(sim)
        cfg["name"] = f"{name}_config"
    tr = dict(REAL.traffic(traffic), name=name, **cut)
    return {"name": name, "config": cfg, "traffic": tr,
            "limits": REAL.limits(limits), "chips": chips}


def write_bench(root: Path, cells, files=None) -> Bench:
    """A benchmark at ``root`` holding ``cells`` (``tiny_cell`` dicts),
    the real harness files, and ``files`` ({path under bench: text}); its
    metrics report in every cell."""
    d = Path(root) / "bench"
    for sub in SHARED:
        shutil.copytree(REAL.dir / sub, d / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for sub in ("configs", "traffic", "limits"):
        (d / sub).mkdir(parents=True)
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    doc["workloads"] = []
    for c in cells:
        cfg, tr = c["config"], c["traffic"]
        (d / "configs" / f"{cfg['name']}.json").write_text(json.dumps(cfg))
        (d / "traffic" / f"{tr['name']}.json").write_text(json.dumps(tr))
        (d / "limits" / f"{c['name']}.json").write_text(
            json.dumps(c["limits"])
        )
        doc["workloads"].append({
            "name": c["name"], "config": cfg["name"], "traffic": tr["name"],
            "chips": c["chips"], "why": "test",
        })
    for path, text in (files or {}).items():
        (d / path).parent.mkdir(parents=True, exist_ok=True)
        (d / path).write_text(text)
    for mt in doc["end_to_end"] + doc["per_layer"]:
        mt.pop("workloads", None)
    (Path(root) / "BENCHMARK.json").write_text(json.dumps(doc))
    return Bench(doc, d)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The testbed cell cut to 40 ticks, two scenarios and two seeds per
    sweep, with pinned targets, as ``tiny_cell``."""
    cell = tiny_cell(
        "tiny_cell", "paper_testbed", "midas_cache_e8", "testbed_midas",
        T=40, scenarios=["bursty", "flash_crowd"], seeds_per_sweep=2,
        warmup=False, targets=[0.5, 400.0],
    )
    cell["limits"]["sample_cells"] = 2
    return write_bench(tmp_path_factory.mktemp("tiny"), [cell])
