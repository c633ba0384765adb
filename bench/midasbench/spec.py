"""The benchmark's definition: ``BENCHMARK.json`` and the files it names.

Everything that belongs to one deployment, one traffic mix, one cell or
one per-layer metric is a file of its own, found by the name
``BENCHMARK.json`` or another of these files gives it:

* ``configs/<config>.json``: the deployment: its simulator settings
  under ``"sim"`` (every key goes to the program's ``SimConfig`` as it
  is, a list as a tuple; a key ``SimConfig`` lacks fails at set-up), the
  name of its plain reference under ``"reference"``, and its source,
  cuts, assumptions and guarantees;
* ``traffic/<traffic>.json``: the sweep grid a user submits (policy
  stack, scenarios, horizon, slots, seeds per sweep, devices, targets),
  with the name of its generator under ``"generator"``;
* ``reference/<reference>.py``: a plain reference of the deployment's
  semantics, importing nothing of the program.  It provides
  ``deployment(sim, traffic)``, its view of the deployment, which raises
  a ``ValueError`` naming every ``sim`` or traffic key and value it does
  not implement; ``FIELDS``, pairs of (reference field, attribute of the
  program's ``SweepResult.row``) that the rows are compared over;
  ``targets(dep, traffic, gen)``, the control targets, with ``gen`` the
  cell's generator module for any grid it needs (a warmup's); and
  ``simulate(dep, grid, seed, targets, dtype=jnp.float32)``, one grid
  cell's row as ``{field: float64 array}``, ``dtype`` the precision
  of its floats (a lower one is the control that ``correct`` rejects);
* ``traffic_gen/<generator>.py``: a traffic generator with
  ``make(scenario, *, T, m, seed, N, R, dt_ms, service_ms)`` returning a
  ``NamedTuple`` of ``(T, R)`` arrays from the seed; its fields go to the
  program's ``repro.core.workloads.Workload`` by name, and one
  ``Workload`` lacks fails at set-up;
* ``limits/<workload>.json``: the numbers that decide ``correct`` in
  that cell, each with its limit, and ``sample_cells``, the grid cells
  the reference simulates (on several devices, scenarios × devices of
  them read every device's seeds in every scenario);
* ``metrics/<metric>.py``: a reader with ``read(ctx)`` that returns the
  metric or ``None`` when it finds nothing to read;
* ``cost/<kernel>.py``: the operations and bytes of one kernel call.

Adding a cell, a mix, a deployment with its own semantics and traffic,
or a metric is adding such files and an entry in ``BENCHMARK.json``;
no file of the harness changes.
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Dict

BENCH_DIR = Path(__file__).resolve().parent.parent
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def check_name(name: str, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise ValueError(
            f"{what} {name!r}: a name is 1-64 letters, digits, '_', '.' "
            f"or '-', and does not start with '.' or '-'"
        )
    return name


def check_unit(unit: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise ValueError(
            f"unit {unit!r}: 1-16 letters, digits, '_', '/', '%', '.', '-'"
        )
    return unit


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


class Bench:
    """``BENCHMARK.json`` with its names checked, and the lookups of the
    files each name stands for.  ``bench_dir`` is where the harness's
    data lives (a test points it at a temporary directory)."""

    def __init__(self, benchmark: Dict[str, Any], bench_dir: Path):
        self.doc = benchmark
        self.dir = Path(bench_dir)
        self._modules: Dict[Path, ModuleType] = {}
        for c in benchmark["configs"]:
            check_name(c["name"], "config")
        for w in benchmark["workloads"]:
            check_name(w["name"], "workload")
            check_name(w["config"], "config")
            check_name(w["traffic"], "traffic")
        for kind in ("end_to_end", "per_layer"):
            for mt in benchmark[kind]:
                check_name(mt["name"], "metric")
                check_unit(mt["unit"])

    @classmethod
    def from_root(cls, root: Path) -> "Bench":
        root = Path(root)
        return cls(load_json(root / "BENCHMARK.json"), root / "bench")

    def workload(self, name: str) -> Dict[str, Any]:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise ValueError(
            f"unknown workload {name!r}; available: "
            + ", ".join(w["name"] for w in self.doc["workloads"])
        )

    def _file(self, sub: str, name: str, ext: str) -> Path:
        path = self.dir / sub / f"{check_name(name, sub)}{ext}"
        if not path.is_file():
            raise FileNotFoundError(f"no {sub} file for {name!r}: {path}")
        return path

    def config(self, name: str) -> Dict[str, Any]:
        return load_json(self._file("configs", name, ".json"))

    def traffic(self, name: str) -> Dict[str, Any]:
        return load_json(self._file("traffic", name, ".json"))

    def limits(self, workload: str) -> Dict[str, Any]:
        return load_json(self._file("limits", workload, ".json"))

    def _module(self, sub: str, name: str) -> ModuleType:
        """The module ``<sub>/<name>.py``, loaded once per ``Bench`` (a
        reference's jitted functions then compile once)."""
        path = self._file(sub, name, ".py")
        if path not in self._modules:
            self._modules[path] = _load_module(path)
        return self._modules[path]

    def reader(self, metric: str) -> ModuleType:
        return self._module("metrics", metric)

    def cost(self, kernel: str) -> ModuleType:
        return self._module("cost", kernel)

    def reference(self, name: str) -> ModuleType:
        return self._module("reference", name)

    def generator(self, name: str) -> ModuleType:
        return self._module("traffic_gen", name)

    def metrics_for(self, kind: str, workload: str):
        """The ``kind`` metrics that ``workload`` reports."""
        return [
            mt
            for mt in self.doc[kind]
            if workload in mt.get("workloads", [workload])
        ]


def _load_module(path: Path) -> ModuleType:
    """The module at ``path``, registered in ``sys.modules`` (dataclasses
    look their module up there) under a name unique to its path."""
    tag = hashlib.sha1(str(path).encode()).hexdigest()[:12]
    name = f"bench_{path.parent.name}_{path.stem}_{tag}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod
