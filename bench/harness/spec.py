"""Finding a cell's files by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own; the harness reads
``BENCHMARK.json`` and finds the rest by the names it gives:

- a cell (``workloads[]``) names a configuration and a traffic mix;
- a configuration's JSON file is the one its ``configs[].file`` names, and
  it names its plain reference (``bench/reference/<reference>.py``);
- a traffic mix is ``bench/traffic/<traffic>.json``;
- a per-layer metric is read by ``bench/metrics/<metric name>.py``.

Adding any of these is adding files and entries; no code here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file's contents
    traffic: dict           # the traffic file's contents
    traffic_name: str
    end_to_end: list        # the BENCHMARK.json metric entries this cell reports
    per_layer: list


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of BENCHMARK.json with its files read."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH_DIR / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, traffic_name=w["traffic"],
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)])


def _load_file(path: Path, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """``read(run) -> float | None`` of ``bench/metrics/<name>.py``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    return _load_file(path, "bench_metric_" + name.replace(".", "_")).read


def reference_module(config: dict):
    """The plain reference a configuration names."""
    ref = config["reference"]
    return _load_file(BENCH_DIR / "reference" / f"{ref}.py",
                      "bench_reference_" + ref.replace(".", "_"))
