"""The harness is driven by data: cells, mixes, configurations and metrics
are found by the names BENCHMARK.json gives them. And the entry point
refuses to run without a TPU."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import benchtest_util  # noqa: F401  (puts bench/ and src/ on the path)
from harness import spec

ROOT = spec.ROOT


def test_every_named_file_exists_and_every_metric_has_a_reader():
    bench = spec.load_benchmark()
    assert bench["paths"] == ["bench"]
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert spec.reference_module(cell.config).dims(cell.config)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


def test_a_cell_mix_and_metric_added_as_files_are_found(tmp_path,
                                                       monkeypatch):
    """New data files plus new entries in BENCHMARK.json, no code edit."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "qwen05b.burst",
                               "config": bench["configs"][0]["name"],
                               "traffic": "burst", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "lanes_per_step", "unit": "1",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "scheduler and pool",
                               "moves": "tpot_p50_ms",
                               "workloads": ["qwen05b.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    mix = dict(spec.load_cell("qwen05b.chat").traffic, rate_per_s=20.0)
    (tmp_path / "bench" / "traffic" / "burst.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench" / "metrics" / "lanes_per_step.py").write_text(
        "def read(run):\n    return 42.0\n")
    loc = importlib.util.spec_from_file_location(
        "spec_copy", tmp_path / "bench" / "harness" / "spec.py")
    copy = importlib.util.module_from_spec(loc)
    monkeypatch.setitem(sys.modules, "spec_copy", copy)
    loc.loader.exec_module(copy)
    cell = copy.load_cell("qwen05b.burst", root=tmp_path)
    assert cell.traffic["rate_per_s"] == 20.0
    assert [m["name"] for m in cell.per_layer] == ["lanes_per_step"]
    assert copy.metric_reader("lanes_per_step")(None) == 42.0


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"),
                        "--workload", "qwen05b.chat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
