"""The harness finds every cell's files by name: a configuration, a
traffic mix or a per-layer metric added as a file (and named in
BENCHMARK.json) is picked up with no edit to the harness."""
import json
import shutil
import types

import pytest

import harness
from conftest import BENCH

SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves(workload):
    cell = harness.resolve(SPEC, workload)
    assert cell.config["model_type"] and cell.limits["max_logit_gap"] > 0
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"]).read)
    for kind in ("program", "reference"):
        cell.module("configs", f"{cell.config['model_type']}_{kind}")


def test_every_named_file_exists():
    for c in SPEC["configs"]:
        assert (BENCH.parent / c["file"]).is_file()
    for w in SPEC["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (BENCH / "limits" / f"{w['name']}.json").is_file()
    for m in SPEC["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name'].split('.')[0]}.py").is_file()


def test_new_files_are_picked_up_by_name(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    spec = json.loads(json.dumps(SPEC))
    (bench / "configs" / "other.json").write_text(json.dumps(
        dict(json.loads((BENCH / "configs" / "qwen1.5-0.5b.json")
                        .read_text()), num_hidden_layers=4)))
    (bench / "traffic" / "burst.json").write_text(json.dumps({
        "arrivals": {"process": "poisson", "rate_per_s": 2.5},
        "prompt": {"dist": "lognormal", "median": 400, "sigma": 0.8,
                   "min": 128, "max": 1536},
        "output": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                   "min": 4, "max": 64}}))
    (bench / "limits" / "other.burst.json").write_text(
        json.dumps({"max_logit_gap": 0.5, "sample_tokens": 64}))
    (bench / "metrics" / "requests_due.py").write_text(
        "def read(run):\n    return float(len(run.reqs))\n")
    spec["configs"].append({"name": "other", "source": "x",
                            "file": "bench/configs/other.json",
                            "reduced": ["num_hidden_layers"], "why": "x"})
    spec["workloads"].append({"name": "other.burst", "config": "other",
                              "traffic": "burst", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "requests_due", "unit": "1",
                              "better": "higher", "source": "host_clock",
                              "layer": "serve loop", "moves": "setup_s",
                              "workloads": ["other.burst"]})
    cell = harness.resolve(spec, "other.burst", bench, tmp_path)
    assert cell.config["num_hidden_layers"] == 4
    assert cell.mix["arrivals"]["rate_per_s"] == 2.5
    assert [m["name"] for m in cell.per_layer] == ["requests_due"]
    run = types.SimpleNamespace(reqs={1: 0, 2: 0})
    assert cell.reader("requests_due").read(run) == 2.0
    # a quantity split by the metric it moves shares its reader
    assert cell.reader("requests_due.other").read(run) == 2.0


def test_an_unknown_workload_is_refused():
    with pytest.raises(KeyError, match="no workload"):
        harness.resolve(SPEC, "nope")


def test_without_the_program_no_result(tmp_path):
    """In a directory holding only BENCHMARK.json and the benchmark's own
    files, a run exits non-zero and prints no result."""
    import subprocess
    import sys

    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    res = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", SPEC["workloads"][0]
         ["name"], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert res.returncode != 0 and not res.stdout.strip()
