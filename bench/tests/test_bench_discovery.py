"""The harness finds cells, configurations, mixes and per-layer metrics by
name in ``BENCHMARK.json``, so that a cell is added with data files and
entries alone; and ``BENCHMARK.json`` keeps to the benchmark's contract."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.tests.conftest import make_small_root, run_small

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = harness.load_benchmark()


def test_every_cell_resolves_by_name():
    for cell in BENCH["workloads"]:
        config = harness.config_of(BENCH, cell)
        mix = harness.traffic_of(cell)
        assert config["name"] == cell["config"]
        assert mix["kind"] in harness.RUNNERS
    for m in BENCH["per_layer"]:
        assert callable(harness.reader_of(m["name"]))


def test_a_cell_is_added_by_data_files_alone(tmp_path):
    root = make_small_root(str(tmp_path))
    with open(os.path.join(root, "bench", "traffic", "collab_c10k.json")) as f:
        mix = json.load(f)
    mix["rows_per_engineer"] = 20
    with open(os.path.join(root, "bench", "traffic", "collab_c20.json"),
              "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "bench", "metrics",
                           "diffs_per_round.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx.counts['diffs'] / 1.0\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "pk_collab_c20",
                               "config": "lineitem_sf1_pk",
                               "traffic": "collab_c20", "chips": 1,
                               "why": "a cell made of data files"})
    bench["per_layer"].append({"name": "diffs_per_round", "unit": "count",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "delta scan (core/delta.py)",
                               "moves": "diff_s",
                               "workloads": ["pk_collab_c20"]})
    with open(path, "w") as f:
        json.dump(bench, f)
    r = run_small(root, "pk_collab_c20", traced=True)
    assert r["correct"] is True
    assert r["metrics"]["diffs_per_round"]["value"] >= 4
    assert "delta_ms_per_diff" not in r["metrics"]   # listed for other cells
    assert r["check"]["diff_rows_compared"]["value"] % 40 == 0


def _exact_keys(entry, keys, optional=()):
    assert set(keys) <= set(entry) <= set(keys) | set(optional), entry


def test_benchmark_json_keeps_the_contract():
    b = BENCH
    _exact_keys(b, ["command", "paths", "run_seconds", "configs",
                    "workloads", "end_to_end", "per_layer"])
    assert b["paths"] == ["bench"] and b["command"][1] == "bench/run.py"
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        _exact_keys(c, ["name", "source", "file", "reduced", "why"])
        assert c["file"].startswith("bench/")
        assert os.path.isfile(os.path.join(harness.ROOT, c["file"]))
        assert 1 <= len(c["source"]) <= 200 and len(c["reduced"]) <= 16
        for text in (c["source"], c["why"]):
            assert 1 <= len(text) <= 200 and not re.search(r"[\t\n]", text)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    names = set()
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        _exact_keys(m, ["name", "unit", "better", "bound", "source"],
                    ["workloads"])
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"]: w for w in b["workloads"]}
    for w in b["workloads"]:
        _exact_keys(w, ["name", "config", "traffic", "chips", "why"])
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        reported = [m["name"] for m in b["end_to_end"]
                    if harness.applies(m, w["name"])]
        assert "setup_s" in reported and len(reported) >= 2
        assert any(harness.applies(m, w["name"]) for m in b["per_layer"])
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) == \
        len(cells)
    for m in b["per_layer"]:
        _exact_keys(m, ["name", "unit", "better", "source", "layer", "moves"],
                    ["workloads"])
        for w in m.get("workloads", cells):
            assert harness.applies(e2e[m["moves"]], w), (m["name"], w)
    assert len(json.dumps(b)) < 64 * 1024


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "pk_collab_c10k", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=harness.ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_no_program_no_result(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(harness.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "pk_collab_c10k", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_reader_finds_nothing_in_an_empty_window(name):
    ctx = harness.Context(workload="w", device_kind="TPU v5 lite", spans=[],
                          counters={}, counts={}, trace=None)
    value = harness.reader_of(name)(ctx)
    assert value is None or value == 0
