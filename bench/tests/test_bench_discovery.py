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

from bench import harness, workload
from bench.tests.conftest import make_small_root, run_small

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = harness.load_benchmark()


def test_every_cell_resolves_by_name():
    for cell in BENCH["workloads"]:
        config = harness.config_of(BENCH, cell)
        mix = harness.traffic_of(cell)
        assert config["name"] == cell["config"]
        assert callable(harness.runner_of(mix["kind"]))
        gen = workload.generator_of(config, harness.ROOT)
        assert config["table"] in gen.columns(config)
        assert callable(workload.reference_of(config, harness.ROOT)
                        .table_after)
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


TOY_GENERATOR = """
import numpy as np

COLUMNS = {"parts": (("p_key", "i64"), ("p_qty", "f64")),
           "orders": (("o_key", "i64"), ("o_part", "i64"),
                      ("o_note", "lob"))}


def columns(config):
    return COLUMNS


def generate(config, seed):
    rng = np.random.default_rng([seed, 1])
    n = int(config["rows"])
    notes = np.empty(2 * n, dtype=object)
    notes[:] = [b"note %d" % i for i in rng.integers(0, 1000, 2 * n)]
    return {"parts": {"p_key": np.arange(n, dtype=np.int64),
                      "p_qty": rng.integers(1, 50, n).astype(np.float64)},
            "orders": {"o_key": np.arange(2 * n, dtype=np.int64),
                       "o_part": rng.integers(0, n, 2 * n),
                       "o_note": notes}}, None
"""

TOY_REFERENCE = """
import numpy as np


def after(rows, updates):
    out = {t: {c: v.copy() for c, v in cols.items()}
           for t, cols in rows.items()}
    for table, idx, column, values in updates:
        out[table][column][idx] = values
    return out


def rows_wrong(got, want, key):
    if got[key].shape != want[key].shape:
        return max(got[key].shape[0], want[key].shape[0])
    order = np.argsort(got[key])
    bad = np.zeros(want[key].shape[0], bool)
    for c in want:
        bad |= np.asarray(got[c])[order] != want[c]
    return int(bad.sum())
"""

TOY_RUNNER = """
import time

import numpy as np

from bench.workload import generator_of, reference_of


class TwoTables:
    def __init__(self, config, mix, seed, verbs, root):
        from repro.core import Column, CType, Repo, Schema
        gen = generator_of(config, root)
        self.ref = reference_of(config, root)
        self.rows, _ = gen.generate(config, seed)
        self.keys = config["keys"]
        self.repo = Repo()
        for t, cols in gen.columns(config).items():
            self.repo.create_table(t, Schema(
                tuple(Column(n, CType(k)) for n, k in cols),
                primary_key=(self.keys[t],)))
            self.repo.insert(t, {c: v.copy()
                                 for c, v in self.rows[t].items()})
        self.current = {t: {c: v.copy() for c, v in cols.items()}
                        for t, cols in self.rows.items()}
        self.mix, self.verb = mix, verbs
        self.rng = np.random.default_rng([seed, 2])
        self.updates, self.rounds, self.in_window = [], 0, 0

    def round(self):
        b = f"dev{self.rounds}"
        self.rounds += 1
        self.repo.branch(b, sorted(self.rows))
        m = int(self.mix["rows_per_round"])
        for t, col in (("parts", "p_qty"), ("orders", "o_part")):
            cur = self.current[t]
            idx = np.sort(self.rng.choice(cur[col].shape[0], m,
                                          replace=False))
            values = (cur[col][idx] + 1).astype(cur[col].dtype)
            new = {c: v[idx].copy() for c, v in cur.items()}
            new[col] = values
            with self.verb("update"):
                self.repo.update_by_keys(f"{b}/{t}", new)
            cur[col][idx] = values
            self.updates.append((t, idx, col, values))
        with self.verb("publish"):
            pr = self.repo.open_pr(b)
            self.repo.publish(pr.id, self.mix["publish_mode"])

    def warm_up(self):
        self.round()

    def window(self, seconds):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self.round()
            self.in_window += 1
        return time.perf_counter() - t0

    def end_to_end(self, window_s):
        m = 2 * int(self.mix["rows_per_round"])
        return {"landed_rows_per_s": m * self.in_window / window_s}

    def counts(self):
        return {"attempted": self.in_window, "failed": 0}

    def check(self, lower_precision=False):
        want = self.ref.after(self.rows, self.updates)
        wrong = sum(self.ref.rows_wrong(self.repo.table(t).scan()[0],
                                        want[t], self.keys[t])
                    for t in want)
        return {"table_rows_wrong": (wrong, 0)}


RUNNER = TwoTables
"""


def _tree(root):
    out = {}
    for d, _, files in os.walk(root):
        for name in files:
            path = os.path.join(d, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = f.read()
    return out


def test_a_configuration_is_added_by_new_files_alone(tmp_path):
    """Two tables, with their own generator, reference and runner: new
    files and new entries of ``BENCHMARK.json``, and no other file of the
    harness touched."""
    root = make_small_root(str(tmp_path))
    before = _tree(root)
    new_files = {
        "bench/toy_pair.py": TOY_GENERATOR,
        "bench/reference/toy_pair.py": TOY_REFERENCE,
        "bench/runners/two_tables.py": TOY_RUNNER,
        "bench/configs/toy_pair.json": json.dumps(
            {"generator": "toy_pair", "reference": "toy_pair", "rows": 200,
             "keys": {"parts": "p_key", "orders": "o_key"}}),
        "bench/traffic/two_tables_r10.json": json.dumps(
            {"kind": "two_tables", "rows_per_round": 10,
             "publish_mode": "fail"}),
    }
    for rel, text in new_files.items():
        assert rel not in before
        with open(os.path.join(root, rel), "w") as f:
            f.write(text)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy_pair", "source": "a test",
                             "file": "bench/configs/toy_pair.json",
                             "reduced": [], "why": "two tables"})
    bench["workloads"].append({"name": "toy_pair_rounds",
                               "config": "toy_pair",
                               "traffic": "two_tables_r10", "chips": 1,
                               "why": "a two-table PR per round"})
    for m in bench["end_to_end"]:
        if m["name"] == "landed_rows_per_s":
            m["workloads"].append("toy_pair_rounds")
    with open(path, "w") as f:
        json.dump(bench, f)
    r = run_small(root, "toy_pair_rounds")
    assert r["correct"] is True, r["check"]
    assert r["check"]["table_rows_wrong"]["value"] == 0
    assert r["attempted"] >= 1
    assert set(r["metrics"]) == {"setup_s", "landed_rows_per_s"}
    after = _tree(root)
    changed = {rel for rel in before if after.get(rel) != before[rel]}
    assert changed <= {"BENCHMARK.json"}, changed


def test_a_collab_mix_states_its_publish_mode(tmp_path):
    root = make_small_root(str(tmp_path))
    path = os.path.join(root, "bench", "traffic", "collab_c10k.json")
    with open(path) as f:
        mix = json.load(f)
    del mix["publish_mode"]
    with open(path, "w") as f:
        json.dump(mix, f)
    with pytest.raises(ValueError, match="publish_mode"):
        run_small(root, "pk_collab_c10k")


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
