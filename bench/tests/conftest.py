"""A small copy of the benchmark's data files: the same cells, metrics and
mixes, with the table cut to a few thousand rows and each engineer's change
set to 50 rows, so that a whole run of a cell takes seconds on the CPU."""
import json
import os
import shutil

import pytest

from bench import harness

SMALL_SCALE = 0.002          # 3,000 orders, about 12,000 rows
SMALL_CHANGES = 50


def make_small_root(dst: str, src: str = harness.ROOT) -> str:
    os.makedirs(os.path.join(dst, "bench"), exist_ok=True)
    shutil.copy(os.path.join(src, "BENCHMARK.json"), dst)
    for d in ("configs", "traffic", "metrics", "runners", "reference"):
        shutil.copytree(os.path.join(src, "bench", d),
                        os.path.join(dst, "bench", d), dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for name in os.listdir(os.path.join(dst, "bench", "configs")):
        path = os.path.join(dst, "bench", "configs", name)
        with open(path) as f:
            config = json.load(f)
        shutil.copy(os.path.join(src, "bench", config["generator"] + ".py"),
                    os.path.join(dst, "bench"))
        config["scale_factor"] = SMALL_SCALE
        with open(path, "w") as f:
            json.dump(config, f)
    for name in os.listdir(os.path.join(dst, "bench", "traffic")):
        path = os.path.join(dst, "bench", "traffic", name)
        with open(path) as f:
            mix = json.load(f)
        for part in (mix, mix.get("setup", {})):
            if "rows_per_engineer" in part:
                part["rows_per_engineer"] = SMALL_CHANGES
        with open(path, "w") as f:
            json.dump(mix, f)
    return dst


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    return make_small_root(str(tmp_path_factory.mktemp("bench_root")))


def run_small(root: str, workload: str, seed: int = 2**33 + 5,
              traced: bool = False, seconds: float = 0.3) -> dict:
    import time
    return harness.run_cell(workload, seed, seconds, traced,
                            t_start=time.perf_counter(), root=root,
                            chip=False)
