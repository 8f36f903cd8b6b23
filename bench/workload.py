"""What the cells' runners share: the name-based lookup of a
configuration's generator and reference, the loader of a one-table
configuration, the verbs' clock, and the log.

A configuration file names its ``generator``, a module
``bench/<generator>.py`` whose ``columns(config)`` gives each table's
columns and whose ``generate(config, seed)`` gives each table's rows and
the text pool updates draw from; and its ``reference``, a module
``bench/reference/<reference>.py`` that imports nothing of the program.
A traffic mix's ``kind`` names its runner, ``bench/runners/<kind>.py``,
whose ``RUNNER(config, mix, seed, verbs, root)`` loads the configuration
and drives the program: set-up, the measured window, and the comparison
with the reference. Each is loaded by path from the checkout's root, once
per process, so a new configuration or kind of mix is new files alone.

A runner talks to the program through its public surface only: ``Repo``
verbs, ``Table`` probes, ``Engine`` transactions, and the signature and
gather functions a client of the engine calls. It hands the program copies
of the generator's rows and keeps the originals for the reference.
"""
from __future__ import annotations

import contextlib
import hashlib
import importlib.util
import os
import re
import sys
import time
from collections import defaultdict
from types import ModuleType
from typing import Dict, List

MODULE_NAME = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: str) -> ModuleType:
    """The module in the file ``path``, executed once per process and kept
    in ``sys.modules`` under a name made from its path."""
    path = os.path.abspath(path)
    name = "bench_file_" + hashlib.sha1(path.encode()).hexdigest()[:16]
    mod = sys.modules.get(name)
    if mod is None:
        if not os.path.isfile(path):
            raise FileNotFoundError(path)
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return mod


def _named(config: dict, key: str) -> str:
    name = config.get(key)
    if not isinstance(name, str) or not MODULE_NAME.match(name):
        raise ValueError(f"{config.get('name')}: the configuration names no "
                         f"{key} module ({key!r}: {name!r})")
    return name


def generator_of(config: dict, root: str) -> ModuleType:
    """``bench/<generator>.py`` of the configuration."""
    return load_module(os.path.join(root, "bench",
                                    _named(config, "generator") + ".py"))


def reference_of(config: dict, root: str) -> ModuleType:
    """``bench/reference/<reference>.py`` of the configuration."""
    return load_module(os.path.join(root, "bench", "reference",
                                    _named(config, "reference") + ".py"))


class Verbs:
    """Times each harness verb on the host clock and, when ``annotate`` is
    set, marks it in the profiler trace as ``bench.<verb>``."""

    def __init__(self):
        self.annotate = False
        self.walls: Dict[str, List[float]] = defaultdict(list)

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation("bench." + name)
        with ann:
            t0 = time.perf_counter()
            yield
            self.walls[name].append(time.perf_counter() - t0)

    def reset(self) -> None:
        self.walls.clear()


class Loaded:
    """The one table of a configuration (``table``, ``columns``,
    ``row_key``, ``primary_key``), generated from the seed by the
    configuration's generator and loaded into a fresh ``Repo``, tagged
    ``base``; with the configuration's reference as ``ref``."""

    def __init__(self, config: dict, seed: int, root: str):
        from repro.core import Column, CType, Repo, Schema
        gen = generator_of(config, root)
        self.ref = reference_of(config, root)
        self.name = config["table"]
        cols = [tuple(c) for c in config["columns"]]
        if tuple(cols) != tuple(tuple(c) for c in
                                gen.columns(config)[self.name]):
            raise ValueError(f"{config['name']}: columns differ from those "
                             f"its generator {config['generator']!r} makes")
        self.key = tuple(config["row_key"])
        self.float_columns = [n for n, t in cols if t == "f64"]
        pk = config.get("primary_key")
        self.schema = Schema(tuple(Column(n, CType(t)) for n, t in cols),
                             primary_key=tuple(pk) if pk else None)
        t0 = time.perf_counter()
        tables, self.pool = gen.generate(config, seed)
        self.rows = tables[self.name]
        self.n_rows = int(self.rows[self.key[0]].shape[0])
        t1 = time.perf_counter()
        self.repo = Repo()
        self.repo.create_table(self.name, self.schema)
        self.repo.insert(self.name, {c: v.copy()
                                     for c, v in self.rows.items()})
        self.repo.tag("base", self.name)
        log(f"setup: generated {self.n_rows} rows in {t1 - t0:.3f} s, "
            f"loaded in {time.perf_counter() - t1:.3f} s into "
            f"{len(self.repo.table(self.name).directory.data_oids)} objects")
