"""The cells' runners: set-up, the measured window, and the comparison
with the reference, for each kind of traffic mix.

A runner talks to the program through its public surface only: ``Repo``
verbs, ``Table`` probes, ``Engine`` transactions, and the signature and
gather functions a client of the engine calls. It hands the program copies
of the generator's rows and keeps the originals for the reference.
"""
from __future__ import annotations

import contextlib
import sys
import time
import traceback
from collections import defaultdict
from typing import Dict, List, Optional

import numpy as np

from . import tpch, traffic
from .reference import lineitem as ref


#: the configurations' publish guarantee: any true conflict refuses a PR
PUBLISH_MODE = "fail"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _mean(values: List[float]) -> Optional[float]:
    return float(np.mean(values)) if values else None


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
    """The configuration's table, generated from the seed and loaded into a
    fresh ``Repo``, tagged ``base``."""

    def __init__(self, config: dict, seed: int):
        from repro.core import Column, CType, Repo, Schema
        cols = [tuple(c) for c in config["columns"]]
        if tuple(cols) != tpch.COLUMNS:
            raise ValueError(f"{config['name']}: columns differ from the "
                             "generator's TPC-H LINEITEM columns")
        self.name = config["table"]
        self.key = tuple(config["row_key"])
        self.float_columns = [n for n, t in cols if t == "f64"]
        pk = config.get("primary_key")
        self.schema = Schema(tuple(Column(n, CType(t)) for n, t in cols),
                             primary_key=tuple(pk) if pk else None)
        t0 = time.perf_counter()
        self.rows, self.pool = tpch.lineitem(float(config["scale_factor"]),
                                             seed)
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


class Collab:
    """The paper's collaborative rounds (its Tables 4 to 7): each round
    restores main to ``base``, branches the mix's engineers, lets each
    update its rows, then diffs, opens and publishes each PR in turn."""

    def __init__(self, loaded: Loaded, mix: dict, seed: int, verbs: Verbs):
        self.t, self.mix, self.seed, self.verb = loaded, mix, seed, verbs
        self.engineers = [f"eng{w}" for w in range(int(mix["engineers"]))]
        self.live_prs: List[int] = []
        self.branches: List[str] = []
        self.round_no = 0
        self.last_round: Optional[list] = None
        self.diffs: List[tuple] = []          # (updates index, w, DiffResult)
        self.rounds: List[list] = []          # updates of recorded rounds
        self.prs_attempted = self.prs_published = self.rows_landed = 0
        self.failed = 0
        self.record = False

    # ------------------------------------------------------------ updates
    def _new_rows(self, idx, changes):
        new = {c: v[idx] for c, v in self.t.rows.items()}
        new.update(changes)
        return new

    def update(self, phys: str, idx: np.ndarray, changes) -> None:
        """One engineer's update, as one transaction: by primary key where
        the table has one, else by locating the old rows by content."""
        repo, schema = self.t.repo, self.t.schema
        new = self._new_rows(idx, changes)
        if schema.has_pk:
            repo.update_by_keys(phys, new)
            return
        from repro.core import compute_sigs
        old = schema.normalize_batch({c: v[idx]
                                      for c, v in self.t.rows.items()})
        lo, hi = compute_sigs(schema, old)[:2]
        rowids = repo.table(phys).locate_rowsig_multi(
            lo, hi, np.ones(idx.shape[0], np.int64), flat=True)
        tx = repo.engine.begin()
        tx.delete_rowids(phys, rowids)
        tx.insert(phys, new)
        tx.commit()

    # ------------------------------------------------------------- rounds
    def round(self) -> None:
        repo, table = self.t.repo, self.t.name
        ups = traffic.round_updates(self.t.rows, self.t.pool, self.mix,
                                    self.seed, self.round_no)
        self.round_no += 1
        if self.record:
            self.rounds.append(ups)
        with self.verb("restore"):
            for pr in self.live_prs:
                repo.close_pr(pr)
            for b in self.branches:
                repo.drop_branch(b)
            self.live_prs, self.branches = [], []
            repo.restore(table, "base")
        with self.verb("branch"):
            for b in self.engineers:
                repo.branch(b, [table])
                self.branches.append(b)
        for b, (idx, changes) in zip(self.engineers, ups):
            with self.verb("update"):
                self.update(f"{b}/{table}", idx, changes)
        n_rows = self.t.n_rows
        for w, b in enumerate(self.engineers):
            if self.record:
                self.prs_attempted += 1
            with self.verb("diff"):
                d = repo.diff("base", b, table=table)
            if self.record:
                self.diffs.append((len(self.rounds) - 1, w, d))
            with self.verb("open_pr"):
                pr = repo.open_pr(b)
                pr.add_check(lambda ctx: ctx.count(table) == n_rows,
                             "row-count")
            with self.verb("publish"):
                repo.publish(pr.id, PUBLISH_MODE)
            self.live_prs.append(pr.id)
            if self.record:
                self.prs_published += 1
                self.rows_landed += int(ups[w][0].shape[0])
        self.last_round = ups

    def warm_up(self) -> None:
        for _ in range(int(self.mix["warmup_rounds"])):
            self.round()

    def window(self, seconds: float) -> float:
        """Whole rounds until ``seconds`` have passed; returns the window's
        length. A round that fails ends the window."""
        self.record = True
        t0 = time.perf_counter()
        while True:
            before = self.prs_attempted
            try:
                self.round()
            except Exception:
                traceback.print_exc()
                self.failed += int(self.mix["engineers"]) - (
                    self.prs_published - before)
                self.last_round = None
                break
            if time.perf_counter() - t0 >= seconds:
                break
        self.record = False
        return time.perf_counter() - t0

    # ------------------------------------------------------------ results
    def end_to_end(self, window_s: float) -> Dict[str, float]:
        w = self.verb.walls
        return {
            "landed_rows_per_s": self.rows_landed / window_s,
            "diff_s": _mean(w["diff"]),
            "publish_s": _mean(w["publish"]),
        }

    def counts(self) -> Dict[str, int]:
        return {"attempted": self.prs_attempted, "failed": self.failed,
                "diffs": len(self.verb.walls["diff"]),
                "publishes": len(self.verb.walls["publish"]),
                "prs_published": self.prs_published}

    def answers(self):
        """What the window produced, as rows: each diff's change set and
        main's content after the last round."""
        from repro.core import gather_payload
        repo, schema = self.t.repo, self.t.schema
        store = repo.engine.store
        diffs = []
        for r, w, d in self.diffs:
            rows = gather_payload(store, schema, d.rowid)
            rows[ref.CNT] = d.diff_cnt.astype(np.int64)
            diffs.append((r, w, rows))
        main = repo.table(self.t.name).scan()[0] if self.last_round else None
        return diffs, main

    def check(self, lower_precision: bool = False) -> Dict[str, tuple]:
        """Numbers compared with the reference, each with its limit. With
        ``lower_precision`` the control stands in the program's place: the
        reference's own answers in float32 against the reference."""
        base, key = self.t.rows, self.t.key
        diffs, main = self.answers()
        wrong = compared = 0
        for r, w, got in diffs:
            idx, changes = self.rounds[r][w]
            want = ref.change_set(base, idx, changes)
            if lower_precision:
                got = ref.lower_precision(want, self.t.float_columns)
            wrong += ref.mismatched_rows(got, want, key + (ref.CNT,))
            compared += want[key[0]].shape[0]
        out = {"diffs_compared": (len(diffs), None),
               "diff_rows_compared": (compared, None),
               "diff_rows_wrong": (wrong, 0)}
        if main is None:
            out["table_rows_wrong"] = (self.t.n_rows, 0)
        else:
            want = ref.table_after(base, self.last_round)
            if lower_precision:
                main = ref.lower_precision(want, self.t.float_columns)
            out["table_rows_wrong"] = (ref.mismatched_rows(main, want, key),
                                       0)
        return out


class PointReads:
    """YCSB workload C: one closed-loop client reading one row at a time by
    full primary key, after one collaborative round has been published."""

    def __init__(self, loaded: Loaded, mix: dict, seed: int, verbs: Verbs):
        if not loaded.schema.has_pk:
            raise ValueError("point reads need a primary key")
        self.t, self.mix, self.seed, self.verb = loaded, mix, seed, verbs
        self.items: List[int] = []
        self.rows_read: List[Optional[dict]] = []
        self.latency_s: List[float] = []
        self.failed = 0

    def warm_up(self) -> None:
        setup = Collab(self.t, self.mix["setup"], self.seed, self.verb)
        setup.warm_up()
        self.published = setup.last_round
        for i in traffic.point_read_items(self.mix, self.t.n_rows, self.seed,
                                          int(self.mix["warmup_reads"]),
                                          stream=1):
            self.read(int(i))

    def read(self, i: int) -> Optional[dict]:
        from repro.core import gather_payload
        from repro.core.sigs import key_sigs_for_lookup
        repo, schema = self.t.repo, self.t.schema
        key = {c: self.t.rows[c][i:i + 1] for c in schema.primary_key}
        with self.verb("read"):
            lo, hi = key_sigs_for_lookup(schema, key)
            rowid = repo.table(self.t.name).locate_keys(lo, hi)
            row = (gather_payload(repo.engine.store, schema, rowid)
                   if rowid[0] else None)
        return row

    def window(self, seconds: float) -> float:
        items = traffic.point_read_items(self.mix, self.t.n_rows, self.seed,
                                         int(self.mix["max_reads"]))
        walls = self.verb.walls["read"]
        t0 = time.perf_counter()
        for i in items.tolist():
            try:
                row = self.read(i)
            except Exception:
                traceback.print_exc()
                self.failed += 1
                row = None
            self.items.append(i)
            self.rows_read.append(row)
            if time.perf_counter() - t0 >= seconds:
                break
        else:
            raise RuntimeError("the mix's max_reads ran out inside the window")
        self.latency_s = list(walls)
        return time.perf_counter() - t0

    def end_to_end(self, window_s: float) -> Dict[str, float]:
        lat_ms = 1e3 * np.asarray(self.latency_s)
        return {"read_p95_ms": (float(np.percentile(lat_ms, 95))
                                if lat_ms.shape[0] else None)}

    def counts(self) -> Dict[str, int]:
        return {"attempted": len(self.items), "failed": self.failed,
                "reads": len(self.items)}

    def check(self, lower_precision: bool = False) -> Dict[str, tuple]:
        idx = np.asarray(self.items, np.int64)
        want = ref.take(ref.table_after(self.t.rows, self.published), idx)
        found = [r for r in self.rows_read if r is not None]
        if lower_precision:
            got = ref.lower_precision(want, self.t.float_columns)
        elif found:
            got = {c: np.concatenate([r[c] for r in found]) for c in want}
        else:
            got = ref.take(want, np.zeros(0, np.int64))
        # a key read twice answers twice: compare per read, not per key
        seq = np.arange(idx.shape[0], dtype=np.int64)
        want["_read"] = seq
        got = dict(got)
        got["_read"] = (seq if lower_precision else
                        seq[[r is not None for r in self.rows_read]])
        wrong = ref.mismatched_rows(got, want, ("_read",))
        return {"reads_compared": (len(self.items), None),
                "read_rows_wrong": (wrong, 0)}


RUNNERS = {"collab": Collab, "point_reads": PointReads}
