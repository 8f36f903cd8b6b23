"""YCSB workload C, as a runner of traffic mixes of ``kind``
``point_reads``: the mix gives the request ``distribution`` and ``theta``,
``warmup_reads``, ``max_reads``, and under ``setup`` the collaborative
round (``bench/runners/collab.py``) published before the reads."""
from __future__ import annotations

import os
import time
import traceback
from typing import Dict, List, Optional

import numpy as np

from bench import traffic
from bench.workload import Verbs, load_module

COLLAB = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "collab.py"))


class PointReads:
    """YCSB workload C: one closed-loop client reading one row at a time by
    full primary key, after one collaborative round has been published."""

    def __init__(self, config: dict, mix: dict, seed: int, verbs: Verbs,
                 root: str):
        self.setup = COLLAB.Collab(config, mix["setup"], seed, verbs, root)
        self.t, self.repo = self.setup.t, self.setup.repo
        if not self.t.schema.has_pk:
            raise ValueError("point reads need a primary key")
        self.mix, self.seed, self.verb = mix, seed, verbs
        self.items: List[int] = []
        self.rows_read: List[Optional[dict]] = []
        self.latency_s: List[float] = []
        self.failed = 0

    def warm_up(self) -> None:
        self.setup.warm_up()
        self.published = self.setup.last_round
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
        ref = self.t.ref
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


RUNNER = PointReads
