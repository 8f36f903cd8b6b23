"""The collaborative rounds of the paper's Tables 4 to 7, as a runner of
traffic mixes of ``kind`` ``collab``: the mix gives ``engineers``,
``rows_per_engineer``, ``warmup_rounds``, ``publish_mode`` (``fail`` or
``accept``, the conflict guarantee a PR is published under) and, where the
engineers' rows overlap, ``overlap``."""
from __future__ import annotations

import time
import traceback
from typing import Dict, List, Optional

import numpy as np

from bench import traffic
from bench.workload import Loaded, Verbs

#: the conflict modes whose outcome the references model: FAIL refuses a PR
#: with a true conflict, ACCEPT forces the later PR's version
PUBLISH_MODES = ("fail", "accept")


def _mean(values: List[float]) -> Optional[float]:
    return float(np.mean(values)) if values else None


class Collab:
    """The paper's collaborative rounds (its Tables 4 to 7): each round
    restores main to ``base``, branches the mix's engineers, lets each
    update its rows, then diffs, opens and publishes each PR in turn."""

    def __init__(self, config: dict, mix: dict, seed: int, verbs: Verbs,
                 root: str):
        if mix.get("publish_mode") not in PUBLISH_MODES:
            raise ValueError(f"a collab mix states its publish_mode, one of "
                             f"{PUBLISH_MODES}: {mix.get('publish_mode')!r}")
        self.t = Loaded(config, seed, root)
        self.repo = self.t.repo
        self.mix, self.seed, self.verb = mix, seed, verbs
        self.mode = mix["publish_mode"]
        self.engineers = [f"eng{w}" for w in range(int(mix["engineers"]))]
        self.live_prs: List[int] = []
        self.branches: List[str] = []
        self.round_no = 0
        self.last_round: Optional[list] = None
        self.diffs: List[tuple] = []          # (updates index, w, DiffResult)
        self.conflicts: List[tuple] = []      # (updates index, w, count)
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
                reports = repo.publish(pr.id, self.mode)
            self.live_prs.append(pr.id)
            if self.record:
                self.conflicts.append((len(self.rounds) - 1, w, sum(
                    r.true_conflicts for r in reports.values())))
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
        repo, schema, ref = self.t.repo, self.t.schema, self.t.ref
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
        base, key, ref = self.t.rows, self.t.key, self.t.ref
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
        out.update(self._check_conflicts(lower_precision))
        return out

    def _check_conflicts(self, lower_precision: bool) -> Dict[str, tuple]:
        """The true conflicts each publish reported against the
        reference's count for its PR; the control counts them on its rows
        in float32."""
        ref, floats = self.t.ref, self.t.float_columns
        base = self.t.rows
        recorded = {r for r, _, _ in self.conflicts}
        want = {r: ref.true_conflicts(base, self.rounds[r]) for r in recorded}
        if lower_precision:
            low = ref.lower_precision(base, floats)
            control = {r: ref.true_conflicts(low, [
                (idx, ref.lower_precision(ch, [c for c in floats if c in ch]))
                for idx, ch in self.rounds[r]]) for r in recorded}
        got = wrong = 0
        for r, w, n in self.conflicts:
            if lower_precision:
                n = control[r][w]
            got += n
            wrong += abs(n - want[r][w])
        return {"true_conflicts": (got, None), "conflicts_wrong": (wrong, 0)}


RUNNER = Collab
