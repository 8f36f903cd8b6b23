"""Secondary indices on branches: a branch carries its tables' indices and
maintains them, a batched lookup answers each value as a scan for that
value would, a restore puts the index back to the restored snapshot, and
WAL replay rebuilds the same aux tables."""
import numpy as np
import pytest

from repro.core import Column, CType, Engine, Repo, Schema
from repro.core.indices import create_index, lookup_eq

SCH = Schema((Column("w", CType.I64), Column("c", CType.I64),
              Column("last", CType.LOB), Column("bal", CType.F64)),
             primary_key=("w", "c"))
NAMES = [b"BARBAR", b"OUGHTABLE", b"PRIPRES", b"ESEANTI"]


def _repo(n=40):
    repo = Repo()
    repo.create_table("cust", SCH)
    last = np.empty(n, dtype=object)
    last[:] = [NAMES[i % len(NAMES)] for i in range(n)]
    repo.insert("cust", {"w": np.arange(n) % 2, "c": np.arange(n),
                         "last": last, "bal": np.zeros(n)})
    create_index(repo.engine, "cust", "by_last", ["w", "last"])
    repo.tag("base", "cust")
    return repo


def _scan_answer(engine, table, w, last):
    """The oracle: the PK rows a full scan finds for one value."""
    rows, _ = engine.table(table).scan()
    hit = (rows["w"] == w) & (rows["last"] == last)
    return sorted(zip(rows["w"][hit].tolist(), rows["c"][hit].tolist()))


def _groups(hits, n):
    return [sorted(zip(hits.group(i)["w"].tolist(),
                       hits.group(i)["c"].tolist())) for i in range(n)]


def _aux_rows(engine, table):
    aux = engine.indices[table][0].aux_table
    rows, _ = engine.table(aux).scan()
    return sorted(zip(*(rows[c].tolist() for c in sorted(rows))))


def test_batched_lookup_equals_one_scan_per_value():
    repo = _repo()
    e = repo.engine
    e.update_by_keys("cust", {"w": [1], "c": [1], "last": [b"NEW"],
                              "bal": [1.0]})
    w = np.array([0, 1, 0, 1, 0, 0, 1])
    last = np.empty(7, dtype=object)
    # duplicates in the batch, an absent name, a name absent in one w
    last[:] = [b"BARBAR", b"OUGHTABLE", b"BARBAR", b"NEW", b"NOSUCH",
               b"NEW", b"OUGHTABLE"]
    hits = lookup_eq(e, "cust", "by_last", {"w": w, "last": last})
    assert hits.offsets.shape == (8,)
    want = [_scan_answer(e, "cust", int(a), b) for a, b in zip(w, last)]
    assert _groups(hits, 7) == want
    assert len(want[0]) == 10 and want[4] == [] and want[5] == []
    # the one-value call is the batch of one
    one = lookup_eq(e, "cust", "by_last", {"w": 1, "last": b"NEW"})
    assert one["c"].tolist() == [1]
    empty = lookup_eq(e, "cust", "by_last",
                      {"w": np.zeros(0, np.int64),
                       "last": np.empty(0, dtype=object)})
    assert empty.offsets.tolist() == [0] and empty["c"].shape == (0,)


def test_lookup_values_counter_and_span():
    repo = _repo()
    with repo.trace() as t:
        lookup_eq(repo.engine, "cust", "by_last",
                  {"w": np.array([0, 1]),
                   "last": np.array([b"BARBAR", b"PRIPRES"], dtype=object)})
        repo.engine.update_by_keys("cust", {"w": [0], "c": [0],
                                            "last": [b"Z"], "bal": [2.0]})
    assert [s.name for s in t.roots][0] == "index.lookup"
    assert repo.stats()["index.lookup_values"] == 2
    # one aux row deleted, one inserted
    assert repo.stats()["index.aux_rows"] == 2


def test_branch_carries_maintains_and_drops_its_index():
    repo = _repo()
    e = repo.engine
    repo.branch("dev", ["cust"])
    assert [s.name for s in e.indices["dev/cust"]] == ["by_last"]
    aux = e.indices["dev/cust"][0].aux_table
    assert _aux_rows(e, "dev/cust") == _aux_rows(e, "cust")
    e.update_by_keys("dev/cust", {"w": [0], "c": [4], "last": [b"ZED"],
                                  "bal": [1.0]})
    assert lookup_eq(e, "dev/cust", "by_last",
                     {"w": 0, "last": b"ZED"})["c"].tolist() == [4]
    assert 4 not in lookup_eq(e, "dev/cust", "by_last",
                              {"w": 0, "last": b"BARBAR"})["c"].tolist()
    # main's index is untouched by the branch's transaction
    assert lookup_eq(e, "cust", "by_last",
                     {"w": 0, "last": b"ZED"})["c"].shape == (0,)
    repo.drop_branch("dev")
    assert "dev/cust" not in e.indices and aux not in e.tables


def test_branch_index_is_maintained_by_a_publish():
    repo = _repo()
    e = repo.engine
    repo.branch("dev", ["cust"])
    e.update_by_keys("dev/cust", {"w": [1], "c": [3], "last": [b"ZED"],
                                  "bal": [1.0]})
    pr = repo.open_pr("dev")
    repo.publish(pr.id, "fail")
    assert lookup_eq(e, "cust", "by_last",
                     {"w": 1, "last": b"ZED"})["c"].tolist() == [3]
    assert _aux_rows(e, "cust") == _aux_rows(e, "dev/cust")


def test_restore_puts_the_index_back_to_the_snapshot():
    """The stale-index case: a PR changes ``last`` A -> Z, main is
    restored to ``base``; lookups answer as of ``base``."""
    repo = _repo()
    e = repo.engine
    before = {n: _scan_answer(e, "cust", 0, n) for n in NAMES}
    repo.branch("dev", ["cust"])
    e.update_by_keys("dev/cust", {"w": [0, 0], "c": [0, 4],
                                  "last": [b"Z", b"Z"], "bal": [1.0, 1.0]})
    pr = repo.open_pr("dev")
    repo.publish(pr.id, "fail")
    assert lookup_eq(e, "cust", "by_last",
                     {"w": 0, "last": b"Z"})["c"].tolist() == [0, 4]
    repo.close_pr(pr.id)
    repo.drop_branch("dev")
    repo.restore("cust", "base")
    assert lookup_eq(e, "cust", "by_last",
                     {"w": 0, "last": b"Z"})["c"].shape == (0,)
    for n in NAMES:
        got = lookup_eq(e, "cust", "by_last", {"w": 0, "last": n})
        assert sorted(zip(got["w"].tolist(), got["c"].tolist())) == \
            before[n]
    # a branch made after the restore clones the restored index
    repo.branch("dev2", ["cust"])
    assert _aux_rows(e, "dev2/cust") == _aux_rows(e, "cust")


def test_restore_rebuilds_an_index_younger_than_the_snapshot():
    repo = Repo()
    e = repo.engine
    repo.create_table("cust", SCH)
    last = np.array([b"A", b"B", b"A"], dtype=object)
    repo.insert("cust", {"w": [0, 0, 0], "c": [0, 1, 2], "last": last,
                         "bal": np.zeros(3)})
    repo.tag("old", "cust")
    e.update_by_keys("cust", {"w": [0], "c": [0], "last": [b"B"],
                              "bal": [0.0]})
    create_index(e, "cust", "by_last", ["w", "last"])
    repo.restore("cust", "old")
    assert lookup_eq(e, "cust", "by_last",
                     {"w": 0, "last": b"A"})["c"].tolist() == [0, 2]
    assert lookup_eq(e, "cust", "by_last",
                     {"w": 0, "last": b"B"})["c"].tolist() == [1]


def test_an_index_made_right_after_a_snapshot_does_not_answer_for_it():
    """An index whose aux table was created at the snapshot's own
    timestamp (and filled one commit later) is rebuilt for that horizon,
    not read as empty."""
    e = Engine()
    e.create_table("cust", SCH)
    e.insert("cust", {"w": [0, 0], "c": [0, 1],
                      "last": np.array([b"A", b"B"], dtype=object),
                      "bal": np.zeros(2)})
    e.create_snapshot("s", "cust")
    create_index(e, "cust", "by_last", ["w", "last"])
    e.clone_table("C", "s", with_indices=True)
    assert lookup_eq(e, "C", "by_last",
                     {"w": 0, "last": b"A"})["c"].tolist() == [0]


@pytest.mark.parametrize("final", ["published", "restored"])
def test_wal_replay_rebuilds_identical_aux_tables(final):
    repo = _repo()
    e = repo.engine
    repo.branch("dev", ["cust"])
    e.update_by_keys("dev/cust", {"w": [0, 1], "c": [0, 1],
                                  "last": [b"Q", b"R"], "bal": [1.0, 2.0]})
    pr = repo.open_pr("dev")
    repo.publish(pr.id, "accept")
    if final == "restored":
        repo.close_pr(pr.id)
        repo.drop_branch("dev")
        repo.restore("cust", "base")
    e2 = Engine.replay(e.wal)
    assert set(e2.tables) == set(e.tables)
    assert {t: [s.name for s in v] for t, v in e2.indices.items()} == \
        {t: [s.name for s in v] for t, v in e.indices.items()}
    for table, specs in e.indices.items():
        for spec in specs:
            a = e.table(spec.aux_table)
            b = e2.table(spec.aux_table)
            assert a.directory == b.directory, spec.aux_table
            assert _aux_rows(e, table) == _aux_rows(e2, table)
    q = {"w": np.array([0, 1, 0]),
         "last": np.array([b"Q", b"R", b"BARBAR"], dtype=object)}
    assert _groups(lookup_eq(e2, "cust", "by_last", q), 3) == \
        _groups(lookup_eq(e, "cust", "by_last", q), 3)
