"""The TPC-H LINEITEM generator's domains and the traffic generator."""
import hashlib
import json
import os

import numpy as np
import pytest

from bench import harness, tpch, traffic

SEEDS = [0, 7, 2**31 + 11, 2**33 + 5]


@pytest.fixture(scope="module", params=SEEDS)
def table(request):
    rows, pool = tpch.lineitem(0.001, request.param)
    return request.param, rows, pool


def test_sixteen_columns_in_spec_order(table):
    _, rows, _ = table
    assert tuple(rows) == tuple(n for n, _ in tpch.COLUMNS)
    kinds = {"i64": np.int64, "i32": np.int32, "f64": np.float64,
             "lob": object}
    for name, kind in tpch.COLUMNS:
        assert rows[name].dtype == kinds[kind], name


def test_one_to_seven_lines_per_order(table):
    _, rows, _ = table
    ok, ln = rows["l_orderkey"], rows["l_linenumber"]
    assert (np.diff(ok) >= 0).all()
    starts = np.flatnonzero(np.r_[True, ok[1:] != ok[:-1]])
    lens = np.diff(np.r_[starts, ok.shape[0]])
    assert starts.shape[0] == 1500               # SF 0.001
    assert lens.min() >= 1 and lens.max() <= 7
    assert set(lens.tolist()) == set(range(1, 8))
    expect = np.concatenate([np.arange(1, k + 1) for k in lens])
    assert (ln == expect).all()


def test_sparse_order_keys(table):
    _, rows, _ = table
    keys = np.unique(rows["l_orderkey"])
    assert ((keys & 31) < 8).all() and keys[0] == 1
    assert (tpch.sparse_orderkeys(9) ==
            np.array([1, 2, 3, 4, 5, 6, 7, 32, 33])).all()


def test_value_domains(table):
    _, r, _ = table
    q = r["l_quantity"]
    assert q.min() >= 1 and q.max() <= 50 and (q == np.round(q)).all()
    assert (r["l_partkey"] >= 1).all() and (r["l_partkey"] <= 200).all()
    assert (r["l_suppkey"] >= 1).all() and (r["l_suppkey"] <= 10).all()
    price = q * tpch.retail_cents(r["l_partkey"]) / 100.0
    assert (r["l_extendedprice"] == price).all()
    assert set(np.round(r["l_discount"] * 100).astype(int)) <= set(range(11))
    assert set(np.round(r["l_tax"] * 100).astype(int)) <= set(range(9))
    ship, commit, receipt = (r["l_shipdate"], r["l_commitdate"],
                             r["l_receiptdate"])
    assert commit.min() >= tpch.STARTDATE + 30
    assert commit.max() <= tpch.ENDDATE - 151 + 90
    assert (receipt - ship >= 1).all() and (receipt - ship <= 30).all()
    assert ship.min() >= tpch.STARTDATE + 1
    assert receipt.max() <= tpch.ENDDATE
    late = receipt > tpch.CURRENTDATE
    flags = r["l_returnflag"]
    assert (flags[late] == ord("N")).all()
    assert set(flags[~late].tolist()) == {ord("R"), ord("A")}
    status = r["l_linestatus"]
    assert ((status == ord("O")) == (ship > tpch.CURRENTDATE)).all()
    assert set(r["l_shipinstruct"]) == set(tpch.INSTRUCTIONS)
    assert set(r["l_shipmode"]) == set(tpch.MODES)
    lens = np.array([len(c) for c in r["l_comment"]])
    assert lens.min() >= 10 and lens.max() <= 43


def test_same_seed_same_rows():
    a, _ = tpch.lineitem(0.0005, 2**33 + 1)
    b, _ = tpch.lineitem(0.0005, 2**33 + 1)
    c, _ = tpch.lineitem(0.0005, 2**33 + 2)
    assert all((a[k] == b[k]).all() for k in a)
    assert not (a["l_comment"][:100] == c["l_comment"][:100]).all()


def test_round_updates_are_disjoint_and_change_the_row(table):
    seed, rows, pool = table
    mix = {"engineers": 4, "rows_per_engineer": 100}
    ups = traffic.round_updates(rows, pool, mix, seed, 3)
    assert len(ups) == 4
    idx = np.concatenate([u[0] for u in ups])
    assert np.unique(idx).shape[0] == 400
    for i, ch in ups:
        assert (np.diff(i) > 0).all()
        q = ch["l_quantity"]
        assert (q != rows["l_quantity"][i]).all()
        assert q.min() >= 1 and q.max() <= 50
    again = traffic.round_updates(rows, pool, mix, seed, 3)
    assert all((a[0] == b[0]).all() for a, b in zip(ups, again))


def test_overlap_shares_k_rows_with_the_previous_engineer(table):
    seed, rows, pool = table
    mix = {"engineers": 4, "rows_per_engineer": 100, "overlap": 0.1}
    ups = traffic.round_updates(rows, pool, mix, seed, 2)
    sets = [set(i.tolist()) for i, _ in ups]
    for w in range(4):
        assert len(sets[w]) == 100
        for v in range(w + 1, 4):
            assert len(sets[w] & sets[v]) == (10 if v == w + 1 else 0)
    idx = np.concatenate([u[0] for u in ups])
    assert np.bincount(idx).max() == 2          # no row in three sets
    assert np.unique(idx).shape[0] == 400 - 3 * 10
    for i, ch in ups:
        assert (np.diff(i) > 0).all()
        assert (ch["l_quantity"] != rows["l_quantity"][i]).all()


def test_overlap_draws_the_same_stream(table):
    """The shared rows replace part of the draw; engineer 0's rows and
    every engineer's new comments are those of the disjoint draw."""
    seed, rows, pool = table
    mix = {"engineers": 4, "rows_per_engineer": 100}
    plain = traffic.round_updates(rows, pool, mix, seed, 1)
    shared = traffic.round_updates(rows, pool, dict(mix, overlap=0.1),
                                   seed, 1)
    assert (plain[0][0] == shared[0][0]).all()
    for (_, a), (_, b) in zip(plain, shared):
        assert (a["l_comment"] == b["l_comment"]).all()
    with pytest.raises(ValueError):
        traffic.round_updates(rows, pool, dict(mix, overlap=0.6), seed, 1)


def _digest(arrays):
    h = hashlib.sha256()
    for v in arrays:
        if v.dtype == object:
            h.update(b"".join(len(b).to_bytes(2, "little") + b for b in v))
        else:
            h.update(np.ascontiguousarray(v).tobytes())
    return h.hexdigest()


def _mix(name):
    path = os.path.join(harness.ROOT, "bench", "traffic", name + ".json")
    with open(path) as f:
        return json.load(f)


#: digests of the draws the benchmark made before the overlap option
#: (``collab_c10k`` rounds 0 and 3 over SF 0.01 rows; ``ycsb_c_zipfian``
#: reads of the window and of the warm-up over SF1's 6,001,215 rows)
PINNED = {
    7: ("2a399a316b027bc1ca202469d28e2498824ef95046e0459df274e2f631a4a117",
        "35e2b375791d34dd41256c9fe1ad1f6e64420f74efec837e903d5061e1cd1dc9",
        "0a694fc2725bb82af9666ab392331a921af399e80fb286dd4f48efa5234bfc57",
        "01b3573d85263210e36a50a97dfb4fe16bbc28719c0db47089133594895c728e"),
    2**33 + 5: (
        "377a28b4ea3a3a738cad011e2b9d8083e4a45cdf4a5de36be6ab704ccb5d0a4e",
        "e283627d109b8e909588988f328e5ded990780159362fd91e19b630b3ae8fa92",
        "970df61dfef61aeecaaeef0a0ec832f7d57ab84205359d6c666198985e426a4f",
        "03f881a1cff394392ab37aef6bb722eb71211b1659490fd08d3a6165853e751c"),
}


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_the_existing_mixes_draw_what_they_drew(seed):
    rows, pool = tpch.lineitem(0.01, seed)
    mix = _mix("collab_c10k")
    got = []
    for rnd in (0, 3):
        ups = traffic.round_updates(rows, pool, mix, seed, rnd)
        got.append(_digest([a for idx, ch in ups
                            for a in [idx] + [ch[c] for c in sorted(ch)]]))
    reads = _mix("ycsb_c_zipfian")
    got.append(_digest([traffic.point_read_items(reads, 6001215, seed,
                                                 5000)]))
    got.append(_digest([traffic.point_read_items(reads, 6001215, seed, 100,
                                                 stream=1)]))
    assert tuple(got) == PINNED[seed]


def test_fnv_matches_ycsb():
    # FNV-1 64 of the long 0, by hand: offset basis times the prime, 8 times
    h, prime = 0xCBF29CE484222325, 1099511628211
    for _ in range(8):
        h = (h * prime) & (2**64 - 1)
    signed = h - 2**64 if h >= 2**63 else h
    assert traffic.fnv1_64(np.array([0]))[0] == abs(signed)


def test_zipfian_reads_are_skewed_and_in_range():
    mix = {"distribution": "scrambled_zipfian", "theta": 0.99}
    items = traffic.point_read_items(mix, 100_000, 2**33 + 9, 20_000)
    assert items.min() >= 0 and items.max() < 100_000
    counts = np.bincount(items)
    top = np.sort(counts)[::-1]
    assert top[0] > 20 * np.median(counts[counts > 0])
    again = traffic.point_read_items(mix, 100_000, 2**33 + 9, 20_000)
    assert (items == again).all()
