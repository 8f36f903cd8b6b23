"""Fixed-seed digest workloads that pin the Δ pipeline's bytes.

Runs a PK + NoPK update workload and hashes every array of the resulting
``DiffResult``s (built-in and SQL paths), the merge application (report
counters + post-merge table scan), and a PITR diff; and an apply workload
that merges in every conflict mode, reverts and publishes. The golden
digests below were recorded on the PR 1 and PR 3 engines; any refactor of
the signed-Δ pipeline (sorted emission, k-way merge, aggregation) or of a
backend's kernels must keep them stable — "sort-free" is an execution
strategy, not a semantics change. ``tests/test_diff_digest.py`` checks them
on the CPU, ``chip_smoke.py`` on the TPU.

All inputs are deterministic: gen_lineitem uses seeded PCG64 (stable streams
across numpy versions), signatures are exact integer math, and sort orders
are fully determined by the 128-bit signatures.
"""
import hashlib

import numpy as np

from repro.core import (ConflictMode, refs, snapshot_diff, sql_diff,
                        three_way_merge)

from .vcs_tables import _mk_engine, _random_update


def _h(update, arr):
    a = np.ascontiguousarray(arr)
    update(a.tobytes())


def diff_digest(d) -> str:
    h = hashlib.sha256()
    for f in ("diff_cnt", "key_lo", "key_hi", "row_lo", "row_hi", "rowid"):
        _h(h.update, getattr(d, f))
    return h.hexdigest()[:16]


def scan_digest(engine, table) -> str:
    batch, rowids, lo, hi = engine.table(table).scan(with_sigs=True)
    h = hashlib.sha256()
    _h(h.update, rowids)
    _h(h.update, lo)
    _h(h.update, hi)
    for name in sorted(batch):
        col = batch[name]
        if col.dtype == object:
            h.update(b"\x00".join(bytes(x) for x in col))
        else:
            _h(h.update, col)
    return h.hexdigest()[:16]


def run_workload(pk: bool, n_rows: int = 50_000, csize: int = 2_000):
    rng = np.random.default_rng([csize] + list(b"DIG"))
    engine, base = _mk_engine(n_rows, pk)
    sn1 = engine.create_snapshot("sn1", "lineitem")
    engine.clone_table("t", sn1)
    _random_update(engine, "t", base, csize, rng, pk)
    sn3 = engine.create_snapshot("sn3", "t")
    cur = engine.current_snapshot("lineitem")

    d_b = snapshot_diff(engine.store, cur, sn3)
    d_s = sql_diff(engine.store, cur, sn3)
    rep = three_way_merge(engine, "lineitem", sn3, base=sn1,
                          mode=ConflictMode.ACCEPT)
    d_pitr = snapshot_diff(engine.store,
                           refs.resolve(engine, "lineitem@{1}").snapshot,
                           engine.current_snapshot("lineitem"))
    return {
        "diff": diff_digest(d_b),
        "sql_diff": diff_digest(d_s),
        "merge": f"{rep.inserted}/{rep.deleted}/{rep.true_conflicts}",
        "scan": scan_digest(engine, "lineitem"),
        "pitr": diff_digest(d_pitr),
    }


def _edit(engine, table, base, idx, pk, col="l_quantity", tag=1):
    """Deterministic update of ``base[idx]`` rows on ``table``."""
    newvals = {k: v[idx].copy() for k, v in base.items()}
    if col == "l_quantity":
        newvals["l_quantity"] = newvals["l_quantity"] + 1.0 + tag
    else:
        newvals["l_comment"] = np.array(
            [b"edit-%d-%d" % (tag, i) for i in range(idx.shape[0])],
            dtype=object)
    tx = engine.begin()
    if pk:
        tx.update_by_keys(table, newvals)
    else:
        t = engine.table(table)
        _, rowids = t.scan()
        tx.delete_rowids(table, rowids[idx])
        tx.insert(table, newvals)
    tx.commit()


def _apply_setup(pk, overlap, n_rows=30_000, csize=1_500,
                 cell_cols=False):
    """Engine with target ('lineitem') and source ('t') edits vs sn1.

    ``overlap`` rows are edited by BOTH branches (PK: different values —
    or different columns when ``cell_cols`` — NoPK: source deletes them
    while the target gains duplicate copies, the §3 cardinality conflict).
    """
    engine, base = _mk_engine(n_rows, pk)
    sn1 = engine.create_snapshot("sn1", "lineitem")
    engine.clone_table("t", sn1)
    rng = np.random.default_rng([n_rows, csize, int(overlap * 1000), int(pk)])
    idx = rng.choice(n_rows, size=2 * csize, replace=False)
    t_idx, s_rest = np.sort(idx[:csize]), np.sort(idx[csize:])
    k = int(overlap * csize)
    ov = t_idx[:k]                       # rows both branches touch
    if pk:
        _edit(engine, "lineitem", base, t_idx, pk, tag=1,
              col="l_comment" if cell_cols else "l_quantity")
        _edit(engine, "t", base, np.sort(np.concatenate([ov, s_rest])),
              pk, tag=2, col="l_quantity")
    else:
        # NoPK §3 cardinality conflict: the target gains a duplicate copy
        # of each overlap row's VALUE while the source deletes that row —
        # residual deltas per value group disagree (+1 vs -1)
        scan_batch, rowids = engine.table("t").scan()  # pristine == sn1
        _edit(engine, "lineitem", base, t_idx[k:], pk, tag=1)
        if k:
            engine.insert("lineitem", {c: v[ov].copy()
                                       for c, v in scan_batch.items()})
        tx = engine.begin()
        if k:
            tx.delete_rowids("t", rowids[ov])
        newvals = {c: v[s_rest].copy() for c, v in scan_batch.items()}
        newvals["l_quantity"] = newvals["l_quantity"] + 5.0
        tx.delete_rowids("t", rowids[s_rest])
        tx.insert("t", newvals)
        tx.commit()
    sn3 = engine.create_snapshot("sn3", "t")
    return engine, sn1, sn3


def run_apply_workload(pk: bool, pack_root=None):
    """Apply-path digests: merge in every conflict mode, revert, publish.

    The scan digest pins the POST-APPLY table bytes (object contents,
    rowids, signatures) — the seal path itself, not just the DiffResult.

    With ``pack_root`` set (ISSUE 10), every engine gets a pack tier and
    is fully evicted before AND after each apply: the same goldens then
    also pin that spill/evict/fault-in round trips are byte-invisible."""
    seq = [0]

    def _tier(engine):
        if pack_root is None:
            return
        import os
        from repro.store import attach_packs
        if engine.store.packs is None:
            seq[0] += 1
            attach_packs(engine.store,
                         os.path.join(str(pack_root), f"p{seq[0]}"))
        engine.store.evict_all()
    out = {}
    # merges: disjoint edits under FAIL; overlapping under SKIP/ACCEPT/CELL
    modes = [("fail", ConflictMode.FAIL, 0.0, False),
             ("skip", ConflictMode.SKIP, 0.5, False),
             ("accept", ConflictMode.ACCEPT, 0.5, False)]
    if pk:
        modes.append(("cell", ConflictMode.CELL, 0.5, True))
    for name, mode, overlap, cell_cols in modes:
        engine, sn1, sn3 = _apply_setup(pk, overlap, cell_cols=cell_cols)
        _tier(engine)
        rep = three_way_merge(engine, "lineitem", sn3, base=sn1, mode=mode)
        _tier(engine)
        out[f"merge_{name}"] = (
            f"{rep.inserted}/{rep.deleted}/{rep.true_conflicts}/"
            f"{rep.false_conflicts}/{rep.cell_merged}/"
            + scan_digest(engine, "lineitem"))
    # no-base merges (cross-delta §5.3 path)
    engine, sn1, sn3 = _apply_setup(pk, 0.5)
    engine._base.clear()
    _tier(engine)
    rep = three_way_merge(engine, "lineitem", sn3, base=None,
                          mode=ConflictMode.ACCEPT)
    _tier(engine)
    out["merge_nobase"] = (f"{rep.inserted}/{rep.deleted}/"
                           f"{rep.true_conflicts}/"
                           + scan_digest(engine, "lineitem"))
    # revert: undo the ACCEPT merge via the inverse delta
    engine, sn1, sn3 = _apply_setup(pk, 0.0)
    pre = engine.create_snapshot("pre", "lineitem")
    _tier(engine)
    three_way_merge(engine, "lineitem", sn3, base=sn1,
                    mode=ConflictMode.ACCEPT)
    post = engine.create_snapshot("post", "lineitem")
    engine.revert("lineitem", pre, post)
    _tier(engine)
    out["revert"] = scan_digest(engine, "lineitem")
    # publish + revert_publish through the workflow porcelain
    engine, base = _mk_engine(30_000, pk)
    engine.create_branch("dev", ["lineitem"])
    rng = np.random.default_rng([77, pk])
    idx = np.sort(rng.choice(30_000, size=1_500, replace=False))
    _edit(engine, "dev/lineitem", base, idx, pk, tag=3)
    pr = engine.open_pr("main", "dev")
    _tier(engine)
    pr.publish()
    out["publish"] = scan_digest(engine, "lineitem")
    pr.revert_publish()
    _tier(engine)
    out["publish_revert"] = scan_digest(engine, "lineitem")
    return out


# Golden digests recorded on the PR 1 engine (fixed-seed workload above).
GOLDEN = {
    True: {
        "diff": "4953744753d67b10",
        "sql_diff": "4953744753d67b10",
        "merge": "2000/2000/0",
        "scan": "8ef72a49adf021ca",
        "pitr": "593ece73c0d631df",
    },
    False: {
        "diff": "b265412cf4eb3342",
        "sql_diff": "b265412cf4eb3342",
        "merge": "2000/2000/0",
        "scan": "a7500c287b142086",
        "pitr": "7de964732d98a93e",
    },
}


# Apply-path goldens recorded on the PR 3 engine (pre ISSUE 4): the
# sig-carrying seal path must land byte-identical objects.
GOLDEN_APPLY = {
    True: {
        "merge_fail": "1500/1500/0/1500/0/9175a02fb5212c8b",
        "merge_skip": "1500/1500/750/1500/0/4bed1479eb2d935c",
        "merge_accept": "2250/2250/750/1500/0/3dcc9d6952350aea",
        "merge_cell": "2250/2250/750/1500/750/1a9fce248a60f246",
        "merge_nobase": "3000/3000/3000/0a867bd86d60e5c0",
        "revert": "d7d4eebfa086d68b",
        "publish": "1f0ff3dab3c88b9c",
        "publish_revert": "255d731b902dc7bf",
    },
    False: {
        "merge_fail": "1500/1500/0/3000/0/c3ad540e2e7ab79f",
        "merge_skip": "1500/1500/750/3000/0/d8d647613324fefa",
        "merge_accept": "1500/3000/750/3000/0/04a454d7d8aa2a54",
        "merge_nobase": "2250/0/0/f79b73c6652df224",
        "revert": "267ea3643bb54dd8",
        "publish": "6cdb0f2c0762963f",
        "publish_revert": "d6722819d4896927",
    },
}


if __name__ == "__main__":
    import json
    print(json.dumps({("PK" if pk else "NoPK"): run_workload(pk)
                      for pk in (True, False)}, indent=1))
    print(json.dumps({("PK" if pk else "NoPK"): run_apply_workload(pk)
                      for pk in (True, False)}, indent=1))
