"""Plain reference of a versioned LINEITEM table: its content as numpy
arrays, kept from the generator's rows and the traffic's updates alone.

It imports nothing of the program. The harness hands it the rows the
generator made before the program saw them, and the updates the traffic
drew; from those it says what each diff, the table after each publish,
each publish's true conflicts and each point read must hold, and counts
the rows of an answer that differ.
Rows are compared value for value, every column, never by signature.

The control (``lower_precision``) is this reference with every float64
column computed in float32, the next precision below the one the
configuration states. It must fail the comparison.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Sequence

import numpy as np

Rows = Dict[str, np.ndarray]
#: the net-count column of a change set (-1: only before, +1: only after)
CNT = "_cnt"


def take(rows: Rows, idx: np.ndarray) -> Rows:
    return {c: v[idx] for c, v in rows.items()}


def updated(base: Rows, idx: np.ndarray, changes: Rows) -> Rows:
    """The new versions of rows ``base[idx]``: ``changes`` replaces the
    columns it names."""
    new = take(base, idx)
    new.update(changes)
    return new


def table_after(base: Rows, updates: Iterable[tuple]) -> Rows:
    """``base`` with each ``(idx, changes)`` update applied in turn, in the
    order the PRs are published. Where two updates change one row the
    later one's version stands: the ACCEPT rule, under which a publish
    forces its own version over a true conflict. With disjoint updates
    every conflict mode gives this table."""
    out = {c: v.copy() for c, v in base.items()}
    for idx, changes in updates:
        for c, v in changes.items():
            out[c][idx] = v
    return out


def true_conflicts(base: Rows, updates: Iterable[tuple]) -> List[int]:
    """The true conflicts of each update when the updates are published in
    turn: rows of its change set that an earlier update also changed,
    counted only where the two new versions differ in some column (equal
    new versions cancel)."""
    out = []
    seen_idx = np.zeros(0, np.int64)
    seen = {c: v[:0] for c, v in base.items()}
    for idx, changes in updates:
        new = updated(base, idx, changes)
        _, si, ni = np.intersect1d(seen_idx, idx, return_indices=True)
        differ = np.zeros(si.shape[0], bool)
        for c in new:
            differ |= seen[c][si] != new[c][ni]
        out.append(int(differ.sum()))
        keep = np.ones(seen_idx.shape[0], bool)
        keep[si] = False          # the later version replaces the earlier
        seen_idx = np.concatenate([seen_idx[keep], idx])
        seen = {c: np.concatenate([seen[c][keep], new[c]]) for c in new}
    return out


def change_set(base: Rows, idx: np.ndarray, changes: Rows) -> Rows:
    """What a diff from ``base`` to the updated table holds: each updated
    row's old version with count -1 and its new version with count +1."""
    old = take(base, idx)
    new = updated(base, idx, changes)
    out = {c: np.concatenate([old[c], new[c]]) for c in base}
    m = idx.shape[0]
    out[CNT] = np.concatenate([np.full(m, -1, np.int64),
                               np.full(m, 1, np.int64)])
    return out


def _row_keys(rows: Rows, key: Sequence[str]) -> np.ndarray:
    """One opaque, comparable value per row made of its key columns."""
    cols = np.stack([np.asarray(rows[c]).astype(np.int64) for c in key],
                    axis=1)
    return np.ascontiguousarray(cols).view(
        np.dtype((np.void, 8 * len(key)))).reshape(-1)


def mismatched_rows(got: Rows, want: Rows, key: Sequence[str]) -> int:
    """Rows in which ``got`` and ``want`` differ, as multisets: rows of one
    side whose key the other lacks, rows whose key repeats on one side, and
    rows matched by key that differ in any column."""
    if set(got) != set(want):
        raise ValueError(f"columns differ: {sorted(got)} vs {sorted(want)}")
    n_got = np.asarray(got[key[0]]).shape[0]
    n_want = np.asarray(want[key[0]]).shape[0]
    _, gi, wi = np.intersect1d(_row_keys(got, key), _row_keys(want, key),
                               return_indices=True)
    differ = np.zeros(gi.shape[0], bool)
    for c in want:
        differ |= np.asarray(got[c])[gi] != np.asarray(want[c])[wi]
    matched = gi.shape[0]
    return int((n_got - matched) + (n_want - matched) + differ.sum())


def lower_precision(rows: Rows, float_columns: Sequence[str]) -> Rows:
    """The control: ``rows`` with each float64 column rounded to float32."""
    out = dict(rows)
    for c in float_columns:
        out[c] = np.asarray(rows[c]).astype(np.float32).astype(np.float64)
    return out
