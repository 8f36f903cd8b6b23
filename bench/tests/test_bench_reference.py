"""The LINEITEM reference's rules for rounds published in turn: the later
PR's version stands, and each PR's true conflicts are the rows an earlier
PR changed to another value."""
import numpy as np

from bench.reference import lineitem as ref


def _base():
    return {"k": np.arange(6, dtype=np.int64),
            "q": np.arange(6, dtype=np.float64),
            "s": np.array([b"a", b"b", b"c", b"d", b"e", b"f"], dtype=object)}


def test_later_publish_wins():
    base = _base()
    ups = [(np.array([1, 2]), {"q": np.array([10.0, 20.0])}),
           (np.array([2, 3]), {"q": np.array([21.0, 30.0])})]
    got = ref.table_after(base, ups)
    assert got["q"].tolist() == [0, 10, 21, 30, 4, 5]
    assert (base["q"] == np.arange(6)).all()


def test_true_conflicts_count_rows_changed_to_another_value():
    base = _base()
    ups = [(np.array([0, 1, 2]), {"q": np.array([10.0, 11.0, 12.0])}),
           # row 1 to the same row as the first PR's: no true conflict
           (np.array([1, 2, 4]), {"q": np.array([11.0, 99.0, 14.0])}),
           # row 2 differs from the second PR's version, row 0 only in "s"
           (np.array([0, 2, 5]), {"q": np.array([10.0, 12.0, 15.0]),
                                  "s": np.array([b"x", b"c", b"f"],
                                                dtype=object)})]
    assert ref.true_conflicts(base, ups) == [0, 1, 2]


def test_disjoint_updates_have_no_conflicts():
    base = _base()
    ups = [(np.array([0, 1]), {"q": np.array([7.0, 8.0])}),
           (np.array([2, 3]), {"q": np.array([7.0, 8.0])})]
    assert ref.true_conflicts(base, ups) == [0, 0]
