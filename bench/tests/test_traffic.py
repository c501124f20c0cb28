"""The seeded token rows: the same seed gives the same rows, and every row
of a run differs."""
import numpy as np

from bench.traffic import tokens


def test_token_rows_repeat_per_seed_and_differ_by_row():
    s = tokens.train_seed(2**31 + 5)
    a = tokens.rows(s, 3, 1, 2, 1, 64, 4190)
    b = tokens.rows(s, 3, 1, 2, 1, 64, 4190)
    c = tokens.rows(s, 3, 0, 2, 1, 64, 4190)
    np.testing.assert_array_equal(a[0], b[0])
    assert not np.array_equal(a[0], c[0])
    np.testing.assert_array_equal(a[0][:, 1:], a[1][:, :-1])
