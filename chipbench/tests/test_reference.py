"""The plain reference and the comparison that decides ``correct``."""
import numpy as np
import pytest

from chipbench import reference


def test_fir_direct_matches_a_hand_computed_fir():
    x = np.array([1, -2, 3, 0, 5, -1], np.int8)
    q = np.array([[2, 1, 2], [0, -3, 0]])
    # y[r, t] = sum_k q[r, k] * x[t + k]
    want = np.array([[2 * 1 + 1 * -2 + 2 * 3, 2 * -2 + 1 * 3 + 2 * 0,
                      2 * 3 + 1 * 0 + 2 * 5, 2 * 0 + 1 * 5 + 2 * -1],
                     [6, -9, 0, -15]])
    np.testing.assert_array_equal(reference.fir_direct(x, q), want)


def test_fir_exact_equals_the_definition_at_full_width():
    rng = np.random.default_rng(3)
    q = rng.integers(-2 ** 15, 2 ** 15, (37, 255))
    x = rng.integers(-128, 128, 3000).astype(np.int8)
    np.testing.assert_array_equal(reference.fir_exact(x, q),
                                  reference.fir_direct(x, q))


def test_fir_exact_refuses_values_past_the_float64_bound():
    q = np.full((1, 255), 2 ** 31)  # 255 · 2**31 · 2**15 > 2**53
    with pytest.raises(ValueError):
        reference.fir_exact(np.full(300, 2 ** 15), q)


def test_compare_counts_wrong_missing_and_extra_outputs():
    rng = np.random.default_rng(0)
    q = rng.integers(-100, 100, (3, 5))
    x = rng.integers(-128, 128, 40)
    good = reference.fir_direct(x, q)
    assert reference.compare(good, x, q) == (0, 0, 3 * 36)
    bad = good.copy()
    bad[1, 7] += 1
    assert reference.compare(bad, x, q) == (1, 0, 108)
    assert reference.compare(good[:, :30], x, q) == (0, 18, 108)
    extra = np.concatenate([good, good[:, :2]], axis=1)
    assert reference.compare(extra, x, q) == (6, 0, 108)
    assert reference.compare(good[:2], x, q) == (0, 108, 108)
