"""Traffic from a seed: the same seed gives the same inputs, and every
seed gives the same amount of work."""
import numpy as np
import pytest

from chipbench import generator

BIG = 2 ** 31 + 12345  # the driver's seeds pass 32 signed bits


def _mix(tenants, rate, **kw):
    return dict({"tenants": tenants, "rate_chunks_per_s": rate,
                 "chunk": 512}, **kw)


def test_samples_repeat_per_seed_and_differ_across_seeds():
    a = generator.chunk_pool(BIG, 4, 2, 512, 8)
    np.testing.assert_array_equal(a, generator.chunk_pool(BIG, 4, 2, 512, 8))
    assert not np.array_equal(a, generator.chunk_pool(BIG + 1, 4, 2, 512, 8))
    assert a.dtype == np.int8 and a.min() >= -128 and a.max() <= 127


def test_session_streams_repeat_per_seed():
    a = generator.session_streams(BIG, 3, 5 * 256, 8)
    assert a.shape == (3, 5 * 256)
    np.testing.assert_array_equal(a, generator.session_streams(BIG, 3,
                                                               5 * 256, 8))


def test_arrivals_repeat_per_seed_with_the_same_work_for_every_seed():
    due, who, size = generator.arrivals(BIG, _mix(8, 100.0), 4.0)
    due2, who2, _ = generator.arrivals(BIG, _mix(8, 100.0), 4.0)
    np.testing.assert_array_equal(due, due2)
    np.testing.assert_array_equal(who, who2)
    other, who3, _ = generator.arrivals(7, _mix(8, 100.0), 4.0)
    assert not np.array_equal(due, other)
    assert len(due) == len(other) == 8 * 50
    for w in (who, who3):  # every tenant sends the same count
        assert np.bincount(w, minlength=8).tolist() == [50] * 8
    assert np.all(np.diff(due) >= 0)
    assert 0.0 <= due.min() and due.max() <= 4.0
    assert np.all(size == 512)


def test_arrival_gaps_are_one_set_in_another_order():
    def gaps(seed):
        due, who, _ = generator.arrivals(seed, _mix(4, 40.0), 5.0)
        return np.sort(np.concatenate(
            [np.diff(due[who == i]) for i in range(4)]))

    # the seed shuffles one set of exponential quantiles: each tenant's
    # gaps are that set less the one that came first
    g1, g2 = gaps(1), gaps(2)
    assert len(g1) == len(g2) == 4 * 49
    common = np.intersect1d(np.round(g1, 12), np.round(g2, 12))
    assert len(common) >= 45

    def last(seed):
        due, who, _ = generator.arrivals(seed, _mix(4, 40.0), 5.0)
        return [due[who == i].max() for i in range(4)]

    np.testing.assert_allclose(last(1) + last(2), [last(1)[0]] * 8)


def test_skewed_rates_send_zipf_counts_in_the_same_work_per_seed():
    mix = _mix(4, 100.0, rate_skew=1.0)
    due, who, _ = generator.arrivals(BIG, mix, 10.0)
    counts = np.bincount(who, minlength=4)
    share = 1 / np.arange(1, 5)
    np.testing.assert_array_equal(counts, np.rint(1000 * share / share.sum()))
    _, who2, _ = generator.arrivals(BIG + 1, mix, 10.0)
    np.testing.assert_array_equal(np.bincount(who2, minlength=4), counts)


def test_bursts_send_only_in_their_on_periods():
    mix = _mix(4, 100.0, burst={"on_s": 0.5, "off_s": 1.5})
    due, _, _ = generator.arrivals(BIG, mix, 8.0)
    assert len(due) == 800
    assert np.all(np.mod(due, 2.0) <= 0.5 + 1e-9)
    assert due.max() <= 8.0


@pytest.mark.parametrize("seed", [BIG, 3])
def test_chunk_sizes_are_one_clipped_log_normal_set_per_tenant(seed):
    spec = {"median": 1024, "sigma": 1.0, "min": 256, "max": 4096}
    _, who, size = generator.arrivals(seed, _mix(2, 40.0, chunk_sizes=spec),
                                      10.0)
    a, b = np.sort(size[who == 0]), np.sort(size[who == 1])
    np.testing.assert_array_equal(a, b)
    assert a.min() == 256 and a.max() == 4096
    assert np.median(a) == pytest.approx(1024, rel=0.05)
