import math

import pytest

import stats


def test_percentile_on_a_fixed_list():
    xs = [5, 1, 4, 2, 3]
    assert stats.percentile(xs, 0) == 1
    assert stats.percentile(xs, 50) == 3
    assert stats.percentile(xs, 100) == 5
    assert stats.percentile(xs, 95) == pytest.approx(4.8)
    assert stats.percentile(list(range(1, 101)), 95) == pytest.approx(95.05)
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_tpot_is_the_mean_gap_after_the_first_token():
    # 257 tokens: first at 1.0 s, done at 3.56 s -> 256 gaps of 10 ms
    assert stats.tpot_s(1.0, 3.56, 257) == pytest.approx(0.010)
    assert stats.tpot_s(1.0, 2.0, 1) is None


def test_grid_is_the_programs_grid_today():
    from seldon_core_tpu.obs.history import BUCKET_EDGES

    assert stats.BUCKET_EDGES == BUCKET_EDGES
    assert stats.HIST_SLOTS == 242


def test_hist_delta_and_percentile():
    before = {"ttft": [0] * 242}
    after = {"ttft": [0] * 242}
    before["ttft"][100] = 3
    after["ttft"][100] = 3 + 10
    after["ttft"][120] = 10
    d = stats.hist_delta(before, after, "ttft")
    assert sum(d) == 20 and d[100] == 10
    p50 = stats.hist_percentile_s(d, 50)
    assert p50 == pytest.approx(math.sqrt(stats.BUCKET_EDGES[99] * stats.BUCKET_EDGES[100]))
    assert stats.hist_percentile_s(d, 95) > p50
    assert stats.hist_delta(before, before, "ttft") is None
    assert stats.hist_delta({}, {}, "ttft") is None


def test_a_changed_grid_is_an_error_not_a_number():
    with pytest.raises(stats.HistogramGridChanged):
        stats.hist_delta({}, {"ttft": [1] * 100}, "ttft")


def test_union_of_intervals():
    assert stats.union_seconds([(0, 1), (0.5, 2), (3, 4)]) == pytest.approx(3.0)
    assert stats.union_seconds([]) == 0.0
