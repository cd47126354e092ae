"""The arithmetic every reported number rests on, on synthetic schedules
worked out by hand, and the generator's determinism."""

import numpy as np
import pytest

from benchmark import loadgen, stats


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    (list(range(1, 101)), 95, 95.05),      # numpy's linear rule
    ([7.0], 99, 7.0),
    ([], 95, None),
])
def test_percentile_matches_numpy_rule(values, q, want):
    got = stats.percentile(values, q)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)
        assert got == pytest.approx(float(np.percentile(values, q)))


def test_open_loop_latency_runs_from_the_due_time():
    # due at 10.0, the generator got to it at 10.3, first token 0.2 later:
    # the user waited 0.5, of which 0.3 was the generator's lateness
    latency, late = stats.open_loop_latency(10.0, 10.3, 0.2)
    assert latency == pytest.approx(0.5) and late == pytest.approx(0.3)
    # a request sent early (never happens, but) is not credited
    latency, late = stats.open_loop_latency(10.0, 9.9, 0.2)
    assert latency == pytest.approx(0.2) and late == 0.0


def test_time_weighted_mean_holds_each_value_until_the_next():
    # value 0.5 from t=0, 1.0 from t=4, 0.0 from t=8; window [2, 10]:
    # 2 s at 0.5 + 4 s at 1.0 + 2 s at 0.0 = 5 / 8
    samples = [(0.0, 0.5), (4.0, 1.0), (8.0, 0.0)]
    assert stats.time_weighted_mean(samples, 2.0, 10.0) == \
        pytest.approx(5.0 / 8.0)
    # no sample before the window: the uncovered lead-in is left out
    assert stats.time_weighted_mean([(6.0, 1.0)], 2.0, 10.0) == \
        pytest.approx(1.0)
    assert stats.time_weighted_mean([], 0.0, 1.0) is None


def test_spans_are_clipped_to_the_window():
    spans = [(0.0, 2.0), (3.0, 1.0), (9.5, 2.0)]     # (start, duration)
    # window [1, 10]: 1 + 1 + 0.5
    assert stats.spans_in_window(spans, 1.0, 10.0) == pytest.approx(2.5)


def test_spread_is_interquartile_over_median():
    assert stats.spread([100, 101, 102, 103, 104]) == pytest.approx(2 / 102)


MIX = {"prompt_len": {"dist": "lognormal", "median": 192, "sigma": 0.9,
                      "min": 16, "max": 768},
       "output_len": {"dist": "lognormal", "median": 64, "sigma": 0.9,
                      "min": 8, "max": 256}}


def test_same_seed_same_requests_other_seed_other_requests():
    a = loadgen.plan_requests(MIX, 3, 50, 50257, rate_rps=20.0)
    b = loadgen.plan_requests(MIX, 3, 50, 50257, rate_rps=20.0)
    c = loadgen.plan_requests(MIX, 4, 50, 50257, rate_rps=20.0)
    assert all(x.due_s == y.due_s and x.want == y.want
               and np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))
    assert any(not np.array_equal(x.tokens, y.tokens) for x, y in zip(a, c))


def test_lengths_respect_the_clip_and_arrivals_the_rate():
    plan = loadgen.plan_requests(MIX, 0, 4000, 50257, rate_rps=25.0)
    lens = np.array([len(p.tokens) for p in plan])
    wants = np.array([p.want for p in plan])
    assert lens.min() >= 16 and lens.max() <= 768
    assert wants.min() >= 8 and wants.max() <= 256
    assert 150 <= np.median(lens) <= 240 and 50 <= np.median(wants) <= 80
    due = np.array([p.due_s for p in plan])
    assert np.all(np.diff(due) >= 0)
    assert 4000 / due[-1] == pytest.approx(25.0, rel=0.1)
    closed = loadgen.plan_requests(MIX, 0, 10, 50257)
    assert all(p.due_s == 0.0 for p in closed)


def test_shared_prefix_and_bursts_are_data_only():
    mix = dict(MIX, shared_prefix={"share": 1.0, "length": 32,
                                   "n_prefixes": 1},
               burst={"size": 4})
    plan = loadgen.plan_requests(mix, 0, 16, 1000, rate_rps=10.0)
    long_enough = [p for p in plan if len(p.tokens) > 32]
    first = long_enough[0].tokens[:32]
    assert all(np.array_equal(p.tokens[:32], first) for p in long_enough)
    due = [p.due_s for p in plan]
    assert due[0] == due[3] and due[4] == due[7] and due[3] < due[4]
