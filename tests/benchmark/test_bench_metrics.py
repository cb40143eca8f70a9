"""Due-time latency arithmetic and the percentile rule, on a synthetic
completion log."""
import math

import pytest

from benchmark import metrics
from benchmark.loadgen import Request


def _req(i, due, done, placements=10, status="complete", http=200):
    r = Request(index=i, placements=placements, due=due)
    r.sent = due + 0.002
    r.acked = due + 0.004
    r.done = done
    r.eval_status = status if done else ""
    r.http_status = http
    return r


def test_latency_is_timed_from_the_due_instant_not_the_send():
    r = _req(0, due=100.0, done=100.050)
    r.sent = 100.030  # the generator ran 30 ms late
    assert metrics.latencies_ms([r]) == [pytest.approx(50.0)]
    assert metrics.lateness_ms([r]) == [pytest.approx(30.0)]


def test_nearest_rank_percentile():
    values = list(range(1, 101))  # 1..100
    assert metrics.percentile(values, 50) == 50
    assert metrics.percentile(values, 95) == 95
    assert metrics.percentile(values, 100) == 100
    assert metrics.percentile([5.0], 95) == 5.0
    assert metrics.percentile([3, 1, 2], 50) == 2
    with pytest.raises(ValueError):
        metrics.percentile([], 50)


def test_a_failed_or_unfinished_request_is_beyond_any_percentile():
    reqs = [_req(i, due=float(i), done=float(i) + 0.010) for i in range(94)]
    reqs += [_req(94 + k, due=94.0 + k, done=0.0) for k in range(6)]  # never ended
    assert metrics.latency_percentile_ms(reqs, 50) == pytest.approx(10.0)
    assert metrics.latency_percentile_ms(reqs, 94) == pytest.approx(10.0)
    assert metrics.latency_percentile_ms(reqs, 95) == metrics.BEYOND_MS
    shed = [_req(0, 0.0, 0.0, http=429)]
    assert math.isinf(metrics.latencies_ms(shed)[0])
    failed = [_req(0, 0.0, 1.0, status="failed")]
    assert math.isinf(metrics.latencies_ms(failed)[0])


def test_percentiles_are_over_all_requests_due_in_the_window():
    reqs = [_req(i, due=10.0 + i * 0.1, done=10.0 + i * 0.1 + 0.02) for i in range(100)]
    due = metrics.due_in_window(reqs, 12.0, 15.0)
    assert len(due) == 30 and due[0].index == 20


def test_placements_per_s_counts_completions_inside_the_window_over_all_of_it():
    reqs = [
        _req(0, due=0.0, done=9.999),    # completes before the window
        _req(1, due=9.0, done=10.0),     # in flight at the open edge, in
        _req(2, due=12.0, done=14.0, placements=6),
        _req(3, due=19.0, done=20.0),    # completes at the close edge, out
        _req(4, due=15.0, done=16.0, status="failed"),
        _req(5, due=18.0, done=0.0),     # still in flight at the close
    ]
    assert metrics.placements_per_s(reqs, 10.0, 20.0) == pytest.approx(1.6)
    assert [r.index for r in metrics.window_completions(reqs, 10.0, 20.0)] == [1, 2, 4]


def test_spread_is_the_interquartile_range_over_the_median():
    values = [100, 102, 98, 101, 99, 100]
    assert metrics.spread(values) == pytest.approx(0.025)


def test_longest_gaps_include_the_window_edges():
    reqs = [_req(i, due=0.0, done=t) for i, t in enumerate([10.5, 11.0, 14.0, 14.2])]
    gaps = metrics.longest_gaps(reqs, 10.0, 20.0)
    assert gaps[0] == (pytest.approx(4.2), pytest.approx(5.8))  # 14.2 .. close
    assert gaps[1] == (pytest.approx(1.0), pytest.approx(3.0))
    assert metrics.longest_gaps([], 0.0, 5.0) == [(0.0, 5.0)]


def test_due_instants_of_an_even_rate_and_of_stepped_rates():
    from benchmark.loadgen import LoadGen

    def gen(traffic):
        return LoadGen(port=0, stream=None, traffic=traffic, wait_index=None,
                       latest_index=None, eval_status=None)

    even = gen({"loop": "open", "rate_per_s": 50})
    assert [even.due_offset(i) for i in (0, 1, 100)] == [0.0, 0.02, 2.0]
    # a sweep: 10 a second for 2 s, then 20 a second for 1 s, then nothing
    stepped = gen({"loop": "open", "rate_steps": [[10, 2], [20, 1]]})
    assert stepped.due_offset(19) == 1.9
    assert stepped.due_offset(20) == 2.0 and stepped.due_offset(21) == 2.05
    assert stepped.due_offset(40) == float("inf")
