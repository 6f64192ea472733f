"""Client-side arithmetic: percentiles, TTFT from due time, gaps."""

import pytest

from bench import stats
from bench.serve import Record, Window


class Req:
    def __init__(self, tokens):
        self.tokens = tokens


def rec(due, times, failed=False, prompt_len=10):
    r = Record(Req(list(range(len(times)))), prompt_len, due)
    r.times = list(times)
    r.failed = failed
    return r


def window(records, t0=0.0, t1=10.0):
    return Window(t0, t1, records, [], {}, {}, 0, [])


def test_nearest_rank():
    assert stats.nearest_rank([3, 1, 2], 50) == 2
    assert stats.nearest_rank(range(1, 101), 95) == 95
    assert stats.nearest_rank(range(1, 21), 95) == 19
    assert stats.nearest_rank([7], 0) == 7
    assert stats.nearest_rank([], 50) is None
    with pytest.raises(ValueError):
        stats.nearest_rank([1], 101)


def test_ttft_from_due_time():
    w = window([rec(1.0, [1.5, 1.5, 2.0]), rec(2.0, [4.0])])
    assert stats.ttfts(w.records, w.t0, w.t1) == [0.5, 2.0]


def test_unfinished_and_failed_count_to_the_window_end():
    w = window([rec(8.0, []), rec(9.0, [9.5], failed=True),
                rec(None, [0.5, 1.0])])
    assert stats.ttfts(w.records, w.t0, w.t1) == [2.0, 1.0]
    assert stats.attempted_failed(w) == (2, 1)


def test_requests_due_outside_the_window_are_not_attempted():
    w = window([rec(-1.0, [0.5]), rec(10.0, [])], t0=0.0, t1=10.0)
    assert stats.ttfts(w.records, w.t0, w.t1) == []
    assert stats.attempted_failed(w) == (0, 0)


def test_tokens_of_one_chunk_have_gaps_of_zero():
    w = window([rec(0.0, [1.0, 1.0, 1.0, 1.0, 1.4, 1.4, 1.4, 1.4])])
    g = stats.gaps(w.records, w.t0, w.t1)
    assert g == pytest.approx([0, 0, 0, 0.4, 0, 0, 0])
    assert stats.nearest_rank(g, 95) == pytest.approx(0.4)


def test_gaps_and_tokens_only_inside_the_window():
    w = window([rec(None, [None, None, 0.5, 1.0, 11.0])])
    assert stats.gaps(w.records, w.t0, w.t1) == [0.5]
    assert stats.tokens_in_window(w.records, w.t0, w.t1) == 2


def test_end_to_end():
    w = window([rec(1.0, [1.25, 1.25, 1.75]), rec(2.0, [2.5, 3.0])])
    e = stats.end_to_end(w)
    assert e["output_tok_per_s"] == pytest.approx(0.5)
    assert e["ttft_p50_s"] == pytest.approx(0.25)
    assert e["ttft_p95_s"] == pytest.approx(0.5)
    assert e["itl_p95_s"] == pytest.approx(0.5)
