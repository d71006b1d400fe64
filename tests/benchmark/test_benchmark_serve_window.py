"""What a serving window reports, on hand-built requests: the rate counts
what reached the client inside the window and nothing after it, a request
that did not finish is failed and enters the tails where the client gave up."""

import pytest

from benchmarks.runners.serve_open_loop import Sent, outstanding, reduce_window


def _sent(due, n_prompt, times, n_out=None):
    s = Sent(due, n_prompt, len(times) if n_out is None else n_out)
    s.sent, s.times = due, list(times)
    return s


def test_rate_counts_only_what_reached_the_client_inside_the_window():
    sent = [_sent(-1.0, 100, [-0.5, 0.5, 1.5]),     # lead-in: prompt before 0
            _sent(1.0, 50, [2.0, 3.0, 4.0]),        # all inside
            _sent(8.0, 70, [9.5, 10.5, 11.5])]      # first token only
    out = reduce_window(sent, 10.0, 30.0)
    # 2 + (50 + 3) + (70 + 1) tokens inside [0, 10)
    assert out["serve_tok_s"] == pytest.approx(12.6)
    assert out["attempted"] == 2 and out["failed"] == 0
    assert out["ttft_p50_ms"] == pytest.approx(1250.0)
    assert out["itl_p50_ms"] == pytest.approx(1000.0)


def test_a_stall_lowers_the_rate_though_every_request_finishes_in_the_grace():
    brisk = [_sent(float(i), 10, [i + 0.1 * k for k in range(1, 11)])
             for i in range(10)]
    stalled = [_sent(float(i), 10, [15.0 + i + 0.1 * k for k in range(1, 11)])
               for i in range(10)]
    a = reduce_window(brisk, 10.0, 30.0)
    b = reduce_window(stalled, 10.0, 30.0)
    assert a["failed"] == b["failed"] == 0
    assert a["serve_tok_s"] > 19 and b["serve_tok_s"] == 0.0
    assert b["ttft_p95_ms"] > 15000


def test_an_unfinished_request_is_failed_and_enters_the_tails_at_give_up():
    sent = [_sent(1.0, 10, [1.5, 2.0], n_out=2), _sent(2.0, 10, [], n_out=4),
            _sent(3.0, 10, [3.5], n_out=4)]
    out = reduce_window(sent, 10.0, 5.0)
    assert out["attempted"] == 3 and out["failed"] == 2
    assert out["ttft_p95_ms"] > 10000          # 15 s - 2 s for the silent one
    assert out["itl_p95_ms"] > 10000           # 15 s - 3.5 s for the stuck one
    assert outstanding(sent, 5.0) == 2 and out["outstanding_end"] == 2
