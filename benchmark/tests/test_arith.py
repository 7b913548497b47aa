import math

import pytest

from benchmark import arith

PANEL = ["a", "b"]


def rec(due, judge_events, done, tokens=(4, 4), consensus="wxyz", error=None,
        sent=None, streamed=None, **doc):
    return {
        "streamed": streamed or {},
        "index": 0, "due": due, "sent": due + 0.001 if sent is None else sent,
        "done": done, "max_tokens": 4, "prompt_tokens": 10,
        "judge_events": judge_events, "error": error,
        "token_events": [(due + 0.5, sum(tokens))] + list(judge_events),
        "doc": None if error else dict({
            "responses": [
                {"model": f"tpu:{m}", "tokens": t, "latency_ms": 1000.0 * (i + 1)}
                for i, (m, t) in enumerate(zip(PANEL, tokens))],
            "consensus": consensus}, **doc),
    }


def test_quantiles():
    assert arith.quantile([], 0.5) is None
    assert arith.median([3.0]) == 3.0
    assert arith.median([4, 1, 3, 2]) == 2.5
    assert arith.quantile(list(range(11)), 0.9) == 9.0


def test_latency_is_from_due_not_from_sent():
    r = rec(10.0, [(12.0, 1), (12.5, 1), (12.5, 1), (13.0, 1)], 13.01, sent=11.5)
    assert arith.run_s(r) == pytest.approx(3.0)            # last judge token - due
    assert arith.consensus_ttft_s(r) == pytest.approx(2.0)  # first judge chunk - due
    assert arith.gen_lag_ms(r) == pytest.approx(1500.0)


def test_judge_tpot_counts_tokens_after_the_first_chunk():
    r = rec(0.0, [(1.0, 1), (1.3, 2), (1.6, 1)], 1.7)
    assert arith.judge_tpot_ms(r) == pytest.approx(600.0 / 3)
    assert arith.judge_tpot_ms(rec(0.0, [(1.0, 4)], 1.1)) is None


def test_failed_runs_count_in_failed_and_in_no_latency():
    good = rec(0.0, [(1.0, 2), (2.0, 2)], 2.1, streamed={"tpu:a": 4, "tpu:b": 4})
    runs = [
        good,
        rec(0.0, [], 3.0, error="HTTP 503"),
        rec(0.0, [(1.0, 4)], 2.0, tokens=(4, 3)),              # a short answer
        rec(0.0, [(1.0, 3)], 2.0, consensus="wxy"),            # a short synthesis
        rec(0.0, [(1.0, 4)], 2.0, warnings=["judge prompt truncated"]),
        rec(0.0, [(1.0, 4)], 2.0, failed_models=["tpu:a"]),
        rec(0.0, [(1.0, 4)], 2.0, cached=True),
        # the stream's characters against the tokens the program reports
        rec(0.0, [(1.0, 4)], 2.0, streamed={"tpu:a": 4, "tpu:b": 3}),
        rec(0.0, [(1.0, 2), (1.5, 1)], 2.0),                   # 3 judge characters streamed
        rec(0.0, [(1.0, 4)], 99.0),                            # ended after the window
        dict(rec(0.0, [], 1.0), done=None),                    # never ended
    ]
    ok, failed = arith.split(runs, 0.0, 10.0, PANEL)
    assert ok == [good] and len(failed) == 8
    assert all(f["reason"] for f in failed)
    assert arith.of(ok, arith.run_s) == [2.0]
    # tokens that arrived inside the window, in-flight runs included, runs
    # that ended in an error not: 8 panel + 4 judge tokens of each of the
    # runs without an error whose events fall in [0, 10]
    counted = [r for r in runs if not r["error"]]
    assert len(counted) == 10
    want = sum(n for r in counted for t, n in r["token_events"] if t <= 10.0)
    assert arith.out_tok_s(runs, 0.0, 10.0) == pytest.approx(want / 10.0)
    assert arith.out_tok_s(runs, 0.0, 0.9) == pytest.approx((9 * 8 + 7) / 0.9)  # panel tokens only yet
    assert arith.panel_gate_s(good) == 2.0


def test_counter_deltas_and_histogram_quantile():
    before = {"batchers": {"m": {"admit_s": 1.0}}}
    after = {"batchers": {"m": {"admit_s": 3.5, "decode_s": 2.0}}}
    assert arith.delta(after, before, "batchers", "m", "admit_s") == 2.5
    assert arith.delta(after, before, "batchers", "m", "decode_s") == 2.0
    assert arith.delta(after, before, "batchers", "x", "admit_s") == 0.0
    text = lambda a, b, c: "\n".join([  # noqa: E731
        "# TYPE llmc_queue_wait_seconds histogram",
        f'llmc_queue_wait_seconds_bucket{{class="normal",le="0.1",outcome="ok"}} {a}',
        f'llmc_queue_wait_seconds_bucket{{class="normal",le="0.2",outcome="ok"}} {b}',
        f'llmc_queue_wait_seconds_bucket{{class="normal",le="+Inf",outcome="ok"}} {c}',
        f'llmc_queue_wait_seconds_bucket{{class="high",le="0.1",outcome="ok"}} 0',
        f'llmc_queue_wait_seconds_bucket{{class="high",le="0.2",outcome="ok"}} 0',
        f'llmc_queue_wait_seconds_bucket{{class="high",le="+Inf",outcome="ok"}} 0',
        'llmc_queue_wait_seconds_count{class="normal",outcome="ok"} 9',
    ])
    h0 = arith.histogram(text(5, 5, 5), "llmc_queue_wait_seconds")
    h1 = arith.histogram(text(5, 15, 15), "llmc_queue_wait_seconds")
    assert h1 == {0.1: 5.0, 0.2: 15.0, math.inf: 15.0}
    # ten new observations, all in (0.1, 0.2]: the median sits mid-bucket
    assert arith.histogram_delta_quantile(h1, h0, 0.5) == pytest.approx(0.15)
    assert arith.histogram_delta_quantile(h0, h0, 0.5) is None
