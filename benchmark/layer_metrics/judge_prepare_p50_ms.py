"""Fan-out and judge: median over the window's completed runs of
`timings.judge_prepare_ms`: from the last panel answer to the start of the
judge's worker (agreement, confidence, prompt render, tokenise; the span
`judge.prepare`, PR 37), while no pool has work of this run. Nothing to
read from a program whose result has no `timings.panel`."""

from benchmark.layer_metrics.judge_queue_p50_ms import timing


def read(ctx):
    return timing(ctx, "judge_prepare_ms")
