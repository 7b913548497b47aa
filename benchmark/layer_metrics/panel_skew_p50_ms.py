"""Fan-out and judge: median over the window's completed runs of
`timings.panel_skew_ms`: from the first panel answer to the last (the
panel workers' own clock reads, PR 37), which is how long the other
pools, and on four chips their chips, stood with nothing of this run.
Nothing to read from a program whose result has no `timings.panel`."""

from benchmark.layer_metrics.judge_queue_p50_ms import timing


def read(ctx):
    return timing(ctx, "panel_skew_ms")
