"""Engine: median over the window's completed runs of the result
document's `timings.judge_prefill_ms`: from the dispatch of the admission
wave that carries the run's judge prompt until its first token is on the
host (the prefill and the first decode chunk the token rides down with).
Nothing to read from a program whose result has no `timings`."""

from benchmark.layer_metrics import judge_queue_p50_ms


def read(ctx):
    return judge_queue_p50_ms.timing(ctx, "judge_prefill_ms")
