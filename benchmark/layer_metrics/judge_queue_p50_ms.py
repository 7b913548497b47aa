"""Fan-out and judge: median over the window's completed runs of the
result document's `timings.judge_queue_ms`: from the start of the judge's
worker to the dispatch of the first admission wave that carries the run's
judge prompt (the program's own spans' clock reads, PR 23). Nothing to read
from a program whose result has no `timings`."""

from benchmark import arith


def timing(ctx, key):
    values = [
        ((rec.get("doc") or {}).get("timings") or {}).get(key)
        for rec in ctx["ok"]
    ]
    values = [v for v in values if v is not None]
    return arith.median(values)


def read(ctx):
    return timing(ctx, "judge_queue_ms")
