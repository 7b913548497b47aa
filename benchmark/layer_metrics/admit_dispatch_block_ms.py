"""Scheduler: host milliseconds the dispatch of a one-row prefill held the
judge pool's scheduler thread: d admit_dispatch_s / d
admit_single_dispatches, /statsz batchers (PR 37: the `pool.admit` span's
`dispatch_ms`, summed; `admit_alloc_s` and `admit_splice_s` beside it are
the row cache's allocation and the splice). Nothing to read from a
program without the counters, or where no row went one by one."""

from benchmark import arith


def read(ctx):
    judge = ctx["config"]["judge"]
    d = lambda key: arith.delta(  # noqa: E731
        ctx["stats_after"], ctx["stats_before"], "batchers", judge, key)
    n = d("admit_single_dispatches")
    return d("admit_dispatch_s") / n * 1e3 if n > 0 else None
