"""Median, over runs completed in the window, of the time from when the
request was due until the last judge token arrived (client clock)."""

from benchmark import arith


def read(ctx):
    return arith.median(arith.of(ctx["ok"], arith.run_s))
