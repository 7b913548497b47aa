"""Entry: 90th percentile of run time (client clock). Information only:
with tens of runs in a window it is close to a maximum."""

from benchmark import arith


def read(ctx):
    return arith.quantile(arith.of(ctx["ok"], arith.run_s), 0.9)
