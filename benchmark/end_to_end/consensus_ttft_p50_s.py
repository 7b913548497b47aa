"""Median time from due until the first judge chunk arrives on the SSE
stream: the slowest panel answer, queueing, and the judge prefill."""

from benchmark import arith


def read(ctx):
    return arith.median(arith.of(ctx["ok"], arith.consensus_ttft_s))
