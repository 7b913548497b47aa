"""Fan-out: median over runs of the slowest panel answer (the maximum of
responses[].latency_ms in the result document): the judge cannot start
before it."""

from benchmark import arith


def read(ctx):
    return arith.median(arith.of(ctx["ok"], arith.panel_gate_s))
