"""Median over runs of (last judge chunk - first judge chunk) / (judge
tokens after the first chunk): the gap between tokens of the answer the
user reads."""

from benchmark import arith


def read(ctx):
    return arith.median(arith.of(ctx["ok"], arith.judge_tpot_ms))
