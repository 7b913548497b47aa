"""Panel plus judge output tokens that arrived on the streams inside the
window, per second of window (runs in flight at its end included)."""

from benchmark import arith


def read(ctx):
    return arith.out_tok_s(ctx["records"], ctx["t0"], ctx["t1"])
