"""Load generator: 95th percentile of (sent - due) over the runs that
ended in the window. Large against run_p50_s means the generator, not the
server, set the latencies."""

from benchmark import arith


def read(ctx):
    return arith.quantile(arith.of(ctx["ok"] + ctx["failed"], arith.gen_lag_ms), 0.95)
