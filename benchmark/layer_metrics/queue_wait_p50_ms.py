"""Entry: median admission queue wait, from the delta of the program's
own /metricsz histogram llmc_queue_wait_seconds between window start and
end. Buckets are powers of two: good to a factor of two."""

from benchmark import arith

FAMILY = "llmc_queue_wait_seconds"


def read(ctx):
    v = arith.histogram_delta_quantile(
        arith.histogram(ctx["metrics_after"], FAMILY),
        arith.histogram(ctx["metrics_before"], FAMILY), 0.5,
    )
    return None if v is None else v * 1e3
