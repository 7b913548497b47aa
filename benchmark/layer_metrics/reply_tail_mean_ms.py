"""Entry: mean milliseconds between the end of a run's `consensus_run` and
the end of its `request`: persist, the flight's end, the result cache, the
reply's last write (the spans `run.persist` and `reply.close`, PR 37); d
serve.reply_tail_s / d serve.reply_tails, /statsz. A closed loop's next
request waits for it. Nothing to read from a program without the block."""

from benchmark import arith


def read(ctx):
    d = lambda key: arith.delta(  # noqa: E731
        ctx["stats_after"], ctx["stats_before"], "serve", key)
    n = d("reply_tails")
    return d("reply_tail_s") / n * 1e3 if n > 0 else None
