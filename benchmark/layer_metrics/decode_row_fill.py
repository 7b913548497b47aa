"""Scheduler: live rows over rows in the judge pool's decode steps:
d decode_row_steps / (d decode_steps x rows), /statsz batchers. Every step
dispatched in the window is counted, at the dispatch (batch_occupancy
samples live_streams twice a second through the traced window). Nothing to
read from a program without the counters."""

from benchmark import arith


def read(ctx):
    judge = ctx["config"]["judge"]
    rows = ctx["config"]["serve"]["max_batch"]
    after = (ctx["stats_after"].get("batchers") or {}).get(judge) or {}
    if "decode_row_steps" not in after:
        return None
    d = lambda key: arith.delta(  # noqa: E731
        ctx["stats_after"], ctx["stats_before"], "batchers", judge, key)
    steps = d("decode_steps")
    return d("decode_row_steps") / (steps * rows) * 100.0 if steps > 0 else None
