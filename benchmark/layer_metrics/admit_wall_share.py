"""Scheduler: share of the judge pool's booked wall that went to
admission: d admit_s / (d admit_s + d decode_s + d absorb_s), /statsz
batchers, host clock."""

from benchmark import arith


def read(ctx):
    judge = ctx["config"]["judge"]
    d = lambda key: arith.delta(  # noqa: E731
        ctx["stats_after"], ctx["stats_before"], "batchers", judge, key)
    admit, total = d("admit_s"), d("admit_s") + d("decode_s") + d("absorb_s")
    return admit / total * 100.0 if total > 0 else None
