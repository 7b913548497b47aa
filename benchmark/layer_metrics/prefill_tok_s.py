"""Engine: prompt tokens the judge pool admitted per second of admission
wall (d admit_tokens / d admit_s, /statsz batchers). Host wall around
device work, not device time."""

from benchmark import arith


def read(ctx):
    judge = ctx["config"]["judge"]
    d = lambda key: arith.delta(  # noqa: E731
        ctx["stats_after"], ctx["stats_before"], "batchers", judge, key)
    return d("admit_tokens") / d("admit_s") if d("admit_s") > 0 else None
