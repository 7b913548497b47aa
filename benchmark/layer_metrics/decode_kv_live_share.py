"""Scheduler: the share of the decode steps' cache sweep that belongs to
rows with a stream, inside their own windows: d decode_kv_slots_live /
d decode_kv_slots_swept of the judge pool, /statsz batchers. Swept is
steps x pool rows x bucket width, what a kernel that reads every row's
every block covers; live is what the traffic leaves to do (a lone judge
row of six reads about a sixth; six rows that fill their bucket nearly
all). Nothing to read from a program without the counters."""

from benchmark import arith


def read(ctx):
    judge = ctx["config"]["judge"]
    after = (ctx["stats_after"].get("batchers") or {}).get(judge) or {}
    if "decode_kv_slots_swept" not in after:
        return None
    d = lambda key: arith.delta(  # noqa: E731
        ctx["stats_after"], ctx["stats_before"], "batchers", judge, key)
    swept = d("decode_kv_slots_swept")
    return d("decode_kv_slots_live") / swept * 100.0 if swept > 0 else None
