"""Engine: programs compiled (or loaded from the compile cache) between
the two /statsz reads: the sum of attrib.compiles over families. Expected
0; anything else makes the run incorrect."""


def count(stats: dict) -> float:
    return float(sum(((stats.get("attrib") or {}).get("compiles") or {}).values()))


def read(ctx):
    return count(ctx["stats_after"]) - count(ctx["stats_before"])
