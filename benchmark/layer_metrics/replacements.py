"""Provider: engines rebuilt or re-placed inside the window, read from
/statsz recovery.restarts (supervisor rebuilds) plus a change in any
engine's device set between the two reads. Expected 0."""

from benchmark import arith


def read(ctx):
    before, after = ctx["stats_before"], ctx["stats_after"]
    n = arith.delta(after, before, "recovery", "restarts")
    eng_b = (before.get("device") or {}).get("engines") or {}
    eng_a = (after.get("device") or {}).get("engines") or {}
    n += sum(
        1 for m in eng_a
        if m in eng_b and eng_a[m].get("devices") != eng_b[m].get("devices")
    )
    return n
