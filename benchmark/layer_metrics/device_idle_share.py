"""Device: 1 - (union of the intervals in which an operation ran) / traced
window, mean over the chips of the cell, from the device trace."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace.get("window_s") or not trace.get("device_planes"):
        return None
    return (1.0 - trace["busy_s"] / trace["window_s"]) * 100.0
