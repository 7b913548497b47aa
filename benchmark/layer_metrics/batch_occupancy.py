"""Scheduler: mean live streams of the judge pool over its rows. The
program reports live_streams as a point read (/statsz utilization), so
the parent samples it about twice a second through the traced window."""


def read(ctx):
    judge = ctx["config"]["judge"]
    rows = ctx["config"]["serve"]["max_batch"]
    live = [
        ((doc.get("utilization") or {}).get(judge) or {}).get("live_streams")
        for _, doc in ctx["samples"]
    ]
    live = [v for v in live if v is not None]
    return sum(live) / len(live) / rows * 100.0 if live else None
