"""Engine: seconds the judge model's engine took to build (its constructor:
the random tree made leaf by leaf under its sharding, pools and tables),
as the program reports it on /statsz `device.engines.<judge>.build_s`
(PR 25). Part of set-up: the first warm-up request builds the engines.
Nothing to read from a program that does not report it."""


def read(ctx):
    engines = (ctx["stats_after"].get("device") or {}).get("engines") or {}
    return (engines.get(ctx["config"]["judge"]) or {}).get("build_s")
