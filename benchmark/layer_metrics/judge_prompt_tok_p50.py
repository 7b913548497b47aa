"""Judge: tokens of the judge prompt per run. The result document does
not carry the judge's prompt tokens, so this is the judge pool's
delta of admit_tokens (/statsz batchers) over the window, less the panel
prompts that pool also admitted (the judge model is a panelist too), per
run that ended in the window. A mean taken from counters, not a median of
per-run values; runs that straddle the window's ends make it
approximate."""

from benchmark import arith


def read(ctx):
    runs = ctx["ok"] + ctx["failed"]
    if not runs:
        return None
    judge = ctx["config"]["judge"]
    admitted = arith.delta(
        ctx["stats_after"], ctx["stats_before"], "batchers", judge, "admit_tokens")
    as_panelist = (
        sum(r["prompt_tokens"] for r in runs)
        if judge in ctx["config"]["panel"] else 0
    )
    return (admitted - as_panelist) / len(runs)
