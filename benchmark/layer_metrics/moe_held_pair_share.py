"""Model step: share of the (token, chosen expert) pairs that fell on
experts held here: d moe_pairs_held / d moe_pairs_total of the judge pool
(/statsz batchers; decode chunks and prefill programs alike, every row and
token slot the programs routed). An eighth (12.5%) for one routing group of
eight if routing is even; far from it, the routing or the cut is wrong.
Nothing to read from a program without the counters."""

from benchmark import arith


def read(ctx):
    judge = ctx["config"]["judge"]
    after = (ctx["stats_after"].get("batchers") or {}).get(judge) or {}
    if "moe_pairs_total" not in after:
        return None
    d = lambda key: arith.delta(  # noqa: E731
        ctx["stats_after"], ctx["stats_before"], "batchers", judge, key)
    total = d("moe_pairs_total")
    return d("moe_pairs_held") / total * 100.0 if total > 0 else None
