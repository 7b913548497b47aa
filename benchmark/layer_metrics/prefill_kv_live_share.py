"""Kernels: the share of the (query, key) pairs the prefill programs'
attention scored that causality needed: d prefill_kv_pairs_live /
d prefill_kv_pairs_swept of the judge pool, /statsz batchers. Swept is,
for every prefill program the pool dispatched, rows x query slots x the
cache slots its attention covered for that chunk (a dense model's XLA
chunk route its whole bucket, a latent model's prefill form the width its
frontier picked); live is n (n + 1) / 2 for a row of n real tokens. A
four-chunk judge prompt of 1,870 tokens that sweeps its 2,048-slot bucket
in every chunk reads about 42, one that stops at each chunk's frontier
about 67; padding rows and padding inside rows count as swept. Nothing to
read from a program without the counters."""

from benchmark import arith


def read(ctx):
    judge = ctx["config"]["judge"]
    after = (ctx["stats_after"].get("batchers") or {}).get(judge) or {}
    if "prefill_kv_pairs_swept" not in after:
        return None
    d = lambda key: arith.delta(  # noqa: E731
        ctx["stats_after"], ctx["stats_before"], "batchers", judge, key)
    swept = d("prefill_kv_pairs_swept")
    return d("prefill_kv_pairs_live") / swept * 100.0 if swept > 0 else None
