"""Kernels: the share of the positions the judge pool's prefill programs'
scans ran over that were real tokens: d ssm_positions_live /
d ssm_positions_swept, /statsz batchers. Swept is, for every prefill program
the pool dispatched, rows x token slots, padding rows, padding inside rows
and whole scan chunks included (a chunked scan pads its T to a multiple of
``ssm_chunk``); live is the real tokens admitted. A padded position costs the
scan what a real one does, and must not advance the state: a judge prompt of
1,740 tokens in four 512-token chunks reads 85, a wave of six short rows in
one 256-slot bucket less. Nothing to read from a program without the
counters."""

from benchmark import arith


def read(ctx):
    judge = ctx["config"]["judge"]
    after = (ctx["stats_after"].get("batchers") or {}).get(judge) or {}
    if "ssm_positions_swept" not in after:
        return None
    d = lambda key: arith.delta(  # noqa: E731
        ctx["stats_after"], ctx["stats_before"], "batchers", judge, key)
    swept = d("ssm_positions_swept")
    return d("ssm_positions_live") / swept * 100.0 if swept > 0 else None
