"""Model step: what a window layer's decode sweep is of a full layer's, in
live key and value slots: d decode_kv_slots_window_layer / d
decode_kv_slots_live of the judge pool x 100 (/statsz batchers: the slots ONE
layer of each kind sweeps for the rows with a stream, summed over a
dispatch's steps; for a pool whose attention layers differ in their window
``decode_kv_slots_live`` is a full layer's, each row's whole context, and the
counter beside it a window layer's, the last ``min(context, sliding_window)``
slots of it). 100 when no row's context is past the window: the window does
not bind and both kinds sweep alike; below 100 by the share of slots the
window spares. Nothing to read from a program without the counter: a model
of one kind of attention layer, or a program from before PR 48."""

from benchmark import arith


def read(ctx):
    judge = ctx["config"]["judge"]
    after = (ctx["stats_after"].get("batchers") or {}).get(judge) or {}
    if "decode_kv_slots_window_layer" not in after:
        return None
    d = lambda key: arith.delta(  # noqa: E731
        ctx["stats_after"], ctx["stats_before"], "batchers", judge, key)
    full = d("decode_kv_slots_live")
    return d("decode_kv_slots_window_layer") / full * 100.0 if full > 0 else None
