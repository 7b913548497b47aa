"""Fan-out and judge: median over the window's completed runs of the
GATING panelist's `queue_ms + prefill_ms` (the result document's
`timings.panel`, the entry `timings.panel_gate` names): from the start of
its worker to its first token on the host, on the program's own spans'
clock reads (PR 37). Nothing to read from a program whose result has no
`timings.panel`, or whose gate went through no pool."""

from benchmark import arith


def gates(ctx):
    """Each completed run's gating panel entry, for the runs that have
    one."""
    out = []
    for rec in ctx["ok"]:
        timings = (rec.get("doc") or {}).get("timings") or {}
        for entry in timings.get("panel") or []:
            if entry.get("model") == timings.get("panel_gate"):
                out.append(entry)
                break
    return out


def read(ctx):
    return arith.median([
        e["queue_ms"] + e["prefill_ms"] for e in gates(ctx)
        if "prefill_ms" in e])
