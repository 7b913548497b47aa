"""Scheduler: median over the window's completed runs of the GATING
panelist's `decode_ms / decode_steps` (`timings.panel`, PR 37): host
milliseconds between its first and its last token over the decode steps
its pool landed between the two, so the cadence its stream saw. Beside its
model's device step by name (the breakdown's `device_ops`) the difference
is the turns the other pools took. Nothing to read without `timings.panel`
or where the gate's pool counted no steps."""

from benchmark import arith
from benchmark.layer_metrics.panel_gate_prefill_p50_ms import gates


def read(ctx):
    return arith.median([
        e["decode_ms"] / e["decode_steps"] for e in gates(ctx)
        if e.get("decode_steps")])
