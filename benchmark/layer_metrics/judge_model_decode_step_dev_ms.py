"""Model step: device time of one decode step of the judge model, from the
device trace, by name: the programs called
`decode_chunk__<judge model>__kv<width>__s<steps>` on the judge's first chip,
summed duration over summed (runs x steps of the name). No inference: the
name says the model and the steps (PR 23). Nothing to read where the
programs are not named so."""

from benchmark import trace_spans


def judge_decode_programs(ctx):
    """[(kv_width, steps, runs, total_s)] of the judge model's decode
    programs on its first chip; None without a trace."""
    trace = ctx.get("trace")
    if not trace or not trace.get("chips"):
        return None
    judge = ctx["config"]["judge"]
    engines = (ctx["stats_after"].get("device") or {}).get("engines") or {}
    devices = (engines.get(judge) or {}).get("devices") or [0]
    chip = trace["chips"].get(f"/device:TPU:{devices[0]}")
    if chip is None:
        return None
    out = []
    for name, p in chip["programs"].items():
        prog = trace_spans.program_of(name)
        if prog and prog[0] == "decode_chunk" and prog[3] \
                and prog[1] == trace_spans.name_safe(judge):
            out.append((prog[2], prog[3], p["runs"], p["total_s"]))
    return out


def read(ctx):
    programs = judge_decode_programs(ctx)
    if not programs:
        return None
    steps = sum(s * runs for _, s, runs, _ in programs)
    return sum(t for _, _, _, t in programs) / steps * 1e3
