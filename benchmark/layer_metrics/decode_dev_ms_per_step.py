"""Model step: device time of one decode step of the judge model, from
the device trace: the summed duration of its decode-chunk programs over the
steps they ran.

The trace names programs, not models (every model's decode chunk at every
decode width is jit__decode_chunk with another id), and the program keeps no
step counter per pool. So both are inferred: the judge model is the largest
of its configuration, so its decode chunks are the slowest on the judge's
first chip: the decode-chunk programs whose mean duration is at least 0.55 of
the slowest one's. On the chip (PR 22) the judge model's narrowest bucket ran
at 0.69 (qwen2.5-3b) and 0.86 (mistral-7b int8) of its widest, the next
model's slowest chunk at 0.42 and 0.23, and a chunk of half the duration, taken
to be one clamped to 8 steps at an answer's end, at 0.485: it falls out, as a
clamped variant should. A chunk runs 16 steps (the provider's
stream_interval). The step is the mean over the widths the window saw,
weighted by runs. Open question for the tracing issue: names that carry the
model, and a step counter."""

import re

STEPS_PER_CHUNK = 16
SAME_MODEL = 0.55  # of the slowest decode chunk's mean duration


def judge_decode_program(ctx):
    trace = ctx.get("trace")
    if not trace or not trace.get("chips"):
        return None
    engines = (ctx["stats_after"].get("device") or {}).get("engines") or {}
    devices = (engines.get(ctx["config"]["judge"]) or {}).get("devices") or [0]
    chip = trace["chips"].get(f"/device:TPU:{devices[0]}")
    if chip is None:
        return None
    decode = [
        p for name, p in chip["programs"].items()
        if re.search(r"decode_chunk", name)
    ]
    if not decode:
        return None
    slowest = max(p["mean_ms"] for p in decode)
    judge = [p for p in decode if p["mean_ms"] >= SAME_MODEL * slowest]
    runs = sum(p["runs"] for p in judge)
    total_s = sum(p["total_s"] for p in judge)
    return {"runs": runs, "total_s": total_s, "mean_ms": total_s / runs * 1e3,
            "programs": len(judge)}


def read(ctx):
    p = judge_decode_program(ctx)
    return None if p is None else p["mean_ms"] / STEPS_PER_CHUNK
