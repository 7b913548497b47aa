"""Scheduler: share of the token slots the judge pool's admission prefills
covered that held no real token: 1 - d admit_tokens / d prefill_slot_tokens
(/statsz batchers, counted where the wave is dispatched: padded rows x chunks
x chunk length against the prompt tokens really prefilled). The pool admits
the judge model's panel prompts too where the judge is a panelist. Nothing
to read from a program without the counter."""

from benchmark import arith


def read(ctx):
    judge = ctx["config"]["judge"]
    after = (ctx["stats_after"].get("batchers") or {}).get(judge) or {}
    if "prefill_slot_tokens" not in after:
        return None
    d = lambda key: arith.delta(  # noqa: E731
        ctx["stats_after"], ctx["stats_before"], "batchers", judge, key)
    slots = d("prefill_slot_tokens")
    return (1.0 - d("admit_tokens") / slots) * 100.0 if slots > 0 else None
