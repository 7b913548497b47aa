"""Metric arithmetic: from the records of a window to numbers.

Kept here, with the benchmark, so that every PR computes a number the same
way. A record is what ``load.consensus`` returns. A run COUNTS when it ended
inside the window; it is OK when the server answered 200 with a ``done``
document that keeps the configuration's guarantees as far as one run can
show them (every panel answer present and of exactly ``max_tokens`` tokens,
a synthesis of exactly ``max_tokens`` tokens, no failed model, no warning —
a truncated judge prompt is a warning — and no cached or coalesced reply).
Anything else that ended inside the window is FAILED: it counts in ``failed``
against ``attempted`` and in no latency.
"""

from __future__ import annotations

from typing import Optional


def quantile(values: list, q: float) -> Optional[float]:
    """Linear-interpolated quantile of unsorted ``values``; None if empty."""
    if not values:
        return None
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def median(values: list) -> Optional[float]:
    return quantile(values, 0.5)


def why_failed(rec: dict, panel: list) -> Optional[str]:
    """None for an OK run, else the first guarantee it broke."""
    if rec.get("error"):
        return rec["error"]
    doc = rec.get("doc")
    if not doc:
        return "no result document"
    if doc.get("failed_models"):
        return f"failed_models {doc['failed_models']}"
    if doc.get("warnings"):
        return f"warnings {doc['warnings']}"
    if doc.get("cached") or doc.get("coalesced"):
        return "served from the result cache or coalesced: prompts must be unique"
    want = rec["max_tokens"]
    answered = {r.get("model"): r for r in doc.get("responses", [])}
    for model in panel:
        r = answered.get(f"tpu:{model}")
        if r is None:
            return f"no answer from {model}"
        if r.get("tokens") != want:
            return f"{model} answered {r.get('tokens')} tokens, not {want}"
        # What the stream carried, in characters, against the tokens the
        # program reports: the stream metrics count characters as tokens.
        streamed = rec.get("streamed") or {}
        if streamed and streamed.get(f"tpu:{model}") != r["tokens"]:
            return (f"{model} streamed {streamed.get(f'tpu:{model}')} "
                    f"characters for {r['tokens']} tokens")
    if judge_tokens(rec) != want:
        return f"synthesis of {judge_tokens(rec)} tokens, not {want}"
    judged = sum(n for _, n in rec.get("judge_events") or [])
    if rec.get("judge_events") and judged != want:
        return f"synthesis streamed {judged} characters for {want} tokens"
    return None


def judge_tokens(rec: dict) -> int:
    """Tokens of the synthesis. The program reports none for the judge (no
    field of the result, no count on a chunk), so this is the text's
    length: one character per token under the benchmark's byte fold, which
    benchmark/server.py checks when it makes it and why_failed holds
    against responses[].tokens for every panel answer of the same run."""
    return len((rec.get("doc") or {}).get("consensus") or "")


def split(records: list, t0: float, t1: float, panel: list) -> tuple:
    """(ok, failed) among the records of runs that ended in [t0, t1]."""
    ok, failed = [], []
    for rec in records:
        if rec.get("done") is None or not (t0 <= rec["done"] <= t1):
            continue
        reason = why_failed(rec, panel)
        if reason is None:
            ok.append(rec)
        else:
            failed.append(dict(rec, reason=reason))
    return ok, failed


def run_s(rec: dict) -> float:
    """From when the request was due until the last judge token arrived."""
    last = rec["judge_events"][-1][0] if rec["judge_events"] else rec["done"]
    return last - rec["due"]


def consensus_ttft_s(rec: dict) -> Optional[float]:
    """From due until the first judge chunk arrived on the stream."""
    if not rec["judge_events"]:
        return None
    return rec["judge_events"][0][0] - rec["due"]


def judge_tpot_ms(rec: dict) -> Optional[float]:
    """(last judge chunk - first judge chunk) / judge tokens after the
    first chunk, in milliseconds."""
    ev = rec["judge_events"]
    if len(ev) < 2:
        return None
    after_first = sum(n for _, n in ev[1:])
    if after_first <= 0:
        return None
    return (ev[-1][0] - ev[0][0]) / after_first * 1e3


def gen_lag_ms(rec: dict) -> Optional[float]:
    if rec.get("sent") is None:
        return None
    return (rec["sent"] - rec["due"]) * 1e3


def panel_gate_s(rec: dict) -> Optional[float]:
    """The slowest panel answer of the run, as the result document has it."""
    lat = [
        r.get("latency_ms") for r in (rec.get("doc") or {}).get("responses", [])
        if r.get("latency_ms") is not None
    ]
    return max(lat) / 1e3 if lat else None


def of(records: list, fn) -> list:
    return [v for v in (fn(r) for r in records) if v is not None]


def out_tok_s(records: list, t0: float, t1: float) -> float:
    """Output tokens, panel and judge, that arrived on the streams inside
    the window, per second of window: one visible character per token. Runs
    still in flight when the window ends count with what they had delivered
    by then (whole runs completed would move in steps of a run, or of a wave
    of runs, as one crosses the window's end); a run that ended in an error
    counts nothing."""
    tokens = sum(
        n for r in records if not r.get("error")
        for t, n in r["token_events"] if t0 <= t <= t1
    )
    return tokens / (t1 - t0)


# -- counters: deltas between two reads ---------------------------------------


def delta(after: dict, before: dict, *path) -> float:
    """after[path] - before[path] for a numeric leaf of two /statsz reads
    (a key missing from the first read counts from 0)."""
    def dig(doc):
        for key in path:
            if not isinstance(doc, dict) or key not in doc:
                return 0.0
            doc = doc[key]
        return float(doc or 0.0)

    return dig(after) - dig(before)


def histogram(metrics_text: str, family: str) -> dict:
    """Cumulative bucket counts of one Prometheus histogram family, summed
    over its label sets: {upper bound: count}, bounds as floats (+Inf as
    float('inf'))."""
    out: dict = {}
    prefix = f"{family}_bucket{{"
    for line in metrics_text.splitlines():
        if not line.startswith(prefix):
            continue
        labels, _, value = line.rpartition("} ")
        le = None
        for part in labels[len(prefix):].split(","):
            k, _, v = part.partition("=")
            if k == "le":
                le = v.strip('"')
        if le is None:
            continue
        bound = float("inf") if le == "+Inf" else float(le)
        out[bound] = out.get(bound, 0.0) + float(value)
    return out


def histogram_delta_quantile(after: dict, before: dict, q: float) -> Optional[float]:
    """Quantile of the observations that fell between two reads of a
    cumulative histogram, interpolated inside its bucket (log-linear
    buckets are coarse: the answer is good to a factor of two)."""
    bounds = sorted(after)
    counts = [after[b] - before.get(b, 0.0) for b in bounds]
    total = counts[-1] if counts else 0.0
    if total <= 0:
        return None
    target = q * total
    prev_bound, prev_count = 0.0, 0.0
    for bound, count in zip(bounds, counts):
        if count >= target:
            if bound == float("inf") or count == prev_count:
                return prev_bound
            frac = (target - prev_count) / (count - prev_count)
            return prev_bound + (bound - prev_bound) * frac
        prev_bound, prev_count = bound, count
    return prev_bound
