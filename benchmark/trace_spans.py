#!/usr/bin/env python3
"""The reduction of ``benchmark/trace_reduce.py`` with the program's own
spans beside it: each idle gap of a chip gets a cause.

    JAX_PLATFORMS=cpu python3 benchmark/trace_spans.py <trace.xplane.pb> <out.json>

Since PR 23 the program writes its spans into the profiler's own trace
(``llmc.<name>`` events with their arguments, on the line of the thread that
ran them), so they are on the device trace's clock. ``trace_reduce.py`` reads
the host planes for their extent alone and calls every gap ``unattributed``;
this file adds what it leaves out and changes nothing of it: every key of
``trace_reduce.reduce`` comes out of ``reduce`` here as it does there, but
for the labels of the gaps.

A gap's cause is the innermost ``llmc.pool.*`` span open across it in the
pool whose program runs next on that chip (a program's name says which pool
that is: ``decode_chunk__<model>__kv<width>__s<steps>``) -- the spans of the
scheduler's thread first, then those of the fetch thread, since fetch and
emit run off the dispatch path; ``no_work`` where nothing but a ``pool.wait``
is open; ``unattributed`` where there is none (and in a trace of a program
that writes no spans). A gap that several spans share is split by overlap.
Where neither neighbour of a gap is a hot program (a small helper program,
the window's edge), every pool's spans count.

New keys: ``host_spans``, per span name and model: count, total and self
seconds (a span less the spans nested in it on its thread);
``idle_by_cause``, the idle seconds of all chips by cause;
``idle_attributed_share``, the idle seconds with a cause other than
``unattributed`` over all idle seconds, in percent (None where the trace has
no pool span: every gap is then unattributed by construction, not by
measurement). ``idle_gaps`` and ``idle_gap_detail`` are labelled
``<cause> tpu<n> <program before>-><program after>``.

``benchmark/run.py`` does not call this file (its ``reduce_trace`` runs
``trace_reduce.py`` and removes the trace): it is run by hand on a window's
trace until a ``benchmark`` PR joins the two.
"""

from __future__ import annotations

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import trace_reduce  # noqa: E402
from benchmark.trace_reduce import DEVICE_PLANE, MODULES_LINE, OPS_LINE, TOP  # noqa: E402

SPAN_PREFIX = "llmc."
# A hot program's name: family, model (sanitised), width, steps.
PROGRAM = re.compile(
    r"(decode_chunk|prefill_chunks_loop|prefill_chunk)__(.+?)__kv(\d+)(?:__s(\d+))?"
    r"(?:\(\d+\))?$")
# Spans of a pool's fetch thread; every other pool.* span is its scheduler's.
OFF_DISPATCH = ("pool.fetch", "pool.emit")
NO_WORK, UNATTRIBUTED = "no_work", "unattributed"


def program_of(event_name: str):
    """(family, model as the name has it, kv_width, steps or None) of a hot
    program's event name; None for any other program."""
    m = PROGRAM.search(event_name)
    if not m:
        return None
    family, model, width, steps = m.groups()
    return family, model, int(width), int(steps) if steps else None


def name_safe(model: str) -> str:
    """A model's name as the program's names carry it."""
    return "".join(c if c.isalnum() else "_" for c in str(model))


def host_spans_of(planes: list) -> list:
    """Every ``llmc.*`` span of the host planes: {"name" (prefix dropped),
    "start", "end" (ns), "thread", "args"}."""
    out = []
    for plane in planes:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for i, line in enumerate(plane["lines"]):
            for sp in line.get("spans") or []:
                out.append({
                    "name": sp["name"][len(SPAN_PREFIX):],
                    "start": sp["start"], "end": sp["start"] + sp["dur"],
                    "thread": f"{plane['name']}#{i}",
                    "args": sp.get("args") or {},
                })
    return out


def summarise_spans(spans: list) -> dict:
    """Per span name and model: count, total seconds, and self seconds (the
    span less the spans nested in it on the same thread)."""
    threads: dict = {}
    for sp in spans:
        threads.setdefault(sp["thread"], []).append(sp)
    out: dict = {}
    for mine in threads.values():
        mine.sort(key=lambda sp: (sp["start"], -sp["end"]))
        stack: list = []
        for sp in mine:
            sp["_child_ns"] = 0.0
            while stack and stack[-1]["end"] <= sp["start"]:
                stack.pop()
            if stack:
                stack[-1]["_child_ns"] += min(sp["end"], stack[-1]["end"]) - sp["start"]
            stack.append(sp)
        for sp in mine:
            model = sp["args"].get("model")
            key = f"{sp['name']} {model}" if model else sp["name"]
            s = out.setdefault(key, {"count": 0, "total_s": 0.0, "self_s": 0.0})
            dur = sp["end"] - sp["start"]
            s["count"] += 1
            s["total_s"] += dur / 1e9
            s["self_s"] += max(dur - sp.pop("_child_ns"), 0.0) / 1e9
    return out


class OpenSpans:
    """The spans open across each of a run of stretches that come in order
    of time (a chip's gaps): one sweep, not a scan per gap."""

    def __init__(self, spans: list):
        self._spans = sorted(spans, key=lambda sp: sp["start"])
        self._next = 0
        self._open: list = []

    def across(self, g0: float, g1: float) -> list:
        while self._next < len(self._spans) and self._spans[self._next]["start"] < g1:
            self._open.append(self._spans[self._next])
            self._next += 1
        self._open = [sp for sp in self._open if sp["end"] > g0]
        return self._open


def causes_of_gap(gap: tuple, open_here: list, models: list) -> dict:
    """{cause: ns} over the idle stretch ``gap`` = (start, end), given the
    pool spans open across it. ``models``: the pools to look in, in order
    of preference (the next program's, then the previous one's; sanitised
    names); where neither has a span open in the gap, or neither is a hot
    program, every pool's spans count."""
    g0, g1 = gap
    chosen: list = []
    for model in models:
        chosen = [sp for sp in open_here if sp["_pool"] == model]
        if chosen:
            break
    if not chosen:
        chosen = open_here
    cuts = sorted({g0, g1, *(
        t for sp in chosen for t in (sp["start"], sp["end"]) if g0 < t < g1
    )})
    out: dict = {}
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        cover = [sp for sp in chosen if sp["start"] <= mid < sp["end"]]
        # At each instant: a scheduler at work, else a fetch thread at work,
        # else a scheduler waiting for work (one that waits explains nothing
        # while another span is at work).
        at_work = [sp for sp in cover if sp["name"] != "pool.wait"]
        on_dispatch = [sp for sp in at_work if sp["name"] not in OFF_DISPATCH]
        cover = on_dispatch or at_work or cover
        if cover:
            inner = max(cover, key=lambda sp: sp["start"])["name"]
            cause = NO_WORK if inner == "pool.wait" else inner[len("pool."):]
        else:
            cause = UNATTRIBUTED
        out[cause] = out.get(cause, 0.0) + (b - a)
    return out


def attributed_share(idle_by_cause: dict, host_spans: dict):
    """Percent of the idle seconds with a cause; None without a pool span."""
    total = sum(idle_by_cause.values())
    if total <= 0 or not any(name.startswith("pool.") for name in host_spans):
        return None
    return (total - idle_by_cause.get(UNATTRIBUTED, 0.0)) / total * 100.0


def reduce(planes: list) -> dict:
    """``planes`` as ``trace_reduce.reduce`` takes them, a host line with
    its ``llmc.*`` events under ``"spans"``: [{"name", "start", "dur",
    "args"}, ...] (optional). Times in the result are seconds."""
    out = trace_reduce.reduce(planes)
    spans = host_spans_of(planes)
    pool_spans = [sp for sp in spans if sp["name"].startswith("pool.")]
    for sp in pool_spans:
        sp["_pool"] = name_safe(sp["args"].get("model", ""))
    starts = [s for p in planes for ln in p["lines"] for _, s, _ in ln["events"]]
    ends = [s + d for p in planes for ln in p["lines"] for _, s, d in ln["events"]]
    t_min, t_max = min(starts, default=0.0), max(ends, default=0.0)
    idle_by_cause: dict = {}
    gap_time: dict = {}
    for plane in planes:
        m = DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        # The busy intervals and the gaps between them, as trace_reduce
        # walks them.
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or [
            e for ln in plane["lines"] for e in ln["events"]
        ]
        busy = trace_reduce.union([(s, s + d) for _, s, d in ops if d > 0])
        modules = sorted(
            ((n, s, s + d) for n, s, d in lines.get(MODULES_LINE) or []),
            key=lambda mod: mod[1],
        )
        open_spans = OpenSpans(pool_spans)
        edges = [[t_min, t_min]] + busy + [[t_max, t_max]]
        for (_, prev_end), (next_start, _) in zip(edges, edges[1:]):
            if next_start - prev_end <= 0:
                continue
            before = trace_reduce.base_name(
                trace_reduce._program_at(modules, prev_end, True))
            after = trace_reduce.base_name(
                trace_reduce._program_at(modules, next_start, False))
            pools = [p[1] for p in (program_of(after), program_of(before)) if p]
            for cause, ns in causes_of_gap(
                    (prev_end, next_start),
                    open_spans.across(prev_end, next_start), pools).items():
                idle_by_cause[cause] = idle_by_cause.get(cause, 0.0) + ns / 1e9
                g = gap_time.setdefault(
                    f"{cause} tpu{m.group(1)} {before}->{after}",
                    {"total_s": 0.0, "count": 0, "max_s": 0.0})
                g["total_s"] += ns / 1e9
                g["count"] += 1
                g["max_s"] = max(g["max_s"], ns / 1e9)
    top = sorted(gap_time.items(), key=lambda kv: -kv[1]["total_s"])[:TOP]
    host_spans = summarise_spans(spans)
    out.update({
        "idle_gaps": [[k, v["total_s"]] for k, v in top],
        "idle_gap_detail": dict(top),
        "idle_by_cause": idle_by_cause,
        "host_spans": host_spans,
        "idle_attributed_share": attributed_share(idle_by_cause, host_spans),
    })
    return out


def load_xplane(path: str) -> list:
    """``trace_reduce.load_xplane``'s planes (a host line keeps its extent,
    which sets the window), each host line with the program's own spans
    beside it: the events named ``llmc.*``, with their arguments from the
    event's stats. A host line is a thread, and several have the same name:
    ``reduce`` tells them apart by their place in the plane and finds a pool
    by the span's ``model``."""
    from jax.profiler import ProfileData

    planes = trace_reduce.load_xplane(path)
    for plane, raw in zip(planes, ProfileData.from_file(path).planes):
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line, raw_line in zip(plane["lines"], raw.lines):
            spans = [
                {"name": e.name, "start": float(e.start_ns),
                 "dur": float(e.duration_ns), "args": dict(e.stats)}
                for e in raw_line.events if e.name.startswith(SPAN_PREFIX)
            ]
            if spans:
                line["spans"] = spans
    return planes


def main(argv: list) -> int:
    if len(argv) != 3:
        print(__doc__, file=sys.stderr)
        return 2
    result = reduce(load_xplane(argv[1]))
    with open(argv[2], "w") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
