#!/usr/bin/env python3
"""From a profiler trace (``.xplane.pb``) to numbers.

    python3 benchmark/trace_reduce.py <trace.xplane.pb> <out.json>

Run by ``benchmark/run.py`` as a process of its own after the server has
exited, with ``JAX_PLATFORMS=cpu``: it needs ``jax.profiler.ProfileData`` to
read the file and must not reach for a chip. The arithmetic (``reduce``)
works on plain lists, so the tests drive it without a trace file.

What it computes, per device plane (``/device:TPU:<n>``):

  busy_s     the union of the intervals in which an operation ran: the
             events of the plane's ``XLA Ops`` line (of ``XLA Modules`` where
             a trace has no ops line)
  programs   per jitted program (an event of the ``XLA Modules`` line, under
             the name the trace gives it): runs, summed and mean duration.
             These are the ``device_ops`` of a result's ``breakdown``: an
             event of the ops line is named by its whole HLO text, which
             says less in more bytes (they go to an earlier output line)
  gaps       the idle stretches between busy intervals, each labelled with
             the program that ended before it and the one that started
             after it. The host's spans are not on this clock (the `tracing`
             issue), so what the host was doing is ``unattributed``.

and over the trace: ``window_s``, from the first to the last event of any
plane (host threads included, so a device that sat idle at either end of the
window is counted idle there); ``busy_s`` as the mean over device planes.
"""

from __future__ import annotations

import json
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
TOP = 10


def union(intervals: list) -> list:
    """Merge [start, end) intervals; returns the sorted disjoint cover."""
    out: list = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
        else:
            out.append([start, end])
    return out


def _program_at(modules: list, t: float, before: bool) -> str:
    """Name of the last program that ended by ``t`` (before=True) or of the
    first that starts at or after ``t``; modules sorted by start."""
    name = "none"
    if before:
        for m_name, start, end in modules:
            if end <= t + 1:
                name = m_name
            elif start > t:
                break
    else:
        for m_name, start, _ in modules:
            if start >= t - 1:
                return m_name
    return name


def base_name(event_name: str) -> str:
    """``jit__decode_chunk(1234)`` -> ``jit__decode_chunk``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def reduce(planes: list) -> dict:
    """``planes``: [{"name", "lines": [{"name", "events": [(name, start_ns,
    duration_ns), ...]}]}]. Times in the result are seconds."""
    t_min, t_max = None, None
    for plane in planes:
        for line in plane["lines"]:
            for _, start, dur in line["events"]:
                t_min = start if t_min is None else min(t_min, start)
                t_max = start + dur if t_max is None else max(t_max, start + dur)
    window_ns = (t_max - t_min) if t_min is not None else 0.0
    chips: dict = {}
    op_time: dict = {}
    gap_time: dict = {}
    for plane in planes:
        m = DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or [
            e for ln in plane["lines"] for e in ln["events"]
        ]
        busy = union([(s, s + d) for _, s, d in ops if d > 0])
        busy_ns = sum(e - s for s, e in busy)
        programs: dict = {}
        modules = sorted(
            ((n, s, s + d) for n, s, d in lines.get(MODULES_LINE) or []),
            key=lambda m: m[1],
        )
        for name, start, end in modules:
            p = programs.setdefault(name, {"runs": 0, "total_s": 0.0})
            p["runs"] += 1
            p["total_s"] += (end - start) / 1e9
        for p in programs.values():
            p["mean_ms"] = p["total_s"] / p["runs"] * 1e3
        for name, _, dur in lines.get(OPS_LINE, []):
            op_time[name] = op_time.get(name, 0.0) + dur / 1e9
        # Idle: before the first busy interval, between them, after the last.
        edges = [[t_min, t_min]] + busy + [[t_max, t_max]]
        for (_, prev_end), (next_start, _) in zip(edges, edges[1:]):
            gap = next_start - prev_end
            if gap <= 0:
                continue
            label = (
                f"unattributed tpu{m.group(1)} "
                f"{base_name(_program_at(modules, prev_end, True))}->"
                f"{base_name(_program_at(modules, next_start, False))}"
            )
            g = gap_time.setdefault(label, {"total_s": 0.0, "count": 0, "max_s": 0.0})
            g["total_s"] += gap / 1e9
            g["count"] += 1
            g["max_s"] = max(g["max_s"], gap / 1e9)
        chips[plane["name"]] = {
            "busy_s": busy_ns / 1e9, "ops": len(ops), "programs": programs,
        }
    n = len(chips)
    program_time: dict = {}
    for chip in chips.values():
        for name, p in chip["programs"].items():
            program_time[name] = program_time.get(name, 0.0) + p["total_s"]
    if not op_time:
        # No ops line: the programs are the operations the trace names.
        for chip in chips.values():
            for name, p in chip["programs"].items():
                op_time[name] = op_time.get(name, 0.0) + p["total_s"]
    top = lambda d, key: sorted(  # noqa: E731
        ((k, key(v)) for k, v in d.items()), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": window_ns / 1e9,
        "device_planes": n,
        "busy_s": (sum(c["busy_s"] for c in chips.values()) / n) if n else 0.0,
        "chips": chips,
        "device_ops": [[k, v] for k, v in top(op_time, lambda v: v)],
        "device_programs": [[k, v] for k, v in top(program_time, lambda v: v)],
        "idle_gaps": [[k, v] for k, v in top(gap_time, lambda v: v["total_s"])],
        "idle_gap_detail": {
            k: gap_time[k] for k, _ in top(gap_time, lambda v: v["total_s"])
        },
    }


def load_xplane(path: str) -> list:
    """The trace as plain lists. Host planes keep only their extent (one
    event from first start to last end per line): ``reduce`` needs them for
    the window alone."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                events = [
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events
                ]
            else:
                lo = hi = None
                for e in line.events:
                    s, d = float(e.start_ns), float(e.duration_ns)
                    lo = s if lo is None else min(lo, s)
                    hi = s + d if hi is None else max(hi, s + d)
                events = [("extent", lo, hi - lo)] if lo is not None else []
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
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
