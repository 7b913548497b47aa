#!/usr/bin/env python3
"""The reduction of ``benchmark/trace_spans.py`` with the device programs'
own scopes beneath it: a program's time, and a step's, by part.

    JAX_PLATFORMS=cpu python3 benchmark/trace_scopes.py <trace.xplane.pb> <out.json>

Since PR 37 every part of a hot program is traced under
``jax.named_scope("llmc.<part>")`` (llm_consensus_tpu/obs/scopes.py has the
vocabulary), so each operation's ``op_name`` carries its part as a path
component: ``jit(decode_chunk__m__kv512__s16)/while/body/llmc.mlp/dot_general``.

**Where a trace carries it.** An event of the ``XLA Ops`` line is named by
its HLO text; its ``op_name`` is the stat ``tf_op`` of the event's METADATA
(one record an instruction a program, beside ``hlo_category``, ``flops``,
``bytes_accessed`` and ``source``), not of the event.
``jax.profiler.ProfileData`` hands out an event's own stats alone (offset,
duration), so this file reads the metadata out of the file's wire format
itself (``read_device_lines``: the few fields of ``XSpace`` it needs, no
schema module). An executable that the persistent compile cache serves was
compiled by whoever put it there: the cache's key leaves an operation's
metadata out (jax 0.9: debug info is stripped before hashing), so an entry
cached by a tree without scopes gives a trace without them. A window meant
for this file wants a cold cache.

Every key of ``trace_spans.reduce`` comes out of ``reduce`` here unchanged.
New keys:

  program_scopes  per chip and program (the name without its run id):
                  ``runs`` (whole runs), ``cut_runs``, ``total_s`` (the whole
                  runs' device time), ``scopes``: SELF seconds by scope: an
                  operation less the operations nested in it, so a ``while``
                  does not count its body twice; ``unscoped`` for operations
                  whose ``op_name`` has no ``llmc.`` part (but ``RENAMED``); ``between_ops``
                  for the time inside a run in which no operation ran, so
                  that the scopes sum to ``total_s``; ``bytes``: the bytes
                  the compiler counted (``bytes_accessed``) for the innermost
                  operations of each scope; ``unscoped_top``: the unscoped
                  operations that took most, each by name and result
                  shape. Whole program runs only: a run cut by the window's
                  edge is left out and counted.
  step_split      for ``decode_chunk__*``: ms a STEP by scope (seconds over
                  whole runs x steps, mean over the chips that ran it); for
                  ``prefill_chunks_loop__*`` and ``prefill_chunk__*``: ms a
                  RUN by scope. ``total_ms`` is their sum, ``runs`` and
                  ``steps`` what it was divided by, ``mb`` the bytes beside
                  each scope in MB a step (a run).
  cut_runs        program runs left out, all chips.

A run is cut when its first or last operation is not the one its program's
other runs on that chip begin or end with (a device runs a program's
operations in one order), when it holds no operation, or when it is the one
run of its program there and touches the plane's first or last event.

``benchmark/run.py`` does not call this file: its ``reduce_trace`` runs
``trace_reduce.py`` and removes the trace. Until a ``benchmark`` PR makes
that one line ``trace_scopes.py`` (PERF.md section 7) it is run by hand.
"""

from __future__ import annotations

import json
import os
import re
import struct
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import trace_spans  # noqa: E402
from benchmark.trace_reduce import (  # noqa: E402
    DEVICE_PLANE, MODULES_LINE, OPS_LINE, base_name)
from benchmark.trace_spans import program_of  # noqa: E402

SCOPE = re.compile(r"llmc\.([a-z_]+(?:\.[a-z_]+)?)")
UNSCOPED, BETWEEN = "unscoped", "between_ops"
TOP_UNSCOPED = 8
# Operations the chip's compiler RENAMES, path and all: ``jax.lax.ragged_dot``
# becomes custom calls whose whole ``op_name`` is ``ragged-dot-none`` (and
# ``ragged-dot-metadata``), so no scope reaches them. One part of the program
# emits them, the expert layer's grouped products (ops/moe.py), and they are
# a quarter of a routed model's decode step: booked there, by name.
RENAMED = (("ragged-dot", "moe.experts"),)


def scope_of(op_name: str) -> str:
    """The innermost ``llmc.<part>`` of an operation's ``op_name``."""
    found = SCOPE.findall(op_name or "")
    if found:
        return found[-1]
    for prefix, scope in RENAMED:
        if (op_name or "").startswith(prefix):
            return scope
    return UNSCOPED


def short(hlo_text: str) -> str:
    """``%copy.5 = bf16[28,3072,3072]{1,2,0:T(8,128)} copy(...)`` ->
    ``copy.5 bf16[28,3072,3072]``: an operation the compiler made has no
    ``op_name``, and its shape is what says whose it is."""
    name, _, rest = hlo_text.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{name.lstrip('%')} {shape}".strip()


def self_times(ops: list) -> list:
    """``ops``: [(start, end, ...)] of one run, any order. Returns, in the
    order of ``sorted(ops)``, (op, self ns, has children): an operation's
    time less that of the operations directly nested in it."""
    ordered = sorted(ops, key=lambda op: (op[0], -op[1]))
    child = [0.0] * len(ordered)
    stack: list = []
    for i, op in enumerate(ordered):
        while stack and ordered[stack[-1]][1] <= op[0]:
            stack.pop()
        if stack:
            parent = ordered[stack[-1]]
            child[stack[-1]] += min(op[1], parent[1]) - op[0]
        stack.append(i)
    return [
        (op, max(op[1] - op[0] - child[i], 0.0), child[i] > 0)
        for i, op in enumerate(ordered)]


def runs_of(modules: list, ops: list) -> list:
    """Each module event with the operations that started inside it:
    [{"name", "start", "end", "ops": [(start, end, hlo, op_name, bytes)]}]."""
    runs = [
        {"name": n, "start": s, "end": s + d, "ops": []}
        for n, s, d in sorted(modules, key=lambda m: m[1])]
    i = 0
    for hlo, start, dur, op_name, nbytes in sorted(ops, key=lambda o: o[1]):
        while i < len(runs) and runs[i]["end"] <= start:
            i += 1
        if i < len(runs) and runs[i]["start"] <= start:
            runs[i]["ops"].append((start, start + dur, hlo, op_name, nbytes))
    return runs


def mark_cut(runs: list) -> None:
    """Sets ``run["cut"]`` on every run of one chip (module docstring)."""
    by_program: dict = {}
    for run in runs:
        by_program.setdefault(base_name(run["name"]), []).append(run)
    edge = {id(r) for r in (runs[:1] + runs[-1:])}
    for mine in by_program.values():
        ends = [
            (min(r["ops"])[2], max(r["ops"], key=lambda o: o[1])[2])
            if r["ops"] else None for r in mine]

        def mode(k: int):
            seen = [e[k] for e in ends if e]
            return max(set(seen), key=seen.count) if seen else None

        first, last = mode(0), mode(1)
        for run, e in zip(mine, ends):
            run["cut"] = (
                e is None or e[0] != first or e[1] != last
                or (len(mine) == 1 and id(run) in edge))


def program_scopes(planes: list) -> tuple:
    """``program_scopes`` and the count of cut runs, from each device
    plane's ``"scoped"`` entry: {"modules": [(name, start_ns, dur_ns)],
    "ops": [(hlo text, start_ns, dur_ns, op_name, bytes_accessed)]}."""
    out: dict = {}
    cut_total = 0
    for plane in planes:
        if not DEVICE_PLANE.match(plane["name"]) or "scoped" not in plane:
            continue
        runs = runs_of(plane["scoped"]["modules"], plane["scoped"]["ops"])
        mark_cut(runs)
        chip: dict = {}
        for run in runs:
            p = chip.setdefault(base_name(run["name"]), {
                "runs": 0, "cut_runs": 0, "total_s": 0.0, "scopes": {},
                "bytes": {}, "_unscoped": {}})
            if run["cut"]:
                p["cut_runs"] += 1
                cut_total += 1
                continue
            p["runs"] += 1
            p["total_s"] += (run["end"] - run["start"]) / 1e9
            covered = 0.0
            for op, self_ns, has_children in self_times(run["ops"]):
                scope = scope_of(op[3])
                p["scopes"][scope] = p["scopes"].get(scope, 0.0) + self_ns / 1e9
                covered += self_ns
                if not has_children:
                    p["bytes"][scope] = p["bytes"].get(scope, 0) + op[4]
                if scope == UNSCOPED:
                    key = short(op[2])
                    p["_unscoped"][key] = p["_unscoped"].get(key, 0.0) + self_ns / 1e9
            idle = (run["end"] - run["start"] - covered) / 1e9
            p["scopes"][BETWEEN] = p["scopes"].get(BETWEEN, 0.0) + max(idle, 0.0)
        for p in chip.values():
            bare = p.pop("_unscoped")
            p["unscoped_top"] = sorted(
                ([k, v] for k, v in bare.items()), key=lambda kv: -kv[1]
            )[:TOP_UNSCOPED]
        out[plane["name"]] = chip
    return out, cut_total


def step_split(by_chip: dict) -> dict:
    """``step_split`` from ``program_scopes`` (module docstring)."""
    programs: dict = {}
    for chip in by_chip.values():
        for name, p in chip.items():
            if program_of(name) and p["runs"]:
                programs.setdefault(name, []).append(p)
    out: dict = {}
    for name, on_chips in programs.items():
        family, _, _, steps = program_of(name)
        steps = steps if family == "decode_chunk" and steps else 1
        n = len(on_chips)
        ms: dict = {}
        mb: dict = {}
        for p in on_chips:
            per = p["runs"] * steps * n
            for scope, s in p["scopes"].items():
                ms[scope] = ms.get(scope, 0.0) + s / per * 1e3
            for scope, b in p["bytes"].items():
                mb[scope] = mb.get(scope, 0.0) + b / per / 1e6
        out[name] = {
            "runs": sum(p["runs"] for p in on_chips) // n, "chips": n,
            "steps": steps, "total_ms": sum(ms.values()), "ms": ms, "mb": mb,
        }
    return out


def reduce(planes: list) -> dict:
    """``planes`` as ``trace_spans.reduce`` takes them, a device plane with
    its operations' ``op_name`` under ``"scoped"`` (optional: a plane
    without it adds nothing to the new keys). Times in the result are
    seconds but for ``step_split``'s, which are milliseconds."""
    out = trace_spans.reduce(planes)
    by_chip, cut = program_scopes(planes)
    out.update({
        "program_scopes": by_chip,
        "step_split": step_split(by_chip),
        "cut_runs": cut,
    })
    return out


# -- the file's wire format ---------------------------------------------------


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                break
        wire = key & 7
        if wire == 0:
            v = shift = 0
            while True:
                b = buf[i]
                i += 1
                v |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
            yield key >> 3, v
        elif wire == 2:
            size = shift = 0
            while True:
                b = buf[i]
                i += 1
                size |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
            yield key >> 3, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            yield key >> 3, buf[i:i + size]
            i += size
        else:
            raise ValueError(f"wire type {wire} is not in an XSpace")


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _stat(view, stat_names: dict):
    """(name, value) of one ``XStat``: a string (inline or by reference),
    an integer or a double."""
    name, value = None, None
    for f, v in _fields(view):
        if f == 1:
            name = stat_names.get(v)
        elif f in (3, 4):
            value = v
        elif f == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif f in (5, 6):
            value = _text(v)
        elif f == 7:
            value = stat_names.get(v, "")
    return name, value


def read_device_lines(path: str) -> dict:
    """{device plane name: {"modules": [(name, start_ns, dur_ns)], "ops":
    [(hlo text, start_ns, dur_ns, op_name, bytes_accessed)]}} straight from
    an ``.xplane.pb``'s bytes: ``XSpace.planes`` (1) -> ``XPlane`` name (2),
    lines (3), event metadata (4), stat metadata (5); ``XLine`` name (2),
    timestamp_ns (3), events (4); ``XEvent`` metadata_id (1), offset_ps (2),
    duration_ps (3); ``XEventMetadata`` name (2), stats (5)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, lines, raw_events, stat_names = None, [], {}, {}
        for f, v in _fields(plane):
            if f == 2:
                name = _text(v)
            elif f == 3:
                lines.append(v)
            elif f == 4:
                entry = dict(_fields(v))
                raw_events[entry[1]] = entry[2]
            elif f == 5:
                entry = dict(_fields(v))
                meta = dict(_fields(entry[2]))
                stat_names[entry[1]] = _text(meta.get(2, b""))
        if not name or not DEVICE_PLANE.match(name):
            continue
        events: dict = {}
        for key, raw in raw_events.items():
            text, op_name, nbytes = "", "", 0
            for f, v in _fields(raw):
                if f == 2:
                    text = _text(v)
                elif f == 5:
                    stat, value = _stat(v, stat_names)
                    if stat == "tf_op":
                        op_name = value or ""
                    elif stat == "bytes_accessed":
                        nbytes = int(value or 0)
            events[key] = (text, op_name, nbytes)
        found = {"modules": [], "ops": []}
        for raw in lines:
            line_name, t0, raw_evs = "", 0, []
            for f, v in _fields(raw):
                if f == 2:
                    line_name = _text(v)
                elif f == 3:
                    t0 = v
                elif f == 4:
                    raw_evs.append(v)
            if line_name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in raw_evs:
                e = dict(_fields(ev))
                text, op_name, nbytes = events.get(e.get(1), ("", "", 0))
                start, dur = t0 + e.get(2, 0) / 1e3, e.get(3, 0) / 1e3
                if line_name == MODULES_LINE:
                    found["modules"].append((text, start, dur))
                else:
                    found["ops"].append((text, start, dur, op_name, nbytes))
        out[name] = found
    return out


def load_xplane(path: str) -> list:
    """``trace_spans.load_xplane``'s planes, each device plane with its
    program runs and its operations' ``op_name`` under ``"scoped"``."""
    planes = trace_spans.load_xplane(path)
    scoped = read_device_lines(path)
    for plane in planes:
        if plane["name"] in scoped:
            plane["scoped"] = scoped[plane["name"]]
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
