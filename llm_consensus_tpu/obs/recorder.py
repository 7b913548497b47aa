"""Run-wide telemetry: spans, counters, and instant events on one timeline.

A :class:`Recorder` collects three event kinds from every subsystem of a
run — engine dispatch/fetch, the batcher scheduler loop, runner workers,
the multi-controller exchange, SSE streams, fault injection — onto one
``time.monotonic_ns`` timeline:

  * **spans** — an interval with a duration (a prefill, a decode-chunk
    dispatch, an allgather wait). Recorded either after the fact via
    ``complete(name, t0)`` (the hot-path form: one clock read before the
    work, one event append after) or with the ``span(...)`` context
    manager on cool paths.
  * **instants** — a point on the timeline (an injected fault, an SSE
    chunk arrival, a degraded-mode transition).
  * **counters** — run-aggregate numbers (tokens decoded, decode seconds,
    chunks fetched) exported into ``metrics.json``; they carry no
    timestamp and cost one dict update.

Events carry a ``tid`` — a *subsystem* label ("engine", "batcher",
"runner", "mc", "sse", "faults"), not a Python thread id: the timeline's
useful rows are pipeline stages, and thread ids churn per run. The Chrome
trace exporter (obs/export.py) maps labels to stable integer tids with
``thread_name`` metadata, so Perfetto shows named rows.

The recorder follows the faults-package zero-cost pattern exactly
(faults/__init__.py): ``obs.recorder()`` resolves ``LLMC_EVENTS`` once per
process and consumers bind the result at construction time
(``self._obs = obs.recorder()``) — with events disabled the hot dispatch
and fetch loops carry a single bound ``is not None`` check and touch no
recorder state (asserted in tests/test_obs.py).

Memory is bounded: past ``max_events`` (``LLMC_EVENTS_MAX``, default
200k ≈ tens of MB of trace JSON) new events are counted as dropped, never
appended — a long serving run must not grow host memory without bound.
Drops are accounted, not silent: the ``obs.dropped_events`` counter
exports into metrics.json and ``/metricsz``, and the first drop appends
a one-time ``events_dropped`` warning instant (one event past the cap)
so a truncated timeline says so on its own face.
"""

from __future__ import annotations

import threading
import time

from llm_consensus_tpu.analysis import sanitizer
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Optional

DEFAULT_MAX_EVENTS = 200_000


@dataclass(frozen=True)
class Event:
    """One timeline event. ``ph`` is the Chrome trace phase this event
    exports as: "X" (complete span, ``dur_ns`` set) or "i" (instant)."""

    name: str
    ph: str
    ts_ns: int
    tid: str
    dur_ns: int = 0
    args: dict = field(default_factory=dict)


class Recorder:
    """Thread-safe span/counter/instant recorder for one run.

    All mutation happens under one lock; ``events()``/``counters()``
    return copies, so exporters and the live UI read consistent state
    while workers keep appending.
    """

    def __init__(self, max_events: int = DEFAULT_MAX_EVENTS):
        self._lock = sanitizer.make_lock("obs.recorder")
        self._events: list[Event] = []
        self._counters: dict[str, float] = {}
        self._max_events = max_events
        self.dropped = 0
        self._drop_warned = False

    # -- clock ---------------------------------------------------------------

    @staticmethod
    def now() -> int:
        """Timeline clock: monotonic nanoseconds. All events (and the
        multihost clock-offset estimate) use this one clock."""
        return time.monotonic_ns()

    # -- recording -----------------------------------------------------------

    def append(self, ev: Event) -> None:
        """Append one finished event (the span emitter's sink form)."""
        with self._lock:
            if len(self._events) >= self._max_events:
                # Dropped, not silently: the counter exports as
                # ``obs.dropped_events`` (metrics.json, /metricsz), and
                # the FIRST drop appends a one-time warning instant —
                # one event past the cap, so the truncation itself is
                # visible on the timeline it truncated.
                self.dropped += 1
                self._counters["obs.dropped_events"] = (
                    self._counters.get("obs.dropped_events", 0.0) + 1.0
                )
                if not self._drop_warned:
                    self._drop_warned = True
                    self._events.append(Event(
                        name="events_dropped", ph="i",
                        ts_ns=time.monotonic_ns(), tid="obs",
                        args={"max_events": self._max_events},
                    ))
                return
            self._events.append(ev)

    def complete(self, name: str, t0_ns: int, tid: str = "main",
                 **args) -> None:
        """Record a span that started at ``t0_ns`` (from :meth:`now`) and
        ends now — the hot-path form: the caller pays one clock read up
        front and one append here, nothing else."""
        t1 = time.monotonic_ns()
        self.append(Event(
            name=name, ph="X", ts_ns=t0_ns, tid=tid,
            dur_ns=max(t1 - t0_ns, 0), args=args,
        ))

    @contextmanager
    def span(self, name: str, tid: str = "main", **args):
        """Span context manager for cool paths (the body's exceptions
        still record the span — a failed prefill's wall time is exactly
        what the timeline must show)."""
        t0 = time.monotonic_ns()
        try:
            yield
        finally:
            self.complete(name, t0, tid=tid, **args)

    def instant(self, name: str, tid: str = "main", **args) -> None:
        self.append(Event(
            name=name, ph="i", ts_ns=time.monotonic_ns(), tid=tid, args=args,
        ))

    def count(self, name: str, value: float = 1.0) -> None:
        """Accumulate a run-aggregate counter (no timestamp)."""
        with self._lock:
            self._counters[name] = self._counters.get(name, 0.0) + value

    # -- reading -------------------------------------------------------------

    def events(self) -> list[Event]:
        with self._lock:
            return list(self._events)

    def depth(self) -> int:
        """Recorded-event count WITHOUT copying the list (stats scrapes
        poll this; a 200k-event copy per scrape is pure waste)."""
        with self._lock:
            return len(self._events)

    def counters(self) -> dict[str, float]:
        with self._lock:
            return dict(self._counters)

    def span_names(self) -> set[str]:
        """Distinct names of recorded spans (export goldens / CI gates)."""
        with self._lock:
            return {e.name for e in self._events if e.ph == "X"}

    def clear(self) -> None:
        """Drop recorded events and counters (the CLI's per-query reset:
        consumers keep their bound reference — interactive sessions reuse
        warm engines — so the recorder empties in place rather than being
        replaced)."""
        with self._lock:
            self._events.clear()
            self._counters.clear()
            self.dropped = 0
            self._drop_warned = False


def resolve_max_events() -> int:
    from llm_consensus_tpu.utils import knobs

    return knobs.get_int("LLMC_EVENTS_MAX", DEFAULT_MAX_EVENTS)


__all__ = ["DEFAULT_MAX_EVENTS", "Event", "Recorder", "resolve_max_events"]
