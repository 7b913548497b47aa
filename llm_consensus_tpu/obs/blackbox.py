"""The always-on flight recorder: a bounded ring of recent spans.

The per-run :class:`~llm_consensus_tpu.obs.recorder.Recorder` is opt-in
(``--events``) and run-scoped: when an engine crashes at 3 a.m. with
events off, the timeline that would explain it was never recorded. The
:class:`FlightRecorder` closes that gap the way an aircraft blackbox
does — a fixed-size ring (``LLMC_BLACKBOX_EVENTS``, default 4096) of the
most recent spans and instants from the hot subsystems (batcher decode/
fetch/admit, engine streams, gateway requests, governor transitions),
recording ALWAYS (``LLMC_BLACKBOX=0`` opts out), costing one deque
append per event and a bounded, pre-allocated memory ceiling.

On an anomaly the ring **dumps**: a Perfetto-loadable Chrome-trace
snapshot written atomically to ``LLMC_BLACKBOX_DIR`` (default
``data/_artifacts/blackbox/``) carrying the seconds of activity BEFORE the trigger
— the part of the timeline post-hoc tooling can never recover. Triggers:

  * **engine crash / wedge** — the batcher's pool-fatal exception path
    and the supervisor's wedge watchdog (recovery/supervisor.py);
  * **pressure escalation past ``preempt``** — the governor reaching
    brownout or shed (pressure/governor.py): user-visible degradation
    started, snapshot why;
  * **SLO burn** — p99 TTFT over ``LLMC_SLO_TTFT_P99_S`` for
    ``LLMC_SLO_WINDOWS`` consecutive live-metrics windows
    (obs/live.SLOWatcher, wired by the gateway).

Dumps are rate-limited (``LLMC_BLACKBOX_MIN_INTERVAL_S``, default 30 s)
so a crash-looping pool costs one snapshot per interval, not one per
restart attempt.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Optional

from llm_consensus_tpu.analysis import sanitizer
from llm_consensus_tpu.obs.recorder import Event
from llm_consensus_tpu.utils import knobs

DEFAULT_CAPACITY = 4096
DEFAULT_MIN_INTERVAL_S = 30.0
# Under data/_artifacts/: the corpus scanner (flywheel/corpus.py) treats
# everything below that namespace as non-run telemetry, so dumps never
# collide with run-id dirs or trip the manifest-validation counters.
DEFAULT_DIR = os.path.join("data", "_artifacts", "blackbox")


class FlightRecorder:
    """Bounded ring of recent Events + anomaly-triggered trace dumps.

    Recording is lock-free on the hot path (``deque.append`` with a
    maxlen is atomic under the GIL); only ``dump``/``snapshot`` take the
    lock, and only dump's rate-limit state needs it.
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 out_dir: str = DEFAULT_DIR,
                 min_interval_s: float = DEFAULT_MIN_INTERVAL_S):
        self._ring: deque = deque(maxlen=max(16, capacity))
        self.out_dir = out_dir
        self.min_interval_s = min_interval_s
        self._lock = sanitizer.make_lock("obs.blackbox")
        self._last_dump = 0.0
        self.dumps = 0
        self.suppressed = 0
        self.last_reason: Optional[str] = None
        self.last_path: Optional[str] = None

    # -- recording (hot path) ------------------------------------------------

    @staticmethod
    def now() -> int:
        return time.monotonic_ns()

    def append(self, ev: Event) -> None:
        """Append one finished event (the span emitter's sink form)."""
        self._ring.append(ev)

    def complete(self, name: str, t0_ns: int, tid: str = "main",
                 **args) -> None:
        """Record a span that started at ``t0_ns`` and ends now — the
        same hot-path shape Recorder.complete has."""
        t1 = time.monotonic_ns()
        self._ring.append(Event(
            name=name, ph="X", ts_ns=t0_ns, tid=tid,
            dur_ns=max(t1 - t0_ns, 0), args=args,
        ))

    def instant(self, name: str, tid: str = "main", **args) -> None:
        self._ring.append(Event(
            name=name, ph="i", ts_ns=time.monotonic_ns(), tid=tid, args=args,
        ))

    # -- reading / dumping ---------------------------------------------------

    def snapshot(self) -> list:
        """The ring's events, oldest first (a consistent copy)."""
        return list(self._ring)

    def depth(self) -> int:
        return len(self._ring)

    def dump(self, reason: str, extra: Optional[dict] = None,
             force: bool = False) -> Optional[str]:
        """Write the ring as a Perfetto-loadable trace; returns the path
        (None when rate-limited, empty, or the write failed — a blackbox
        must never fail the system it is recording)."""
        try:
            events = list(self._ring)
            if not events:
                return None  # nothing captured: touch no dump state
            with self._lock:
                now = time.monotonic()
                if not force and (
                    now - self._last_dump < self.min_interval_s
                    and self.dumps > 0
                ):
                    self.suppressed += 1
                    return None
                # Reserve the rate-limit window now (a concurrent
                # trigger must not race a second dump of the same ring).
                prev_last = self._last_dump
                self._last_dump = now
            from llm_consensus_tpu.obs.export import (
                chrome_events, trace_document)
            from llm_consensus_tpu.output.persist import save_file

            doc = trace_document(
                chrome_events(events, pid=0, process_name="blackbox")
            )
            doc["blackbox"] = {
                "reason": reason,
                "events": len(events),
                "dumped_unix": time.time(),
                **(extra or {}),
            }
            name = f"blackbox-{_safe(reason)}-{time.time_ns()}.json"
            path = save_file(
                self.out_dir, name, json.dumps(doc, indent=2) + "\n"
            )
            with self._lock:
                if path is None:
                    # Nothing landed on disk: release the window so the
                    # NEXT anomaly retries, and leave dumps/last_* naming
                    # the last dump that actually exists.
                    self._last_dump = prev_last
                    return None
                self.dumps += 1
                self.last_reason = reason
                self.last_path = path
            return path
        except Exception:  # noqa: BLE001
            return None

    def stats(self) -> dict:
        with self._lock:
            return {
                "depth": len(self._ring),
                "capacity": self._ring.maxlen,
                "dumps": self.dumps,
                "suppressed": self.suppressed,
                "last_reason": self.last_reason,
                "last_path": self.last_path,
            }

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._last_dump = 0.0
            self.dumps = 0
            self.suppressed = 0
            self.last_reason = None
            self.last_path = None


def _safe(reason: str) -> str:
    return "".join(
        c if c.isalnum() or c in "-_" else "-" for c in str(reason)
    )[:48] or "anomaly"


# -- process-wide resolution (the faults/obs binding pattern) ----------------

_lock = sanitizer.make_lock("obs.blackbox.registry")
_ring: Optional[FlightRecorder] = None
_resolved = False


def _resolve() -> Optional[FlightRecorder]:
    if not knobs.get_bool("LLMC_BLACKBOX"):
        return None
    capacity = knobs.get_int("LLMC_BLACKBOX_EVENTS", DEFAULT_CAPACITY)
    interval = knobs.get_float(
        "LLMC_BLACKBOX_MIN_INTERVAL_S", DEFAULT_MIN_INTERVAL_S
    )
    out_dir = knobs.get_str("LLMC_BLACKBOX_DIR") or DEFAULT_DIR
    return FlightRecorder(
        capacity=capacity, out_dir=out_dir, min_interval_s=interval
    )


def ring() -> Optional[FlightRecorder]:
    """The process-wide flight recorder, or None when ``LLMC_BLACKBOX=0``.
    Resolved once; consumers bind at construction time."""
    global _ring, _resolved
    if not _resolved:
        with _lock:
            if not _resolved:
                _ring = _resolve()
                _resolved = True
    return _ring


def install(r: Optional[FlightRecorder]) -> None:
    """Install ``r`` as the process flight recorder (tests / CLI)."""
    global _ring, _resolved
    with _lock:
        _ring = r
        _resolved = True


def reset() -> None:
    """Forget the cached ring; the next :func:`ring` re-reads env."""
    global _ring, _resolved
    with _lock:
        _ring = None
        _resolved = False


__all__ = [
    "DEFAULT_CAPACITY", "DEFAULT_DIR", "DEFAULT_MIN_INTERVAL_S",
    "FlightRecorder", "install", "reset", "ring",
]
