"""Telemetry entry point: the process-wide recorder.

``recorder()`` resolves ``LLMC_EVENTS`` exactly once and caches the result
(None when unset/0) — the same zero-cost pattern as faults/__init__.py.
Consumers bind the recorder at construction time
(``self._obs = obs.recorder()``) so disabled runs pay a single bound
``is not None`` check on the hot dispatch/fetch paths; the enable decision
is made at recorder-resolution time, never per-event.

``emitter()`` is how spans are written: one call per span, fanned out
to the recorder, the flight ring and — while a profiler window is open —
the profiler's own trace (obs/spans.py), bound at construction like the
recorder.

``install()`` / ``reset()`` exist for tests, the CLI's ``--events`` flag,
and the events dryrun lane, which enable telemetry mid-process (before any
engine/batcher/runner is constructed); production resolves from the
environment.

Sibling planes with the same resolution pattern:

  * ``obs.live`` — the continuous serving metrics (windowed mergeable
    histograms behind ``/metricsz``) and request trace ids;
  * ``obs.blackbox`` — the always-on flight recorder ring that dumps a
    Perfetto snapshot on crash/pressure/SLO-burn anomalies;
  * ``obs.attrib`` — chip-time attribution: device time per program
    family, the goodput token ledger, host-gap (bubble) detection, and
    the retrace / HBM-watermark sentinels;
  * ``obs.profiler`` — the on-demand bounded ``jax.profiler`` window
    behind ``POST /debugz/profile``.
"""

from __future__ import annotations

import threading
from typing import Optional

from llm_consensus_tpu.analysis import sanitizer
from llm_consensus_tpu.obs import (  # noqa: F401 — public API
    attrib, blackbox, live, profiler)
from llm_consensus_tpu.obs.recorder import (  # noqa: F401 — public API
    Event, Recorder, resolve_max_events)
from llm_consensus_tpu.obs.spans import Emitter, Span  # noqa: F401
from llm_consensus_tpu.utils import knobs

__all__ = [
    "Emitter", "Event", "Recorder", "Span", "attrib", "blackbox", "emitter",
    "live", "profiler", "recorder", "install", "reset",
]

_lock = sanitizer.make_lock("obs.registry")
_recorder: Optional[Recorder] = None
_resolved = False


def recorder() -> Optional[Recorder]:
    """The process-wide recorder, or None when telemetry is disabled."""
    global _recorder, _resolved
    if not _resolved:
        with _lock:
            if not _resolved:
                env = knobs.get_str("LLMC_EVENTS")
                if env and env != "0":
                    _recorder = Recorder(max_events=resolve_max_events())
                _resolved = True
    return _recorder


def emitter() -> Emitter:
    """A span emitter over the sinks that are on NOW: the recorder, the
    flight ring, and the installed profiler's window (obs/spans.py).
    Consumers call this once, at construction, like ``recorder()``."""
    prof = profiler.profiler()
    return Emitter(
        recorder(), blackbox.ring(),
        prof.window if prof is not None else None,
    )


def install(r: Optional[Recorder]) -> None:
    """Install ``r`` as the process recorder (tests / --events / dryrun)."""
    global _recorder, _resolved
    with _lock:
        _recorder = r
        _resolved = True


def reset() -> None:
    """Forget the cached recorder; the next ``recorder()`` re-reads the
    environment."""
    global _recorder, _resolved
    with _lock:
        _recorder = None
        _resolved = False
