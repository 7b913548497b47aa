"""On-demand deep profiling: a bounded ``jax.profiler`` trace window.

The attribution ledger (obs/attrib.py) says which program family the
chip's time went to; WHICH fusion, WHICH transfer, WHAT overlap, and how
far a named program ran from the chip's roofline, need the real
profiler. This module arms one
``jax.profiler.start_trace``/``stop_trace`` window on demand
(``POST /debugz/profile``, the router fan-out, or ``--profile`` on
one-shot CLI runs) with the blackbox plane's safety rails:

  * **bounded** — the window stops itself after ``duration_s`` (clamped
    to ``LLMC_PROFILE_MAX_S``) on a daemon timer; a wedged caller can
    not leave the profiler running forever.
  * **single-flight + rate-limited** — one window at a time, and at
    most one window start per ``LLMC_PROFILE_MIN_INTERVAL_S`` (XLA's
    profiler is process-global and NOT free; the 429 path exists so a
    crash-looping dashboard cannot turn the serving process into a
    permanent profiling session).
  * **atomic artifact dir** — the trace lands in ``<final>.partial``
    and is renamed to ``<final>`` only after ``stop_trace`` returns, so
    a consumer that sees the directory sees a complete artifact.

The window is LEAN: ``python_tracer_level=0, host_tracer_level=1`` — no
Python frames (they were most of a trace's bytes and of its cost: 18,894
host events for five small matmuls, against a 20-26 MB trace for 4 s of
serving without them), and in their place the program's own spans: while
the window is open the span emitter (obs/spans.py) writes every span as a
``llmc.<name>`` TraceAnnotation with its arguments, so pools, requests,
device programs and idle gaps sit on one timeline. Beneath the programs,
the window's operations carry their part: every hot program is traced
under ``jax.named_scope("llmc.<part>")`` (obs/scopes.py), so an ``XLA Ops``
event's ``op_name`` (its metadata's ``tf_op`` stat) says which part of a
layer or of a chunk it ran for, and ``benchmark/trace_scopes.py`` sums a
step by part.

Resolution follows the blackbox pattern: ``profiler()`` reads
``LLMC_PROFILE*`` once; ``install()``/``reset()`` rebind for tests and
dryrun lanes.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

from llm_consensus_tpu.analysis import sanitizer
from llm_consensus_tpu.obs.spans import Window
from llm_consensus_tpu.utils import knobs

# Under data/_artifacts/ — non-run telemetry namespace; the flywheel
# corpus scanner skips it wholesale (flywheel/corpus.py).
DEFAULT_DIR = os.path.join("data", "_artifacts", "profiles")
DEFAULT_MAX_S = 10.0
DEFAULT_MIN_INTERVAL_S = 60.0


class DeepProfiler:
    """Arms bounded ``jax.profiler`` trace windows; never raises."""

    def __init__(self, out_dir: Optional[str] = None,
                 max_s: Optional[float] = None,
                 min_interval_s: Optional[float] = None):
        self.out_dir = out_dir or (
            knobs.get_str("LLMC_PROFILE_DIR") or DEFAULT_DIR
        )
        self.max_s = max_s if max_s is not None else knobs.get_float(
            "LLMC_PROFILE_MAX_S", DEFAULT_MAX_S
        )
        self.min_interval_s = (
            min_interval_s if min_interval_s is not None
            else knobs.get_float(
                "LLMC_PROFILE_MIN_INTERVAL_S", DEFAULT_MIN_INTERVAL_S
            )
        )
        self._lock = sanitizer.make_lock("obs.profiler")
        self._active = False
        self._closing = False
        self._last_start = 0.0
        self._timer: Optional[threading.Timer] = None
        self.windows = 0
        self.suppressed = 0
        self.failed = 0
        self.last_path: Optional[str] = None
        self.last_duration_s: Optional[float] = None
        self.last_error: Optional[str] = None
        # Open exactly while a trace is being taken: emitters built while
        # this profiler is installed read it per span (obs/spans.py).
        self.window = Window()
        # Whether the last window's trace carries the program's spans
        # (lean options were accepted by this JAX's profiler).
        self.program_spans: Optional[bool] = None

    # -- the window -----------------------------------------------------------

    def arm(self, duration_s: Optional[float] = None,
            tag: str = "ondemand") -> "tuple[Optional[str], str]":
        """Start one bounded window; returns ``(final_path, status)``.

        ``status`` is ``"armed"`` (the artifact dir will appear at
        ``final_path`` when the window closes), ``"busy"`` /
        ``"rate_limited"`` (the HTTP layer's 429s), or ``"failed"``.
        """
        dur = float(duration_s) if duration_s else self.max_s
        dur = max(0.05, min(dur, self.max_s))
        with self._lock:
            if self._active:
                self.suppressed += 1
                return None, "busy"
            now = time.monotonic()
            if self.windows > 0 and (
                now - self._last_start < self.min_interval_s
            ):
                self.suppressed += 1
                return None, "rate_limited"
            # Reserve the window under the lock; a concurrent arm sees
            # busy, not a second start_trace on XLA's global profiler.
            self._active = True
            self._last_start = now
        safe = "".join(
            c if c.isalnum() or c in "-_" else "-" for c in str(tag)
        )[:32] or "ondemand"
        final = os.path.join(
            self.out_dir, f"profile-{safe}-{time.time_ns()}"
        )
        partial = final + ".partial"
        try:
            import jax

            os.makedirs(partial, exist_ok=True)
            lean = _lean_options(jax)
            if lean is not None:
                jax.profiler.start_trace(partial, profiler_options=lean)
            else:
                jax.profiler.start_trace(partial)
            self.program_spans = True
            self.window.open = True
        except Exception as e:  # noqa: BLE001 — telemetry never raises
            with self._lock:
                self._active = False
                self.failed += 1
                self.last_error = f"{type(e).__name__}: {e}"[:200]
            return None, "failed"
        t = threading.Timer(dur, self._finish, args=(partial, final, dur))
        t.daemon = True
        with self._lock:
            self._timer = t
        t.start()
        return final, "armed"

    def _finish(self, partial: str, final: str, dur: float) -> None:
        with self._lock:
            # One closer per window: the bound timer and an explicit
            # stop_now() may race — first claim wins, the loser no-ops
            # (a second stop_trace would raise into failure counters).
            if not self._active or self._closing:
                return
            self._closing = True
        self.window.open = False
        try:
            import jax

            jax.profiler.stop_trace()
            os.replace(partial, final)
            with self._lock:
                self.windows += 1
                self.last_path = final
                self.last_duration_s = dur
        except Exception as e:  # noqa: BLE001
            with self._lock:
                self.failed += 1
                self.last_error = f"{type(e).__name__}: {e}"[:200]
        finally:
            with self._lock:
                self._active = False
                self._closing = False
                self._timer = None

    def stop_now(self) -> Optional[str]:
        """Close the in-flight window immediately (the CLI's --profile
        closes at end-of-run instead of waiting out the cap); returns
        the artifact path, or None when no window was open."""
        with self._lock:
            t = self._timer
            if not self._active or t is None:
                return None
        t.cancel()
        self._finish(*t.args)
        with self._lock:
            return self.last_path

    def wait(self, timeout_s: float = 30.0) -> bool:
        """Block until the in-flight window (if any) closes; True when
        idle. For the CLI's ``--profile`` and the dryrun lane."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._lock:
                t = self._timer
                active = self._active
            if not active:
                return True
            if t is not None:
                t.join(timeout=min(1.0, deadline - time.monotonic()))
            else:
                time.sleep(0.02)
        with self._lock:
            return not self._active

    def active(self) -> bool:
        with self._lock:
            return self._active

    def stats(self) -> dict:
        with self._lock:
            return {
                "active": self._active,
                "windows": self.windows,
                "suppressed": self.suppressed,
                "failed": self.failed,
                "max_s": self.max_s,
                "min_interval_s": self.min_interval_s,
                "last_path": self.last_path,
                "last_duration_s": self.last_duration_s,
                "last_error": self.last_error,
                "program_spans": self.program_spans,
            }


def _lean_options(jax):
    """No Python frames, host TraceMe events on (the program's spans are
    those); None on a profiler too old to take options."""
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        return options
    except Exception:  # noqa: BLE001 — default options then
        return None


# -- process-wide resolution (the faults/obs binding pattern) ----------------

_lock = sanitizer.make_lock("obs.profiler.registry")
_profiler: Optional[DeepProfiler] = None
_resolved = False


def profiler() -> Optional[DeepProfiler]:
    """The process-wide deep profiler, or None when ``LLMC_PROFILE=0``.
    Resolved once; consumers bind at construction time."""
    global _profiler, _resolved
    if not _resolved:
        with _lock:
            if not _resolved:
                if knobs.get_bool("LLMC_PROFILE"):
                    _profiler = DeepProfiler()
                _resolved = True
    return _profiler


def install(p: Optional[DeepProfiler]) -> None:
    """Install ``p`` as the process profiler (tests / CLI / dryrun)."""
    global _profiler, _resolved
    with _lock:
        _profiler = p
        _resolved = True


def reset() -> None:
    """Forget the cached profiler; the next :func:`profiler` re-reads
    the environment."""
    global _profiler, _resolved
    with _lock:
        _profiler = None
        _resolved = False


__all__ = [
    "DEFAULT_DIR", "DEFAULT_MAX_S", "DEFAULT_MIN_INTERVAL_S",
    "DeepProfiler", "install", "profiler", "reset",
]
