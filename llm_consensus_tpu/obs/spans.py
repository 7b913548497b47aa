"""The one span emitter: every span site writes through it, once.

A consumer binds an :class:`Emitter` at construction
(``self._spans = obs.emitter()``) and writes each span with ONE call; the
emitter fans it out to every sink that is on:

  * the opt-in run :class:`~llm_consensus_tpu.obs.recorder.Recorder`
    (``LLMC_EVENTS`` / ``--events``);
  * the always-on flight ring (obs/blackbox.py);
  * the profiler's own trace, while a :class:`DeepProfiler` window is
    open: a ``jax.profiler.TraceAnnotation`` named ``llmc.<name>`` with
    the span's arguments as its keyword arguments. That puts the
    program's spans on the device trace's clock, on the line of the
    thread that ran them — which is what lets a reduction say what the
    host was doing while the device idled.

Sinks are resolved when the emitter is built, never per event: with the
recorder off and no window open a span costs the ring append plus the
read of the window flag.

Two forms, same :class:`Event` shape as the recorder's own:

  * ``with spans.span("pool.decode", tid, model=..., steps=16) as sp:``
    around the work (the annotation has to open before the work does);
    ``sp.set(tokens=n)`` adds arguments known only at the end.
    ``sp.t0_ns`` / ``sp.t1_ns`` are the span's own clock reads, for
    callers that book a duration from the same reads.
    The profiler keeps an annotation only if it both starts and ends
    inside the window, so a span that can outlast a window's edge (a
    pool waiting for work) calls ``sp.slice()`` from its loop: while a
    window is open that ends the annotation there and starts the next,
    and the trace has the span in pieces up to the last slice. The
    recorder and the ring still get ONE event for the whole span.
  * ``spans.complete(name, t0_ns, tid, **args)`` after the fact, for a
    span whose start was taken elsewhere (a request's arrival): recorder
    and ring only — the profiler cannot be told about the past.
"""

from __future__ import annotations

import time
from typing import Optional

from llm_consensus_tpu.obs.recorder import Event


class Window:
    """Whether a profiler window is open. One per :class:`DeepProfiler`;
    emitters built while that profiler is installed share it."""

    __slots__ = ("open",)

    def __init__(self) -> None:
        self.open = False


_NO_WINDOW = Window()  # never opens: the emitter of a profiler-less process


class Span:
    """One span in flight. A plain context manager (not ``contextlib``:
    the hot sites enter a few of these per decode chunk)."""

    __slots__ = ("_em", "name", "tid", "args", "t0_ns", "t1_ns", "_ann")

    def __init__(self, em: "Emitter", name: str, tid: str, args: dict):
        self._em = em
        self.name = name
        self.tid = tid
        self.args = args
        self.t0_ns = 0
        self.t1_ns = 0
        self._ann = None

    def set(self, **args) -> None:
        """Arguments known only once the work is done."""
        self.args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**_flat(args))

    def slice(self) -> None:
        """Called from the loop of a span that may outlast a profiler
        window's edge: end the annotation here and, while a window is
        open, start the next (see the module docstring). Two attribute
        reads when no window is open."""
        ann = self._ann
        if ann is not None:
            self._ann = None
            ann.__exit__(None, None, None)
        if self._em._window.open:
            self._ann = _annotation(self.name, self.args)

    def __enter__(self) -> "Span":
        if self._em._window.open:
            self._ann = _annotation(self.name, self.args)
        self.t0_ns = time.monotonic_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.t1_ns = t1 = time.monotonic_ns()
        ann = self._ann
        if ann is not None:
            self._ann = None
            ann.__exit__(exc_type, exc, tb)
        self._em._write(Event(
            name=self.name, ph="X", ts_ns=self.t0_ns, tid=self.tid,
            dur_ns=max(t1 - self.t0_ns, 0), args=self.args,
        ))


def _flat(args: dict) -> dict:
    """Annotation arguments are scalars and strings: a list (the request
    ids of a wave) goes in joined by ``|`` (the profiler's own encoding
    splits arguments at commas), None is left out."""
    out = {}
    for k, v in args.items():
        if v is None:
            continue
        if isinstance(v, (list, tuple)):
            v = "|".join(str(x) for x in v)
        out[k] = v
    return out


def _annotation(name: str, args: dict):
    """An entered ``TraceAnnotation``, or None where the profiler is not
    importable (telemetry never raises)."""
    try:
        import jax

        ann = jax.profiler.TraceAnnotation("llmc." + name, **_flat(args))
        ann.__enter__()
        return ann
    except Exception:  # noqa: BLE001
        return None


class Emitter:
    """Writes each span to every sink that is on (see module docstring)."""

    __slots__ = ("_recorder", "_ring", "_window")

    def __init__(self, recorder=None, ring=None,
                 window: Optional[Window] = None):
        self._recorder = recorder
        self._ring = ring
        self._window = window if window is not None else _NO_WINDOW

    def _write(self, ev: Event) -> None:
        if self._ring is not None:
            self._ring.append(ev)
        if self._recorder is not None:
            self._recorder.append(ev)

    def span(self, name: str, tid: str = "main", **args) -> Span:
        return Span(self, name, tid, args)

    def complete(self, name: str, t0_ns: int, tid: str = "main",
                 **args) -> int:
        """A span that started at ``t0_ns`` and ends now; returns the end
        (the caller's next span may start from the same read)."""
        t1 = time.monotonic_ns()
        self._write(Event(
            name=name, ph="X", ts_ns=t0_ns, tid=tid,
            dur_ns=max(t1 - t0_ns, 0), args=args,
        ))
        return t1

    def instant(self, name: str, tid: str = "main", **args) -> None:
        self._write(Event(
            name=name, ph="i", ts_ns=time.monotonic_ns(), tid=tid, args=args,
        ))


__all__ = ["Emitter", "Span", "Window"]
