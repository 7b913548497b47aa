"""Parallel best-effort fan-out of one prompt to N models.

Parity: /root/reference/internal/runner/runner.go:15-131. Semantics preserved
exactly:

  * One worker per model, all started concurrently (runner.go:62-63; the
    reference uses one goroutine per model — here one thread per model, which
    is the right host-side shape for the TPU build too: each panel model's
    decode loop is driven by its own host thread against its own mesh slice).
  * Per-model deadline via a child context (runner.go:65-66).
  * Best-effort: a model failure is recorded as a warning + failed_models
    entry and never cancels siblings (runner.go:75-83, 100-107); workers
    never raise.
  * Responses appended in completion order under a lock (runner.go:97-98).
  * Only a total wipeout is an error (runner.go:122-124).

Beyond the reference: a **per-model watchdog**. The reference's goroutines
always return when their context expires because net/http honors it; here a
worker can wedge inside non-cooperative code (a stuck device transfer, a
DNS stall, an injected fault). A worker that is past its deadline *and* has
not streamed for a grace period (``LLMC_STALL_GRACE``, default 5 s) is
recorded as failed and abandoned — ``run`` never blocks on a dead worker,
so one stuck model degrades the run instead of hanging it. Abandoned
workers run as daemon threads against a *sealed* result: late completions
are dropped, never spliced into a result the caller already consumed.

Progress flows through :class:`Callbacks` so the runner has no UI dependency
(runner.go:15-20); the CLI bridges runner→ui.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

from llm_consensus_tpu.analysis import sanitizer
from llm_consensus_tpu.providers import Provider, Registry, Request, Response
from llm_consensus_tpu.utils.context import Context
from llm_consensus_tpu.utils import knobs


@dataclass
class Callbacks:
    """Progress hooks (runner.go:15-20). All optional.

    ``on_model_response`` is the TPU-build extension behind judge
    prefill overlap (consensus/overlap.py): it fires with the FULL
    :class:`Response` the moment a worker's answer is recorded, so a
    consumer can start work on it (e.g. prefill it into the judge's
    growing KV) while sibling models are still decoding. Called from the
    worker's thread, outside the runner lock, in completion order per
    worker; exceptions are swallowed (best-effort parity — a hook must
    never fail a model that answered)."""

    on_model_start: Optional[Callable[[str], None]] = None
    on_model_stream: Optional[Callable[[str, str], None]] = None
    on_model_complete: Optional[Callable[[str], None]] = None
    on_model_error: Optional[Callable[[str, Exception], None]] = None
    on_model_response: Optional[Callable[[Response], None]] = None


@dataclass
class RunResult:
    """Outcome of a fan-out run (runner.go:23-27)."""

    responses: list[Response] = field(default_factory=list)
    warnings: list[str] = field(default_factory=list)
    failed_models: list[str] = field(default_factory=list)
    # One entry a panelist, in PANEL order (``responses`` is in completion
    # order): {"model", "t0_ns", "t1_ns"} from its ``worker`` span's own
    # clock reads, and "marks", its answer's way through its pool
    # (Response.marks; None for a remote, unpooled or failed panelist).
    # None for a worker the watchdog abandoned. The serving tier turns
    # them into the result's ``timings.panel`` (serve/scheduler.py).
    workers: list[Optional[dict]] = field(default_factory=list)


class AllModelsFailed(RuntimeError):
    """Every panel model failed (runner.go:122-124)."""


class WorkerStalled(RuntimeError):
    """A worker exceeded its deadline without streaming and was abandoned."""


def _default_stall_grace() -> float:
    return knobs.get_float("LLMC_STALL_GRACE")


class Runner:
    """Queries N models concurrently, collecting partial results."""

    def __init__(self, registry: Registry, timeout: float,
                 max_tokens: "int | None" = None,
                 system: "str | None" = None,
                 stall_grace: "float | None" = None,
                 priority: "int | None" = None,
                 trace_id: "str | None" = None,
                 resume: "dict | None" = None):
        self._registry = registry
        self._timeout = timeout
        self._max_tokens = max_tokens
        self._system = system  # system prompt for every panel query
        # Priority class for every panel query (pressure/priority.py);
        # None = provider default (NORMAL). The judge outranks the
        # panel by default — see consensus/judge.py.
        self._priority = priority
        # Cross-hop trace id (obs/live.py): stamped on every worker span
        # and threaded into each provider Request, so the serving tier's
        # per-request id reaches the engine hop.
        self._trace = trace_id
        # Migration resume payloads, keyed by model name (serve/elastic):
        # a resumed run hands each panel worker its model's sealed-journal
        # snapshot so the engine replays instead of re-decoding.
        self._resume = resume or {}
        self._callbacks = Callbacks()
        # Watchdog grace: how long past its deadline a silent worker may
        # run before it is declared stalled and abandoned.
        self._stall_grace = (
            stall_grace if stall_grace is not None else _default_stall_grace()
        )
        # Fault injection (faults/): bound once, None-check per worker.
        from llm_consensus_tpu import faults

        self._faults = faults.plan()
        # Telemetry (obs/): bound once — per-worker spans + watchdog
        # instants land on the run timeline when events are enabled.
        from llm_consensus_tpu import obs

        self._obs = obs.recorder()
        # Worker spans go through the one emitter: the always-on ring gets
        # them too, so a crash snapshot shows the fan-out shape.
        self._spans = obs.emitter()

    def with_callbacks(self, callbacks: Callbacks) -> "Runner":
        self._callbacks = callbacks
        return self

    def run(self, ctx: Context, models: list[str], prompt: str,
            callbacks: Optional[Callbacks] = None) -> RunResult:
        result = self._collect(ctx, models, prompt, callbacks=callbacks)
        # Zero responses — including an empty model list — is a run failure
        # (runner.go:122-124).
        if not result.responses:
            raise AllModelsFailed(
                "all models failed: " + "; ".join(result.warnings)
            )
        return result

    def _collect(self, ctx: Context, models: list[str], prompt: str,
                 callbacks: Optional[Callbacks] = None) -> RunResult:
        """The fan-out without the all-fail check: multi-controller runs
        judge "all failed" on the MERGED result, not any one process's
        local subset (runner/multihost.py).

        ``callbacks`` overrides the instance-level hooks for THIS run
        only: a shared Runner serving concurrent runs (serve/scheduler)
        passes per-request callbacks here, so no callback state is ever
        shared between runs in flight — ``with_callbacks`` mutates the
        instance and remains the single-run CLI's API."""
        result = RunResult(workers=[None] * len(models))
        lock = sanitizer.make_lock("runner.result")
        # Sealed once _collect returns: an abandoned (stalled) worker that
        # wakes up later must not mutate a result the caller already holds.
        sealed = [False]
        # All per-worker state is keyed by worker INDEX, not model name — a
        # panel may request the same model twice (reference parity), and
        # name-keyed bookkeeping would conflate the duplicates' deadlines,
        # liveness, and outcomes.
        #   done:      workers that already recorded an outcome (response
        #              or failure) — exactly one outcome per worker.
        #   abandoned: workers the watchdog booked as stalled; their late
        #              completions/failures are dropped.
        done: set = set()
        abandoned: set = set()
        # Per-worker liveness the watchdog reads: the child context (its
        # deadline is the authority — utils/context.expired_for) and the
        # last time any chunk streamed.
        ctxs: dict[int, Context] = {}
        activity: dict[int, float] = {}
        cb = callbacks if callbacks is not None else self._callbacks

        def record_failure(wid: int, model: str, err: Exception) -> None:
            with lock:
                if sealed[0] or wid in abandoned:
                    return  # watchdog already booked this worker's outcome
                done.add(wid)
                result.warnings.append(f"{model}: {err}")
                result.failed_models.append(model)

        def worker(model: str, wid: int) -> None:
            # Workers never raise: failures — including ones thrown by the
            # caller's own callbacks — become warnings so siblings always run
            # to completion (runner.go:75-83, 100-111).
            with self._spans.span(
                "worker", "runner", model=model, role="panel", wid=wid,
                trace=self._trace,
            ) as sp:
                marks = None
                try:
                    marks = query_one(model, wid)
                except Exception as err:
                    with lock:
                        accounted = wid in done or wid in abandoned
                    if not accounted:
                        record_failure(wid, model, err)
                        if cb.on_model_error:
                            try:
                                cb.on_model_error(model, err)
                            except Exception:
                                pass  # the error hook may be the broken one
            with lock:
                if not sealed[0]:
                    result.workers[wid] = {
                        "model": model, "t0_ns": sp.t0_ns, "t1_ns": sp.t1_ns,
                        "marks": marks,
                    }

        def query_one(model: str, wid: int) -> Optional[dict]:
            """One panelist's query; returns its answer's marks."""
            model_ctx = ctx.with_timeout(self._timeout)
            with lock:
                ctxs[wid] = model_ctx
            try:
                if cb.on_model_start:
                    cb.on_model_start(model)
                if self._faults is not None:
                    # worker_stall[@model=name][@s=secs]: a NON-cooperative
                    # sleep (deliberately ignores model_ctx) — the wedge
                    # the watchdog exists to catch.
                    fs = self._faults.fire("runner", model=model)
                    if fs is not None:
                        time.sleep(float(fs.param(
                            "s", self._timeout + 2 * self._stall_grace + 1.0
                        )))
                try:
                    provider = self._registry.get(model)
                except Exception as err:
                    record_failure(wid, model, err)
                    if cb.on_model_error:
                        cb.on_model_error(model, err)
                    return

                def on_chunk(chunk: str) -> None:
                    with lock:
                        activity[wid] = time.monotonic()
                    if cb.on_model_stream:
                        cb.on_model_stream(model, chunk)

                try:
                    resp = provider.query_stream(
                        model_ctx,
                        Request(model=model, prompt=prompt,
                                max_tokens=self._max_tokens,
                                system=self._system,
                                priority=self._priority,
                                trace_id=self._trace,
                                resume=self._resume.get(model)),
                        on_chunk,
                    )
                except Exception as err:
                    record_failure(wid, model, err)
                    if cb.on_model_error:
                        cb.on_model_error(model, err)
                    return

                with lock:
                    if sealed[0] or wid in abandoned:
                        return  # watchdog already booked this worker failed
                    done.add(wid)
                    result.responses.append(resp)
                    if resp.truncated:
                        result.warnings.append(
                            f"{model}: prompt truncated to fit context window"
                        )
                if cb.on_model_response:
                    # Judge-overlap feed: the full response, the moment
                    # it lands — outside the lock (the hook may dispatch
                    # device work), failures swallowed (a hook must not
                    # fail a model that answered).
                    try:
                        cb.on_model_response(resp)
                    except Exception:  # noqa: BLE001
                        pass
                if cb.on_model_complete:
                    cb.on_model_complete(model)
                return getattr(resp, "marks", None)
            finally:
                # The analog of the reference's deferred context cancel:
                # release the per-model context from the run context.
                model_ctx.close()

        threads = [
            (threading.Thread(target=worker, args=(m, i),
                              name=f"runner-{i}-{m}", daemon=True), m, i)
            for i, m in enumerate(models)
        ]
        for t, _, _ in threads:
            t.start()
        self._join_with_watchdog(threads, ctxs, activity, lock, result,
                                 done, abandoned, cb)
        with lock:
            sealed[0] = True
        return result

    def _join_with_watchdog(self, threads, ctxs, activity, lock, result,
                            done: set, abandoned: set,
                            cb: Optional[Callbacks] = None) -> None:
        """Join workers, abandoning any that wedge past their deadline.

        A worker whose model context has been expired for longer than the
        stall grace, with no streaming activity inside that grace window,
        is recorded as failed and dropped from the join set — ``run``
        returns on the survivors' schedule, never the wedged worker's.
        """
        grace = self._stall_grace
        if cb is None:
            cb = self._callbacks
        pending = list(threads)
        while pending:
            still: list = []
            for t, model, wid in pending:
                t.join(timeout=0.05)
                if not t.is_alive():
                    continue
                with lock:
                    mctx = ctxs.get(wid)
                    last = activity.get(wid)
                overdue = mctx.expired_for() if mctx is not None else 0.0
                recent = (
                    last is not None
                    and time.monotonic() - last < grace
                )
                if overdue > grace and not recent:
                    # Stalled: past the deadline, silent through the whole
                    # grace window. Book it failed and stop waiting; the
                    # daemon thread dies with the process or exits into a
                    # sealed/abandoned check. The outcome check, the
                    # failure booking, and the abandoned marking happen
                    # under ONE lock hold, so a worker resolving
                    # concurrently gets exactly one outcome — either its
                    # result landed first (we skip booking) or the
                    # abandonment landed first (its late append/failure
                    # is dropped).
                    err = WorkerStalled(
                        f"worker exceeded its deadline by {overdue:.1f}s "
                        "without streaming; abandoned"
                    )
                    with lock:
                        accounted = wid in done or wid in abandoned
                        if not accounted:
                            abandoned.add(wid)
                            result.warnings.append(f"{model}: {err}")
                            result.failed_models.append(model)
                    if not accounted and self._obs is not None:
                        self._obs.instant(
                            "watchdog_abandon", tid="runner",
                            model=model, wid=wid, overdue_s=round(overdue, 3),
                        )
                    if not accounted and cb.on_model_error:
                        try:
                            cb.on_model_error(model, err)
                        except Exception:
                            pass
                    continue
                still.append((t, model, wid))
            pending = still
