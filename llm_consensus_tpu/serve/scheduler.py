"""Run sessions: one consensus run per admitted request, shared engines.

The CLI's run lifecycle (cli/main.py::_run) is process-scoped — one
prompt, one progress UI, one exit. Serving needs the same panel → judge
pipeline but *per request*, many at once, with no UI and no process
lifecycle: that is :class:`Scheduler`. Each :meth:`execute` gives the
request

  * its own :class:`~llm_consensus_tpu.utils.context.Context` (deadline =
    the request's timeout, child of the gateway's root so drain/shutdown
    cancels stragglers),
  * its own collision-free run id + ``data/<run-id>/`` persistence
    (output/persist.reserve_run_dir — wall-clock ids collide under
    concurrent runs, reserved dirs cannot),
  * headless streaming via an ``emit(kind, model, text)`` callback
    (``kind`` is ``"model_chunk"`` or ``"judge_chunk"``) instead of the
    CLI's Progress UI,

while every request shares the warm engines behind the registry's
providers — the whole point of a resident service: compiled programs and
weights stay on the chips, requests multiplex onto them through the
continuous batcher.

Concurrency: one :class:`~llm_consensus_tpu.runner.Runner` is built per
run (construction is two bound lookups — cheap) and callbacks are passed
per ``run()`` call, so no callback state is shared between concurrent
runs. Persistence failures are non-fatal, exactly like the CLI's aux
writes: a run that produced its answer must not fail because a disk
write did.
"""

from __future__ import annotations

import json
import threading
from dataclasses import dataclass
from typing import Callable, Optional

from llm_consensus_tpu.analysis import sanitizer
from llm_consensus_tpu import output as output_mod
from llm_consensus_tpu.consensus import Judge, score_agreement
from llm_consensus_tpu.output.persist import reserve_run_dir, save_file
from llm_consensus_tpu.providers import Registry
from llm_consensus_tpu.runner import Callbacks, Runner
from llm_consensus_tpu.utils.context import Context

# emit(kind, model, text): kind is "model_chunk" | "judge_chunk".
EmitFn = Callable[[str, str, str], None]


@dataclass
class ServeRequest:
    """One validated consensus request (the gateway parses JSON into this)."""

    prompt: str
    models: list[str]
    judge: str
    system: Optional[str] = None
    max_tokens: Optional[int] = None
    timeout: float = 120.0
    stream: bool = False
    # Priority class (pressure/priority.py): explicit "priority" field
    # or deadline-derived at parse time. Orders admission dequeue,
    # scales shed Retry-After, and selects preemption victims on the
    # engine tier. NOT part of the cache/coalescing key: priority
    # changes WHEN a request runs, never what it computes.
    priority: int = 1
    # Cross-hop trace id (obs/live.py): minted at the router (or the
    # gateway for direct hits), threaded through runner/judge into
    # engine spans, returned in the done envelope. NOT part of the
    # cache key — identity is what a request computes, not its id.
    trace_id: Optional[str] = None
    # Live-migration resume payload (serve/elastic.py): model name →
    # sealed-journal snapshot ({"prompt_ids", "sampling", "tokens"}) or
    # emitted-text prefix ({"text"}). Set only on the re-submission that
    # claims a parked MigrationRecord. NOT part of the cache key: a
    # resumed stream computes the same answer, it just skips re-decoding
    # the prefix.
    resume: Optional[dict] = None

    def cache_fields(self) -> dict:
        """The identity fields the cache key covers (serve/cache.py)."""
        return {
            "models": self.models,
            "judge": self.judge,
            "prompt": self.prompt,
            "system": self.system,
            "max_tokens": self.max_tokens,
        }


@dataclass
class RunSession:
    """One request's identity: run id, persistence dir, context."""

    run_id: str
    run_dir: str  # "" when persistence is disabled
    ctx: Context
    # Where ``consensus_run`` ended (``time.monotonic_ns``; 0 until it has):
    # the gateway counts the reply's tail from here (``serve.reply_tail_s``).
    run_end_ns: int = 0


def panel_timings(run_ns: int, judge_ns: int, workers: list) -> dict:
    """What divides ``panel_ms``, from the clock reads the panel's spans
    made: each panelist's ``worker`` span (``t0_ns``, ``t1_ns``) and its
    answer's marks through its pool (runner/runner.py ``RunResult.workers``,
    engine/batcher.py ``_Stream.marks``).

      panel             one entry a panelist, in panel order: ``model``;
                        ``lead_in_ms`` the run starts → its worker starts;
                        ``wall_ms`` → its answer is back (the worker ends);
                        and inside the wall, for a pooled panelist,
                        ``queue_ms`` → its first ``pool.admit``,
                        ``prefill_ms`` → its first token is on the host,
                        ``decode_ms`` → its last token is (what is left of
                        the wall is the answer's way back to the worker),
                        ``tokens``, ``prompt_tokens`` and ``decode_steps``,
                        the steps its pool landed between the two tokens.
                        A panelist without marks (remote, unpooled, failed)
                        has its lead-in and its wall only.
      panel_gate        the model whose answer came last
      panel_skew_ms     first answer → last answer
      judge_prepare_ms  last answer → the judge's worker starts
                        (agreement, confidence, render, tokenise)

    so that the gating panelist's ``lead_in_ms + wall_ms`` and
    ``judge_prepare_ms`` are ``panel_ms``. A boundary never precedes the
    one before it, as in ``run_timings``."""
    entries, answers = [], []
    for w in workers:
        if not w or w.get("t1_ns") is None:
            continue
        t0 = max(w["t0_ns"], run_ns)
        t1 = max(w["t1_ns"], t0)
        entry = {"model": w["model"], "lead_in_ms": (t0 - run_ns) / 1e6,
                 "wall_ms": (t1 - t0) / 1e6}
        marks = w.get("marks") or {}
        if marks.get("admit_ns") is not None and marks.get(
                "first_token_ns") is not None:
            edges = [t0, marks["admit_ns"], marks["first_token_ns"],
                     marks.get("last_token_ns", t1)]
            for i in range(1, len(edges)):
                edges[i] = min(max(edges[i], edges[i - 1]), t1)
            entry.update(
                queue_ms=(edges[1] - edges[0]) / 1e6,
                prefill_ms=(edges[2] - edges[1]) / 1e6,
                decode_ms=(edges[3] - edges[2]) / 1e6,
                tokens=marks.get("tokens"),
                prompt_tokens=marks.get("prompt_tokens"),
            )
            if "first_step" in marks and "last_step" in marks:
                entry["decode_steps"] = max(
                    marks["last_step"] - marks["first_step"], 0)
        entries.append(entry)
        answers.append((t1, w["model"]))
    if not entries:
        return {}
    last, gate = max(answers)
    return {
        "panel": entries,
        "panel_gate": gate,
        "panel_skew_ms": (last - min(answers)[0]) / 1e6,
        "judge_prepare_ms": max(judge_ns - last, 0) / 1e6,
    }


def run_timings(arrival_ns: int, run_ns: int, judge_ns: int, end_ns: int,
                marks: Optional[dict],
                workers: Optional[list] = None) -> Optional[dict]:
    """Where one served run's time went, from the clock reads its spans
    made (``time.monotonic_ns``): the request's arrival, the start of
    ``consensus_run``, the start of the judge's ``worker`` span, the end
    of ``consensus_run``, and the judge stream's marks through its pool
    (engine/batcher.py). Six consecutive stretches that share their
    boundaries, so they sum to ``total_ms``:

      queue_ms              arrival → the run starts (cache, coalescing,
                            admission queue, run dir)
      panel_ms              → the judge's worker starts (fan-out, every
                            panel answer, agreement, judge prompt render)
      judge_queue_ms        → the first ``pool.admit`` wave that carries
                            this run's judge prompt is dispatched
      judge_prefill_ms      → its first token is on the host (prefill and
                            the first decode chunk it rides down with)
      judge_first_chunk_ms  → its first text is handed to the stream
      judge_decode_ms       → the run ends

    ``workers`` (the panel's clock reads, ``RunResult.workers``) adds what
    divides ``panel_ms``: ``panel_timings``'s keys, beside the six.

    None when the judge reported no marks (a single-response passthrough,
    a remote or unpooled judge): the block is then left out."""
    if not marks:
        return None
    admit = marks.get("admit_ns")
    first = marks.get("first_token_ns")
    if admit is None or first is None:
        return None
    edges = [
        arrival_ns, run_ns, judge_ns, admit, first,
        marks.get("first_chunk_ns", first), end_ns,
    ]
    # A boundary never precedes the one before it: a prompt already
    # admitted by an overlapping judge prefill clamps to the worker start.
    for i in range(1, len(edges)):
        edges[i] = max(edges[i], edges[i - 1])
    names = ("queue_ms", "panel_ms", "judge_queue_ms", "judge_prefill_ms",
             "judge_first_chunk_ms", "judge_decode_ms")
    out = {
        name: (b - a) / 1e6 for name, a, b in zip(names, edges, edges[1:])
    }
    out["total_ms"] = (edges[-1] - edges[0]) / 1e6
    out["judge_prompt_tokens"] = marks.get("prompt_tokens")
    out["judge_tokens"] = marks.get("tokens")
    out.update(panel_timings(edges[1], edges[2], workers or []))
    return out


class Scheduler:
    """Executes consensus runs over a shared registry of warm providers."""

    def __init__(
        self,
        registry: Registry,
        *,
        data_dir: str = "data",
        save: bool = True,
        root_ctx: Optional[Context] = None,
        live=None,
    ):
        self._registry = registry
        self._data_dir = data_dir
        self._save = save
        # All request contexts derive from this root: cancelling it (hard
        # shutdown) cancels every in-flight run cooperatively.
        self._root = root_ctx if root_ctx is not None else Context.background()
        self._lock = sanitizer.make_lock("serve.scheduler")
        self.runs_executed = 0
        from llm_consensus_tpu import obs

        self._obs = obs.recorder()
        # Live plane: judge-synthesis wall histogram (/metricsz) + run
        # spans in the always-on flight recorder ring. ``live`` override
        # keeps multi-gateway tests per-replica; production binds the
        # process singleton.
        self._live = live if live is not None else obs.live.metrics()
        self._spans = obs.emitter()

    # -- sessions ------------------------------------------------------------

    def request_ctx(self, req: ServeRequest) -> Context:
        """The request's own deadline context, child of the gateway root.

        Created before admission so time spent queued counts against the
        request's budget (a client that waited its whole deadline out in
        the queue gets an error, not a doomed run)."""
        return self._root.with_timeout(req.timeout)

    def open_session(
        self, req: ServeRequest, ctx: Optional[Context] = None
    ) -> RunSession:
        """Reserve the request's run id/dir; adopt ``ctx`` or derive one.

        Called after admission: rejected requests never reserve a dir."""
        if ctx is None:
            ctx = self.request_ctx(req)
        if not self._save:
            from llm_consensus_tpu.output.persist import generate_run_id

            return RunSession(run_id=generate_run_id(), run_dir="", ctx=ctx)
        run_id, run_dir = reserve_run_dir(self._data_dir)
        # Manifest BEFORE execution, mirroring the CLI's crash-resume
        # journal (cli/main.py::write_run_manifest): run.json is the sole
        # authority the flywheel corpus scanner trusts — a data/ dir
        # without one is not a run (flywheel/corpus.py).
        save_file(run_dir, "run.json", json.dumps({
            "prompt": req.prompt,
            "models": list(req.models),
            "judge": req.judge,
            "system": req.system,
            "max_tokens": req.max_tokens,
            "timeout": req.timeout,
            "source": "serve",
        }, indent=2))
        return RunSession(run_id=run_id, run_dir=run_dir, ctx=ctx)

    def cancel_all(self) -> None:
        """Hard-cancel every in-flight run (post-drain-timeout shutdown)."""
        self._root.cancel()

    # -- execution -----------------------------------------------------------

    def execute(
        self,
        session: RunSession,
        req: ServeRequest,
        emit: Optional[EmitFn] = None,
        arrival_ns: Optional[int] = None,
    ) -> output_mod.Result:
        """Run panel fan-out + judge synthesis for one request.

        Streams through ``emit``; persists into the session's run dir;
        returns the finished Result. Raises on total failure (all panel
        models failed, judge failed, deadline expired). ``arrival_ns``
        is when the request reached the gateway (the ``request`` span's
        start): the result's ``timings`` count from there."""
        ctx = session.ctx
        import time as _time

        t0_run = _time.monotonic_ns()
        try:
            runner = Runner(
                self._registry,
                req.timeout,
                max_tokens=req.max_tokens,
                system=req.system or None,
                priority=req.priority,
                trace_id=req.trace_id,
                resume=req.resume,
            )
            # Judge prefill overlap (consensus/overlap.py): when enabled
            # and the judge is an on-device engine, panel answers prefill
            # into the judge's growing KV as they arrive, so synthesis
            # TTFT drops by nearly the whole judge-prompt prefill. The
            # shim is per-request (its session is single-use) and falls
            # back to the classic Judge internally on any condition it
            # cannot honor.
            overlap = None
            try:
                from llm_consensus_tpu.consensus import make_overlap_judge

                overlap = make_overlap_judge(
                    self._registry.get(req.judge), req.judge, req.prompt,
                    max_tokens=req.max_tokens,
                    priority=max(0, req.priority - 1),
                    trace_id=req.trace_id,
                )
            except Exception:  # noqa: BLE001 — unknown judge errors below
                overlap = None
            callbacks = None
            if emit is not None or overlap is not None:
                callbacks = Callbacks(
                    on_model_stream=(
                        (lambda m, c: emit("model_chunk", m, c))
                        if emit is not None else None
                    ),
                    on_model_response=(
                        overlap.on_response if overlap is not None else None
                    ),
                )
            result = runner.run(ctx, list(req.models), req.prompt, callbacks=callbacks)

            # Between the last panel answer and the judge's worker: no
            # pool has work while the request thread is here.
            with self._spans.span(
                "judge.prepare", "runner", trace=req.trace_id,
            ):
                agreement = score_agreement(result.responses)
                judge_provider = self._registry.get(req.judge)
                # Judge work outranks this request's own panel class by
                # one step (floored at HIGH): the judge serializes the
                # run, so on a contended engine its stream must not queue
                # behind other runs' panel streams of the same class.
                judge = overlap if overlap is not None else Judge(
                    judge_provider, req.judge, max_tokens=req.max_tokens,
                    priority=max(0, req.priority - 1),
                    trace_id=req.trace_id,
                )
            judge_cb = None
            if emit is not None:
                judge_cb = lambda c: emit("judge_chunk", req.judge, c)  # noqa: E731
            with self._spans.span(
                "worker", "runner", model=req.judge, role="judge",
                trace=req.trace_id,
            ) as judge_span:
                consensus = judge.synthesize_stream(
                    ctx, req.prompt, result.responses, judge_cb
                )
            if self._live is not None:
                from llm_consensus_tpu.obs.live import class_label

                # Judge synthesis wall for the /metricsz histogram —
                # labeled with the JUDGE's class (one step above the
                # request's own panel class, the same derivation the
                # Judge itself runs under).
                self._live.observe(
                    "judge_synthesis",
                    (judge_span.t1_ns - judge_span.t0_ns) / 1e9,
                    outcome="ok",
                    **{"class": class_label(max(0, req.priority - 1))},
                )
            if judge.last_truncated:
                result.warnings.append(
                    f"{req.judge}: judge prompt truncated to fit context window"
                )

            out = output_mod.Result(
                prompt=req.prompt,
                responses=result.responses,
                consensus=consensus,
                judge=req.judge,
                warnings=result.warnings,
                failed_models=result.failed_models,
                agreement=agreement.to_dict() if agreement else None,
            )
            with self._lock:
                self.runs_executed += 1
            if self._obs is not None:
                self._obs.count("serve.runs")
            t1_run = self._spans.complete(
                "consensus_run", t0_run, "serve",
                trace=req.trace_id, run_id=session.run_id,
            )
            out.timings = run_timings(
                arrival_ns if arrival_ns is not None else t0_run, t0_run,
                judge_span.t0_ns, t1_run,
                getattr(judge, "last_marks", None), result.workers,
            )
            session.run_end_ns = t1_run
            self.persist(session, out, telemetry=True, trace=req.trace_id)
            return out
        finally:
            ctx.close()

    # -- persistence ---------------------------------------------------------

    def persist(self, session: RunSession, out: output_mod.Result,
                telemetry: bool = False, trace: Optional[str] = None) -> None:
        """Flush one run's artifacts into its reserved dir (non-fatal).

        result.json / prompt.txt / consensus.md always; with
        ``telemetry`` and a live recorder, trace.json + metrics.json too —
        the serve-side spans (queue_wait/admit) and instants
        (cache_hit/coalesced) land in the same Chrome trace the CLI's
        ``--events`` produces. Only EXECUTED runs pass ``telemetry``:
        the recorder is process-scoped under serving (concurrent runs
        share it, so there is no per-request clear), meaning each
        snapshot covers everything since startup, bounded by
        ``LLMC_EVENTS_MAX`` — cheap once per real run, but pure overhead
        to rewrite for every cache hit and coalesced follower.
        """
        if not session.run_dir:
            return
        with self._spans.span(
            "run.persist", "serve", trace=trace, run_id=session.run_id,
        ) as sp:
            save_file(session.run_dir, "prompt.txt", out.prompt)
            save_file(session.run_dir, "consensus.md", out.consensus)
            payload = self._stamp(out.to_json())
            save_file(session.run_dir, "result.json", payload)
            sp.set(bytes=len(out.prompt) + len(out.consensus) + len(payload))
            self._persist_telemetry(session, out, telemetry)

    def _persist_telemetry(self, session: RunSession,
                           out: output_mod.Result, telemetry: bool) -> None:
        """``persist``'s trace.json and metrics.json (its docstring)."""
        if not telemetry or self._obs is None:
            return
        from llm_consensus_tpu.obs import export as obs_export

        trace_doc = obs_export.local_trace(self._obs)
        metrics_doc = obs_export.metrics_summary(
            self._obs,
            responses=out.responses,
            batcher_stats=obs_export.collect_batcher_stats(self._registry),
            kv_stats=obs_export.collect_kv_stats(self._registry),
            spec_stats=obs_export.collect_spec_stats(self._registry),
            disagg_stats=obs_export.collect_disagg_stats(self._registry),
            failed_models=out.failed_models,
            warnings=out.warnings,
            live=obs_export.live_summary(self._live),
            attrib=obs_export.attrib_summary(),
        )
        obs_export.save_run_telemetry(session.run_dir, trace_doc, metrics_doc)

    def _stamp(self, payload: str) -> str:
        """With the integrity plane on, stamp ``result.json`` with a
        content digest over the fields the flywheel corpus distills from
        — ``build_corpus`` re-derives it before admitting the pair, so a
        run whose bytes rotted on disk is booked and excluded instead of
        training the student on garbage. Plane off: payload unchanged."""
        from llm_consensus_tpu import integrity

        p = integrity.plane()
        if p is None:
            return payload
        try:
            doc = json.loads(payload)
        except ValueError:
            return payload
        if not isinstance(doc, dict):
            return payload
        from llm_consensus_tpu.flywheel.corpus import pair_digest

        doc["integrity_digest"] = pair_digest(doc)
        return json.dumps(doc, indent=2)

    def persist_copy(self, req: ServeRequest, out: output_mod.Result) -> RunSession:
        """A follower's / cache hit's own run dir for a shared result.

        Every served request keeps its own ``data/<run-id>/`` — distinct,
        collision-free run ids even when M requests shared one execution.
        """
        session = self.open_session(req)
        try:
            self.persist(session, out, trace=req.trace_id)
        finally:
            session.ctx.close()
        return session
