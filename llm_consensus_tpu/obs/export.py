"""Export a run's telemetry: Chrome trace-event JSON + metrics.json.

Two artifacts per run, persisted into ``data/<run-id>/`` next to
``result.json`` (output/persist.py):

  * ``trace.json`` — Chrome trace-event format (the JSON array-of-events
    form inside ``{"traceEvents": [...]}``), loadable in Perfetto
    (https://ui.perfetto.dev) or ``chrome://tracing``. ``pid`` is the
    controller process index (one row group per host under
    multi-controller execution, obs/multihost.py), ``tid`` the subsystem
    row ("engine", "batcher", "runner", ...). Timestamps are microseconds
    on the recorder's monotonic clock, rebased so the earliest event sits
    at t=0 — absolute wall time is in metrics.json, not the timeline.
  * ``metrics.json`` — the run's aggregate numbers: recorder counters,
    batcher phase-accounting snapshots, per-model token/throughput/MFU
    stats, the fault-injection decision trace, and degraded-mode /
    failed-model bookkeeping.

The trace-event fields follow the Trace Event Format spec: "X" complete
events carry ``dur``, "i" instants carry scope ``s`` ("t": thread), "M"
metadata names processes and threads.
"""

from __future__ import annotations

import json
import os
from typing import Iterable, Optional

from llm_consensus_tpu.obs.recorder import Event, Recorder

TRACE_FILE = "trace.json"
METRICS_FILE = "metrics.json"


def _tid_table(events: Iterable[Event]) -> dict[str, int]:
    """Stable subsystem-label → integer tid mapping (first-seen order
    would vary across thread interleavings; sorted names don't)."""
    return {name: i + 1 for i, name in enumerate(
        sorted({e.tid for e in events})
    )}


def chrome_events(
    events: list[Event],
    pid: int = 0,
    process_name: Optional[str] = None,
    clock_offset_ns: int = 0,
    base_ns: Optional[int] = None,
) -> list[dict]:
    """One process's events as trace-event dicts (metadata included).

    ``clock_offset_ns`` shifts this process's monotonic clock onto the
    merging host's (obs/multihost.py estimates it from the exchange);
    ``base_ns`` is the merged timeline's zero — defaults to this event
    list's earliest timestamp.
    """
    out: list[dict] = [{
        "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
        "args": {"name": process_name or f"controller {pid}"},
    }]
    tids = _tid_table(events)
    for label, tid in tids.items():
        out.append({
            "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
            "args": {"name": label},
        })
    if base_ns is None:
        base_ns = min((e.ts_ns for e in events), default=0) + clock_offset_ns
    for e in events:
        ts_us = (e.ts_ns + clock_offset_ns - base_ns) / 1e3
        d: dict = {
            "name": e.name, "ph": e.ph, "ts": ts_us,
            "pid": pid, "tid": tids[e.tid],
        }
        if e.ph == "X":
            d["dur"] = e.dur_ns / 1e3
        elif e.ph == "i":
            d["s"] = "t"
        if e.args:
            d["args"] = dict(e.args)
        out.append(d)
    return out


def trace_document(trace_events: list[dict]) -> dict:
    return {"traceEvents": trace_events, "displayTimeUnit": "ms"}


def local_trace(recorder: Recorder, pid: int = 0) -> dict:
    """This process's timeline alone, as a loadable trace document."""
    return trace_document(chrome_events(recorder.events(), pid=pid))


# Spans that bound the decode activity window: the single-stream engine's
# dispatch and fetch, and the pooled batcher's (the last fetch ends the
# window, so its emit half is not needed).
_DECODE_ACTIVITY = ("decode", "fetch", "pool.decode", "pool.fetch")


def aggregate_throughput(
    recorder: Recorder, events: Optional[list[Event]] = None
) -> Optional[dict]:
    """Pool-wide decode throughput, or None when nothing was measured.

    Tokens over the UNION of the run's decode activity window (first
    decode dispatch to last fetch end on this recorder's timeline) —
    dividing by the SUM of per-stream decode walls would double-count
    concurrently-decoding streams/models and understate the pool rate by
    the concurrency factor. When no decode/fetch spans were recorded
    (counters-only recorders), falls back to the summed walls — correct
    for the sequential single-stream case they describe. MFU is the
    token-weighted mean of the per-response values. ``events`` lets a
    caller that already copied the event list (metrics_summary) avoid a
    second full copy under the recorder lock.
    """
    counters = recorder.counters()
    tokens = counters.get("decode_tokens", 0.0)
    if not tokens:
        return None
    if events is None:
        events = recorder.events()
    spans = [
        e for e in events if e.ph == "X" and e.name in _DECODE_ACTIVITY
    ]
    if spans:
        window_s = (
            max(e.ts_ns + e.dur_ns for e in spans)
            - min(e.ts_ns for e in spans)
        ) / 1e9
    else:
        window_s = counters.get("decode_s", 0.0)
    if window_s <= 0:
        return None
    out = {
        "tokens": tokens,
        "tokens_per_sec": tokens / window_s,
        "window_s": window_s,
    }
    weighted = counters.get("mfu_weighted_tokens", 0.0)
    # Divide by the tokens that REPORTED an MFU, not all decode tokens —
    # a model whose chip has no known peak must not dilute the mean.
    mfu_tokens = counters.get("mfu_tokens", 0.0)
    if weighted and mfu_tokens:
        out["mfu"] = weighted / mfu_tokens
    return out


def _collect_provider_stats(registry, attr: str) -> dict:
    """Per-preset stats dicts merged from every distinct provider
    registered (providers repeat across models; dedup by identity),
    read via the provider method named ``attr``.

    Best-effort: a provider whose snapshot throws loses its entry, never
    the telemetry of a run that already produced its answer. Shared by
    the CLI's metrics export, the serve scheduler's per-run persistence,
    and the gateway's ``/statsz``.
    """
    out: dict = {}
    seen: set = set()
    for model in registry.models():
        provider = registry.get(model)
        if id(provider) in seen:
            continue
        seen.add(id(provider))
        stats_fn = getattr(provider, attr, None)
        if stats_fn is not None:
            try:
                out.update(stats_fn())
            except Exception:
                pass
    return out


def collect_batcher_stats(registry) -> dict:
    """Batcher phase-accounting snapshots, keyed by preset — see
    :func:`_collect_provider_stats` for the dedup/best-effort contract."""
    return _collect_provider_stats(registry, "batcher_stats")


def collect_disagg_stats(registry) -> dict:
    """Disaggregated prefill/decode handoff snapshots, keyed by preset
    (engine/handoff.py) — see :func:`_collect_provider_stats` for the
    dedup/best-effort contract."""
    return _collect_provider_stats(registry, "disagg_stats")


def collect_kv_stats(registry) -> dict:
    """Paged-KV-pool snapshots (kv/pool.KVPool.stats), keyed by preset —
    same contract as :func:`collect_batcher_stats`. Empty unless some
    live engine runs with LLMC_KV_POOL on."""
    return _collect_provider_stats(registry, "kv_stats")


def collect_spec_stats(registry) -> dict:
    """Speculative-decoding snapshots (TPUProvider.spec_stats: rounds,
    accepted tokens, acceptance EMA, governor state per preset) — same
    contract as :func:`collect_batcher_stats`. Empty unless a draft /
    spec decode mode is configured."""
    return _collect_provider_stats(registry, "spec_stats")


def live_summary(live=None) -> Optional[dict]:
    """Final quantiles of every live-histogram family (obs/live) as a
    JSON block: per (family, labels) count / sum / p50 / p90 / p99.

    The CLI-parity half of the live plane: a one-shot run's
    ``metrics.json`` carries the same per-family summary a serve-mode
    scrape would have shown, instead of losing the histograms at exit.
    Like a scrape, the summary is CUMULATIVE over the process (exact
    for one-shot runs; interactive/serving processes accumulate across
    runs — the per-run recorder, not this plane, owns run-scoped
    deltas). None when the plane is off or empty.
    """
    if live is None:
        from llm_consensus_tpu.obs import live as live_mod

        live = live_mod.metrics()
    if live is None:
        return None
    out: dict = {}
    for name, entries in sorted(live.families().items()):
        rows = []
        for labels, hist in sorted(
            entries, key=lambda lh: sorted(lh[0].items())
        ):
            if not hist.count:
                continue
            rows.append({
                "labels": dict(labels),
                "count": hist.count,
                "sum_s": round(hist.sum, 6),
                "p50_s": round(hist.quantile(0.5), 6),
                "p90_s": round(hist.quantile(0.9), 6),
                "p99_s": round(hist.quantile(0.99), 6),
            })
        if rows:
            out[name] = rows
    return out or None


def attrib_summary() -> Optional[dict]:
    """The chip-time attribution ledger's snapshot (obs/attrib), or None
    when the plane is off — metrics.json's ``attrib`` block."""
    from llm_consensus_tpu.obs import attrib as attrib_mod

    led = attrib_mod.ledger()
    return led.snapshot() if led is not None else None


def metrics_summary(
    recorder: Optional[Recorder] = None,
    responses=None,
    batcher_stats: Optional[dict] = None,
    kv_stats: Optional[dict] = None,
    spec_stats: Optional[dict] = None,
    disagg_stats: Optional[dict] = None,
    fault_trace: Optional[list[str]] = None,
    degraded_peers=None,
    failed_models: Optional[list[str]] = None,
    warnings: Optional[list[str]] = None,
    live: Optional[dict] = None,
    attrib: Optional[dict] = None,
) -> dict:
    """The run's aggregate numbers as one JSON-serializable dict.

    ``live`` / ``attrib`` carry the live-histogram summary
    (:func:`live_summary`) and the chip-time attribution snapshot
    (:func:`attrib_summary`) when the caller collected them."""
    out: dict = {}
    if recorder is not None:
        events = recorder.events()  # one copy, shared with the aggregate
        out["counters"] = recorder.counters()
        out["events"] = {
            "recorded": len(events),
            "dropped": recorder.dropped,
        }
        agg = aggregate_throughput(recorder, events=events)
        if agg is not None:
            out["aggregate"] = agg
    if batcher_stats:
        out["batchers"] = batcher_stats
    if kv_stats:
        out["kv"] = kv_stats
    if spec_stats:
        out["spec"] = spec_stats
    if disagg_stats:
        out["disagg"] = disagg_stats
    if responses:
        out["models"] = [
            {
                k: v
                for k, v in (
                    ("model", r.model),
                    ("tokens", getattr(r, "tokens", None)),
                    ("tokens_per_sec", getattr(r, "tokens_per_sec", None)),
                    ("mfu", getattr(r, "mfu", None)),
                    ("mbu", getattr(r, "mbu", None)),
                    ("latency_ms", getattr(r, "latency_ms", None)),
                )
                if v is not None
            }
            for r in responses
        ]
    if live:
        out["live"] = live
    if attrib:
        out["attrib"] = attrib
    if fault_trace:
        out["faults"] = list(fault_trace)
    if degraded_peers:
        out["degraded_peers"] = sorted(int(p) for p in degraded_peers)
    if failed_models:
        out["failed_models"] = list(failed_models)
    if warnings:
        out["warnings"] = list(warnings)
    return out


def save_run_telemetry(
    run_dir: str,
    trace: dict,
    metrics: dict,
    warn=None,
) -> list[str]:
    """Write trace.json + metrics.json into ``run_dir`` (non-fatal on
    failure, like the other aux files — output/persist.save_aux_files)."""
    from llm_consensus_tpu.output.persist import save_file

    written = []
    for name, doc in ((TRACE_FILE, trace), (METRICS_FILE, metrics)):
        path = save_file(
            run_dir, name, json.dumps(doc, indent=2) + "\n", warn=warn
        )
        if path:
            written.append(path)
    return written


def load_trace(path: str) -> dict:
    """Parse a persisted trace (CI / tests gate on span presence)."""
    with open(path, "r", encoding="utf-8") as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or not isinstance(
        doc.get("traceEvents"), list
    ):
        raise ValueError(f"{os.path.basename(path)} is not a trace document")
    return doc


def trace_span_names(doc: dict) -> set[str]:
    return {
        e["name"] for e in doc["traceEvents"]
        if isinstance(e, dict) and e.get("ph") == "X"
    }
