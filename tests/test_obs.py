"""Run telemetry (llm_consensus_tpu/obs/): recorder semantics, Chrome
trace export, multihost merge, and the zero-overhead-when-disabled
contract.

The recorder follows the faults-package binding pattern (resolve once,
bind at construction), so these tests install/reset the process recorder
explicitly and verify that consumers built while telemetry is OFF never
touch a recorder installed later — the whole cost of a disabled run is
the bound None-check.
"""

from __future__ import annotations

import json
import threading

import pytest

from llm_consensus_tpu import faults, obs
from llm_consensus_tpu.obs import export as obs_export
from llm_consensus_tpu.obs.multihost import merge_timelines


@pytest.fixture(autouse=True)
def _clean_obs_state():
    """Process-wide recorder/fault state must never leak across tests."""
    obs.reset()
    faults.reset()
    yield
    obs.reset()
    faults.reset()
    from llm_consensus_tpu.parallel import multicontroller as mc

    mc.reset_degraded()


# -- recorder ----------------------------------------------------------------


def test_recorder_concurrent_writers_lose_nothing():
    """N threads × M events each: every event and counter increment lands,
    and each thread's own events keep their program order (appends happen
    under one lock; the per-thread subsequence is the thread's call
    order)."""
    rec = obs.Recorder()
    n_threads, n_events = 8, 200

    def writer(tid: int) -> None:
        for i in range(n_events):
            t0 = rec.now()
            rec.complete(f"span-{tid}", t0, tid=f"w{tid}", i=i)
            rec.count("total")
            rec.count(f"per-{tid}")

    threads = [
        threading.Thread(target=writer, args=(t,)) for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    events = rec.events()
    assert len(events) == n_threads * n_events
    counters = rec.counters()
    assert counters["total"] == n_threads * n_events
    for t in range(n_threads):
        mine = [e for e in events if e.tid == f"w{t}"]
        assert [e.args["i"] for e in mine] == list(range(n_events))
        assert counters[f"per-{t}"] == n_events
    assert rec.dropped == 0


def test_recorder_bounds_memory_and_counts_drops():
    rec = obs.Recorder(max_events=10)
    for i in range(25):
        rec.instant("e", tid="t", i=i)
    # 10 recorded + the ONE-TIME events_dropped warning instant (one
    # event past the cap, so a truncated timeline says so on its face).
    events = rec.events()
    assert len(events) == 11
    warnings_ = [e for e in events if e.name == "events_dropped"]
    assert len(warnings_) == 1 and warnings_[0].tid == "obs"
    assert rec.dropped == 15
    # Drop accounting surfaces as a counter too (metrics.json/metricsz).
    assert rec.counters()["obs.dropped_events"] == 15
    rec.clear()
    rec.instant("e", tid="t")
    assert rec.dropped == 0 and len(rec.events()) == 1


def test_span_context_manager_records_on_exception():
    rec = obs.Recorder()
    with pytest.raises(ValueError):
        with rec.span("doomed", tid="t"):
            raise ValueError("boom")
    assert rec.span_names() == {"doomed"}


# -- Chrome trace export -----------------------------------------------------


def test_chrome_trace_export_golden():
    """The exported document is valid trace-event JSON: metadata names the
    process and every subsystem row, spans carry ``dur``, instants carry a
    scope, and the timeline is rebased to zero."""
    rec = obs.Recorder()
    t0 = rec.now()
    rec.complete("prefill", t0, tid="engine", tokens=7)
    rec.complete("decode", rec.now(), tid="batcher", steps=4)
    rec.instant("fault:decode_fault", tid="faults", site="decode")

    doc = obs_export.local_trace(rec, pid=3)
    # Round-trips as JSON (what Perfetto loads).
    doc = json.loads(json.dumps(doc))
    events = doc["traceEvents"]
    assert isinstance(events, list)

    meta = [e for e in events if e["ph"] == "M"]
    assert {"process_name"} == {
        e["name"] for e in meta if e["tid"] == 0
    }
    thread_names = {
        e["args"]["name"] for e in meta if e["name"] == "thread_name"
    }
    assert thread_names == {"engine", "batcher", "faults"}

    spans = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in spans} == {"prefill", "decode"}
    assert all(e["pid"] == 3 and e["dur"] >= 0 for e in spans)
    assert obs_export.trace_span_names(doc) == {"prefill", "decode"}

    instants = [e for e in events if e["ph"] == "i"]
    assert instants[0]["s"] == "t"
    assert instants[0]["args"]["site"] == "decode"

    # Rebased: the earliest event sits at ts == 0.
    assert min(e["ts"] for e in spans + instants) == 0.0


def test_metrics_summary_aggregates_counters():
    rec = obs.Recorder()
    rec.count("decode_tokens", 100)
    rec.count("decode_s", 4.0)
    rec.count("mfu_weighted_tokens", 100 * 0.5)
    rec.count("mfu_tokens", 100)
    m = obs_export.metrics_summary(
        rec, batcher_stats={"tiny": {"decode_tokens": 100}},
        fault_trace=["decode#1[]->-"], failed_models=["m"],
    )
    # No decode spans recorded → falls back to the summed decode walls.
    assert m["aggregate"]["tokens_per_sec"] == pytest.approx(25.0)
    assert m["aggregate"]["mfu"] == pytest.approx(0.5)
    assert m["batchers"]["tiny"]["decode_tokens"] == 100
    assert m["faults"] == ["decode#1[]->-"]
    assert m["failed_models"] == ["m"]
    json.dumps(m)


def test_aggregate_throughput_uses_union_window_not_summed_walls():
    """Concurrent streams overlap their decode windows: the pool rate
    divides by the union window spanned by the decode/fetch spans, not
    the sum of per-stream walls (which would understate the pool by the
    concurrency factor)."""
    from llm_consensus_tpu.obs.recorder import Event

    rec = obs.Recorder()
    # Four streams, each "100 tokens in 2s", all in the SAME 2s window.
    base = rec.now()
    for _ in range(4):
        rec.count("decode_tokens", 100)
        rec.count("decode_s", 2.0)
    rec._events.append(Event(
        name="decode", ph="X", ts_ns=base, tid="batcher",
        dur_ns=1_000_000_000,
    ))
    rec._events.append(Event(
        name="fetch", ph="X", ts_ns=base + 1_000_000_000, tid="batcher",
        dur_ns=1_000_000_000,
    ))
    agg = obs_export.aggregate_throughput(rec)
    # 400 tokens over the 2s union window = 200 tok/s; the summed-wall
    # form would report 400/8 = 50.
    assert agg["tokens_per_sec"] == pytest.approx(200.0)
    assert agg["window_s"] == pytest.approx(2.0)


def test_aggregate_mfu_ignores_mfu_less_tokens():
    """A model whose chip reports no MFU contributes tokens to the pool
    rate but must not dilute the MFU mean."""
    rec = obs.Recorder()
    rec.count("decode_tokens", 100)      # model A: mfu 0.5
    rec.count("mfu_weighted_tokens", 50)
    rec.count("mfu_tokens", 100)
    rec.count("decode_tokens", 100)      # model B: no known peak
    rec.count("decode_s", 4.0)
    agg = obs_export.aggregate_throughput(rec)
    assert agg["mfu"] == pytest.approx(0.5)


def test_recorder_clear_empties_in_place():
    rec = obs.Recorder(max_events=1)
    rec.instant("a", tid="t")
    rec.instant("b", tid="t")  # dropped (cap 1)
    rec.count("c", 2.0)
    assert rec.dropped == 1
    rec.clear()
    assert rec.events() == [] and rec.counters() == {} and rec.dropped == 0
    rec.instant("d", tid="t")
    assert len(rec.events()) == 1


# -- multihost merge ---------------------------------------------------------


@pytest.mark.faults
def test_multihost_merge_with_degraded_peer():
    """A controller that never reaches the timeline exchange costs its
    timeline, not the merge: the survivors' events still produce a
    loadable trace and the missing peer is reported."""
    faults.install(faults.FaultPlan("controller_drop@host=1", seed=5))
    from llm_consensus_tpu.parallel import multicontroller as mc

    mc.reset_degraded()
    rec = obs.Recorder()
    obs.install(rec)
    rec.complete("prefill", rec.now(), tid="engine")

    doc, missing = merge_timelines(rec, timeout=2.0)
    assert missing == [1]
    assert mc.degraded_peers() == frozenset({1})
    # Survivor-only merge: every real event belongs to process 0 and the
    # local spans survive.
    pids = {e["pid"] for e in doc["traceEvents"]}
    assert pids == {0}
    assert "prefill" in obs_export.trace_span_names(doc)
    # The exchange itself still recorded its allgather span (it lands
    # after the snapshot the merge shipped, so on the recorder, not in
    # this doc).
    assert "allgather" in rec.span_names()
    json.dumps(doc)


def test_multihost_merge_single_process_is_local_identity():
    rec = obs.Recorder()
    obs.install(rec)
    rec.complete("decode", rec.now(), tid="engine")
    doc, missing = merge_timelines(rec, timeout=2.0)
    assert missing == []
    assert obs_export.trace_span_names(doc) == {"decode"}
    # The exchange recorded its own span after snapshotting the events.
    assert "allgather" in rec.span_names()


# -- zero overhead when disabled ---------------------------------------------


def _emitted_by(recorder, *tids):
    """What the engine / pool UNDER TEST wrote, by its rows. The recorder
    is process-wide, and planes that resolve it per call write to whatever
    is installed — the retrace sentinel of an attribution ledger an
    earlier file left installed (``retrace``, tid ``attrib``: a new Engine
    compiles), the fault plan (tid ``faults``) — so "the recorder is
    empty" is not this test's to assert under xdist."""
    return [e for e in recorder.events() if e.tid in tids]


def test_engine_hot_loops_consult_only_bound_none(monkeypatch):
    """An engine built with telemetry off binds its sinks ONCE; a recorder
    installed afterwards must see nothing from its decode/fetch loops —
    the disabled hot path touches no recorder state."""
    monkeypatch.delenv("LLMC_EVENTS", raising=False)
    obs.reset()
    from llm_consensus_tpu.engine import Engine, SamplingParams
    from llm_consensus_tpu.models import get_config

    engine = Engine(get_config("tiny-llama"), stream_interval=4)
    assert engine._obs is None and engine._spans._recorder is None
    late = obs.Recorder()
    obs.install(late)
    out = engine.generate(
        "quiet run", SamplingParams(max_new_tokens=12, ignore_eos=True)
    )
    assert len(out.token_ids) == 12
    assert _emitted_by(late, "engine", "pool:tiny-llama") == []
    assert not any(
        e.args.get("model") == "tiny-llama" for e in late.events()
    )


def test_batcher_binds_recorder_at_construction(monkeypatch):
    monkeypatch.delenv("LLMC_EVENTS", raising=False)
    obs.reset()
    from llm_consensus_tpu.engine import ContinuousBatcher, Engine, SamplingParams
    from llm_consensus_tpu.models import get_config

    engine = Engine(get_config("tiny-llama"), stream_interval=4)
    batcher = ContinuousBatcher(engine, max_batch=2)
    try:
        assert batcher._obs is None and batcher._spans._recorder is None
        late = obs.Recorder()
        obs.install(late)
        fut = batcher.submit(
            "quiet pool", SamplingParams(max_new_tokens=8, ignore_eos=True)
        )
        assert len(fut.result(timeout=120).token_ids) == 8
        assert _emitted_by(late, "engine", "pool:tiny-llama") == []
    finally:
        batcher.close()


def test_enabled_engine_records_required_spans():
    rec = obs.Recorder()
    obs.install(rec)
    from llm_consensus_tpu.engine import Engine, SamplingParams
    from llm_consensus_tpu.models import get_config

    engine = Engine(get_config("tiny-llama"), stream_interval=4)
    engine.generate(
        "loud run", SamplingParams(max_new_tokens=12, ignore_eos=True)
    )
    assert {"prefill", "decode", "fetch"} <= rec.span_names()


def test_enabled_batcher_records_admit_and_decode_spans():
    rec = obs.Recorder()
    obs.install(rec)
    from llm_consensus_tpu.engine import ContinuousBatcher, Engine, SamplingParams
    from llm_consensus_tpu.models import get_config

    engine = Engine(get_config("tiny-llama"), stream_interval=4)
    batcher = ContinuousBatcher(engine, max_batch=2)
    try:
        fut = batcher.submit(
            "loud pool", SamplingParams(max_new_tokens=8, ignore_eos=True)
        )
        assert len(fut.result(timeout=120).token_ids) == 8
    finally:
        batcher.close()
    pool_spans = {
        e.name for e in rec.events()
        if e.ph == "X" and e.tid == "pool:tiny-llama"
    }
    assert {
        "pool.admit", "pool.decode", "pool.fetch", "pool.emit"
    } <= pool_spans
    assert all(
        e.args["model"] == "tiny-llama" for e in rec.events()
        if e.tid == "pool:tiny-llama"
    )
    snap = batcher.snapshot()
    assert isinstance(snap, dict) and "decode_tokens" in snap


@pytest.mark.faults
def test_fault_fire_lands_instant_on_timeline():
    rec = obs.Recorder()
    obs.install(rec)
    plan = faults.FaultPlan("decode_fault@step=2", seed=1)
    assert plan.fire("decode") is None
    assert plan.fire("decode") is not None
    instants = [e for e in rec.events() if e.ph == "i"]
    assert [e.name for e in instants] == ["fault:decode_fault"]
    assert instants[0].args["site"] == "decode"
    assert instants[0].args["n"] == 2
