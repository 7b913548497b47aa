"""The panel side of a served run's ``timings`` and the spans and counters
of the stretches that had none (ISSUE 37): ``timings.panel`` from the
panel workers' clock reads and their streams' marks, ``judge.prepare``,
``run.persist``, ``reply.close``, /statsz ``serve.reply_tail_*``, and what
holds the scheduler thread inside a one-row ``pool.admit``.

As in tests/test_spans.py, everything asserts on what the pool or gateway
UNDER TEST emitted (its ``tid``, its ``trace``), never on a process-wide
plane being empty.
"""

import glob
import http.client
import json
import os
import time

import pytest

from llm_consensus_tpu import obs, serve
from llm_consensus_tpu.obs import blackbox as bb_mod
from llm_consensus_tpu.obs import profiler as prof_mod
from llm_consensus_tpu.obs.blackbox import FlightRecorder
from llm_consensus_tpu.obs.profiler import DeepProfiler
from llm_consensus_tpu.providers import Registry
from llm_consensus_tpu.serve.scheduler import panel_timings, run_timings

MS = 1_000_000
STRETCHES = ("queue_ms", "prefill_ms", "decode_ms")


def _worker(model, t0, t1, **marks):
    return {"model": model, "t0_ns": t0 * MS, "t1_ns": t1 * MS,
            "marks": {k: v * MS if k.endswith("_ns") else v
                      for k, v in marks.items()} or None}


def _pooled(model, t0, t1, admit, first, last, steps=(4, 20)):
    return _worker(
        model, t0, t1, admit_ns=admit, first_token_ns=first,
        last_token_ns=last, first_step=steps[0], last_step=steps[1],
        tokens=17, prompt_tokens=40)


# The run starts at 10 ms and the judge's worker at 100 ms.
RUN, JUDGE = 10 * MS, 100 * MS


def test_panel_entries_are_in_panel_order_and_name_the_gate():
    t = panel_timings(RUN, JUDGE, [
        _pooled("a", 11, 60, 15, 30, 58),
        _pooled("b", 12, 90, 20, 50, 89, steps=(8, 40)),
        _pooled("c", 13, 70, 14, 25, 69),
    ])
    assert [e["model"] for e in t["panel"]] == ["a", "b", "c"]
    assert t["panel_gate"] == "b"
    assert t["panel_skew_ms"] == 30.0 and t["judge_prepare_ms"] == 10.0
    b = t["panel"][1]
    assert [b[k] for k in STRETCHES] == [8.0, 30.0, 39.0]
    assert (b["lead_in_ms"], b["wall_ms"]) == (2.0, 78.0)
    assert (b["tokens"], b["prompt_tokens"], b["decode_steps"]) == (17, 40, 32)


@pytest.mark.parametrize("gate", [
    _pooled("g", 12, 90, 20, 50, 89),
    _pooled("g", 12, 90, 5, 50, 95),     # admitted early, last token late
    _pooled("g", 12, 90, 60, 40, 30),    # marks out of order
    _worker("g", 12, 90),                # no marks at all
], ids=["in-order", "clamped-to-the-wall", "out-of-order", "walls-only"])
def test_the_gates_stretches_and_the_prepare_are_panel_ms(gate):
    marks = {"admit_ns": 101 * MS, "first_token_ns": 110 * MS,
             "prompt_tokens": 9, "tokens": 3}
    t = run_timings(0, RUN, JUDGE, 150 * MS, marks,
                    [_pooled("a", 11, 60, 15, 30, 58), gate])
    g = t["panel"][1]
    assert t["panel_gate"] == "g"
    assert g["lead_in_ms"] + g["wall_ms"] + t["judge_prepare_ms"] == \
        pytest.approx(t["panel_ms"])
    if "queue_ms" in g:
        assert all(g[k] >= 0 for k in STRETCHES)
        assert sum(g[k] for k in STRETCHES) <= g["wall_ms"]
    # the six stretches and their sum are what they were
    six = ("queue_ms", "panel_ms", "judge_queue_ms", "judge_prefill_ms",
           "judge_first_chunk_ms", "judge_decode_ms")
    assert sum(t[k] for k in six) == pytest.approx(t["total_ms"]) == 150.0


def test_a_panelist_without_marks_is_an_entry_with_its_wall_only():
    t = panel_timings(RUN, JUDGE, [
        _pooled("pooled", 11, 60, 15, 30, 58),
        _worker("remote", 11, 80),
        _worker("half", 11, 70, admit_ns=12),  # no first token: failed early
        None,                                   # abandoned by the watchdog
    ])
    assert [e["model"] for e in t["panel"]] == ["pooled", "remote", "half"]
    for entry in t["panel"][1:]:
        assert set(entry) == {"model", "lead_in_ms", "wall_ms"}
    assert t["panel_gate"] == "remote"
    assert t["panel"][1]["wall_ms"] == 69.0


def test_a_stream_that_counted_no_steps_has_no_decode_steps():
    """A speculative pool lands rounds, not steps: the entry says so by
    leaving the count out, and a reader divides by nothing."""
    w = _pooled("spec", 11, 60, 15, 30, 58)
    del w["marks"]["first_step"]
    entry = panel_timings(RUN, JUDGE, [w])["panel"][0]
    assert "decode_steps" not in entry and entry["decode_ms"] == 28.0


def test_no_panel_clock_reads_no_panel_keys():
    assert panel_timings(RUN, JUDGE, []) == {}
    assert panel_timings(RUN, JUDGE, [None, None]) == {}
    marks = {"admit_ns": 101 * MS, "first_token_ns": 110 * MS}
    t = run_timings(0, RUN, JUDGE, 150 * MS, marks)
    assert "panel" not in t and t["panel_ms"] == 90.0


def test_a_judge_that_started_before_the_last_answer_prepares_in_no_time():
    """An overlapped judge: its worker may start before the panel ends."""
    t = panel_timings(RUN, 50 * MS, [_pooled("a", 11, 60, 15, 30, 58)])
    assert t["judge_prepare_ms"] == 0.0


# -- one served run, asked from every side ----------------------------------------


def _http(port: int, method: str, path: str, body=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        conn.request(method, path, None if body is None else json.dumps(body),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        data = r.read()
    finally:
        conn.close()
    return r.status, json.loads(data)


def _wait_for_span(ring, name: str, trace, timeout_s: float = 60.0) -> None:
    """Until the flight ring holds the ended span ``name`` of ``trace``."""
    deadline = time.monotonic() + timeout_s
    while not any(e.name == name and e.args.get("trace") == trace
                  for e in ring.snapshot()):
        assert time.monotonic() < deadline, f"{name} of {trace} never ended"
        time.sleep(0.002)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Two tiny panelists and a judge that is not a panelist, through the
    gateway, twice: once to compile, once inside a profiler window. What
    the second run left: its document, the flight ring's events, /statsz
    at both ends and the names in the window's trace."""
    from jax.profiler import ProfileData
    from llm_consensus_tpu.providers.tpu import TPUProvider

    tmp = str(tmp_path_factory.mktemp("served"))
    for mod in (obs, bb_mod, prof_mod):
        mod.reset()
    ring = FlightRecorder(capacity=8192)
    bb_mod.install(ring)
    prof = DeepProfiler(out_dir=os.path.join(tmp, "profiles"), max_s=60.0,
                        min_interval_s=0.0)
    prof_mod.install(prof)
    prov = TPUProvider(ignore_eos=True, stream_interval=4, batch_streams=4)
    panel, judge = ["tpu:tiny-llama", "tpu:tiny-qwen2"], "tpu:tiny-mistral"
    registry = Registry()
    for m in panel + [judge]:
        registry.register(m, prov)
    gw = serve.build_gateway(
        registry, panel, judge, timeout=300.0, max_concurrency=2,
        max_tokens=12, data_dir=os.path.join(tmp, "data"),
    )
    gw.start()
    try:
        _, port = gw.address
        status, _ = _http(port, "POST", "/v1/consensus",
                          {"prompt": "warm every program"})
        assert status == 200
        _, before = _http(port, "GET", "/statsz")
        path, armed = prof.arm(60.0, tag="panel")
        assert armed == "armed"
        status, doc = _http(port, "POST", "/v1/consensus",
                            {"prompt": "which panelist gates a run?"})
        # The client has the whole reply while the request thread is still
        # inside ``reply.close``: the window may close only once that span
        # has ended (its ring event is written after its annotation).
        _wait_for_span(ring, "reply.close", doc.get("trace_id"))
        assert prof.stop_now() == path
        _, after = _http(port, "GET", "/statsz")
        again, cached = _http(port, "POST", "/v1/consensus",
                              {"prompt": "which panelist gates a run?"})
        assert again == 200 and cached.get("cached")
        _, after_cached = _http(port, "GET", "/statsz")
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request("GET", "/metricsz")
        metrics = conn.getresponse().read().decode()
        conn.close()
    finally:
        gw.close(drain=False, timeout=10.0)
        prov.release()
        for mod in (obs, bb_mod, prof_mod):
            mod.reset()
    assert status == 200, doc
    names = set()
    for trace in glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                           recursive=True):
        for plane in ProfileData.from_file(trace).planes:
            for line in plane.lines:
                names.update(ev.name for ev in line.events)
    events = ring.snapshot()
    return {
        "doc": doc, "panel": panel, "judge": judge, "before": before,
        "after": after, "after_cached": after_cached, "metrics": metrics,
        "names": names,
        "events": events, "mine": [
            e for e in events if e.args.get("trace") == doc["trace_id"]],
    }


def test_a_served_result_carries_the_panel_side(served):
    t = served["doc"]["timings"]
    assert [e["model"] for e in t["panel"]] == served["panel"]
    assert t["panel_gate"] in served["panel"]
    assert t["panel_skew_ms"] >= 0 and t["judge_prepare_ms"] >= 0
    for e in t["panel"]:
        assert all(e[k] >= 0 for k in STRETCHES)
        assert sum(e[k] for k in STRETCHES) <= e["wall_ms"] + 1e-6
        assert e["tokens"] == 12
        # the first token and the chunks of four: 12 tokens are 1 + 11, and
        # the first of the three chunks lands with the first token
        assert e["decode_steps"] == 8 and e["prompt_tokens"] > 0


def test_the_gates_account_sums_to_panel_ms_on_the_spans_clock(served):
    t = served["doc"]["timings"]
    gate = next(e for e in t["panel"] if e["model"] == t["panel_gate"])
    assert gate["lead_in_ms"] + gate["wall_ms"] + t["judge_prepare_ms"] == \
        pytest.approx(t["panel_ms"], abs=1e-6)
    workers = {e.args["model"]: e for e in served["mine"]
               if e.name == "worker" and e.args["role"] == "panel"}
    assert set(workers) == set(served["panel"])
    last = max(e.ts_ns + e.dur_ns for e in workers.values())
    assert workers[t["panel_gate"]].ts_ns + workers[
        t["panel_gate"]].dur_ns == last
    for e in t["panel"]:
        assert e["wall_ms"] == pytest.approx(workers[e["model"]].dur_ns / 1e6)
    judge_worker, = [e for e in served["mine"]
                     if e.name == "worker" and e.args["role"] == "judge"]
    assert t["judge_prepare_ms"] == pytest.approx(
        (judge_worker.ts_ns - last) / 1e6)


def test_a_panelists_queue_ends_where_its_pool_admitted_it(served):
    t = served["doc"]["timings"]
    trace = served["doc"]["trace_id"]
    for e in t["panel"]:
        model = e["model"].split(":", 1)[1]
        worker, = [w for w in served["mine"] if w.name == "worker"
                   and w.args["model"] == e["model"]]
        admit = min(a.ts_ns for a in served["events"]
                    if a.name == "pool.admit" and a.args["model"] == model
                    and trace in a.args.get("traces", []))
        assert e["queue_ms"] == pytest.approx((admit - worker.ts_ns) / 1e6)


def test_the_three_new_spans_sit_where_the_stretches_are(served):
    by_name = {}
    for e in served["mine"]:
        by_name.setdefault(e.name, []).append(e)
    prepare, = by_name["judge.prepare"]
    persist, = by_name["run.persist"]
    close, = by_name["reply.close"]
    run, = by_name["consensus_run"]
    request, = by_name["request"]
    judge_worker, = [e for e in by_name["worker"]
                     if e.args["role"] == "judge"]
    assert (prepare.tid, persist.tid, close.tid) == (
        "runner", "serve", "serve")
    # inside the run, between the last answer and the judge's worker
    assert run.ts_ns <= prepare.ts_ns
    assert prepare.ts_ns + prepare.dur_ns <= judge_worker.ts_ns
    # after the run's end, inside the request, in this order
    run_end = run.ts_ns + run.dur_ns
    assert run_end <= persist.ts_ns
    assert persist.ts_ns + persist.dur_ns <= close.ts_ns
    assert close.ts_ns + close.dur_ns <= request.ts_ns + request.dur_ns
    assert persist.args["run_id"] == close.args["run_id"] == \
        served["doc"]["run_id"]
    assert persist.args["bytes"] > len(served["doc"]["consensus"])


def test_reply_tail_counts_one_a_reply_from_the_runs_end(served):
    before, after = served["before"]["serve"], served["after"]["serve"]
    assert after["reply_tails"] - before["reply_tails"] == 1
    run, = [e for e in served["mine"] if e.name == "consensus_run"]
    request, = [e for e in served["mine"] if e.name == "request"]
    tail = (request.ts_ns + request.dur_ns - run.ts_ns - run.dur_ns) / 1e9
    assert after["reply_tail_s"] - before["reply_tail_s"] == pytest.approx(
        tail, abs=2e-6)
    # /metricsz: gauges of the one family the blocks share, no family more
    assert 'llmc_stat{block="serve",key="reply_tails"}' in served["metrics"]
    assert "reply_tail" not in served["metrics"].replace(
        'llmc_stat{block="serve",key="reply_tail', "")


def test_a_reply_that_executed_nothing_has_no_tail(served):
    """Served from the result cache: no run ended, so there is nothing to
    count a tail from."""
    assert served["after_cached"]["serve"] == served["after"]["serve"]


def test_a_profiler_window_holds_the_request_threads_spans(served):
    assert {"llmc.judge.prepare", "llmc.run.persist", "llmc.reply.close",
            "llmc.worker"} <= served["names"]


# -- inside a one-row pool.admit ---------------------------------------------------


def test_a_one_row_admission_says_what_held_the_scheduler_thread():
    """A prompt longer than the prefill chunk goes row by row: its
    ``pool.admit`` span carries the allocation, the dispatch and the
    splice, which sum to no more than the span and into the counters."""
    from llm_consensus_tpu.engine import (
        ContinuousBatcher, Engine, SamplingParams)
    from llm_consensus_tpu.models import get_config

    for mod in (obs, bb_mod):
        mod.reset()
    ring = FlightRecorder(capacity=1024)
    bb_mod.install(ring)
    try:
        engine = Engine(get_config("tiny-llama"), stream_interval=4,
                        prefill_chunk=16)
        pool = ContinuousBatcher(engine, max_batch=2)
        try:
            sampling = SamplingParams(max_new_tokens=5, ignore_eos=True)
            long_prompt = "a prompt of more than sixteen tokens " * 2
            pool.submit(long_prompt, sampling).result(timeout=300)
            pool.submit("short", sampling).result(timeout=300)
            st = pool.snapshot()
        finally:
            pool.close()
        admits = [e for e in ring.snapshot()
                  if e.name == "pool.admit" and e.tid == "pool:tiny-llama"]
    finally:
        for mod in (obs, bb_mod):
            mod.reset()
    single, = [e for e in admits if e.args["route"] == "single"]
    rows, = [e for e in admits if e.args["route"] == "rows"]
    parts = [single.args[k] for k in ("alloc_ms", "dispatch_ms", "splice_ms")]
    assert all(p >= 0 for p in parts)
    assert 0 < sum(parts) <= single.dur_ns / 1e6
    assert "alloc_ms" not in rows.args
    assert st["prefill_waves"] == 2 and st["admit_single_dispatches"] == 1
    assert st["admit_alloc_s"] == pytest.approx(parts[0] / 1e3)
    assert st["admit_dispatch_s"] == pytest.approx(parts[1] / 1e3)
    assert st["admit_splice_s"] == pytest.approx(parts[2] / 1e3)
    assert st["admit_alloc_s"] + st["admit_dispatch_s"] + \
        st["admit_splice_s"] <= st["admit_s"]
