"""The one span emitter, the named device programs, the dispatch-site
counters and the served result's ``timings`` (ISSUE 23).

Everything here asserts on what the engine, pool or gateway UNDER TEST
emitted (by ``tid`` / ``model`` / its own sinks), never on a process-wide
plane being empty: other pools alive in the same xdist worker write too.
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
from llm_consensus_tpu.obs.spans import Emitter
from llm_consensus_tpu.providers import Registry
from llm_consensus_tpu.serve.scheduler import run_timings


@pytest.fixture(autouse=True)
def _clean_planes():
    for mod in (obs, bb_mod, prof_mod):
        mod.reset()
    yield
    for mod in (obs, bb_mod, prof_mod):
        mod.reset()


# -- the emitter ---------------------------------------------------------------


@pytest.mark.parametrize("recorder_on,ring_on", [
    (True, True), (True, False), (False, True), (False, False),
])
def test_emitter_writes_one_event_to_each_sink_that_is_on(recorder_on, ring_on):
    rec = obs.Recorder() if recorder_on else None
    ring = FlightRecorder(capacity=64) if ring_on else None
    em = Emitter(rec, ring)
    with em.span("pool.decode", "pool:m", model="m", steps=4) as sp:
        sp.set(pos=9)
    t1 = em.complete("request", sp.t0_ns, "serve", trace="abc")
    em.instant("preempt", "pool:m", slot=1)
    for sink in (rec.events() if rec else None,
                 ring.snapshot() if ring else None):
        if sink is None:
            continue
        assert [(e.name, e.ph, e.tid) for e in sink] == [
            ("pool.decode", "X", "pool:m"), ("request", "X", "serve"),
            ("preempt", "i", "pool:m"),
        ]
        assert sink[0].args == {"model": "m", "steps": 4, "pos": 9}
        assert sink[0].ts_ns == sp.t0_ns
        assert sink[0].dur_ns == sp.t1_ns - sp.t0_ns
        assert sink[1].ts_ns + sink[1].dur_ns == t1


def test_emitter_touches_only_the_ring_when_recorder_and_profiler_are_off(
        monkeypatch):
    """No recorder, no window: a span is the ring append and nothing else
    — no TraceAnnotation is built (jax.profiler is not even consulted)."""
    import jax

    def boom(*a, **k):
        raise AssertionError("annotation built outside a profiler window")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    prof = DeepProfiler(out_dir="unused", max_s=1.0, min_interval_s=0.0)
    prof_mod.install(prof)
    ring = FlightRecorder(capacity=16)
    bb_mod.install(ring)
    obs.install(None)
    em = obs.emitter()
    assert em._window is prof.window and not prof.window.open
    with em.span("pool.fetch", "pool:m", model="m"):
        pass
    assert [e.name for e in ring.snapshot()] == ["pool.fetch"]
    # The flag is the installed profiler's: open it and the annotation IS
    # built (here: the patched one raises inside _annotation, which
    # telemetry swallows — the span still lands in the ring).
    prof.window.open = True
    with em.span("pool.fetch", "pool:m", model="m"):
        pass
    assert len(ring.snapshot()) == 2


# -- pools: spans, counters, named programs --------------------------------------


def _pool(rows: int, stream_interval: int = 4):
    from llm_consensus_tpu.engine import ContinuousBatcher, Engine
    from llm_consensus_tpu.models import get_config

    engine = Engine(get_config("tiny-llama"), stream_interval=stream_interval)
    return ContinuousBatcher(engine, max_batch=rows)


def _sampling(n: int):
    from llm_consensus_tpu.engine import SamplingParams

    return SamplingParams(max_new_tokens=n, ignore_eos=True)


def test_lone_prompt_in_a_six_row_pool_pads_to_six_rows():
    ring = FlightRecorder(capacity=512)
    bb_mod.install(ring)
    pool = _pool(6)
    try:
        out = pool.submit("a lone prompt", _sampling(6), trace_id="t-lone")
        res = out.result(timeout=300)
        st = pool.snapshot()
    finally:
        pool.close()
    assert st["prefill_waves"] == 1
    assert st["prefill_rows_real"] == 1 and st["prefill_rows_padded"] == 6
    assert st["admit_tokens"] == res.prompt_tokens
    # one-shot bucket of 16 slots for each of the six rows
    assert st["prefill_slot_tokens"] == 6 * 16
    pad_share = 1 - st["admit_tokens"] / st["prefill_slot_tokens"]
    assert pad_share == pytest.approx(1 - res.prompt_tokens / 96)
    admits = [e for e in ring.snapshot()
              if e.name == "pool.admit" and e.tid == "pool:tiny-llama"]
    assert len(admits) == 1
    a = admits[0].args
    assert (a["rows_real"], a["rows_padded"], a["chunks"]) == (1, 6, 1)
    assert a["slot_tokens"] == st["prefill_slot_tokens"]
    assert a["tokens_real"] == res.prompt_tokens and a["traces"] == ["t-lone"]
    # the stream's marks are the spans' own clock reads
    assert res.marks["admit_ns"] == admits[0].ts_ns
    emits = [e.ts_ns for e in ring.snapshot() if e.name == "pool.emit"]
    assert res.marks["first_token_ns"] in emits


@pytest.mark.parametrize("live,rows", [(1, 4), (4, 4)])
def test_decode_row_steps_is_steps_times_live_rows(live, rows):
    ring = FlightRecorder(capacity=1024)
    bb_mod.install(ring)
    pool = _pool(rows)
    try:
        futs = [pool.submit(f"prompt number {i}", _sampling(9))
                for i in range(live)]
        for f in futs:
            assert len(f.result(timeout=300).token_ids) == 9
        st = pool.snapshot()
    finally:
        pool.close()
    assert st["decode_chunks"] >= 2 and st["decode_steps"] >= 8
    assert st["decode_row_steps"] <= st["decode_steps"] * rows
    if live == rows:
        assert st["decode_row_steps"] == st["decode_steps"] * rows
    else:
        assert st["decode_row_steps"] == st["decode_steps"] * live
    decodes = [e for e in ring.snapshot()
               if e.name == "pool.decode" and e.tid == "pool:tiny-llama"]
    assert len(decodes) == st["decode_chunks"]
    assert sum(e.args["steps"] for e in decodes) == st["decode_steps"]
    assert all(e.args["rows"] == rows and e.args["kv_width"] == 128
               for e in decodes)


def test_named_programs_add_no_compile():
    """One compile per named program, and none for a second engine of the
    same model: the wrappers are keyed by what the name says, not by the
    engine, so the compile count is what one jit per family had."""
    from llm_consensus_tpu.engine import Engine
    from llm_consensus_tpu.engine import engine as eng_mod
    from llm_consensus_tpu.models import get_config
    from llm_consensus_tpu.obs import attrib as attrib_mod

    led = attrib_mod.ChipTimeLedger()
    attrib_mod.install(led)
    try:
        first = Engine(get_config("tiny-llama"), stream_interval=4)
        first.generate("named programs", _sampling(9))
        sizes = eng_mod._decode_chunk._cache_size()
        names = {
            eng_mod._decode_chunk.name_of(k)
            for k in eng_mod._decode_chunk._programs if k[0] == "tiny-llama"
        }
        assert "decode_chunk__tiny_llama__kv128__s4" in names
        hot = lambda: {  # noqa: E731 — building an engine compiles "other"
            k: v for k, v in led.snapshot()["compiles"].items()
            if k in ("decode", "prefill")
        }
        before = hot()  # may be empty: an earlier test compiled them
        second = Engine(get_config("tiny-llama"), stream_interval=4)
        second.generate("named programs", _sampling(9))
        assert hot() == before
        assert eng_mod._decode_chunk._cache_size() == sizes
    finally:
        attrib_mod.reset()


def test_profiler_window_puts_pool_spans_and_named_programs_in_the_trace(
        tmp_path):
    """A DeepProfiler window over a tiny pool: the .xplane.pb carries
    llmc.pool.decode with its arguments on the host plane, and the decode
    program under the name that says model, width and steps."""
    from jax.profiler import ProfileData

    prof = DeepProfiler(out_dir=str(tmp_path), max_s=30.0, min_interval_s=0.0)
    prof_mod.install(prof)
    pool = _pool(2)
    try:
        pool.submit("warm the programs", _sampling(5)).result(timeout=300)
        path, status = prof.arm(20.0, tag="spans")
        assert status == "armed"
        assert prof.stats()["program_spans"] is True
        pool.submit("inside the window", _sampling(9)).result(timeout=300)
        assert prof.stop_now() == path
    finally:
        pool.close()
    traces = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    assert traces
    spans, names = [], set()
    for plane in ProfileData.from_file(traces[0]).planes:
        for line in plane.lines:
            for ev in line.events:
                names.add(ev.name)
                if ev.name == "llmc.pool.decode":
                    spans.append(dict(ev.stats))
    assert spans, sorted(n for n in names if n.startswith("llmc."))
    for args in spans:
        assert args["model"] == "tiny-llama"
        assert args["steps"] == 4 and args["kv_width"] == 128
        assert args["rows_live"] == 1 and args["rows"] == 2
    assert any("decode_chunk__tiny_llama__kv128__s4" in n for n in names)
    assert {"llmc.pool.fetch", "llmc.pool.emit", "llmc.pool.admit"} <= names


# -- timings ---------------------------------------------------------------------


def test_run_timings_stretches_share_their_boundaries():
    marks = {"admit_ns": 3_500_000, "first_token_ns": 5_000_000,
             "first_chunk_ns": 5_250_000, "prompt_tokens": 77, "tokens": 8}
    t = run_timings(0, 1_000_000, 3_000_000, 9_000_000, marks)
    parts = ("queue_ms", "panel_ms", "judge_queue_ms", "judge_prefill_ms",
             "judge_first_chunk_ms", "judge_decode_ms")
    assert [t[k] for k in parts] == [1.0, 2.0, 0.5, 1.5, 0.25, 3.75]
    assert sum(t[k] for k in parts) == t["total_ms"] == 9.0
    assert (t["judge_prompt_tokens"], t["judge_tokens"]) == (77, 8)
    # a prompt admitted before the judge worker started clamps to its start
    early = run_timings(0, 1_000_000, 3_000_000, 9_000_000,
                        dict(marks, admit_ns=2_000_000))
    assert early["judge_queue_ms"] == 0.0
    assert sum(early[k] for k in parts) == early["total_ms"]
    # no first text (an unstreamed judge): that stretch is empty
    del marks["first_chunk_ns"]
    assert run_timings(0, 1, 2, 9_000_000, marks)["judge_first_chunk_ms"] == 0.0
    assert run_timings(0, 1, 2, 3, None) is None
    assert run_timings(0, 1, 2, 3, {"prompt_tokens": 5}) is None


def _post(port: int, body: dict):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        conn.request("POST", "/v1/consensus", json.dumps(body),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        data = r.read()
    finally:
        conn.close()
    return r.status, json.loads(data)


def test_served_result_carries_timings_that_sum_to_the_total(tmp_path):
    """Two tiny panelists and a judge that is not a panelist, through the
    gateway: the six stretches sum to total_ms (self time is the run's
    span less its children), and judge_prompt_tokens is what the judge's
    pool admitted."""
    from llm_consensus_tpu.providers.tpu import TPUProvider

    ring = FlightRecorder(capacity=4096)
    bb_mod.install(ring)
    prov = TPUProvider(ignore_eos=True, stream_interval=4, batch_streams=4)
    panel, judge = ["tpu:tiny-llama", "tpu:tiny-qwen2"], "tpu:tiny-mistral"
    registry = Registry()
    for m in panel + [judge]:
        registry.register(m, prov)
    gw = serve.build_gateway(
        registry, panel, judge, timeout=300.0, max_concurrency=2,
        max_tokens=8, data_dir=os.path.join(str(tmp_path), "data"),
    )
    gw.start()
    try:
        _, port = gw.address
        before = prov.batcher_stats().get("tiny-mistral", {})
        t0 = time.monotonic()
        status, doc = _post(port, {"prompt": "what is a span?"})
        wall_ms = (time.monotonic() - t0) * 1e3
        after = prov.batcher_stats()["tiny-mistral"]
    finally:
        gw.close(drain=False, timeout=10.0)
        prov.release()
    assert status == 200, doc
    t = doc["timings"]
    assert list(doc)[:4] == ["prompt", "responses", "consensus", "judge"]
    parts = ("queue_ms", "panel_ms", "judge_queue_ms", "judge_prefill_ms",
             "judge_first_chunk_ms", "judge_decode_ms")
    assert all(t[k] >= 0 for k in parts)
    assert sum(t[k] for k in parts) == pytest.approx(t["total_ms"], rel=0.01)
    assert t["total_ms"] <= wall_ms
    assert t["judge_tokens"] == 8
    assert t["judge_prompt_tokens"] == (
        after["admit_tokens"] - before.get("admit_tokens", 0))
    # the same clock reads as the spans: the run's span less its children
    events = [e for e in ring.snapshot()
              if e.args.get("trace") == doc["trace_id"]]
    by_name = {}
    for e in events:
        by_name.setdefault(e.name, []).append(e)
    run = by_name["consensus_run"][0]
    judge_worker = [e for e in by_name["worker"] if e.args["role"] == "judge"]
    assert len(judge_worker) == 1
    assert {e.args["role"] for e in by_name["worker"]} == {"panel", "judge"}
    assert t["panel_ms"] == pytest.approx(
        (judge_worker[0].ts_ns - run.ts_ns) / 1e6)
    assert t["queue_ms"] + t["panel_ms"] + t["judge_queue_ms"] + \
        t["judge_prefill_ms"] + t["judge_first_chunk_ms"] + \
        t["judge_decode_ms"] == pytest.approx(
            (run.ts_ns + run.dur_ns - by_name["request"][0].ts_ns) / 1e6)
    assert {"queue_wait", "admit", "engine_stream"} <= set(by_name)
    admits = [e for e in ring.snapshot() if e.name == "pool.admit"
              and doc["trace_id"] in e.args.get("traces", [])
              and e.args["model"] == "tiny-mistral"]
    assert admits and t["judge_queue_ms"] == pytest.approx(
        (admits[0].ts_ns - judge_worker[0].ts_ns) / 1e6)
