"""Live observability tests: histograms, /metricsz, trace ids, blackbox.

Covers the obs/live + obs/prom + obs/blackbox plane and its serving
wiring:

  * histogram correctness — bucket boundaries (Prometheus ``le``
    inclusive-upper semantics), quantile estimates against exact values
    on known distributions (log buckets bound the relative error by the
    growth factor), bucket-wise merge associativity (the router's fleet
    aggregation relies on it), and window rotation under concurrent
    writers (no observation lost, rings bounded);
  * ``GET /metricsz`` on a gateway — Prometheus text format with
    TTFT/queue-wait/e2e histograms labeled by priority class, plus the
    /statsz blocks flattened through the stats registry;
  * the router's ``/metricsz`` equals the bucket-wise merge of its
    replicas' histograms;
  * one trace id linking router → gateway → run spans, surviving an
    injected ``replica_down`` failover, returned in the done envelope;
  * the flight recorder: bounded ring, Perfetto-loadable dumps, rate
    limiting, the governor's escalation trigger, and an injected engine
    crash producing a dump with pre-crash decode spans with events OFF.
"""

from __future__ import annotations

import http.client
import json
import os
import threading
import time

import pytest

from llm_consensus_tpu import faults, obs, serve
from llm_consensus_tpu.obs import blackbox as bb_mod
from llm_consensus_tpu.obs import export as obs_export
from llm_consensus_tpu.obs import live as live_mod
from llm_consensus_tpu.obs import prom
from llm_consensus_tpu.obs.blackbox import FlightRecorder
from llm_consensus_tpu.obs.live import (
    BUCKET_EDGES,
    Histogram,
    LiveMetrics,
    SLOWatcher,
    WindowedHistogram,
    bucket_index,
)
from llm_consensus_tpu.providers.base import Provider, Request, Response
from llm_consensus_tpu.providers.registry import Registry
from llm_consensus_tpu.utils.context import Context

PANEL = ["alpha", "beta"]
JUDGE = "gamma"


@pytest.fixture(autouse=True)
def _clean_planes(monkeypatch):
    from llm_consensus_tpu.obs import attrib as attrib_mod

    monkeypatch.delenv("LLMC_FAULTS", raising=False)
    faults.reset()
    obs.reset()
    live_mod.reset()
    bb_mod.reset()
    attrib_mod.reset()
    yield
    faults.reset()
    obs.reset()
    live_mod.reset()
    bb_mod.reset()
    attrib_mod.reset()


# ---------------------------------------------------------------------------
# histogram correctness


def test_bucket_boundaries_le_inclusive():
    # Exact upper edges land IN their bucket (Prometheus le semantics);
    # epsilon past an edge lands in the next.
    for i, edge in enumerate(BUCKET_EDGES):
        assert bucket_index(edge) == i, edge
        assert bucket_index(edge * 1.0001) == i + 1
    assert bucket_index(0.0) == 0
    assert bucket_index(-1.0) == 0
    # Past the top finite edge: the +Inf overflow bucket.
    assert bucket_index(BUCKET_EDGES[-1] * 2) == len(BUCKET_EDGES)
    h = Histogram()
    h.observe(BUCKET_EDGES[-1] * 10)
    assert h.counts[-1] == 1 and h.count == 1


def test_quantile_estimate_vs_exact_known_distributions():
    # Log buckets with growth 2 ⇒ any estimate is within one growth
    # factor of the exact sample quantile. Check on a uniform and a
    # heavy-tailed deterministic distribution.
    import random

    rng = random.Random(7)
    for samples in (
        [rng.uniform(0.001, 10.0) for _ in range(2000)],
        [0.001 * (1.5 ** (i % 25)) for i in range(2000)],
    ):
        h = Histogram()
        for v in samples:
            h.observe(v)
        s = sorted(samples)
        for q in (0.5, 0.9, 0.99):
            exact = s[min(len(s) - 1, int(q * len(s)))]
            est = h.quantile(q)
            assert est is not None
            assert exact / 2.0 <= est <= exact * 2.0, (q, exact, est)
    assert Histogram().quantile(0.5) is None


def test_merge_associative_and_commutative():
    import random

    rng = random.Random(3)

    def rand_hist():
        h = Histogram()
        for _ in range(200):
            h.observe(rng.uniform(1e-5, 500.0))
        return h

    a, b, c = rand_hist(), rand_hist(), rand_hist()

    def merged(*hs):
        out = Histogram()
        for h in hs:
            out.merge_from(h.copy())
        return out

    left = merged(merged(a, b), c)
    right = merged(a, merged(b, c))
    swapped = merged(c, a, b)
    for other in (right, swapped):
        assert left.counts == other.counts
        assert left.count == other.count
        assert abs(left.sum - other.sum) < 1e-9


def test_window_rotation_under_concurrent_writers():
    lm = LiveMetrics(window_s=60.0, windows=4)
    n_threads, n_obs = 8, 500
    stop = threading.Event()

    def rotator():
        while not stop.is_set():
            lm.rotate()
            time.sleep(0.001)

    def writer(t):
        for i in range(n_obs):
            lm.observe("ttft", 0.01 * (t + 1), outcome="ok",
                       **{"class": "normal"})

    rot = threading.Thread(target=rotator)
    rot.start()
    threads = [
        threading.Thread(target=writer, args=(t,)) for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    rot.join()
    # Rotation never loses an observation from the CUMULATIVE total.
    assert lm.counts("ttft") == n_threads * n_obs
    # Rings stay bounded at their configured depth.
    wh = next(iter(lm._hists.values()))
    assert len(wh.ring) <= 4


def test_windowed_recent_excludes_open_window():
    wh = WindowedHistogram(windows=3)
    wh.observe(1.0)
    assert wh.recent(1).count == 0  # still in the open window
    wh.rotate()
    assert wh.recent(1).count == 1
    wh.observe(2.0)
    wh.rotate()
    assert wh.recent(2).count == 2


# ---------------------------------------------------------------------------
# Prometheus render / parse / merge


def test_prom_roundtrip_and_bucketwise_merge():
    lm = LiveMetrics(window_s=60.0)
    for v, cls in ((0.01, "high"), (0.2, "normal"), (3.0, "normal")):
        lm.observe("ttft", v, outcome="ok", **{"class": cls})
    text = prom.render(
        lm, stats_blocks={"kv": {"p": {"hits": 3}}},
        gauges={"load_score": 0.25},
    )
    parsed = prom.parse_text(text)
    key = ("ttft", (("class", "normal"), ("outcome", "ok")))
    assert parsed["histograms"][key]["count"] == 2
    assert parsed["gauges"][("load_score", ())] == 0.25
    assert parsed["gauges"][
        ("stat", (("block", "kv"), ("key", "p.hits")))
    ] == 3
    # Canonical round-trip: parse(render_parsed(parse(x))) == parse(x).
    again = prom.parse_text(prom.render_parsed(parsed))
    assert again == parsed
    # Merge doubles every bucket/count/sum.
    doubled = prom.merge([parsed, parsed])
    assert doubled["histograms"][key]["count"] == 4
    for le, n in parsed["histograms"][key]["buckets"].items():
        assert doubled["histograms"][key]["buckets"][le] == 2 * n


# ---------------------------------------------------------------------------
# SLO watcher + flight recorder


def test_slo_watcher_burns_after_n_windows():
    burns = []
    w = SLOWatcher(threshold_s=0.1, windows=3, on_burn=burns.append)
    lm = LiveMetrics(window_s=60.0)
    for i in range(3):
        lm.observe("ttft", 5.0, outcome="ok", **{"class": "high"})
        lm.rotate()
        fired = w.check(lm)
        assert fired == (i == 2), i
    assert len(burns) == 1 and burns[0]["threshold_s"] == 0.1
    # A quiet window resets the streak.
    lm2 = LiveMetrics(window_s=60.0)
    w2 = SLOWatcher(threshold_s=0.1, windows=2, on_burn=burns.append)
    lm2.observe("ttft", 5.0, outcome="ok", **{"class": "high"})
    lm2.rotate()
    assert not w2.check(lm2)
    lm2.rotate()  # empty window
    assert not w2.check(lm2)
    assert len(burns) == 1
    # Disabled watcher (threshold 0) never fires.
    assert not SLOWatcher(threshold_s=0.0).check(lm)


def test_flight_recorder_ring_bound_dump_and_rate_limit(tmp_path):
    fr = FlightRecorder(
        capacity=32, out_dir=str(tmp_path), min_interval_s=3600.0
    )
    for i in range(100):
        t0 = fr.now()
        fr.complete("decode", t0, tid="batcher", i=i)
    assert fr.depth() == 32  # bounded ring: oldest evicted
    path = fr.dump("unit_test", extra={"k": 1})
    assert path is not None and os.path.exists(path)
    doc = obs_export.load_trace(path)  # Perfetto-loadable trace document
    assert "decode" in obs_export.trace_span_names(doc)
    assert doc["blackbox"]["reason"] == "unit_test"
    assert doc["blackbox"]["k"] == 1
    # Rate limit: a second dump inside the interval is suppressed.
    assert fr.dump("again") is None
    assert fr.suppressed == 1
    assert fr.dump("forced", force=True) is not None
    # An empty ring never writes.
    fr.clear()
    assert fr.dump("empty", force=True) is None


def test_governor_escalation_past_preempt_dumps_blackbox(tmp_path):
    from llm_consensus_tpu.pressure import PressureGovernor

    bb_mod.install(FlightRecorder(
        capacity=64, out_dir=str(tmp_path), min_interval_s=0.0
    ))
    gov = PressureGovernor(
        high_water=0.8, low_water=0.2, up_patience=1, down_patience=100,
    )
    # Walk ok → evict → preempt → brownout: the brownout escalation is
    # PAST preempt, so it must snapshot the flight recorder.
    for _ in range(3):
        gov.observe(1.0)
    assert gov.state == "brownout"
    fr = bb_mod.ring()
    assert fr.dumps >= 1 and fr.last_reason == "pressure_brownout"
    doc = obs_export.load_trace(fr.last_path)
    names = {
        e.get("name") for e in doc["traceEvents"] if isinstance(e, dict)
    }
    assert "pressure_escalate" in names


# ---------------------------------------------------------------------------
# stats registry


def test_stats_registry_contract():
    from llm_consensus_tpu.serve.stats import StatsRegistry

    reg = StatsRegistry()
    reg.register("good", lambda: {"x": 1})
    reg.register("empty", lambda: {})
    reg.register("none", lambda: None)
    reg.register("boom", lambda: 1 / 0)
    out = reg.collect()
    assert out == {"good": {"x": 1}}
    assert reg.names() == ["good", "empty", "none", "boom"]
    reg.register("good", lambda: {"x": 2})  # replace, not duplicate
    assert reg.collect() == {"good": {"x": 2}}


# ---------------------------------------------------------------------------
# gateway /metricsz + trace ids over real HTTP (fake providers)


class FakeProvider(Provider):
    def query(self, ctx: Context, req: Request) -> Response:
        ctx.raise_if_done()
        return Response(
            model=req.model,
            content=f"{req.model} answers {req.prompt[:16]}",
            provider="fake",
        )

    def query_stream(self, ctx, req, callback):
        resp = self.query(ctx, req)
        if callback is not None:
            for i in range(0, len(resp.content), 8):
                callback(resp.content[i:i + 8])
        return resp


def make_gateway(tmp_path, name="gw", live=None, **kw):
    provider = FakeProvider()
    registry = Registry()
    for m in PANEL + [JUDGE]:
        registry.register(m, provider)
    kw.setdefault("timeout", 30.0)
    kw.setdefault("max_concurrency", 4)
    gw = serve.build_gateway(
        registry, list(PANEL), JUDGE,
        data_dir=os.path.join(str(tmp_path), "data", name),
        live=live if live is not None else LiveMetrics(window_s=60.0),
        **kw,
    )
    gw.start()
    return gw


def post(port: int, body: dict, headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        hdrs = {"Content-Type": "application/json"}
        hdrs.update(headers or {})
        conn.request("POST", "/v1/consensus", json.dumps(body), hdrs)
        r = conn.getresponse()
        data = r.read()
    finally:
        conn.close()
    return r.status, json.loads(data)


def get_text(port: int, path: str):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        ctype = r.getheader("Content-Type", "")
        data = r.read().decode("utf-8")
    finally:
        conn.close()
    return r.status, ctype, data


def test_gateway_metricsz_histograms_labeled_by_class(tmp_path):
    gw = make_gateway(tmp_path)
    try:
        _, port = gw.address
        status, doc = post(port, {"prompt": "interactive q",
                                  "priority": "high"})
        assert status == 200
        assert doc["trace_id"]
        status, doc2 = post(port, {"prompt": "batch q", "priority": "low"})
        assert status == 200

        # The e2e observation lands in the handler's finally AFTER the
        # response bytes are written — poll briefly so a fast scrape
        # doesn't race the second request's bookkeeping.
        deadline = time.monotonic() + 5.0
        while True:
            status, ctype, text = get_text(port, "/metricsz")
            assert status == 200
            assert ctype.startswith("text/plain")
            parsed = prom.parse_text(text)
            hists = parsed["histograms"]
            e2e_total = sum(
                h["count"] for (m, _), h in hists.items() if m == "e2e"
            )
            if e2e_total >= 2 or time.monotonic() > deadline:
                break
            time.sleep(0.02)
        for metric in ("ttft", "e2e", "queue_wait"):
            classes = {
                dict(labels).get("class")
                for (m, labels) in hists if m == metric
            }
            assert {"high", "low"} <= classes, (metric, classes)
            total = sum(
                h["count"] for (m, _), h in hists.items() if m == metric
            )
            assert total >= 2, (metric, total)
        # Judge synthesis rides the run too (judge class = one above).
        assert any(m == "judge_synthesis" for (m, _) in hists)
        # Outcome labels present and well-formed.
        outcomes = {
            dict(labels).get("outcome") for (m, labels) in hists
        }
        assert outcomes <= set(live_mod.OUTCOMES), outcomes
        # The /statsz blocks flattened through the ONE registry.
        stat_blocks = {
            dict(labels)["block"]
            for (name, labels) in parsed["gauges"] if name == "stat"
        }
        assert {"admission", "cache"} <= stat_blocks, stat_blocks
        assert ("load_score", ()) in parsed["gauges"]
        # /statsz itself iterates the same registry.
        status, _, stats_text = get_text(port, "/statsz")
        stats = json.loads(stats_text)
        assert "admission" in stats and "cache" in stats
    finally:
        gw.close(drain=False, timeout=5.0)


def test_trace_header_honored_and_returned(tmp_path):
    gw = make_gateway(tmp_path, name="tr")
    try:
        _, port = gw.address
        status, doc = post(
            port, {"prompt": "traced"},
            headers={"X-LLMC-Trace": "feedbeefcafe0001"},
        )
        assert status == 200
        assert doc["trace_id"] == "feedbeefcafe0001"
        # And a minted one when absent: 16 hex chars.
        status, doc = post(port, {"prompt": "untraced"})
        assert len(doc["trace_id"]) == 16
        int(doc["trace_id"], 16)
    finally:
        gw.close(drain=False, timeout=5.0)


# ---------------------------------------------------------------------------
# router: fleet /metricsz merge + trace across failover


def sse_request(port: int, body: dict, timeout=60):
    body = dict(body)
    body["stream"] = True
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    events = []
    try:
        conn.request(
            "POST", "/v1/consensus", json.dumps(body),
            {"Content-Type": "application/json",
             "Accept": "text/event-stream"},
        )
        resp = conn.getresponse()
        assert resp.status == 200, resp.read()
        event, data_lines = None, []
        for raw in resp:
            line = raw.decode("utf-8").rstrip("\r\n")
            if line.startswith("event: "):
                event = line[len("event: "):]
            elif line.startswith("data: "):
                data_lines.append(line[len("data: "):])
            elif not line and (event or data_lines):
                events.append((event, json.loads("\n".join(data_lines))))
                if event in ("done", "error"):
                    break
                event, data_lines = None, []
    finally:
        conn.close()
    return events


@pytest.mark.faults
def test_router_metricsz_is_bucketwise_merge_of_replicas(tmp_path):
    gws = [
        make_gateway(tmp_path, name=f"r{i}", cache_size=0)
        for i in range(2)
    ]
    router = None
    try:
        router = serve.build_router(
            [f"http://{h}:{p}" for h, p in (g.address for g in gws)],
            poll_s=60.0,
        )
        router.start()
        _, rport = router.address
        for i in range(4):
            status, doc = post(rport, {"prompt": f"merge probe {i}"})
            assert status == 200, doc
            assert doc["trace_id"]

        def request_families(parsed):
            return {
                k: v for k, v in parsed["histograms"].items()
                if k[0] in ("ttft", "e2e", "queue_wait", "token_latency",
                            "judge_synthesis")
            }

        replica_parsed = []
        for g in gws:
            _, _, text = get_text(g.address[1], "/metricsz")
            replica_parsed.append(prom.parse_text(text))
        _, _, rtext = get_text(rport, "/metricsz")
        merged = prom.merge(replica_parsed)
        assert request_families(prom.parse_text(rtext)) == request_families(
            merged
        )
        # Both replicas exist in the fleet picture even if placement
        # sent every probe to one home.
        assert sum(
            h["count"] for h in request_families(merged).values()
        ) >= 4
    finally:
        if router is not None:
            router.close()
        for g in gws:
            g.close(drain=False, timeout=5.0)


@pytest.mark.faults
def test_one_trace_id_links_hops_across_failover(tmp_path):
    rec = obs.Recorder()
    obs.install(rec)
    faults.install(faults.FaultPlan(
        "replica_down@phase=proxy@frame=2", seed=11
    ))
    gws = [
        make_gateway(tmp_path, name=f"f{i}", cache_size=0)
        for i in range(2)
    ]
    router = None
    try:
        router = serve.build_router(
            [f"http://{h}:{p}" for h, p in (g.address for g in gws)],
            poll_s=60.0,
        )
        router.start()
        _, rport = router.address
        events = sse_request(rport, {"prompt": "failover trace probe"})
        assert events[-1][0] == "done", events[-1]
        done = events[-1][1]
        trace = done["trace_id"]
        assert trace and done.get("failovers", 0) >= 1

        def spans_named(name):
            return [
                e for e in rec.events()
                if e.ph == "X" and e.name == name
                and e.args.get("trace") == trace
            ]

        # The client sees the done frame BEFORE the router thread
        # unwinds into the finally that records its route span — poll.
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and not spans_named("route"):
            time.sleep(0.02)
        route_spans = spans_named("route")
        run_spans = spans_named("consensus_run")
        worker_spans = spans_named("worker")
        # One id stitches the router hop, the (re-executed) gateway run,
        # and the runner fan-out — across the replica_down seam.
        assert route_spans and run_spans and worker_spans
        assert route_spans[0].args.get("outcome") == "failover"
    finally:
        if router is not None:
            router.close()
        for g in gws:
            g.close(drain=False, timeout=5.0)


# ---------------------------------------------------------------------------
# trace id survives preempt -> resume (the PR 9×10 gap)


def test_trace_id_survives_preempt_resume():
    """One trace id links BOTH batcher residencies of a preempted
    stream: the sealed journal entry (closed "preempted") and the
    reopened resume entry carry the same id, and the resumed result is
    marked preempted — so the live plane and any post-mortem can stitch
    the full story of a preempted request from one id."""
    import jax
    import jax.numpy as jnp

    from llm_consensus_tpu import recovery
    from llm_consensus_tpu.engine import ContinuousBatcher, Engine
    from llm_consensus_tpu.engine.engine import SamplingParams
    from llm_consensus_tpu.models import init_params
    from llm_consensus_tpu.models.config import get_config
    from llm_consensus_tpu.pressure import PRIORITY_HIGH, PRIORITY_LOW

    cfg = get_config("tiny-llama")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = Engine(cfg, params=params, dtype=jnp.float32, max_seq=256,
                 stream_interval=8, prefill_chunk=16)
    journal = recovery.StreamJournal()
    recovery.install(journal)
    seen_entries = []
    orig_record = journal.record

    def record(*args, **kwargs):
        entry = orig_record(*args, **kwargs)
        seen_entries.append(entry)
        return entry

    journal.record = record
    try:
        b = ContinuousBatcher(eng, max_batch=2)
        try:
            s_low = SamplingParams(max_new_tokens=48, ignore_eos=True)
            s_hi = SamplingParams(max_new_tokens=8, ignore_eos=True)
            low_traces = ["10w0000000000001", "10w0000000000002"]
            r_low = r_hi = None
            for _attempt in range(4):
                seen_entries.clear()
                futs = [
                    b.submit(f"trace lane {i} body", s_low,
                             priority=PRIORITY_LOW, trace_id=low_traces[i])
                    for i in range(2)
                ]
                deadline = time.monotonic() + 120
                while time.monotonic() < deadline:
                    if sum(1 for st in b._slots if st is not None) == 2:
                        break
                    time.sleep(0.005)
                f_hi = b.submit("trace high latecomer", s_hi,
                                priority=PRIORITY_HIGH,
                                trace_id="feedfeedfeed0001")
                r_hi = f_hi.result(timeout=300)
                r_low = [f.result(timeout=300) for f in futs]
                if any(r.preempted for r in r_low):
                    break
            assert any(r.preempted for r in r_low), "no preemption observed"
            assert r_hi.token_ids  # the high class actually ran
            # The victim's ORIGINAL entry sealed as "preempted" and its
            # RESUME entry — both carry the victim's trace id.
            preempted = [
                e for e in seen_entries if e.finish == "preempted"
            ]
            assert preempted, [e.finish for e in seen_entries]
            for old in preempted:
                assert old.trace in low_traces, old.trace
                resumes = [
                    e for e in seen_entries
                    if e.replay_of == old.sid
                ]
                assert resumes, "preempted entry has no resume entry"
                assert resumes[0].trace == old.trace
            # And the high-priority request kept ITS id.
            hi_entries = [
                e for e in seen_entries if e.trace == "feedfeedfeed0001"
            ]
            assert len(hi_entries) == 1
        finally:
            b.close()
    finally:
        recovery.reset()


# ---------------------------------------------------------------------------
# blackbox: injected engine crash with events OFF (real tiny engines)


@pytest.mark.faults
def test_engine_crash_dumps_blackbox_with_events_off(tmp_path):
    import jax

    from llm_consensus_tpu import recovery
    from llm_consensus_tpu.providers.tpu import TPUProvider

    assert obs.recorder() is None  # events OFF is the point
    bb_mod.install(FlightRecorder(
        capacity=256, out_dir=str(tmp_path), min_interval_s=0.0
    ))
    faults.install(faults.FaultPlan("crash@chunk=2", seed=5))
    recovery.install(recovery.StreamJournal())
    prov = None
    try:
        prov = TPUProvider(
            ignore_eos=True, stream_interval=4, batch_streams=2
        )
        prov.prepare(["tpu:tiny-llama"], None, devices=jax.devices()[:2])
        resp = prov.query_stream(
            Context.background(),
            Request(model="tpu:tiny-llama", prompt="crash probe body",
                    max_tokens=12, trace_id="deadbeef00000001"),
            None,
        )
        assert resp.tokens == 12  # recovered and replayed
        fr = bb_mod.ring()
        assert fr.dumps >= 1 and fr.last_reason == "engine_crash"
        doc = obs_export.load_trace(fr.last_path)
        names = obs_export.trace_span_names(doc)
        # The dump holds decode spans from BEFORE the crash.
        assert "pool.decode" in names, names
        instants = {
            e["name"] for e in doc["traceEvents"]
            if isinstance(e, dict) and e.get("ph") == "i"
        }
        assert "engine_crash" in instants
    finally:
        if prov is not None:
            prov.release()
        recovery.reset()
