"""Deep profiler, prom label and router gauge tests.

  * ``hbm_device_stats`` on CPU — returns None cleanly (the gauge is
    simply absent off-accelerator, never an exception);
  * DeepProfiler — armed/busy/rate-limited state machine, the atomic
    artifact-dir rename, stop_now, and the gateway's
    ``POST /debugz/profile`` 404/429/200 contract;
  * prom escaped-label values — render → parse → merge → render_parsed
    round-trips backslashes, quotes, newlines, ``}`` and tolerates
    trailing timestamps (the fleet-merge path's hardening);
  * the router's ``llmc_replica_up`` / scrape-staleness gauges.
"""

from __future__ import annotations

import json
import os
import time

import pytest

from llm_consensus_tpu import obs, serve
from llm_consensus_tpu.obs import attrib as attrib_mod
from llm_consensus_tpu.obs import live as live_mod
from llm_consensus_tpu.obs import profiler as prof_mod
from llm_consensus_tpu.obs import prom
from llm_consensus_tpu.obs.profiler import DeepProfiler
from llm_consensus_tpu.providers.base import Provider, Request, Response
from llm_consensus_tpu.providers.registry import Registry
from llm_consensus_tpu.utils.context import Context

PANEL = ["alpha", "beta"]
JUDGE = "gamma"


@pytest.fixture(autouse=True)
def _clean_planes():
    for mod in (obs, live_mod, attrib_mod, prof_mod):
        mod.reset()
    yield
    for mod in (obs, live_mod, attrib_mod, prof_mod):
        mod.reset()


# -- hbm_device_stats on CPU -------------------------------------------------


def test_hbm_device_stats_returns_none_on_cpu():
    led = attrib_mod.ChipTimeLedger()
    assert led.hbm_device_stats() is None
    # And the snapshot path that embeds it stays clean too.
    snap = led.snapshot()
    assert snap["hbm"].get("device") is None


# -- DeepProfiler ------------------------------------------------------------


def test_profiler_single_flight_rate_limit_and_atomic_dir(tmp_path):
    prof = DeepProfiler(out_dir=str(tmp_path), max_s=5.0,
                        min_interval_s=60.0)
    final, status = prof.arm(0.3, tag="t one!")
    assert status == "armed"
    assert os.path.basename(final).startswith("profile-t-one-")
    path2, status2 = prof.arm(0.1)
    assert (path2, status2) == (None, "busy")
    assert prof.wait(30.0)
    assert os.path.isdir(final) and os.listdir(final)
    assert not os.path.exists(final + ".partial")
    # Window 1 is booked; the next start inside the interval is 429.
    path3, status3 = prof.arm(0.1)
    assert (path3, status3) == (None, "rate_limited")
    st = prof.stats()
    assert st["windows"] == 1
    assert st["suppressed"] == 2
    assert st["last_path"] == final
    assert st["last_error"] is None


def test_profiler_stop_now_closes_early(tmp_path):
    prof = DeepProfiler(out_dir=str(tmp_path), max_s=30.0,
                        min_interval_s=0.0)
    final, status = prof.arm(30.0, tag="early")
    assert status == "armed"
    t0 = time.monotonic()
    assert prof.stop_now() == final
    assert time.monotonic() - t0 < 10.0  # nowhere near the 30 s cap
    assert os.path.isdir(final) and os.listdir(final)
    assert not prof.active()
    assert prof.stop_now() is None  # idempotent when idle


class FakeProvider(Provider):
    def query(self, ctx: Context, req: Request) -> Response:
        ctx.raise_if_done()
        return Response(model=req.model, content="ok", provider="fake")

    def query_stream(self, ctx, req, callback):
        resp = self.query(ctx, req)
        if callback is not None:
            callback(resp.content)
        return resp


def _gateway(tmp_path):
    registry = Registry()
    provider = FakeProvider()
    for m in PANEL + [JUDGE]:
        registry.register(m, provider)
    return serve.build_gateway(
        registry, list(PANEL), JUDGE, timeout=30.0, max_concurrency=4,
        data_dir=os.path.join(str(tmp_path), "data"),
    )


def test_debug_profile_contract_on_the_gateway(tmp_path):
    prof_mod.install(None)
    gw = _gateway(tmp_path)
    status, doc = gw.debug_profile()
    assert status == 404, doc

    prof_mod.install(DeepProfiler(
        out_dir=os.path.join(str(tmp_path), "prof"), max_s=5.0,
        min_interval_s=0.0,
    ))
    gw2 = _gateway(tmp_path)
    status, doc = gw2.debug_profile(duration_s=0.2, tag="contract")
    assert status == 200, doc
    assert doc["status"] == "armed" and doc["path"]
    status2, doc2 = gw2.debug_profile(duration_s=0.2)
    assert status2 == 429, doc2
    assert doc2["status"] == "busy"
    prof = prof_mod.profiler()
    assert prof.wait(30.0)
    assert os.path.isdir(doc["path"]) and os.listdir(doc["path"])


# -- prom: escaped label values round-trip the fleet-merge path --------------

NASTY = [
    'plain',
    'sp ace',
    'quo"te',
    'back\\slash',
    'new\nline',
    'brace}inside',
    'comma,eq=inside',
    'trail\\',
    'mix\\"all\n}"',
]


@pytest.mark.parametrize("value", NASTY)
def test_family_labels_round_trip_render_parse_merge(value):
    fams = {
        "device_time_seconds_total": {
            "type": "counter",
            "samples": [({"family": value}, 7.0)],
        },
    }
    text = prom.render(families=fams)
    parsed = prom.parse_text(text)
    [(key, got)] = list(parsed["gauges"].items())
    name, labels = key
    assert name == "device_time_seconds_total"
    assert dict(labels)["family"] == value
    assert got == 7.0
    merged = prom.merge([parsed, parsed])
    assert merged["gauges"][key] == 14.0
    # The router re-renders the merge; that text must parse back to the
    # same doc (the fleet scrape is itself scraped).
    reparsed = prom.parse_text(prom.render_parsed(merged))
    assert dict(list(reparsed["gauges"])[0][1])["family"] == value
    assert reparsed["gauges"][key] == 14.0


def test_parse_text_tolerates_trailing_timestamps():
    text = (
        "# TYPE llmc_load_score gauge\n"
        'llmc_load_score{url="http://x:1"} 0.5 1700000000000\n'
    )
    parsed = prom.parse_text(text)
    [(key, v)] = list(parsed["gauges"].items())
    assert v == 0.5
    assert dict(key[1])["url"] == "http://x:1"


def test_parse_labels_keeps_unknown_escapes_verbatim():
    text = (
        "# TYPE llmc_x gauge\n"
        'llmc_x{k="a\\qb"} 1\n'
    )
    parsed = prom.parse_text(text)
    [(key, _)] = list(parsed["gauges"].items())
    assert dict(key[1])["k"] == "a\\qb"


def test_parse_labels_rejects_unquoted_values():
    with pytest.raises(ValueError):
        prom._parse_labels("k=unquoted")
    with pytest.raises(ValueError):
        prom._parse_labels('k="unterminated')


# -- router: replica_up + scrape staleness -----------------------------------


def test_router_exports_replica_up_and_staleness(tmp_path):
    gw = _gateway(tmp_path)
    gw.start()
    router = None
    try:
        host, port = gw.address
        url = f"http://{host}:{port}"
        router = serve.build_router([url], poll_s=60.0)
        router.start()
        text = router.metricsz()
        parsed = prom.parse_text(text)
        up = {
            dict(labels)["url"]: v
            for (name, labels), v in parsed["gauges"].items()
            if name == "replica_up"
        }
        stale = {
            dict(labels)["url"]: v
            for (name, labels), v in parsed["gauges"].items()
            if name == "replica_scrape_staleness_seconds"
        }
        assert up == {url: 1.0}
        assert stale[url] >= 0.0
        gw.close(drain=False, timeout=5.0)
        gw = None
        parsed2 = prom.parse_text(router.metricsz())
        up2 = {
            dict(labels)["url"]: v
            for (name, labels), v in parsed2["gauges"].items()
            if name == "replica_up"
        }
        stale2 = {
            dict(labels)["url"]: v
            for (name, labels), v in parsed2["gauges"].items()
            if name == "replica_scrape_staleness_seconds"
        }
        assert up2 == {url: 0.0}
        assert stale2[url] >= 0.0  # it DID answer once; staleness ages
    finally:
        if router is not None:
            router.close()
        if gw is not None:
            gw.close(drain=False, timeout=5.0)


def test_router_fans_profile_out_to_a_replica(tmp_path):
    import http.client

    prof_mod.install(DeepProfiler(
        out_dir=os.path.join(str(tmp_path), "prof"), max_s=5.0,
        min_interval_s=0.0,
    ))
    gw = _gateway(tmp_path)
    gw.start()
    router = None

    def post(port, body):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        try:
            conn.request("POST", "/debugz/profile", json.dumps(body),
                         {"Content-Type": "application/json"})
            r = conn.getresponse()
            return r.status, json.loads(r.read())
        finally:
            conn.close()

    try:
        host, port = gw.address
        url = f"http://{host}:{port}"
        router = serve.build_router([url], poll_s=60.0)
        router.start()
        _, rport = router.address
        status, doc = post(rport, {"replica": "http://nowhere:1"})
        assert status == 404, doc
        assert doc["replicas"] == [url]
        status, doc = post(rport, {"duration_s": 0.2, "replica": url})
        assert status == 200, doc
        assert doc["replica"] == url and doc["path"]
        prof = prof_mod.profiler()
        assert prof.wait(30.0)
        assert os.path.isdir(doc["path"]) and os.listdir(doc["path"])
    finally:
        if router is not None:
            router.close()
        gw.close(drain=False, timeout=5.0)
