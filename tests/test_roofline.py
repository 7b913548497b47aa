"""Kernel-microscope tests: roofline ledger, deep profiler, sentinels.

Covers obs/roofline, obs/profiler, and the ISSUE 17 satellites:

  * RooflineLedger units — one static-cost capture per (family, bucket)
    key with every later dispatch a counter bump, the on-device loop
    ``steps`` multiplier, verdicts against an overridden ridge, and the
    attributed-wall coverage join;
  * the modeled-vs-cost-analysis cross-check — agreeing models pass,
    a modeled figure outside ``LLMC_ROOFLINE_TOL`` reports ``ok: false``;
  * ``hbm_device_stats`` on CPU — returns None cleanly (the gauge is
    simply absent off-accelerator, never an exception);
  * DeepProfiler — armed/busy/rate-limited state machine, the atomic
    artifact-dir rename, stop_now, and the gateway's
    ``POST /debugz/profile`` 404/429/200 contract;
  * prom escaped-label values — render → parse → merge → render_parsed
    round-trips backslashes, quotes, newlines, ``}`` and tolerates
    trailing timestamps (the fleet-merge path's hardening);
  * the router's ``llmc_replica_up`` / scrape-staleness gauges;
  * tools/bench_compare.py — direction awareness, the noise band,
    config-key exemption, and the self-test's injected regression.
"""

from __future__ import annotations

import importlib.util
import json
import os
import threading
import time

import pytest

from llm_consensus_tpu import obs, serve
from llm_consensus_tpu.obs import attrib as attrib_mod
from llm_consensus_tpu.obs import live as live_mod
from llm_consensus_tpu.obs import profiler as prof_mod
from llm_consensus_tpu.obs import prom
from llm_consensus_tpu.obs import roofline as roofline_mod
from llm_consensus_tpu.obs.profiler import DeepProfiler
from llm_consensus_tpu.obs.roofline import RooflineLedger
from llm_consensus_tpu.providers.base import Provider, Request, Response
from llm_consensus_tpu.providers.registry import Registry
from llm_consensus_tpu.utils.context import Context

PANEL = ["alpha", "beta"]
JUDGE = "gamma"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_planes():
    for mod in (obs, live_mod, attrib_mod, roofline_mod, prof_mod):
        mod.reset()
    yield
    for mod in (obs, live_mod, attrib_mod, roofline_mod, prof_mod):
        mod.reset()


def _jitted_matmul():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return x @ x

    x = jnp.ones((8, 8), dtype=jnp.float32)
    return f, x


# -- RooflineLedger units ----------------------------------------------------


def test_dispatch_captures_once_then_counts():
    led = RooflineLedger(ridge=32.0)
    f, x = _jitted_matmul()
    for _ in range(3):
        led.dispatch("decode", ("b8",), f, (x,), {}, tokens=4)
    snap = led.snapshot(device_s={"decode": 0.5})
    fam = snap["families"]["decode"]
    assert fam["programs"] == 1
    assert fam["dispatches"] == 3
    assert fam["tokens"] == 12
    # An 8x8 matmul counts 2*8^3 = 1024 FLOPs per dispatch.
    assert fam["flops"] == pytest.approx(3 * 1024)
    assert fam["bytes"] > 0
    assert fam["achieved_flops_per_s"] == pytest.approx(fam["flops"] / 0.5)
    assert fam["achieved_bytes_per_s"] == pytest.approx(fam["bytes"] / 0.5)


def test_steps_multiplier_scales_loop_body_counts():
    f, x = _jitted_matmul()
    led1 = RooflineLedger(ridge=32.0)
    led1.dispatch("decode", ("k",), f, (x,), {}, steps=1)
    led5 = RooflineLedger(ridge=32.0)
    led5.dispatch("decode", ("k",), f, (x,), {}, steps=5)
    f1 = led1.snapshot(device_s={})["families"]["decode"]
    f5 = led5.snapshot(device_s={})["families"]["decode"]
    assert f5["flops"] == pytest.approx(5 * f1["flops"])
    assert f5["bytes"] == pytest.approx(5 * f1["bytes"])


def test_verdicts_follow_the_ridge_override():
    f, x = _jitted_matmul()
    lo = RooflineLedger(ridge=1e-6)  # everything is compute-bound
    lo.dispatch("decode", ("k",), f, (x,), {})
    hi = RooflineLedger(ridge=1e9)  # everything is memory-bound
    hi.dispatch("decode", ("k",), f, (x,), {})
    s_lo = lo.snapshot(device_s={})
    s_hi = hi.snapshot(device_s={})
    assert s_lo["ridge_source"] == "override"
    assert s_lo["families"]["decode"]["verdict"] == "compute_bound"
    assert s_hi["families"]["decode"]["verdict"] == "memory_bound"


def test_coverage_joins_only_instrumented_families():
    led = RooflineLedger(ridge=32.0)
    f, x = _jitted_matmul()
    led.dispatch("decode", ("k",), f, (x,), {})
    snap = led.snapshot(device_s={"decode": 1.0, "allgather": 1.0})
    cov = snap["coverage"]
    assert cov["covered_wall_s"] == pytest.approx(1.0)
    assert cov["attrib_wall_s"] == pytest.approx(2.0)
    assert cov["fraction"] == pytest.approx(0.5)


def test_transfer_bytes_join_a_family_the_compiler_never_saw():
    led = RooflineLedger(ridge=32.0)
    led.note_transfer("kv_handoff", 4096.0)
    fam = led.snapshot(device_s={})["families"]["kv_handoff"]
    assert fam["bytes"] == pytest.approx(4096.0)
    assert fam["source"] == "transfer"
    # Transfer-only families book no dispatches, so they don't claim
    # coverage credit.
    assert fam["dispatches"] == 0


def test_concurrent_first_dispatches_capture_once():
    led = RooflineLedger(ridge=32.0)
    f, x = _jitted_matmul()
    barrier = threading.Barrier(4)

    def fire():
        barrier.wait()
        led.dispatch("decode", ("k",), f, (x,), {}, tokens=1)

    threads = [threading.Thread(target=fire) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    fam = led.snapshot(device_s={})["families"]["decode"]
    assert fam["programs"] == 1
    assert fam["dispatches"] == 4
    assert fam["tokens"] == 4
    assert fam["flops"] == pytest.approx(4 * 1024)


# -- the modeled-vs-XLA cross-check ------------------------------------------


def test_crosscheck_agreeing_model_is_ok():
    led = RooflineLedger(ridge=32.0)
    f, x = _jitted_matmul()
    led.dispatch("decode", ("k",), f, (x,), {}, tokens=4)
    led.note_modeled("decode", 1024 / 4)  # exactly the XLA count
    chk = led.snapshot(device_s={})["crosscheck"]["decode"]
    assert chk["ratio"] == pytest.approx(1.0)
    assert chk["ok"] is True


def test_crosscheck_flags_model_outside_tolerance():
    led = RooflineLedger(ridge=32.0, tol=4.0)
    f, x = _jitted_matmul()
    led.dispatch("decode", ("k",), f, (x,), {}, tokens=4)
    led.note_modeled("decode", (1024 / 4) * 100.0)  # 100x the XLA count
    chk = led.snapshot(device_s={})["crosscheck"]["decode"]
    assert chk["ok"] is False
    assert chk["ratio"] == pytest.approx(0.01)
    # Widening the modeled range back over the measured value heals it:
    # multiple engines legitimately register different analytic costs.
    led.note_modeled("decode", 1024 / 4)
    chk2 = led.snapshot(device_s={})["crosscheck"]["decode"]
    assert chk2["ok"] is True


# -- instrument() wrapper ----------------------------------------------------


def test_instrument_books_under_the_ambient_attrib_tag():
    led = RooflineLedger(ridge=32.0)
    roofline_mod.install(led)
    f, x = _jitted_matmul()
    wrapped = roofline_mod.instrument(f, family="decode")
    wrapped(x)
    with attrib_mod.tag("draft"):
        wrapped(x)
    fams = led.snapshot(device_s={})["families"]
    assert fams["decode"]["dispatches"] == 1
    assert fams["draft"]["dispatches"] == 1


def test_instrument_lets_the_call_trace_and_books_a_donated_argument():
    """Two programs that share a (family, key), as two models' steps at
    one bucket do: each is traced once, under ``call`` and never under
    ``_capture`` (a Pallas kernel's body carries the frames it was traced
    under, and they are part of the compile cache's key), and the
    capture still reads its cost from arguments the call donated."""
    import inspect
    from functools import partial

    import jax
    import jax.numpy as jnp

    led = RooflineLedger(ridge=32.0)
    roofline_mod.install(led)
    traced_under = []

    @partial(jax.jit, static_argnames=("model",), donate_argnames=("cache",))
    def step(cache, x, model):
        traced_under.append(
            (model, [f.function for f in inspect.stack()
                     if f.function in ("call", "_capture")]))
        return cache + x @ x

    wrapped = roofline_mod.instrument(step, family="decode", key=lambda a, k: "b8")
    x = jnp.ones((8, 8), dtype=jnp.float32)
    for model in ("a", "b", "a"):
        cache = jnp.zeros((8, 8), dtype=jnp.float32)
        wrapped(cache, x, model=model)
        assert cache.is_deleted()
    assert traced_under == [("a", ["call"]), ("b", ["call"])]
    fam = led.snapshot(device_s={})["families"]["decode"]
    assert fam["programs"] == 1
    assert fam["dispatches"] == 3
    assert fam["source"] == "xla"
    assert fam["flops"] >= 3 * 1024


def test_instrument_disabled_is_transparent():
    roofline_mod.install(None)
    f, x = _jitted_matmul()
    wrapped = roofline_mod.instrument(f, family="decode")
    out = wrapped(x)
    assert out.shape == (8, 8)
    assert hasattr(wrapped, "lower")  # jit surface delegates


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
        "roofline_flops_total": {
            "type": "counter",
            "samples": [({"family": value}, 7.0)],
        },
    }
    text = prom.render(families=fams)
    parsed = prom.parse_text(text)
    [(key, got)] = list(parsed["gauges"].items())
    name, labels = key
    assert name == "roofline_flops_total"
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


# -- tools/bench_compare.py --------------------------------------------------


def _bench_compare():
    spec = importlib.util.spec_from_file_location(
        "bench_compare", os.path.join(REPO, "tools", "bench_compare.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write_round(d, n, parsed):
    path = os.path.join(d, f"BENCH_r{n:02d}.json")
    with open(path, "w") as f:
        json.dump({"n": n, "cmd": "bench", "rc": 0, "tail": "",
                   "parsed": parsed}, f)


def test_bench_compare_direction_awareness(tmp_path):
    bc = _bench_compare()
    prev = {"decode_tokens_per_s": 100.0, "ttft_ms": 50.0, "n_chips": 2.0}
    # Throughput UP and latency DOWN are improvements, never flagged.
    regs, _ = bc.compare(prev, {"decode_tokens_per_s": 150.0,
                                "ttft_ms": 30.0, "n_chips": 2.0}, 0.10)
    assert regs == []
    # Throughput down past the band IS a regression.
    regs, _ = bc.compare(prev, {"decode_tokens_per_s": 80.0,
                                "ttft_ms": 50.0, "n_chips": 2.0}, 0.10)
    assert [r["metric"] for r in regs] == ["decode_tokens_per_s"]
    # Latency UP past the band IS a regression.
    regs, _ = bc.compare(prev, {"decode_tokens_per_s": 100.0,
                                "ttft_ms": 70.0, "n_chips": 2.0}, 0.10)
    assert [r["metric"] for r in regs] == ["ttft_ms"]
    # Inside the band: noise, not a regression.
    regs, _ = bc.compare(prev, {"decode_tokens_per_s": 95.0,
                                "ttft_ms": 52.0, "n_chips": 2.0}, 0.10)
    assert regs == []
    # A config-key change is informational even when it halves.
    regs, rows = bc.compare(prev, {"decode_tokens_per_s": 100.0,
                                   "ttft_ms": 50.0, "n_chips": 1.0}, 0.10)
    assert regs == []
    assert {r["metric"]: r["status"] for r in rows}["n_chips"] == "info"


def test_bench_compare_gates_only_shared_keys(tmp_path):
    bc = _bench_compare()
    regs, rows = bc.compare({"old_phase": 10.0}, {"new_phase": 1.0}, 0.10)
    assert regs == [] and rows == []


def test_bench_compare_main_flags_regression(tmp_path):
    bc = _bench_compare()
    _write_round(str(tmp_path), 1, None)  # unparsed rounds are skipped
    _write_round(str(tmp_path), 2, {"decode_tokens_per_s": 100.0})
    _write_round(str(tmp_path), 3, {"decode_tokens_per_s": 50.0})
    assert bc.main(["--dir", str(tmp_path)]) == 1
    _write_round(str(tmp_path), 4, {"decode_tokens_per_s": 49.0})
    assert bc.main(["--dir", str(tmp_path)]) == 0  # r3 -> r4 is in-band


def test_bench_compare_neutral_without_two_parsed_rounds(tmp_path):
    bc = _bench_compare()
    _write_round(str(tmp_path), 1, None)
    _write_round(str(tmp_path), 2, {"x": 1.0})
    assert bc.main(["--dir", str(tmp_path)]) == 2


def test_bench_compare_self_test_catches_injection(tmp_path):
    bc = _bench_compare()
    # A genuinely improving pair: the injected regression must still be
    # flagged (it degrades relative to PREV, not to the improved cur).
    _write_round(str(tmp_path), 1, {"decode_tokens_per_s": 100.0,
                                    "ttft_ms": 50.0})
    _write_round(str(tmp_path), 2, {"decode_tokens_per_s": 130.0,
                                    "ttft_ms": 40.0})
    assert bc.main(["--dir", str(tmp_path), "--self-test"]) == 0


def test_bench_compare_self_test_on_the_real_trajectory():
    bc = _bench_compare()
    rounds = bc.load_rounds(REPO)
    if bc.latest_pair(rounds) is None:
        pytest.skip("repo has fewer than two parsed BENCH rounds")
    assert bc.main(["--dir", REPO, "--self-test"]) == 0
