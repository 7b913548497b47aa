"""Crash recovery tests: stream journal, engine supervision, --resume.

The robustness contract of PR 5 (recovery/): an engine death mid-decode
costs a pause, not the in-flight streams — greedy streams replay
byte-identically onto the rebuilt pool — and a process death mid-run
leaves a ``data/<run-id>/`` dir that ``--resume`` finishes without
rerunning the panel answers its journal already completed.

Engine-level tests run real (tiny) engines on the CPU backend with
deterministic fault plans, the same shape as tests/test_faults.py.
"""

from __future__ import annotations

import io
import json
import os
import threading
import time

import numpy as np
import pytest

from llm_consensus_tpu import faults, obs, recovery
from llm_consensus_tpu.engine import SamplingParams
from llm_consensus_tpu.providers import ProviderFunc, Request, Response
from llm_consensus_tpu.utils.context import Context

pytestmark = pytest.mark.faults


@pytest.fixture(autouse=True)
def _clean_process_state():
    """Every test starts and ends with no plan/journal/recorder installed
    — these are process-global and the suite shares one interpreter."""
    faults.reset()
    recovery.reset()
    yield
    faults.reset()
    recovery.reset()
    obs.install(None)


# ---------------------------------------------------------------------------
# journal unit tests


def test_journal_entry_lifecycle():
    j = recovery.StreamJournal()
    e = j.record([1, 2, 3], SamplingParams(max_new_tokens=8))
    assert j.depth() == 1 and e.open
    e.append(7)
    e.append(8)
    assert e.tokens() == [7, 8]
    e.close("eos")
    assert j.depth() == 0 and not e.open
    assert e.finish == "eos"
    e.close("length")  # idempotent: first close wins
    assert e.finish == "eos"
    assert j.stats() == {"depth": 0, "opened": 1, "closed": 1}


def test_journal_seal_drops_late_appends():
    j = recovery.StreamJournal()
    e = j.record([1], SamplingParams())
    e.append(5)
    snapshot = e.seal()
    assert snapshot == [5]
    e.append(6)  # a wedged worker waking up late
    assert e.tokens() == [5], "sealed entry accepted a late append"


def test_journal_disk_mirror(tmp_path):
    from llm_consensus_tpu import integrity

    j = recovery.StreamJournal(path=str(tmp_path / "wal"))
    e = j.record([1, 2], SamplingParams(max_new_tokens=4))
    e.append(9)
    e.close("length")
    files = os.listdir(tmp_path / "wal")
    assert len(files) == 1
    # Every record is CRC32C-framed: "<crc-8-hex> <payload>".
    lines = (tmp_path / "wal" / files[0]).read_text().splitlines()
    payloads = [integrity.parse_wal_line(ln) for ln in lines]
    assert None not in payloads, lines
    header = json.loads(payloads[0])
    assert header["prompt_ids"] == [1, 2]
    assert payloads[1] == "9"
    assert payloads[-1] == "#finish=length"
    # The reader round-trips the same records.
    doc = recovery.read_wal(str(tmp_path / "wal" / files[0]))
    assert doc["header"]["prompt_ids"] == [1, 2]
    assert doc["tokens"] == [9]
    assert doc["finish"] == "length"
    assert not doc["truncated"]


# ---------------------------------------------------------------------------
# atomic save_file (satellite)


def test_save_file_is_atomic_and_leaves_no_temp(tmp_path):
    from llm_consensus_tpu.output.persist import save_file

    run_dir = str(tmp_path / "run")
    path = save_file(run_dir, "trace.json", '{"a": 1}')
    assert path == os.path.join(run_dir, "trace.json")
    assert json.load(open(path)) == {"a": 1}
    # Overwrite is atomic-replace, bytes round-trip, no temp debris.
    assert save_file(run_dir, "trace.json", b'{"a": 2}') == path
    assert json.load(open(path)) == {"a": 2}
    assert sorted(os.listdir(run_dir)) == ["trace.json"]


def test_save_file_failure_is_nonfatal(tmp_path):
    from llm_consensus_tpu.output.persist import save_file

    target = tmp_path / "not-a-dir"
    target.write_text("file in the way")
    warnings: list[str] = []
    assert save_file(str(target), "x.json", "{}", warn=warnings.append) is None
    assert warnings and "Failed to save x" in warnings[0]


# ---------------------------------------------------------------------------
# engine supervision: crash replay + wedge detection (real tiny engines)


def _provider(**kw):
    from llm_consensus_tpu.providers.tpu import TPUProvider

    kw.setdefault("ignore_eos", True)
    kw.setdefault("stream_interval", 4)
    kw.setdefault("batch_streams", 2)
    return TPUProvider(**kw)


# THREE prompts onto a 2-slot pool: the third stream is still QUEUED
# when the crash lands, so recovery must also reclassify the cancelled
# queued future as pool death (not a benign close) and replay it.
PROMPTS = [
    "crash replay probe one",
    "crash replay probe two — longer body",
    "crash replay probe three, queued behind the pool",
]


def _query_all(prov, prompts, max_tokens=16, collect=None):
    results: list = [None] * len(prompts)

    def fire(i):
        cb = None
        if collect is not None:
            collect[i] = []
            cb = collect[i].append
        results[i] = prov.query_stream(
            Context.background(),
            Request(model="tpu:tiny-llama", prompt=prompts[i],
                    max_tokens=max_tokens),
            cb,
        )

    threads = [threading.Thread(target=fire, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(r is not None for r in results)
    return results


def test_crash_replay_byte_identity():
    # Two baselines. The INDEPENDENT witness is the single-stream engine:
    # the batcher's greedy contract is token-exact against it, so it is
    # order-independent even with 3 streams on 2 slots.
    single = _provider(batch_streams=1)
    ref = _query_all(single, PROMPTS)
    eng = single._engines["tiny-llama"]
    # What replay promises is the bytes of the run the crash interrupted:
    # the fault-free run of the SAME pool shape.
    prov = _provider()
    base = _query_all(prov, PROMPTS)
    prov.release()
    assert all(r.tokens == 16 for r in base)
    # The pool may differ from the witness only where bf16 (these are
    # bf16 engines) cannot tell the two best tokens apart: the 16th
    # token of stream 2, which the single-stream decode program and the
    # pool's resolve differently WITHOUT any fault — what this test
    # reported as "stream 2 diverged" at every commit while the
    # single-stream run was its only baseline.
    for i, (r, b) in enumerate(zip(ref, base)):
        if r.content == b.content:
            continue
        assert r.content[:-1] == b.content[:-1], f"stream {i}: not a tail tie"
        ids = eng.generate(PROMPTS[i], SamplingParams(
            max_new_tokens=16, ignore_eos=True)).token_ids
        logits, _ = eng._prefill_ids(
            eng.tokenizer.encode(PROMPTS[i]) + list(ids[:15]))
        logits = np.asarray(logits, np.float32).ravel()
        second, first = (int(t) for t in np.argsort(logits)[-2:])
        # bf16 keeps 8 significant bits: one step is 2**-6 in [2, 4).
        assert logits[first] - logits[second] < 2 * 2.0 ** -6, (
            f"stream {i} diverged from the single-stream engine at a "
            f"token bf16 does tell apart: {logits[first]} {logits[second]}"
        )
        assert {r.content[-1], b.content[-1]} == {
            eng.tokenizer.decode([first]), eng.tokenizer.decode([second])
        }
    single.release()

    # Crash run: same prompts, journal on, engine crash at the 2nd
    # decode-chunk dispatch — mid-generation, tokens already emitted.
    faults.install(faults.FaultPlan("crash@chunk=2", seed=7))
    recovery.install(recovery.StreamJournal())
    prov2 = _provider()
    try:
        streamed: dict = {}
        got = _query_all(prov2, PROMPTS, collect=streamed)
        for i, r in enumerate(got):
            assert r.content == base[i].content, f"stream {i} diverged"
            assert r.tokens == 16
            # Stream continuity: the chunks the consumer saw concatenate
            # to exactly the final content — nothing dropped, nothing
            # duplicated across the restart seam.
            assert "".join(streamed[i]) == r.content
        sup = prov2._recovery.stats()
        assert sup["restarts"] == 1, sup  # one rebuild served every waiter
        assert sup["replayed_streams"] >= 1, sup
        assert sup["journal"]["depth"] == 0, "journal entries leaked"
    finally:
        prov2.release()


def test_wedge_detection_fires_on_stalled_heartbeat(monkeypatch):
    prov = _provider()
    base = prov.query(Context.background(), Request(
        model="tpu:tiny-llama", prompt="wedge probe", max_tokens=12,
    ))
    prov.release()

    faults.install(faults.FaultPlan("wedge@chunk=2@s=30", seed=7))
    recovery.install(recovery.StreamJournal())
    monkeypatch.setenv("LLMC_ENGINE_HEARTBEAT_S", "2.0")
    prov2 = _provider()
    try:
        t0 = time.monotonic()
        r = prov2.query(Context.background(), Request(
            model="tpu:tiny-llama", prompt="wedge probe", max_tokens=12,
        ))
        wall = time.monotonic() - t0
        assert r.content == base.content
        assert r.tokens == 12
        # The watchdog abandoned the wedged pool and the stream replayed
        # long before the 30 s injected stall would have released it.
        assert wall < 25.0, f"wedge was waited out, not detected ({wall:.1f}s)"
        sup = prov2._recovery.stats()
        assert sup["restarts"] >= 1, sup
        assert sup["replayed_streams"] >= 1, sup
    finally:
        prov2.release()


def test_recovery_stats_shape_without_supervision():
    prov = _provider()
    try:
        prov.query(Context.background(), Request(
            model="tpu:tiny-llama", prompt="stats probe", max_tokens=4,
        ))
        stats = prov.recovery_stats()
        assert stats["state"] == "ok"
        assert stats["restarts"] == 0 and stats["replayed_streams"] == 0
        assert "tiny-llama" in stats["heartbeats"]
        assert stats["heartbeats"]["tiny-llama"]["age_s"] >= 0.0
    finally:
        prov.release()


# ---------------------------------------------------------------------------
# coalesced-follower survival across a restart (gateway over real engines)


def test_coalesced_follower_survives_restart(tmp_path):
    import http.client

    from llm_consensus_tpu import serve
    from llm_consensus_tpu.providers.registry import Registry

    faults.install(faults.FaultPlan("crash@model=tiny-llama", seed=7))
    recovery.install(recovery.StreamJournal())
    prov = _provider(batch_streams=2)
    panel = ["tpu:tiny-llama"]
    judge = "tpu:tiny-gemma"
    reg = Registry()
    for m in panel + [judge]:
        reg.register(m, prov)
    gw = serve.build_gateway(
        reg, panel, judge, max_tokens=8, timeout=300.0,
        max_concurrency=2, max_queue=2,
        data_dir=os.path.join(str(tmp_path), "data"), port=0,
    )
    gw.start()
    try:
        _, port = gw.address

        def post_sse(out, idx):
            conn = http.client.HTTPConnection(
                "127.0.0.1", port, timeout=300
            )
            try:
                conn.request(
                    "POST", "/v1/consensus",
                    json.dumps({"prompt": "follower survival", "stream": True}),
                    {"Content-Type": "application/json"},
                )
                r = conn.getresponse()
                out[idx] = (r.status, r.read())
            finally:
                conn.close()

        results: dict = {}
        threads = [
            threading.Thread(target=post_sse, args=(results, i))
            for i in range(2)
        ]
        threads[0].start()
        # Give the leader a head start so the second request coalesces
        # as a follower instead of racing for leadership.
        time.sleep(0.3)
        threads[1].start()
        for t in threads:
            t.join()

        docs = []
        for i in range(2):
            status, body = results[i]
            assert status == 200, (i, body)
            frames = [
                f for f in body.decode("utf-8").split("\n\n") if f.strip()
            ]
            done = None
            for frame in frames:
                if "event: done" in frame:
                    for line in frame.splitlines():
                        if line.startswith("data: "):
                            done = json.loads(line[len("data: "):])
            assert done is not None, (i, body[-400:])
            docs.append(done)
        # One execution, two completed consumers, identical consensus —
        # the follower rode the leader's flight straight through the
        # engine restart.
        assert gw.scheduler.runs_executed == 1
        assert sum(1 for d in docs if d["coalesced"]) == 1, docs
        assert docs[0]["consensus"] == docs[1]["consensus"]
        assert docs[0]["run_id"] != docs[1]["run_id"]
        assert prov._recovery.stats()["restarts"] >= 1
    finally:
        gw.close(drain=False, timeout=10.0)
        prov.release()


# ---------------------------------------------------------------------------
# --resume (CLI, fake providers)


def _run_cli(argv, factory):
    from llm_consensus_tpu.cli.main import main

    stdout, stderr = io.StringIO(), io.StringIO()
    code = main(
        argv, factory=factory, stdin=io.StringIO(), stdout=stdout,
        stderr=stderr, install_signal_handlers=False,
    )
    return code, stdout.getvalue(), stderr.getvalue()


def test_resume_reuses_completed_panel_answers(tmp_path):
    data = str(tmp_path / "data")
    calls: list[str] = []

    def judge_down(model):
        def fn(ctx, req):
            calls.append(req.model)
            if req.model == "j":
                raise RuntimeError("judge crashed")
            return Response(req.model, f"echo({req.model})", "fake", 1.0)
        return ProviderFunc(fn)

    code, _, err = _run_cli(
        ["--models", "m1,m2", "--judge", "j", "--data-dir", data,
         "--system", "be brief", "--max-tokens", "32", "the question"],
        judge_down,
    )
    assert code == 1 and "consensus synthesis" in err
    run_id = os.listdir(data)[0]
    run_dir = os.path.join(data, run_id)
    assert not os.path.exists(os.path.join(run_dir, "result.json"))
    manifest = json.load(open(os.path.join(run_dir, "run.json")))
    assert manifest["models"] == ["m1", "m2"]
    assert manifest["system"] == "be brief"
    assert len(os.listdir(os.path.join(run_dir, "panel"))) == 2

    # Resume: only the judge reruns; the panel answers come from the
    # journal, the manifest supplies prompt + settings.
    calls2: list[str] = []
    seen_settings: dict = {}

    def healthy(model):
        def fn(ctx, req):
            calls2.append(req.model)
            seen_settings.update(
                system=req.system, max_tokens=req.max_tokens,
            )
            return Response(req.model, f"fresh({req.model})", "fake", 1.0)
        return ProviderFunc(fn)

    code, out, err = _run_cli(
        ["--resume", run_id, "--data-dir", data], healthy
    )
    assert code == 0, err
    assert calls2 == ["j"], calls2
    doc = json.load(open(os.path.join(run_dir, "result.json")))
    assert [r["content"] for r in doc["responses"]] == [
        "echo(m1)", "echo(m2)"
    ]
    assert doc["consensus"] == "fresh(j)"
    assert doc["prompt"] == "the question"
    assert os.path.exists(os.path.join(run_dir, "consensus.md"))


def test_resume_reruns_only_failed_models(tmp_path):
    data = str(tmp_path / "data")

    def m3_and_judge_down(model):
        def fn(ctx, req):
            if req.model in ("m3", "j"):
                raise RuntimeError(f"{req.model} down")
            return Response(req.model, f"echo({req.model})", "fake", 1.0)
        return ProviderFunc(fn)

    # m3 and the judge fail: m1/m2 land in the panel journal, the run
    # dies at synthesis (two survivors ⇒ no single-answer passthrough).
    code, _, _ = _run_cli(
        ["--models", "m1,m2,m3", "--judge", "j", "--data-dir", data, "q"],
        m3_and_judge_down,
    )
    assert code == 1
    run_id = os.listdir(data)[0]

    calls2: list[str] = []

    def healthy(model):
        def fn(ctx, req):
            calls2.append(req.model)
            return Response(req.model, f"fresh({req.model})", "fake", 1.0)
        return ProviderFunc(fn)

    code, _, err = _run_cli(["--resume", run_id, "--data-dir", data], healthy)
    assert code == 0, err
    # m1/m2 were journaled; m3 (failed — never journaled) reran, judge
    # reran.
    assert sorted(calls2) == ["j", "m3"], calls2
    doc = json.load(open(os.path.join(data, run_id, "result.json")))
    assert sorted(r["content"] for r in doc["responses"]) == [
        "echo(m1)", "echo(m2)", "fresh(m3)"
    ]


def test_resume_rejects_completed_or_unknown_runs(tmp_path):
    data = str(tmp_path / "data")

    def healthy(model):
        return ProviderFunc(lambda ctx, req: Response(
            req.model, "ok", "fake", 1.0
        ))

    code, _, _ = _run_cli(
        ["--models", "m1", "--judge", "j", "--data-dir", data, "q"], healthy
    )
    assert code == 0
    run_id = os.listdir(data)[0]
    code, _, err = _run_cli(["--resume", run_id, "--data-dir", data], healthy)
    assert code == 1 and "already completed" in err
    code, _, err = _run_cli(["--resume", "nope", "--data-dir", data], healthy)
    assert code == 1 and "no usable run.json" in err


def test_resume_flag_conflicts():
    from llm_consensus_tpu.cli.main import CLIError, parse_args

    with pytest.raises(CLIError, match="prompt from the saved run"):
        parse_args(["--resume", "r1", "extra prompt"], io.StringIO(),
                   io.StringIO())
    with pytest.raises(CLIError, match="incompatible"):
        parse_args(["--resume", "r1", "--no-save"], io.StringIO(),
                   io.StringIO())
    with pytest.raises(CLIError, match="incompatible"):
        parse_args(["--resume", "r1", "--continue", "r0"], io.StringIO(),
                   io.StringIO())
    # Identity-changing flags are manifest-owned: rejected, not silently
    # discarded.
    with pytest.raises(CLIError, match="saved run's manifest"):
        parse_args(["--resume", "r1", "--models", "a,b"], io.StringIO(),
                   io.StringIO())
    with pytest.raises(CLIError, match="saved run's manifest"):
        parse_args(["--resume", "r1", "--judge", "x"], io.StringIO(),
                   io.StringIO())
