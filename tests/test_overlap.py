"""Prefill/decode overlap (engine sessions + batcher interleave + judge shim).

One mechanism at two layers: prefill never stalls an active decode
frontier. (1) Interleaved admission in the continuous batcher
(LLMC_PREFILL_BUDGET): a new wave's prefill chunks dispatch BETWEEN
decode chunks — token streams must stay byte-identical to the classic
stall-the-pool admission AND to the single-stream engine. (2) Incremental
judge prefill (Engine.PrefillSession + consensus/overlap.py): the judge
prompt appends to a growing KV as panel answers arrive — parity with the
one-shot prefill, arrival-order determinism, the single-response
shortcut, and a classic fallback whenever the incremental path can't
honor the contract. Flag-off ⇒ both layers are byte-for-byte the classic
path (the PR's determinism guard).
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_consensus_tpu import obs
from llm_consensus_tpu.consensus import (
    Judge,
    NoResponsesError,
    make_overlap_judge,
    render_judge_prompt,
)
from llm_consensus_tpu.engine import ContinuousBatcher, Engine, SamplingParams
from llm_consensus_tpu.models import get_config, init_params
from llm_consensus_tpu.providers.base import Response
from llm_consensus_tpu.utils import Context


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("tiny-llama")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return Engine(cfg, params=params, dtype=jnp.float32, max_seq=256,
                  stream_interval=8, prefill_chunk=16)


# -- interleaved admission (batcher) ----------------------------------------


LONG_PROMPT = "resident stream that keeps decoding while admissions land"
LATE_PROMPTS = [
    "late admission alpha beta gamma delta epsilon zeta eta theta",
    "a different late stream with its own rather longer prompt text",
]


def _run_pool(engine, budget):
    """One resident stream decodes; two late streams admit mid-flight."""
    s_long = SamplingParams(max_new_tokens=96, ignore_eos=True)
    s_late = SamplingParams(max_new_tokens=12, ignore_eos=True)
    b = ContinuousBatcher(engine, max_batch=4, prefill_budget=budget)
    try:
        streamed = threading.Event()
        f_long = b.submit(
            LONG_PROMPT, s_long, on_text=lambda _t: streamed.set()
        )
        assert streamed.wait(timeout=300), "resident stream never decoded"
        futs = [b.submit(p, s_late) for p in LATE_PROMPTS]
        results = [f_long.result(timeout=300)]
        results += [f.result(timeout=300) for f in futs]
    finally:
        b.close()
    return results


def test_interleaved_admission_byte_identical(engine):
    """Interleaved admission under concurrent decode: every stream's
    tokens are byte-identical to the classic (budget-0) pool AND to the
    single-stream engine — and the interleave path actually ran."""
    rec = obs.Recorder()
    obs.install(rec)
    try:
        interleaved = _run_pool(engine, budget=32)
    finally:
        obs.install(None)
    classic = _run_pool(engine, budget=0)

    s_long = SamplingParams(max_new_tokens=96, ignore_eos=True)
    s_late = SamplingParams(max_new_tokens=12, ignore_eos=True)
    refs = [engine.generate(LONG_PROMPT, s_long)]
    refs += [engine.generate(p, s_late) for p in LATE_PROMPTS]

    for got, ref in zip(interleaved, refs):
        assert got.token_ids == ref.token_ids
        assert got.finish_reason == ref.finish_reason
    for got, ref in zip(classic, refs):
        assert got.token_ids == ref.token_ids
    # The wave really was paced between decode chunks, not admitted
    # classically (a classic wave's pool.admit is not ``interleaved``).
    credits = [
        e for e in rec.events()
        if e.name == "pool.admit" and e.args.get("interleaved")
    ]
    assert credits and credits[-1].args["done"] is True
    assert credits[-1].args["slot_tokens"] >= credits[-1].args["tokens_real"]


def test_admission_session_paced_equals_one_shot(engine):
    """AdmissionPrefill.step pacing changes WHEN chunks dispatch, never
    what they compute: logits bitwise-equal to the classic drive."""
    rows = [
        list(engine.tokenizer.encode("first admission row with padding")),
        list(engine.tokenizer.encode("second, rather longer, admission row text here")),
    ]
    ll_ref, _cache_ref = engine._prefill_rows([list(r) for r in rows])
    sess = engine.admission_session([list(r) for r in rows])
    steps = 0
    while not sess.step(8):  # tiny budget: many paced calls
        steps += 1
        assert steps < 100
    ll, _cache, width = sess.finish()
    assert width == engine._rows_bucket(max(len(r) for r in rows))
    np.testing.assert_array_equal(
        np.asarray(ll, np.float32), np.asarray(ll_ref, np.float32)
    )


# -- incremental prefill session (engine) -----------------------------------


def test_prefill_session_logits_parity(engine):
    """Append-built KV produces the same last-token logits as the
    one-shot chunked prefill (growing kv_width buckets may reassociate
    float sums — tolerance, not bitwise)."""
    ids = list(engine.tokenizer.encode("parity probe " * 8))[:48]  # 3 chunks
    ll_ref, _ = engine._prefill_ids(list(ids))
    sess = engine.prefill_session()
    sess.append(ids[:10])
    sess.append(ids[10:33])
    sess.append(ids[33:])
    assert sess.prefilled == 48 and sess.tokens == 48
    np.testing.assert_allclose(
        np.asarray(sess._last_logits, np.float32),
        np.asarray(ll_ref, np.float32),
        rtol=2e-4, atol=2e-4,
    )


def test_prefill_session_generate_matches_one_shot(engine):
    """Uneven appends + residue chunk + decode == the classic
    generate_ids path, token for token."""
    ids = list(engine.tokenizer.encode(
        "session decode parity prompt, with some length to it"
    ))
    s = SamplingParams(max_new_tokens=16, ignore_eos=True)
    ref = engine.generate_ids(list(ids), s)
    sess = engine.prefill_session()
    for i in range(0, len(ids), 13):
        sess.append(ids[i:i + 13])
    got = sess.generate(s)
    assert got.token_ids == ref.token_ids
    assert got.text == ref.text
    assert got.prompt_tokens == len(ids)
    with pytest.raises(RuntimeError):
        sess.generate(s)  # single-use: the cache was donated away


def test_prefill_session_append_text_single_bos(engine):
    """Pieces concatenate into ONE prompt: only the first piece keeps
    its BOS — the session's token stream must equal the one-shot encode
    of the concatenation (a BOS per block would condition the judge on
    tokens render_judge_prompt's render never contains)."""
    sess = engine.prefill_session()
    sess.append_text("first piece ")
    sess.append_text("second piece ")
    sess.append_text("third")
    one_shot = engine.tokenizer.encode("first piece second piece third")
    assert sess._ids == list(one_shot)


def test_prefill_session_overflow_flags(engine):
    sess = engine.prefill_session()
    sess.append([1] * (engine.max_seq + 5))
    assert sess.overflowed
    with pytest.raises(ValueError):
        sess.generate(SamplingParams(max_new_tokens=4, ignore_eos=True))


def test_prefill_session_non_multiple_capacity_overflows():
    """max_seq that is not a chunk multiple: a prompt whose final padded
    chunk would end past capacity must flag overflow (clamped
    dynamic_update_slice would otherwise silently shift the write onto
    earlier positions and corrupt the cache) — while chunk-covered
    lengths still work."""
    cfg = get_config("tiny-llama")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = Engine(cfg, params=params, dtype=jnp.float32, max_seq=72,
                 stream_interval=8, prefill_chunk=16)  # 72 % 16 != 0
    sess = eng.prefill_session()
    sess.append([1] * 70)  # legal classic prompt; ceil(70/16)*16 = 80 > 72
    assert sess.overflowed
    ok = eng.prefill_session()
    ok.append([1] * 60)  # ceil(60/16)*16 = 64 <= 72
    assert not ok.overflowed
    out = ok.generate(SamplingParams(max_new_tokens=4, ignore_eos=True))
    assert len(out.token_ids) == 4


# -- judge overlap shim ------------------------------------------------------


class _EngineProvider:
    """Minimal provider over one (float32, deterministic) engine: the
    overlap shim's engine hook plus the classic query path its fallback
    delegates to — both sides of every equality assert run the SAME
    engine, so greedy comparisons don't ride bf16 near-ties."""

    name = "tpu"

    def __init__(self, engine):
        self._engine = engine
        self._ignore_eos = False
        self.stats = {"tokens": 0, "runs": 0}
        self._lock = threading.Lock()

    def _engine_for(self, model):
        return self._engine

    def query(self, ctx, req):
        return self.query_stream(ctx, req, None)

    def query_stream(self, ctx, req, callback):
        s = SamplingParams(
            max_new_tokens=req.max_tokens if req.max_tokens else 64,
            temperature=0.0,
        )
        result = self._engine.generate(req.prompt, s, ctx, on_text=callback)
        return Response(
            model=req.model, content=result.text, provider=self.name,
            truncated=result.truncated_prompt,
        )


@pytest.fixture(scope="module")
def provider():
    cfg = get_config("tiny-llama")
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    eng = Engine(cfg, params=params, dtype=jnp.float32, max_seq=2048,
                 stream_interval=8, prefill_chunk=64)
    return _EngineProvider(eng)


PROMPT = "judge overlap probe prompt"
RESP_A = Response(model="model-a", content="alpha answer text", provider="t")
RESP_B = Response(model="model-b", content="beta answer, different", provider="t")


def test_flag_off_is_classic(monkeypatch, provider):
    monkeypatch.delenv("LLMC_JUDGE_OVERLAP", raising=False)
    assert make_overlap_judge(provider, "tpu:tiny-llama", PROMPT) is None
    # Explicit flag wins over the unset env.
    assert make_overlap_judge(
        provider, "tpu:tiny-llama", PROMPT, enabled=True
    ) is not None
    # Providers without an on-device engine (HTTP, broadcast wrappers)
    # never get a shim, flag or no flag.
    from llm_consensus_tpu.providers.base import ProviderFunc

    http = ProviderFunc(lambda ctx, req: Response(
        model=req.model, content="x", provider="fake"))
    assert make_overlap_judge(http, "m", PROMPT, enabled=True) is None


def test_judge_overlap_out_of_order_matches_classic(monkeypatch, provider):
    """Panel answers arriving out of panel-list order: the ARRIVAL order
    is recorded, becomes the judge-prompt order, and matches what the
    classic path produces for that same completion order (the runner's
    responses list IS completion-ordered)."""
    monkeypatch.setenv("LLMC_JUDGE_OVERLAP", "1")
    ov = make_overlap_judge(provider, "tpu:tiny-llama", PROMPT, max_tokens=8)
    assert ov is not None
    ov.on_response(RESP_B)  # B completes before A
    ov.on_response(RESP_A)
    assert [r.model for r in ov.arrival_order] == ["model-b", "model-a"]
    chunks: list = []
    out = ov.synthesize_stream(
        Context.background(), PROMPT, [RESP_B, RESP_A], chunks.append
    )
    assert out and out == "".join(chunks)
    classic = Judge(provider, "tpu:tiny-llama", max_tokens=8).synthesize(
        Context.background(), PROMPT, [RESP_B, RESP_A]
    )
    assert out == classic


def test_judge_overlap_order_mismatch_falls_back(monkeypatch, provider):
    """Streamed order diverging from the responses list (the rare
    outside-the-lock hook race) must not ship a prompt ordered unlike
    the persisted responses: it degrades to the classic path, rendered
    with the GIVEN order."""
    monkeypatch.setenv("LLMC_JUDGE_OVERLAP", "1")
    ov = make_overlap_judge(provider, "tpu:tiny-llama", PROMPT, max_tokens=8)
    ov.on_response(RESP_B)
    ov.on_response(RESP_A)
    out = ov.synthesize_stream(
        Context.background(), PROMPT, [RESP_A, RESP_B], None
    )
    classic = Judge(provider, "tpu:tiny-llama", max_tokens=8).synthesize(
        Context.background(), PROMPT, [RESP_A, RESP_B]
    )
    assert out == classic


def test_judge_overlap_single_response_shortcut(monkeypatch, provider):
    monkeypatch.setenv("LLMC_JUDGE_OVERLAP", "1")
    ov = make_overlap_judge(provider, "tpu:tiny-llama", PROMPT, max_tokens=8)
    ov.on_response(RESP_A)
    chunks: list = []
    out = ov.synthesize_stream(
        Context.background(), PROMPT, [RESP_A], chunks.append
    )
    assert out == RESP_A.content
    assert chunks == [RESP_A.content]  # callback invoked exactly once
    with pytest.raises(NoResponsesError):
        ov.synthesize_stream(Context.background(), PROMPT, [], None)


def test_judge_overlap_unfed_falls_back_classic(monkeypatch, provider):
    """Responses the hook never saw ⇒ the shim degrades to the classic
    path, byte-for-byte (the determinism guard's judge half)."""
    monkeypatch.setenv("LLMC_JUDGE_OVERLAP", "1")
    ov = make_overlap_judge(provider, "tpu:tiny-llama", PROMPT, max_tokens=8)
    out = ov.synthesize_stream(
        Context.background(), PROMPT, [RESP_A, RESP_B], None
    )
    classic = Judge(provider, "tpu:tiny-llama", max_tokens=8).synthesize(
        Context.background(), PROMPT, [RESP_A, RESP_B]
    )
    assert out == classic


def test_judge_overlap_refine_prompt_falls_back(monkeypatch, provider):
    """A synthesis prompt that differs from the one the header was built
    with (refinement rounds) must not ride the stale session."""
    monkeypatch.setenv("LLMC_JUDGE_OVERLAP", "1")
    ov = make_overlap_judge(provider, "tpu:tiny-llama", PROMPT, max_tokens=8)
    ov.on_response(RESP_A)
    ov.on_response(RESP_B)
    other = "a different (refine-round) prompt"
    out = ov.synthesize_stream(
        Context.background(), other, [RESP_A, RESP_B], None
    )
    classic = Judge(provider, "tpu:tiny-llama", max_tokens=8).synthesize(
        Context.background(), other, [RESP_A, RESP_B]
    )
    assert out == classic


def test_runner_on_model_response_feeds_arrival_order(monkeypatch, provider):
    """End-to-end: the runner's on_model_response hook feeds the shim in
    completion order, and synthesis consumes the streamed session."""
    from llm_consensus_tpu.providers.base import ProviderFunc
    from llm_consensus_tpu.providers.registry import Registry
    from llm_consensus_tpu.runner import Callbacks, Runner

    monkeypatch.setenv("LLMC_JUDGE_OVERLAP", "1")
    reg = Registry()
    reg.register("fast", ProviderFunc(lambda ctx, req: Response(
        model=req.model, content="fast answer", provider="fake")))

    import time as _time

    def slow_fn(ctx, req):
        _time.sleep(0.3)
        return Response(model=req.model, content="slow answer", provider="fake")

    reg.register("slow", ProviderFunc(slow_fn))
    ov = make_overlap_judge(provider, "tpu:tiny-llama", PROMPT, max_tokens=8)
    runner = Runner(reg, timeout=30.0)
    result = runner.run(
        Context.background(), ["slow", "fast"], PROMPT,
        callbacks=Callbacks(on_model_response=ov.on_response),
    )
    assert [r.model for r in ov.arrival_order] == ["fast", "slow"]
    out = ov.synthesize_stream(
        Context.background(), PROMPT, result.responses, None
    )
    assert out
    classic = Judge(provider, "tpu:tiny-llama", max_tokens=8).synthesize(
        Context.background(), PROMPT, list(ov.arrival_order)
    )
    assert out == classic


def test_render_judge_prompt_block_contract():
    """The shared block renderer keeps the load-bearing separator format
    (reference judge.go:21-25)."""
    p = render_judge_prompt("q", [RESP_A])
    assert "\n--- Model: model-a | Provider: t ---\nalpha answer text\n" in p
