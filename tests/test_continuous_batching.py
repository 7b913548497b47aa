"""Continuous batching (engine/batcher.py).

TPU-build extension — the reference's only concurrency is goroutine
fan-out over HTTP calls (SURVEY.md §2 #2); on-device serving adds slot
admission/eviction mid-flight. The load-bearing property: a stream's
tokens are EXACTLY what the single-stream engine would produce (greedy),
no matter what its slot neighbors are doing.
"""

import threading
import time

import jax
import jax.numpy as jnp
import pytest

from llm_consensus_tpu.engine import ContinuousBatcher, Engine, SamplingParams
from llm_consensus_tpu.engine.batcher import singles_cover_fewer
from llm_consensus_tpu.models import get_config, init_params
from llm_consensus_tpu.utils import Context


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("tiny-llama")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return Engine(cfg, params=params, dtype=jnp.float32, max_seq=256,
                  stream_interval=8)


@pytest.fixture()
def batcher(engine):
    b = ContinuousBatcher(engine, max_batch=2)
    yield b
    b.close()


def _single(engine, prompt, s):
    return engine.generate(prompt, s)


def test_single_submission_matches_single_stream(engine, batcher):
    s = SamplingParams(max_new_tokens=24, ignore_eos=True)
    got = batcher.submit("continuous batching probe", s).result(timeout=300)
    ref = _single(engine, "continuous batching probe", s)
    assert got.token_ids == ref.token_ids
    assert got.text == ref.text
    assert got.finish_reason == ref.finish_reason
    assert got.prompt_tokens == ref.prompt_tokens


def test_concurrent_streams_match_single_stream(engine, batcher):
    s = SamplingParams(max_new_tokens=20, ignore_eos=True)
    prompts = ["first stream", "the second, rather longer, stream prompt"]
    futs = [batcher.submit(p, s) for p in prompts]
    results = [f.result(timeout=300) for f in futs]
    for p, r in zip(prompts, results):
        assert r.token_ids == _single(engine, p, s).token_ids, p


def test_oversubscription_queues_and_completes(engine, batcher):
    """5 streams through 2 slots: later submissions are admitted as
    earlier ones retire, every result still exact."""
    s = SamplingParams(max_new_tokens=12, ignore_eos=True)
    prompts = [f"queued stream number {i}" for i in range(5)]
    futs = [batcher.submit(p, s) for p in prompts]
    for p, f in zip(prompts, futs):
        assert f.result(timeout=300).token_ids == _single(engine, p, s).token_ids


def test_admission_mid_flight(engine, batcher):
    """A stream admitted while another decodes must not perturb it."""
    s_long = SamplingParams(max_new_tokens=48, ignore_eos=True)
    s_short = SamplingParams(max_new_tokens=8, ignore_eos=True)
    f1 = batcher.submit("long running stream", s_long)
    time.sleep(0.3)  # let it start decoding
    f2 = batcher.submit("late arrival", s_short)
    r1, r2 = f1.result(timeout=300), f2.result(timeout=300)
    assert r1.token_ids == _single(engine, "long running stream", s_long).token_ids
    assert r2.token_ids == _single(engine, "late arrival", s_short).token_ids


def test_per_stream_max_new(engine, batcher):
    s8 = SamplingParams(max_new_tokens=8, ignore_eos=True)
    s16 = SamplingParams(max_new_tokens=16, ignore_eos=True)
    f8 = batcher.submit("alpha", s8)
    f16 = batcher.submit("beta", s16)
    assert len(f8.result(timeout=300).token_ids) == 8
    assert len(f16.result(timeout=300).token_ids) == 16


def test_streaming_callback_order(engine, batcher):
    s = SamplingParams(max_new_tokens=10, ignore_eos=True)
    chunks: list[str] = []
    got = batcher.submit(
        "stream text callback", s, on_text=chunks.append
    ).result(timeout=300)
    assert "".join(chunks) == got.text
    assert got.text  # byte tokenizer always yields text


def test_cancellation_does_not_kill_neighbors(engine, batcher):
    s_doomed = SamplingParams(max_new_tokens=220, ignore_eos=True)
    s_live = SamplingParams(max_new_tokens=30, ignore_eos=True)
    ctx = Context.background().with_cancel()
    started = threading.Event()
    f_cancel = batcher.submit(
        "doomed", s_doomed, ctx=ctx, on_text=lambda _t: started.set()
    )
    f_live = batcher.submit("survivor stream", s_live)
    assert started.wait(timeout=120)  # doomed stream is mid-decode
    ctx.cancel()
    r_cancel = f_cancel.result(timeout=300)
    r_live = f_live.result(timeout=300)
    assert r_cancel.finish_reason == "cancelled"
    assert len(r_cancel.token_ids) < 220
    assert r_live.finish_reason == "length"
    assert r_live.token_ids == _single(
        engine, "survivor stream", s_live
    ).token_ids


def test_mismatched_sampling_shape_rejected(engine):
    b = ContinuousBatcher(engine, max_batch=2)
    try:
        b.submit("greedy", SamplingParams(max_new_tokens=4, ignore_eos=True))
        with pytest.raises(ValueError, match="sampling shape"):
            b.submit(
                "sampled",
                SamplingParams(max_new_tokens=4, temperature=0.7),
            )
    finally:
        b.close()


def test_submit_after_close_raises(engine):
    b = ContinuousBatcher(engine, max_batch=1)
    b.close()
    with pytest.raises(RuntimeError, match="closed"):
        b.submit("too late", SamplingParams(max_new_tokens=4))


def test_eos_retires_slot(engine):
    """A stream hitting EOS frees its slot for the queue; ignore_eos=False
    path (tiny models emit eos id 0 quickly from random logits... force it
    by decoding until the byte tokenizer's eos shows up or length caps)."""
    b = ContinuousBatcher(engine, max_batch=1)
    try:
        s = SamplingParams(max_new_tokens=6)  # respects EOS
        r = b.submit("eos probe", s).result(timeout=300)
        ref = engine.generate("eos probe", s)
        assert r.finish_reason == ref.finish_reason
        assert r.token_ids == ref.token_ids
    finally:
        b.close()


def test_many_streams_stress(engine):
    """Submissions from several threads, max_batch=2: all complete, all
    exact. Exercises admission/retire/reuse churn under contention."""
    b = ContinuousBatcher(engine, max_batch=2)
    try:
        s = SamplingParams(max_new_tokens=6, ignore_eos=True)
        prompts = [f"stress prompt {i}" for i in range(8)]
        futs = {}
        lock = threading.Lock()

        def submit(p):
            f = b.submit(p, s)
            with lock:
                futs[p] = f

        threads = [threading.Thread(target=submit, args=(p,)) for p in prompts]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for p, f in futs.items():
            assert f.result(timeout=300).token_ids == engine.generate(p, s).token_ids, p
    finally:
        b.close()


def test_waterline_compaction_gives_fresh_runway(engine):
    """Streams outliving the shared frontier survive via compaction: a
    max_seq-256 engine decoding 3 sequential waves of streams must keep
    every wave exact — without compaction the shared frontier would hit
    capacity and truncate later waves."""
    b = ContinuousBatcher(engine, max_batch=2)
    try:
        s = SamplingParams(max_new_tokens=60, ignore_eos=True)
        # 6 streams x (prompt ~20 + 60 new) >> 256 slots of shared frontier.
        prompts = [f"compaction wave stream {i}" for i in range(6)]
        futs = [b.submit(p, s) for p in prompts]
        for p, f in zip(prompts, futs):
            r = f.result(timeout=300)
            assert r.finish_reason == "length"
            assert r.token_ids == engine.generate(p, s).token_ids, p
    finally:
        b.close()


def test_long_prompt_waits_for_frontier(engine):
    """A prompt longer than the live frontier queues until it fits (or the
    pool idles); it must still come out exact."""
    b = ContinuousBatcher(engine, max_batch=2)
    try:
        s = SamplingParams(max_new_tokens=10, ignore_eos=True)
        short = b.submit("tiny", s)
        long_prompt = "a deliberately much longer prompt " * 4
        longf = b.submit(long_prompt, s)
        assert short.result(timeout=300).token_ids == engine.generate("tiny", s).token_ids
        assert longf.result(timeout=300).token_ids == engine.generate(long_prompt, s).token_ids
    finally:
        b.close()


@pytest.mark.parametrize("order", ["short_first", "long_first"])
def test_idle_wave_of_capacity_wide_prompts_admits_the_queue_head(engine, order):
    """Two prompts whose admission bucket saturates cache capacity
    (bucket(140) = bucket(200) = max_seq = 256) can only sit AT the
    frontier. Resetting an idle pool's frontier to the LONGER one used to
    requeue the shorter queue head, then (no leapfrogging) the longer one
    behind it: nothing admitted, pool still idle, forever — the hang two
    concurrent long judge prompts hit on the chip. The head must be
    admitted; the other waits its turn; both come out exact."""
    b, gate = _gated_batcher(engine, max_batch=4)
    try:
        s = SamplingParams(max_new_tokens=12, ignore_eos=True)
        prompts = ["a" * 140, "b" * 200]
        if order == "long_first":
            prompts.reverse()
        futs = [b.submit(p, s) for p in prompts]
        gate.set()
        for p, f in zip(prompts, futs):
            assert f.result(timeout=120).token_ids == engine.generate(p, s).token_ids
    finally:
        b.close()


def test_cache_tail_exact_parity(engine):
    """A stream whose window reaches cache capacity must emit every token
    the single-stream engine would (1-step tail dispatches), not retire a
    chunk early."""
    b = ContinuousBatcher(engine, max_batch=1)
    try:
        prompt = "tail parity " * 16  # ~190 tokens of a 256-slot cache
        s = SamplingParams(max_new_tokens=500, ignore_eos=True)  # capacity-capped
        r = b.submit(prompt, s).result(timeout=300)
        ref = engine.generate(prompt, s)
        assert r.finish_reason == ref.finish_reason == "length"
        assert r.token_ids == ref.token_ids
    finally:
        b.close()


def test_queued_stream_deadline_resolves_without_admission(engine):
    """A stream whose deadline expires while still queued resolves
    promptly (empty, finish=deadline) instead of hanging until a slot
    frees and paying prefill."""
    b = ContinuousBatcher(engine, max_batch=1)
    try:
        blocker = b.submit(
            "occupies the only slot",
            SamplingParams(max_new_tokens=200, ignore_eos=True),
        )
        ctx = Context.background().with_timeout(0.05)
        time.sleep(0.1)  # expire before any slot frees
        doomed = b.submit(
            "never admitted", SamplingParams(max_new_tokens=50), ctx=ctx
        )
        r = doomed.result(timeout=120)
        assert r.finish_reason == "deadline"
        assert r.token_ids == []
        blocker.result(timeout=300)
    finally:
        b.close()


def test_admission_failure_fails_one_stream_not_the_pool(engine, monkeypatch):
    """A prefill exception fails that stream's Future; the pool keeps
    serving other streams. Both admission prefill forms are poisoned:
    the batched wave falls back to singles, whose failure must land on
    the one bad stream only."""
    b = ContinuousBatcher(engine, max_batch=1)
    try:
        real = type(b.engine)._prefill_ids
        real_rows = type(b.engine)._prefill_rows

        def boom(self, ids):
            if len(ids) < 12:
                raise RuntimeError("injected prefill failure")
            return real(self, ids)

        def boom_rows(self, rows):
            if any(len(r) < 12 for r in rows):
                raise RuntimeError("injected prefill failure")
            return real_rows(self, rows)

        monkeypatch.setattr(type(b.engine), "_prefill_ids", boom)
        monkeypatch.setattr(type(b.engine), "_prefill_rows", boom_rows)
        doomed = b.submit("short", SamplingParams(max_new_tokens=4))
        with pytest.raises(RuntimeError, match="injected prefill failure"):
            doomed.result(timeout=120)
        s = SamplingParams(max_new_tokens=6, ignore_eos=True)
        survivor = b.submit("a long enough healthy prompt", s)
        monkeypatch.undo()
        assert survivor.result(timeout=300).token_ids == engine.generate(
            "a long enough healthy prompt", s
        ).token_ids
    finally:
        monkeypatch.undo()
        b.close()


def test_close_cancels_queued_streams(engine):
    """close() while streams wait in the queue must not leave any Future
    unresolved (a cancelled Future raises CancelledError, never hangs)."""
    from concurrent.futures import CancelledError

    b = ContinuousBatcher(engine, max_batch=1)
    s_long = SamplingParams(max_new_tokens=120, ignore_eos=True)
    running = b.submit("occupies the slot", s_long)
    queued = b.submit("never admitted before close", s_long)
    time.sleep(0.2)
    b.close()
    running.result(timeout=300)  # in-flight stream finishes
    try:
        r = queued.result(timeout=10)  # either cancelled or cleanly run
        assert r.token_ids is not None
    except CancelledError:
        pass


def test_fifo_fairness_no_leapfrog(engine):
    """Once a stream is requeued (frontier/capacity), later arrivals must
    not be admitted ahead of it — under sustained short-prompt load a
    long prompt would otherwise starve until the pool drained."""
    b = ContinuousBatcher(engine, max_batch=2)
    try:
        s = SamplingParams(max_new_tokens=40, ignore_eos=True)
        first_text_at: dict = {}

        def mark(name):
            def cb(_chunk):
                first_text_at.setdefault(name, time.monotonic())
            return cb

        # Occupy one slot; its decode advances the shared frontier.
        a = b.submit("x", s, on_text=mark("a"))
        # B's prompt exceeds the young frontier -> requeued for a while.
        long_prompt = "deliberately long prompt " * 2
        bb = b.submit(long_prompt, s, on_text=mark("b"))
        # C arrives later; a free slot exists, but admitting C before B
        # would be the starvation bug.
        cc = b.submit("y", s, on_text=mark("c"))

        ra, rb, rc = (f.result(timeout=300) for f in (a, bb, cc))
        assert ra.token_ids == engine.generate("x", s).token_ids
        assert rb.token_ids == engine.generate(long_prompt, s).token_ids
        assert rc.token_ids == engine.generate("y", s).token_ids
        assert first_text_at["b"] <= first_text_at["c"], (
            "later short prompt leapfrogged a requeued long prompt"
        )
    finally:
        b.close()


def test_tp_sharded_batcher_token_exact():
    """Continuous batching under a TP mesh (the sharded judge's serving
    path): splice/compact touch only slot/position axes, which TP never
    shards, so GSPMD partitions the whole pool — output must be
    token-exact vs the same sharded engine single-stream, including
    through waterline compactions (sequential waves push the shared
    frontier past max_seq=96 with live rows whose row_start > 0, so
    _compact_cache's traced roll actually executes on the sharded
    cache — two equal streams alone would compute shift = 0 and never
    compact)."""
    import numpy as np
    from jax.sharding import Mesh

    from llm_consensus_tpu.models import get_config, init_params

    cfg = get_config("tiny-llama")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))
    eng = Engine(cfg, params=params, dtype=jnp.float32, max_seq=96,
                 stream_interval=4, mesh=mesh)
    b = ContinuousBatcher(eng, max_batch=2)
    try:
        s = SamplingParams(max_new_tokens=24, ignore_eos=True)
        # 6 staggered streams × (~24 prompt + 24 new) >> 96 shared slots.
        prompts = [f"tp sharded wave stream {i}" for i in range(6)]
        futs = [b.submit(p, s, Context.background()) for p in prompts]
        for p, f in zip(prompts, futs):
            ref = eng.generate(p, s)
            assert f.result(timeout=300).token_ids == ref.token_ids, p
    finally:
        b.close()


def test_provider_batching_engages_on_tp_placement():
    """A planned multi-device tp placement routes through the batcher
    (round 2 initially gated this to single-device meshes)."""
    from llm_consensus_tpu.providers.base import Request
    from llm_consensus_tpu.providers.tpu import TPUProvider
    import jax as _jax

    provider = TPUProvider(ignore_eos=True, stream_interval=4, batch_streams=2)
    provider.prepare(["tpu:tiny-llama"], None, devices=_jax.devices()[:2])
    mesh = provider.placement("tpu:tiny-llama")
    assert mesh is not None and mesh.devices.size == 2
    provider.query(
        Context.background(),
        Request(model="tpu:tiny-llama", prompt="tp batched", max_tokens=4),
    )
    assert "tiny-llama" in provider._batchers
    provider.release()


def _gated_batcher(engine, max_batch):
    """Batcher whose scheduler waits on a gate: submissions queued before
    the gate opens form one deterministic admission wave."""
    gate = threading.Event()
    real_loop = ContinuousBatcher._loop

    def gated(self):
        gate.wait(timeout=300)
        real_loop(self)

    ContinuousBatcher._loop = gated
    try:
        b = ContinuousBatcher(engine, max_batch=max_batch)
    finally:
        ContinuousBatcher._loop = real_loop
    return b, gate


def test_burst_batched_admission_exact(engine):
    """A same-instant burst takes the batched-admission path (ONE
    Engine._prefill_rows call for the wave) and every stream is still
    token-exact vs the single-stream engine — including heterogeneous
    prompt lengths that span prefill buckets."""
    b, gate = _gated_batcher(engine, max_batch=4)
    calls = {"rows": 0, "single": 0}
    real_rows = type(engine)._prefill_rows
    real_ids = type(engine)._prefill_ids

    def count_rows(self, rows):
        calls["rows"] += 1
        return real_rows(self, rows)

    def count_ids(self, ids):
        calls["single"] += 1
        return real_ids(self, ids)

    s = SamplingParams(max_new_tokens=12, ignore_eos=True)
    prompts = [
        "a",
        "burst admission stream two",
        "a deliberately rather longer burst admission prompt " * 2,
        "stream four",
    ]
    try:
        type(engine)._prefill_rows = count_rows
        type(engine)._prefill_ids = count_ids
        futs = [b.submit(p, s) for p in prompts]
        gate.set()
        results = [f.result(timeout=300) for f in futs]
        assert calls["rows"] >= 1, "burst did not take batched admission"
        assert calls["single"] == 0, "burst fell back to per-stream prefill"
    finally:
        type(engine)._prefill_rows = real_rows
        type(engine)._prefill_ids = real_ids
        gate.set()
        b.close()
    for p, r in zip(prompts, results):
        assert r.token_ids == engine.generate(p, s).token_ids, p


def test_burst_admission_prefill_failure_falls_back_to_singles(engine):
    """A failing batched prefill degrades to one-by-one admission: the
    wave still completes exactly through the single-stream path."""
    b, gate = _gated_batcher(engine, max_batch=3)
    real_rows = type(engine)._prefill_rows

    def boom(self, rows):
        raise RuntimeError("injected batched prefill failure")

    s = SamplingParams(max_new_tokens=8, ignore_eos=True)
    prompts = [f"fallback wave {i}" for i in range(3)]
    try:
        type(engine)._prefill_rows = boom
        futs = [b.submit(p, s) for p in prompts]
        gate.set()
        for p, f in zip(prompts, futs):
            assert f.result(timeout=300).token_ids == engine.generate(
                p, s
            ).token_ids, p
    finally:
        type(engine)._prefill_rows = real_rows
        gate.set()
        b.close()


def test_burst_batched_admission_int8_kv_exact():
    """Batched admission splices quantized cache trees (codes + scales)
    correctly: int8-KV batcher output matches the same engine's
    single-stream output."""
    cfg = get_config("tiny-llama")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = Engine(cfg, params=params, dtype=jnp.float32, max_seq=256,
                 stream_interval=8, kv_quant="int8")
    b, gate = _gated_batcher(eng, max_batch=3)
    s = SamplingParams(max_new_tokens=10, ignore_eos=True)
    prompts = [f"quantized burst stream {i}" for i in range(3)]
    try:
        futs = [b.submit(p, s) for p in prompts]
        gate.set()
        for p, f in zip(prompts, futs):
            assert f.result(timeout=300).token_ids == eng.generate(
                p, s
            ).token_ids, p
    finally:
        gate.set()
        b.close()


def test_wave_prefix_reuse_across_bursts():
    """Burst waves sharing a multi-chunk prompt prefix re-prefill only
    the tail chunks after the first wave (VERDICT r2 #3: panel prefill
    cost ~1x the shared prompt, not per admission), and stay token-exact
    vs the single-stream engine."""
    import llm_consensus_tpu.engine.engine as eng_mod

    cfg = get_config("tiny-llama")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = Engine(cfg, params=params, dtype=jnp.float32, max_seq=512,
                 stream_interval=8, prefill_chunk=16)
    # 245-247 tokens a row: the two rows of a two-row pool fill their
    # 256-slot bucket. Since ISSUE 26 such a tie goes row by row, so the
    # pool no longer takes this wave through the batched prefill: the
    # reuse is asserted on the batched prefill itself (what mixed waves,
    # suffix waves and interleaved admission still run), the exactness
    # across bursts through the pool.
    # The rows part after ~136 tokens, under LLMC_POOL_PREFIX_MIN: the
    # reuse under test is the engine snapshot's, not the pool's prefix.
    shared = "shared panel prompt prefix " * 5  # ~135 tokens, ~8 chunks
    tail = "y " * 45
    s = SamplingParams(max_new_tokens=6, ignore_eos=True)
    w1 = [shared + f"{i} first wave tail " + tail for i in range(2)]
    w2 = [shared + f"{i} second wave tail " + tail for i in range(2)]
    chunk_calls = []
    real_chunk = eng_mod._prefill_chunk

    def spy(*a, **k):
        chunk_calls.append(1)
        return real_chunk(*a, **k)

    eng_mod._prefill_chunk = spy
    try:
        eng._prefill_rows([eng.tokenizer.encode(p) for p in w1])
        wave1_chunks = len(chunk_calls)
        chunk_calls.clear()
        eng._prefill_rows([eng.tokenizer.encode(p) for p in w2])
        wave2_chunks = len(chunk_calls)
    finally:
        eng_mod._prefill_chunk = real_chunk
    assert 0 < wave2_chunks < wave1_chunks, (wave1_chunks, wave2_chunks)
    b, gate = _gated_batcher(eng, max_batch=2)
    try:
        assert singles_cover_fewer(
            [len(eng.tokenizer.encode(p)) for p in w1],
            b.max_batch, eng.prefill_chunk, eng._rows_bucket)  # the tie
        futs = [b.submit(p, s) for p in w1]
        gate.set()
        r1 = [f.result(timeout=300) for f in futs]
        futs = [b.submit(p, s) for p in w2]
        r2 = [f.result(timeout=300) for f in futs]
    finally:
        gate.set()
        b.close()
    for p, r in zip(w1 + w2, r1 + r2):
        ref = Engine(cfg, params=params, dtype=jnp.float32, max_seq=512,
                     stream_interval=8, prefill_chunk=16).generate(p, s)
        assert r.token_ids == ref.token_ids, p


def _rows(lens, shared=0):
    """Token ids for rows ``lens`` long that part at token ``shared``
    (0: no two rows share even their first token)."""
    head = [3 + j % 190 for j in range(shared)]
    return [head + [5 + (11 * i + 7 * j) % 190 for j in range(n - shared)]
            for i, n in enumerate(lens)]


# chunk 16, so a "long" row is over 16 tokens. Each case: the wave's row
# lengths, the pool's rows, the tokens the wave's rows share, and the
# (route, rows_real, rows_padded, slot_tokens) of every pool.admit span
# the wave must leave, in order.
_ROUTE_CASES = {
    # a lone long row: one row, not six copies of it
    "lone-long": ([40], 6, 0, [("single", 1, 1, 48)]),
    # three long rows of six: 48 + 48 + 64 slots, against 6 x 64 batched
    "three-long-of-six": (
        [40, 37, 50], 6, 0,
        [("single", 1, 1, 48), ("single", 1, 1, 48), ("single", 1, 1, 64)],
    ),
    # two long rows of four: 48 + 64 slots, against 4 x 64 batched
    "two-long-of-four": ([40, 50], 4, 0,
                         [("single", 1, 1, 48), ("single", 1, 1, 64)]),
    # a full wave of long rows that fill their bucket: 6 x 64 either way,
    # and a tie goes row by row (ISSUE 26: the six-row chunk program is
    # the slower one per token, and the one a full pool would now meet)
    "full-wave-long": ([60, 64, 58, 61, 63, 59], 6, 0,
                       [("single", 1, 1, 64)] * 6),
    # the same wave one token over a chunk boundary on one row: 6 x 80
    # batched against 5 x 64 + 80, row by row as before
    "full-wave-long-ragged": ([60, 64, 58, 61, 65, 59], 6, 0,
                              [("single", 1, 1, 64)] * 4
                              + [("single", 1, 1, 80), ("single", 1, 1, 64)]),
    # a lone short row: weights-bound, one padded one-shot wave as ever
    "lone-short": ([10], 6, 0, [("rows", 1, 6, 6 * 16)]),
    # one row within a chunk keeps the whole wave batched
    "short-beside-long": ([12, 40], 6, 0, [("rows", 2, 6, 6 * 64)]),
    # a shared-prefix suffix wave (row by row would cover 2 x 240 slots
    # against 6 x 32): the one-row path cannot join the pool's prefix
    "suffix-wave": ([230, 225], 6, 200, [("rows", 2, 6, 6 * 32)]),
}


@pytest.mark.parametrize("case", list(_ROUTE_CASES))
def test_admission_wave_takes_the_route_that_covers_fewer_slots(case):
    """ISSUE 24: a wave whose rows are each longer than a prefill chunk
    goes row by row when that dispatches fewer token slots than the
    padded wave; every other wave is admitted as before. Whatever the
    route, no attempt fails and the greedy tokens are ``generate_ids``'s."""
    from llm_consensus_tpu.obs import blackbox
    from llm_consensus_tpu.obs.blackbox import FlightRecorder

    lens, max_batch, shared, want = _ROUTE_CASES[case]
    cfg = get_config("tiny-llama")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    ring = FlightRecorder(capacity=1024)
    blackbox.install(ring)  # before the pool: emitters bind at construction
    try:
        eng = Engine(cfg, params=params, dtype=jnp.float32, max_seq=512,
                     stream_interval=8, prefill_chunk=16)
        b, gate = _gated_batcher(eng, max_batch=max_batch)
        s = SamplingParams(max_new_tokens=6, ignore_eos=True)
        rows = _rows(lens, shared)
        try:
            futs = [b.submit_ids(ids, s) for ids in rows]
            gate.set()
            results = [f.result(timeout=300) for f in futs]
            st = b.snapshot()
        finally:
            gate.set()
            b.close()
    finally:
        blackbox.reset()
    admits = [e.args for e in ring.snapshot()
              if e.name == "pool.admit" and e.tid == "pool:tiny-llama"]
    assert all(a["ok"] for a in admits), admits
    assert [(a["route"], a["rows_real"], a["rows_padded"], a["slot_tokens"])
            for a in admits] == want
    assert [a["prefix"] for a in admits] == [shared] * len(want)
    assert st["prefill_waves"] == len(want)
    assert st["prefill_rows_real"] == len(lens)
    assert st["prefill_rows_padded"] == sum(w[2] for w in want)
    assert st["prefill_slot_tokens"] == sum(w[3] for w in want)
    assert st["admit_tokens"] == sum(lens) - shared * len(lens)
    # after the pool's results: generate_ids retains its prompt's KV
    for ids, r in zip(rows, results):
        assert r.token_ids == eng.generate_ids(ids, s).token_ids


def test_large_seed_admission_not_pool_fatal(engine):
    """Seeds >= 2**31 must admit through the batched path (uint32 key
    derivation) instead of killing the scheduler with an int32 overflow."""
    b, gate = _gated_batcher(engine, max_batch=2)
    s = [SamplingParams(max_new_tokens=4, ignore_eos=True, seed=2**31 + i)
         for i in range(2)]
    try:
        futs = [b.submit(f"big seed {i}", s[i]) for i in range(2)]
        gate.set()
        for f in futs:
            assert len(f.result(timeout=300).token_ids) == 4
    finally:
        gate.set()
        b.close()


def test_wave_admission_non_chunk_multiple_capacity():
    """A max_seq that is not a multiple of the prefill chunk forces the
    one-shot wave-prefill path (chunking would floor away tail tokens —
    the round-3 review regression); wave output stays exact."""
    cfg = get_config("tiny-llama")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = Engine(cfg, params=params, dtype=jnp.float32, max_seq=200,
                 stream_interval=8, prefill_chunk=16)
    assert eng._rows_bucket(150) % 16 != 0  # the hazard shape
    b, gate = _gated_batcher(eng, max_batch=2)
    s = SamplingParams(max_new_tokens=6, ignore_eos=True)
    # 193 tokens each: a full wave whose rows fill the 200-slot bucket
    # (padded to chunks they would cover 208), so it stays one wave;
    # shorter rows would be admitted one by one.
    prompts = ["x " * 94 + "yone", "x " * 94 + "ytwo"]
    assert not singles_cover_fewer(
        [len(eng.tokenizer.encode(p)) for p in prompts],
        b.max_batch, eng.prefill_chunk, eng._rows_bucket)
    try:
        futs = [b.submit(p, s) for p in prompts]
        gate.set()
        for p, f in zip(prompts, futs):
            assert f.result(timeout=300).token_ids == eng.generate(
                p, s
            ).token_ids, p
    finally:
        gate.set()
        b.close()


def test_wave_admission_after_compaction_exact():
    """Burst waves keep arriving while earlier waves push the shared
    frontier past capacity: compaction and batched admission must
    compose (the wave splice offsets are computed against the
    post-compaction frontier)."""
    cfg = get_config("tiny-llama")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = Engine(cfg, params=params, dtype=jnp.float32, max_seq=128,
                 stream_interval=8)
    b = ContinuousBatcher(eng, max_batch=2)
    s = SamplingParams(max_new_tokens=40, ignore_eos=True)
    prompts = [f"compaction wave pair stream {i}" for i in range(6)]
    try:
        futs = [b.submit(p, s) for p in prompts]
        for p, f in zip(prompts, futs):
            assert f.result(timeout=300).token_ids == eng.generate(
                p, s
            ).token_ids, p
    finally:
        b.close()


def test_occupancy_bucket_shrinks_and_regrows(monkeypatch):
    """Dead-slot fix: when most of a pool retires, the decode row bucket
    shrinks (live rows compact into low slots) and regrows on the next
    burst — with every stream still exactly matching single-stream
    greedy output across the moves."""
    cfg = get_config("tiny-llama")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = Engine(cfg, params=params, dtype=jnp.float32, max_seq=256,
                 stream_interval=8)
    b = ContinuousBatcher(eng, max_batch=16)
    try:
        assert b._rows_bucket_enabled and b._min_rows == 8
        s_short = SamplingParams(max_new_tokens=6, ignore_eos=True)
        s_long = SamplingParams(max_new_tokens=64, ignore_eos=True)
        prompts_short = [f"short stream number {i}" for i in range(12)]
        prompts_long = [f"long running stream {i}" for i in range(4)]
        futs_s = [b.submit(p, s_short) for p in prompts_short]
        futs_l = [b.submit(p, s_long) for p in prompts_long]
        for p, f in zip(prompts_short, futs_s):
            assert f.result(timeout=600).token_ids == eng.generate(
                p, s_short
            ).token_ids, p
        # Long streams keep decoding at low occupancy: the bucket should
        # shrink to the 8-row floor while they finish.
        results_l = [f.result(timeout=600) for f in futs_l]
        assert b._rows_cap == 8  # shrunk (hysteresis: 3 dispatches at <=50%)
        for p, r in zip(prompts_long, results_l):
            assert r.token_ids == eng.generate(p, s_long).token_ids, p
        # Regrowth: a fresh 12-wide burst needs more than 8 rows.
        prompts2 = [f"second burst stream {i}" for i in range(12)]
        futs2 = [b.submit(p, s_short) for p in prompts2]
        for p, f in zip(prompts2, futs2):
            assert f.result(timeout=600).token_ids == eng.generate(
                p, s_short
            ).token_ids, p
        assert b._rows_cap == 16
    finally:
        b.close()


def test_occupancy_bucket_disabled_by_env(monkeypatch):
    monkeypatch.setenv("LLMC_POOL_BUCKET", "0")
    cfg = get_config("tiny-llama")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = Engine(cfg, params=params, dtype=jnp.float32, max_seq=256,
                 stream_interval=8)
    b = ContinuousBatcher(eng, max_batch=16)
    try:
        assert not b._rows_bucket_enabled
        s = SamplingParams(max_new_tokens=8, ignore_eos=True)
        futs = [b.submit(f"env off {i}", s) for i in range(4)]
        [f.result(timeout=600) for f in futs]
        assert b._rows_cap == 16
    finally:
        b.close()


def test_phase_stats_account_and_overshoot_gate(engine):
    """Per-phase wall accounting (VERDICT r4 #3) plus the overshoot
    gate / final-chunk clamp: a burst whose streams all need fewer
    steps than the in-flight pipeline would otherwise dispatch must
    retire with zero tail dead-stepping and exact token counts."""
    b = ContinuousBatcher(engine, max_batch=4)
    try:
        # max_new=9 with chunk=8: one full chunk (planned 1+8=9) covers
        # the need exactly; the gate must block a second chunk.
        s = SamplingParams(max_new_tokens=9, ignore_eos=True)
        futs = [b.submit(f"gate stream {i}", s) for i in range(4)]
        for i, f in enumerate(futs):
            r = f.result(timeout=300)
            assert len(r.token_ids) == 9
            assert r.token_ids == engine.generate(
                f"gate stream {i}", s
            ).token_ids
        st = b.stats
        for key in ("decode_tokens", "decode_s", "tail_s", "impure_s",
                    "impure_tokens", "establish_s", "admit_s",
                    "admit_tokens", "absorb_s"):
            assert key in st, key
        # Every prompt token admitted must be counted.
        assert st["admit_tokens"] == sum(
            len(engine.tokenizer.encode(f"gate stream {i}"))
            for i in range(4)
        )
        # All covered at the first dispatch: no zero-emit tail chunk.
        assert st["tail_s"] == 0.0
        # Tokens land in decode or impure intervals (plus the 4
        # prefill-sampled firsts, which ride the first chunk's fetch).
        assert st["decode_tokens"] + st["impure_tokens"] <= 9 * 4
    finally:
        b.close()


def test_final_chunk_clamp_non_multiple(engine):
    """max_new not a chunk multiple: the clamped final chunk must not
    cost tokens (exactness) and planned accounting must not stall."""
    b = ContinuousBatcher(engine, max_batch=2)
    try:
        s = SamplingParams(max_new_tokens=11, ignore_eos=True)  # 1+8+2
        f0 = b.submit("clamp alpha", s)
        f1 = b.submit("clamp beta", s)
        for prompt, f in (("clamp alpha", f0), ("clamp beta", f1)):
            r = f.result(timeout=300)
            assert len(r.token_ids) == 11
            assert r.token_ids == engine.generate(prompt, s).token_ids
    finally:
        b.close()
