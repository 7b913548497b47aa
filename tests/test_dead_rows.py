"""Pool rows without a stream are marked dead on the device, and the
decode sweep leaves them out (engine/batcher.py DEAD_ROW,
ops/pallas/decode_attention.py _sweep_plan).

The load-bearing property stays the batcher's: a stream's tokens are
EXACTLY the single-stream engine's (greedy), whatever its neighbours —
here mostly rows nobody owns — and however the row got its slot: a fresh
pool, a slot retired and reused, a compaction shift, a preemption and
restore, a pool that shrinks and regrows. Both decode routes see the
mark: the XLA route (dh 64, the 0.5B's) through ``row_start`` alone, the
Pallas kernel (dh 128, interpreted here) through its sweep plan.
"""

import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_consensus_tpu.engine import ContinuousBatcher, Engine, SamplingParams
from llm_consensus_tpu.engine.batcher import DEAD_ROW, kv_slots_live
from llm_consensus_tpu.models import get_config, init_params
from llm_consensus_tpu.models.transformer import attention_routes
from llm_consensus_tpu.pressure import PRIORITY_HIGH, PRIORITY_LOW

ROUTES = ["xla", "pallas"]


@pytest.fixture(scope="module", params=ROUTES)
def engine(request):
    """A tiny engine whose decode steps take the named attention route."""
    pallas = request.param == "pallas"
    cfg = replace(
        get_config("tiny-llama", **({"head_dim": 128} if pallas else {})),
        name=f"dead-rows-{request.param}",
    )
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return Engine(cfg, params=params, dtype=jnp.float32, max_seq=256,
                  stream_interval=8, prefill_chunk=16,
                  attn_impl="flash" if pallas else "xla")


def _dead_marks(b) -> list:
    return [int(r) >= DEAD_ROW // 2 for r in np.asarray(b._row_start)]


def test_one_stream_in_a_pool_of_six_through_retire_and_reuse(engine):
    s = SamplingParams(max_new_tokens=20, ignore_eos=True)
    b = ContinuousBatcher(engine, max_batch=6)
    try:
        for prompt in ("a lone stream in six rows", "the slot it gave back",
                       "and once more, longer than both before it"):
            got = b.submit(prompt, s).result(timeout=300)
            assert got.token_ids == engine.generate(prompt, s).token_ids, prompt
            # At the last dispatch one row had a stream: the other five
            # carried the mark, and the live row its real start.
            marks = _dead_marks(b)
            assert sum(marks) == 5, np.asarray(b._row_start)
        # Two at once, one far shorter: its row is dead beside a live one.
        s_short = SamplingParams(max_new_tokens=4, ignore_eos=True)
        f_long = b.submit("the long neighbour", s)
        f_short = b.submit("short", s_short)
        assert f_short.result(timeout=300).token_ids == engine.generate(
            "short", s_short).token_ids
        assert f_long.result(timeout=300).token_ids == engine.generate(
            "the long neighbour", s).token_ids
        route = engine.cfg.name.rsplit("-", 1)[1]
        assert list(attention_routes.snapshot(engine.cfg.name)["decode"]) == [
            route
        ]
    finally:
        b.close()


def test_dead_rows_across_a_compaction_shift(engine):
    """Two staggered streams of six rows push the shared frontier past
    capacity: the window slide moves every row_start, the dead rows' mark
    with it, and the streams after it are still exact.

    The schedule is made certain: every yardstick is generated BEFORE the
    pool starts, so nothing but a ``submit`` stands between one stream's
    ``result()`` and the next stream's start, while the stream beside it has
    thirty tokens to go. (Generating the yardstick in between took longer
    than those thirty tokens whenever the workers shared their cores
    unkindly: the pool idled, the frontier was reset, the next two streams
    started together, and neither the slide nor the 256 steps the assertion
    counted on came about: 240 steps and no slide, PR 34.) And what is
    asserted is the slide itself, not a count of steps that depends on how
    the streams overlapped."""
    s = SamplingParams(max_new_tokens=60, ignore_eos=True)
    s_head = SamplingParams(max_new_tokens=30, ignore_eos=True)
    prompts = [f"staggered stream {i} of the slide" for i in range(8)]
    wants = [engine.generate(p, s_head if i == 0 else s).token_ids
             for i, p in enumerate(prompts)]
    b = ContinuousBatcher(engine, max_batch=6)
    slides = []
    compact = b._compact

    def spy():
        before = b._pos
        compact()
        slides.append((before, b._pos))

    b._compact = spy
    try:
        futs = {0: b.submit(prompts[0], s_head), 1: b.submit(prompts[1], s)}
        nxt = 2
        while futs:
            i = min(futs)
            r = futs.pop(i).result(timeout=600)
            assert r.token_ids == wants[i], prompts[i]
            if nxt < len(prompts):  # the pool never idles: no frontier reset
                futs[nxt] = b.submit(prompts[nxt], s)
                nxt += 1
        # 30 + 7 x 60 tokens, two at a time, behind ~33-token prompts in a
        # 256-slot cache: the frontier reached capacity and slid back.
        assert any(after < before for before, after in slides), slides
    finally:
        b.close()


def test_dead_rows_across_preempt_and_restore(engine, monkeypatch):
    """A HIGH latecomer takes a LOW resident's row in a full pool of two;
    the victim's row is dead until it is restored, and every stream
    still emits its uncontended bytes."""
    monkeypatch.setenv("LLMC_KV_POOL", "0")
    s_low = SamplingParams(max_new_tokens=48, ignore_eos=True)
    s_hi = SamplingParams(max_new_tokens=10, ignore_eos=True)
    lows = [f"low class resident {i} body" for i in range(2)]
    b = ContinuousBatcher(engine, max_batch=2)
    try:
        for _attempt in range(4):
            before = b.snapshot()["preemptions"]
            futs = [b.submit(p, s_low, priority=PRIORITY_LOW) for p in lows]
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline and sum(
                    1 for st in b._slots if st is not None) < 2:
                time.sleep(0.005)
            r_hi = b.submit("high class latecomer", s_hi,
                            priority=PRIORITY_HIGH).result(timeout=300)
            r_low = [f.result(timeout=300) for f in futs]
            assert r_hi.token_ids == engine.generate(
                "high class latecomer", s_hi).token_ids
            for p, r in zip(lows, r_low):
                assert r.token_ids == engine.generate(p, s_low).token_ids, p
            if b.snapshot()["preemptions"] > before:
                break
        assert b.snapshot()["preemptions"] >= 1
    finally:
        b.close()


def test_dead_rows_across_shrink_and_regrow(engine):
    """A pool of 16 shrinks to its 8-row floor while four long streams
    finish and regrows for the next burst: rows move, are cut and are
    zero-padded back, and the marks follow ``_slots`` each time."""
    b = ContinuousBatcher(engine, max_batch=16)
    try:
        assert b._rows_bucket_enabled
        s_short = SamplingParams(max_new_tokens=6, ignore_eos=True)
        s_long = SamplingParams(max_new_tokens=48, ignore_eos=True)
        shorts = [f"short stream number {i}" for i in range(12)]
        longs = [f"long running stream {i}" for i in range(4)]
        futs_s = [b.submit(p, s_short) for p in shorts]
        futs_l = [b.submit(p, s_long) for p in longs]
        for p, f in zip(shorts, futs_s):
            assert f.result(timeout=600).token_ids == engine.generate(
                p, s_short).token_ids, p
        for p, f in zip(longs, futs_l):
            assert f.result(timeout=600).token_ids == engine.generate(
                p, s_long).token_ids, p
        assert b._rows_cap == 8
        assert len(_dead_marks(b)) == 8 and sum(_dead_marks(b)) >= 4
        burst = [f"second burst stream {i}" for i in range(12)]
        futs = [b.submit(p, s_short) for p in burst]
        for p, f in zip(burst, futs):
            assert f.result(timeout=600).token_ids == engine.generate(
                p, s_short).token_ids, p
        assert b._rows_cap == 16
    finally:
        b.close()


def test_kv_slot_counters_book_the_arithmetic(engine):
    """One stream of n prompt tokens and 17 new ones alone in six rows,
    chunks of 8: two dispatches of 8 steps. Forward t of the 16 reads the
    row's n + 1 + t slots, and the sweep spans 6 rows x the 128-slot
    bucket every step."""
    s = SamplingParams(max_new_tokens=17, ignore_eos=True)
    b = ContinuousBatcher(engine, max_batch=6)
    try:
        total_live = 0
        for k, prompt in enumerate(("count my slots", "and this one's too")):
            n = len(engine.tokenizer.encode(prompt))
            assert b.submit(prompt, s).result(timeout=300).token_ids
            total_live += sum(n + 1 + t for t in range(16))
            st = b.snapshot()
            assert st["decode_steps"] == 16 * (k + 1)
            assert st["decode_row_steps"] == 16 * (k + 1)
            assert st["decode_kv_slots_swept"] == 16 * (k + 1) * 6 * 128
            assert st["decode_kv_slots_live"] == total_live
    finally:
        b.close()


@pytest.mark.parametrize(
    "pos,steps,stride,starts,window,want",
    [
        (100, 8, 1, [90], None, sum(11 + t for t in range(8))),
        (100, 8, 1, [90, 0], None,
         sum(11 + t for t in range(8)) + sum(101 + t for t in range(8))),
        (100, 8, 1, [], None, 0),                      # nobody live
        (100, 4, 3, [40], None, 63 + 66 + 69 + 72),    # spec rounds of k+1 = 3
        (100, 8, 1, [0, 95], 64, 8 * 64 + sum(6 + t for t in range(8))),
        (100, 8, 1, [0], 104, 101 + 102 + 103 + 104 * 5),  # binds mid-chunk
    ],
)
def test_kv_slots_live_arithmetic(pos, steps, stride, starts, window, want):
    assert kv_slots_live(pos, steps, stride, starts, window) == want
