"""Admission policy as a pure function (engine/batcher.py).

``plan_admission`` decides what one admission pass admits, where and how,
from integers, id lists and small callables — no engine, thread or
device program. Three kinds of test:

  * the PARENT's own decisions: tests/data/admission_decisions.json was
    recorded by hooking the inline admission pass of the commit before
    the planner existed while it ran the batching, pool-prefix,
    pressure, kv, overlap and sp-prefill suites, reduced to one case per
    distinct shape of decision — the planner reproduces every one;
  * hand-written cases for what those suites do not reach;
  * the fit rule's property: ``fits`` says yes exactly when the row's
    window lies inside the cache.
"""

import json
import os
import random

import pytest

from llm_consensus_tpu.engine.batcher import (
    AdmissionPlan, Pending, fits, idle_frontier, plan_admission,
    singles_cover_fewer, wave_k_pad,
)
from llm_consensus_tpu.engine.engine import _bucket


def _rows_bucket(max_seq: int, chunk: int):
    """``Engine._rows_bucket`` for an engine of this capacity and
    prefill chunk."""
    def rows_bucket(n_max: int) -> int:
        bucket = _bucket(n_max, max_seq)
        if (
            chunk and bucket > chunk
            and -(-bucket // chunk) * chunk <= max_seq
        ):
            bucket = -(-bucket // chunk) * chunk
        return bucket

    return rows_bucket


def _plan(pending, free, pos, max_seq, pool_idle, *, max_batch=8,
          chunk=512, **kw) -> AdmissionPlan:
    return plan_admission(
        pending, free, pos, max_seq, pool_idle, max_batch=max_batch,
        chunk=chunk, bucket=lambda n: _bucket(n, max_seq),
        rows_bucket=_rows_bucket(max_seq, chunk), **kw,
    )


def _ids(n: int, base: int = 0) -> list:
    return [base + i for i in range(n)]


# -- the parent's recorded decisions ------------------------------------------

with open(os.path.join(
    os.path.dirname(__file__), "data", "admission_decisions.json"
)) as _f:
    _RECORDED = json.load(_f)


def _recorded_plan(inp: dict, share: bool) -> AdmissionPlan:
    table = {tuple(q): hit for q, hit in inp["resident"]}
    pending = [
        # With sharing off the record keeps only lengths: the ids are
        # never looked at.
        Pending(_ids(ids) if isinstance(ids, int) else ids, pr, done, mn)
        for ids, pr, done, mn in inp["pending"]
    ]
    return _plan(
        pending, inp["free"], inp["pos"], inp["max_seq"], inp["pool_idle"],
        max_batch=inp["max_batch"], chunk=inp["chunk"],
        prefix_enabled=share and inp["prefix_enabled"],
        prefix_ids=(
            tuple(inp["prefix_ids"]) if inp["prefix_ids"] is not None
            else None
        ),
        prefix_min=inp["prefix_min"],
        # A consult the parent did not make is a KeyError here.
        resident_prefix_len=(
            (lambda ids: table[tuple(ids)]) if inp["kv_pool"] else None
        ),
        sp_degree=inp["sp"], may_interleave=inp["may_interleave"],
    )


@pytest.mark.parametrize("case", range(len(_RECORDED)))
def test_planner_reproduces_recorded_decision(case):
    inp, out = _RECORDED[case]["in"], _RECORDED[case]["out"]
    plan = _recorded_plan(inp, share=True)
    assert len(plan.establish) == out["establish"]
    if out["establish"] and not out["established"]:
        # The scheduler plans again with sharing off when the prefix it
        # was asked to establish did not land.
        plan = _recorded_plan(inp, share=False)
    elif not out["establish"]:
        assert (
            plan.clear_prefix and inp["prefix_ids"] is not None
        ) == out["prefix_cleared"]
    assert plan.wave_p == out["wave_p"]
    assert plan.pos == out["pos"]
    assert [list(a) for a in plan.admitted] == out["admitted"]
    assert list(plan.requeue) == out["requeue"]
    assert sorted(plan.resolve) == out["resolve"]
    # The record's "interleave" is a wave whose paced session opened:
    # the parent never chose that wave's classic route.
    assert plan.interleave == (out["route"] == "interleave")
    if not plan.interleave:
        assert plan.route == out["route"]


# -- what the suites do not reach ---------------------------------------------

def test_tie_in_singles_cover_fewer_goes_row_by_row():
    """Six long rows that fill their bucket cover the same token slots
    as one padded wave or as six rows: the tie goes row by row (PR 26)."""
    rb = _rows_bucket(2048, 512)
    lens = [1700] * 6
    assert sum(-(-n // 512) * 512 for n in lens) == \
        wave_k_pad(6, 6) * rb(1700)
    assert singles_cover_fewer(lens, 6, 512, rb)
    plan = _plan([Pending(_ids(1700)) for _ in lens], range(6), 0, 2048,
                 True, max_batch=6)
    assert [slot for _, slot in plan.admitted] == list(range(6))
    assert plan.pos == 1700 and plan.route == "single"
    # One slot fewer covered by the wave (max_seq no multiple of the
    # chunk: the bucket is not chunk-padded) and it stays a wave.
    assert not singles_cover_fewer(
        [193, 193], 2, 16, _rows_bucket(200, 16))


def test_mixed_wave_stays_one_padded_wave():
    """One row within a prefill chunk beside a long one: the wave is
    weights-bound for that row, so it stays batched; two long rows of
    an eight-row pool go one by one."""
    short = [Pending(_ids(1700)), Pending(_ids(100))]
    plan = _plan(short, range(8), 0, 4096, True)
    assert len(plan.admitted) == 2 and plan.route == "rows"
    both_long = [Pending(_ids(1700)), Pending(_ids(600))]
    plan = _plan(both_long, range(8), 0, 4096, True)
    assert len(plan.admitted) == 2 and plan.route == "single"


def test_candidate_that_widens_the_wave_past_a_member_requeues():
    """Each row fits at its own width; the second would make the shared
    width 1024, which the first row's window (1060 slots in) cannot
    take: it waits, and the wave keeps its width."""
    pending = [Pending(_ids(40)), Pending(_ids(900))]
    assert fits(40, 1100, 64, 2048) and fits(900, 1100, 1024, 2048)
    assert not fits(40, 1100, 1024, 2048)
    plan = _plan(pending, [2, 5], 1100, 2048, False)
    assert plan.admitted == ((0, 2),) and plan.requeue == (1,)
    assert plan.pos == 1100 and plan.route == "rows"


def test_higher_class_overtakes_a_requeued_lower_one():
    """A requeued stream sits at the queue head; a later arrival of a
    higher class still takes the one free row first, and FIFO holds
    within a class behind it."""
    pending = [
        Pending(_ids(30), priority=2),  # requeued last pass, at the head
        Pending(_ids(30), priority=1),
        Pending(_ids(30), priority=0),  # arrived last
        Pending(_ids(30), priority=1),
    ]
    plan = _plan(pending, [3], 64, 2048, False)
    assert plan.admitted == ((2, 3),)
    assert plan.requeue == (1, 3, 0)


def test_expired_and_spent_streams_resolve_without_prefill():
    pending = [
        Pending(_ids(500), done=True),   # deadline passed while queued
        Pending(_ids(40), max_new=0),    # nothing left to decode
        Pending(_ids(20)),
    ]
    plan = _plan(pending, range(8), 0, 2048, True)
    assert plan.resolve == (0, 1)
    # Neither takes a row, stands in the queue's way, nor sets the idle
    # frontier.
    assert plan.admitted == ((2, 0),) and plan.requeue == ()
    assert plan.pos == 20
    # Nothing to admit at all: no route, and the frontier stays.
    plan = _plan(pending[:2], range(8), 77, 2048, True)
    assert plan.resolve == (0, 1) and plan.route is None and plan.pos == 77


def test_busy_pool_joins_the_prefix_only_if_every_candidate_has_it():
    prefix = tuple(_ids(100))
    with_it = Pending(_ids(100) + _ids(30, base=1000))
    without = Pending(_ids(130, base=5000))
    kw = dict(prefix_enabled=True, prefix_ids=prefix, prefix_min=64)
    plan = _plan([with_it, with_it], [1, 2], 200, 2048, False, **kw)
    assert plan.wave_p == 100 and not plan.establish
    assert plan.admitted == ((0, 1), (1, 2)) and plan.route == "rows"
    plan = _plan([with_it, without], [1, 2], 200, 2048, False, **kw)
    # All or nothing: full-prompt rows, and live rows keep their prefix.
    assert plan.wave_p == 0 and not plan.establish
    assert not plan.clear_prefix
    assert plan.admitted == ((0, 1), (1, 2))


def test_radix_consult_establishes_for_a_lone_candidate():
    """No intra-wave sharing to find, but the paged pool already holds
    128 tokens of the prompt: establish those, admit the suffix."""
    ids = _ids(200)
    asked = []

    def resident(prefix):
        asked.append(len(prefix))
        return 128

    kw = dict(prefix_enabled=True, prefix_min=64)
    plan = _plan([Pending(ids)], range(8), 0, 2048, True,
                 resident_prefix_len=resident, **kw)
    assert asked == [199]  # all but the last token may be shared
    assert plan.establish == tuple(ids[:128]) and plan.wave_p == 128
    assert plan.pos == 72 and plan.route == "rows"
    # A resident span under the floor, or no pool: full prompt, and a
    # stale prefix goes.
    for res in (lambda _: 32, None):
        plan = _plan([Pending(ids)], range(8), 0, 2048, True,
                     resident_prefix_len=res, **kw)
        assert not plan.establish and plan.wave_p == 0
        assert plan.clear_prefix and plan.pos == 200


def test_idle_wave_establishes_its_common_prefix():
    shared = _ids(90)
    pending = [Pending(shared + _ids(n, base=1000 * n)) for n in (10, 25)]
    plan = _plan(pending, range(8), 0, 2048, True,
                 prefix_enabled=True, prefix_min=64)
    assert plan.establish == tuple(shared) and plan.wave_p == 90
    assert plan.pos == 25  # the longest SUFFIX
    assert plan.route == "rows"  # suffix waves stay batched
    # Sharing off and an old prefix nobody can use: it goes.
    plan = _plan(pending, range(8), 0, 2048, True, prefix_enabled=False,
                 prefix_ids=tuple(shared))
    assert plan.clear_prefix and plan.wave_p == 0 and plan.pos == 115


def test_routes_of_sp_meshes_and_interleaved_waves():
    pending = [Pending(_ids(40)), Pending(_ids(50))]
    plan = _plan(pending, [0, 1], 100, 2048, False, sp_degree=2,
                 may_interleave=True)
    assert plan.route == "single" and not plan.interleave
    plan = _plan(pending, [0, 1], 100, 2048, False, may_interleave=True)
    assert plan.route == "rows" and plan.interleave


def test_no_leapfrog_behind_a_prompt_longer_than_the_frontier():
    """The head does not fit the busy pool's frontier: nothing behind
    it is admitted, however small."""
    pending = [Pending(_ids(300)), Pending(_ids(10))]
    plan = _plan(pending, [0, 1], 100, 2048, False)
    assert plan.admitted == () and plan.requeue == (0, 1)
    assert plan.route is None


def test_idle_frontier_always_admits_the_queue_head():
    """Two 1.7k prompts of different length in a 2048-slot cache: only
    rows AT the frontier fit, so the frontier is the head's and the
    other waits (the hang found on the chip)."""
    rb = _rows_bucket(2048, 512)
    assert idle_frontier(
        [1650, 1700], False, 2048, lambda n: _bucket(n, 2048), rb) == 1650
    plan = _plan([Pending(_ids(1650)), Pending(_ids(1700))], range(6), 0,
                 2048, True, max_batch=6)
    assert plan.pos == 1650
    assert plan.admitted == ((0, 0),) and plan.requeue == (1,)


# -- the fit rule -------------------------------------------------------------

def test_fits_exactly_when_the_window_lies_inside_the_cache():
    rng = random.Random(27)
    said_yes = 0
    for _ in range(20000):
        max_seq = rng.choice([16, 128, 200, 2048])
        n = rng.randint(1, max_seq + 8)
        pos = rng.randint(0, max_seq + 8)
        width = rng.randint(1, max_seq + 8)
        start = pos - n  # the row's window is [start, start + width)
        inside = 0 <= start and start + width <= max_seq
        assert fits(n, pos, width, max_seq) == inside, (n, pos, width)
        said_yes += inside
    assert 1000 < said_yes < 19000  # both answers were exercised
    # The edges, written out.
    assert fits(5, 5, 16, 16) and not fits(5, 6, 16, 16)
    assert not fits(6, 5, 4, 16)  # longer than the frontier: it waits
