"""The readers and the reference's limits that came with the DeepSeek-V2
cell (PR 31), on hand-made contexts: a reading, nothing without the
counters, and a count that cannot top 100% on a made-up step at the
roofline."""

import json
import os

import numpy as np
import pytest

from benchmark import peaks
from benchmark.layer_metrics import (
    latent_moe_decode_roofline as decode_roof,
    latent_moe_prefill_roofline as prefill_roof,
    moe_experts_hit_per_step, moe_held_pair_share)
from benchmark.reference import deepseek_v2

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
JUDGE = "deepseek-v2"
V5E = peaks.peaks_of("TPU v5 lite")

with open(os.path.join(REPO, "benchmark/configs/deepseek-v2-ep8-trio-bf16.json")) as f:
    CONFIG = json.load(f)
SPEC = CONFIG["models"][JUDGE]


def batcher(**counters) -> dict:
    return {"batchers": {JUDGE: counters}, "device": {"engines": {JUDGE: {"devices": [0]}}}}


def ctx(after: dict, programs: dict, runs=(), before=None) -> dict:
    return {
        "config": CONFIG, "peaks": V5E, "ok": list(runs), "failed": [],
        "stats_before": before or batcher(), "stats_after": after,
        "trace": {"chips": {"/device:TPU:0": {"programs": programs}}},
    }


def program(runs: int, total_s: float) -> dict:
    return {"runs": runs, "total_s": total_s, "mean_ms": total_s / runs * 1e3}


DECODE = dict(
    moe_layer_steps=5 * 1600, moe_expert_reads=4 * 5 * 1600, decode_steps=1600,
    decode_kv_slots_live=1600 * 6 * 1900, moe_pairs_total=80_000,
    moe_pairs_held=10_000, moe_prefill_pairs_held=6_000,
    prefill_slot_tokens=8_000, admit_tokens=7_000,
)


def test_the_count_of_bytes_is_the_table_of_the_issue():
    assert decode_roof.attention_params(SPEC) == pytest.approx(149.2e6, rel=1e-3)
    assert decode_roof.expert_params(SPEC) == pytest.approx(23.6e6, rel=1e-3)
    # everything but the routed experts: 7.63 GB less 5 x 20 experts
    fixed = decode_roof.fixed_params(SPEC) * 2
    assert fixed + 5 * 20 * decode_roof.expert_params(SPEC) * 2 == pytest.approx(
        7.63e9 - 12800 * 5120 * 2, rel=1e-3)  # the embedding is a gather: not in it
    step = decode_roof.step_bytes(SPEC, "bfloat16", 4.0, 6 * 1900)
    assert step == pytest.approx(fixed + 5 * 4 * 47.2e6 + 6 * 1900 * 1152 * 6, rel=1e-3)


def test_decode_roofline_reads_and_cannot_top_100_at_the_roofline():
    step = decode_roof.step_bytes(SPEC, "bfloat16", 4.0, 6 * 1900)
    at_roofline_s = step / V5E["hbm_bytes_per_s"]
    name = f"decode_chunk__{JUDGE.replace('-', '_')}__kv2048__s16"
    # a made-up chunk of 16 steps that took exactly the least time
    c = ctx(batcher(**DECODE), {name: program(10, 10 * 16 * at_roofline_s)})
    assert decode_roof.read(c) == pytest.approx(100.0)
    assert moe_experts_hit_per_step.read(c) == pytest.approx(4.0)
    assert moe_held_pair_share.read(c) == pytest.approx(12.5)
    slower = ctx(batcher(**DECODE), {name: program(10, 10 * 16 * at_roofline_s * 2)})
    assert decode_roof.read(slower) == pytest.approx(50.0)
    # counters that claim more experts read than the step's time allows for
    # read over 100: that is how a wrong count shows
    full = dict(DECODE, moe_expert_reads=20 * 5 * 1600)
    assert decode_roof.read(ctx(batcher(**full), {name: program(10, 10 * 16 * at_roofline_s)})) > 100


NOTHING = {
    "no-counters": (batcher(decode_steps=5), True),
    "no-trace-programs": (batcher(**DECODE), False),
}


@pytest.mark.parametrize("case", NOTHING)
def test_readers_find_nothing_and_do_not_raise(case):
    after, with_programs = NOTHING[case]
    name = f"decode_chunk__{JUDGE.replace('-', '_')}__kv2048__s16"
    c = ctx(after, {name: program(4, 0.4)} if with_programs else {})
    assert decode_roof.read(c) is None and prefill_roof.read(c) is None
    if with_programs:  # the parent: named programs, no moe counters
        assert moe_experts_hit_per_step.read(c) is None
        assert moe_held_pair_share.read(c) is None
    no_trace = dict(c, trace=None)
    assert decode_roof.read(no_trace) is None and prefill_roof.read(no_trace) is None
    # a dense judge (another cell's configuration) reads nothing here
    dense = dict(c, config={**CONFIG, "models": {JUDGE: {"family": "mistral"}}})
    assert decode_roof.read(dense) is None and prefill_roof.read(dense) is None


def test_prefill_roofline_reads_and_cannot_top_100_at_the_roofline():
    runs = [{"prompt_tokens": 100}] * 4
    # 4 runs: the judge pool admitted 4 panel prompts of 100 and 4 judge
    # prompts of 1,800; its programs covered 8,000 slots (padding included)
    after = batcher(**dict(DECODE, admit_tokens=4 * 1900, prefill_slot_tokens=8_000,
                           moe_prefill_pairs_held=8_000 * 5 * 0.75))
    ops = prefill_roof.prefill_ops(SPEC, 1800.0, 0.75)
    # the products of 1,800 tokens, and the causal half of attention
    assert ops > 2 * 1800 * (149.0e6 * 6 + 3 * 5120 * 12288)
    at_roofline_s = ops / V5E["bf16_flops_per_s"]
    name = f"prefill_chunks_loop__{JUDGE.replace('-', '_')}__kv2048"
    c = ctx(after, {name: program(3, 3 * at_roofline_s)}, runs)
    assert prefill_roof.read(c) == pytest.approx(100.0)
    c = ctx(after, {name: program(3, 3 * at_roofline_s * 4)}, runs)
    assert prefill_roof.read(c) == pytest.approx(25.0)
    # bare chunks in the window are parts of prompts: not read
    bare = f"prefill_chunk__{JUDGE.replace('-', '_')}__kv2048"
    c = ctx(after, {name: program(3, 1.0), bare: program(2, 0.1)}, runs)
    assert prefill_roof.read(c) is None


ERRORS = {
    # name: (per-position errors, decoded from, ok)
    "sound": (np.full(64, 0.01), 48, True),
    "every-position-off": (np.full(64, 0.5), 48, False),
    "decoded-positions-off": (np.r_[np.full(48, 0.01), np.full(16, 0.5)], 48, False),
    "another-token": (np.r_[np.full(63, 0.01), 1.41], 48, False),
}


@pytest.mark.parametrize("case", ERRORS)
def test_compared_holds_made_up_error_vectors_to_its_limits(case):
    err, n_prefill, ok = ERRORS[case]
    compared = deepseek_v2.compared(np.asarray(err, np.float64), n_prefill)
    assert compared["rel_err_max"][1] == deepseek_v2.TOLERANCE
    assert all(len(pair) == 2 for pair in compared.values())
    assert all(v <= limit for v, limit in compared.values()) == ok
