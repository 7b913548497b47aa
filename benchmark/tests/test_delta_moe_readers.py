"""The two readers that came with the Solar-Open2 cell (PR 44), whose counts
go BY LAYER KIND (delta-rule layers, one gated attention layer, an expert
half after each): each on hand-made contexts (the count against hand numbers
at the cell's sizes, a reading, a count that cannot top 100% on a made-up
step at the roofline and reads over it when the counters claim more than the
time allows, nothing without the counters or the named programs or for
another cell's judge)."""

import json
import os

import pytest

from benchmark import peaks, trace_spans
from benchmark.layer_metrics import (
    delta_moe_decode_roofline as decode_roof,
    delta_moe_prefill_roofline as prefill_roof,
    hybrid_latent_moe_decode_roofline, moe_experts_hit_per_step,
    moe_held_pair_share, ssm_scan_live_share)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
JUDGE = "solar-open2"
V5E = peaks.peaks_of("TPU v5 lite")


def config(name: str) -> dict:
    with open(os.path.join(REPO, f"benchmark/configs/{name}.json")) as f:
        return json.load(f)


CONFIG = config("solar-open2-ep8-trio-bf16")
SPEC = CONFIG["models"][JUDGE]
SAFE = trace_spans.name_safe(JUDGE)
DECODE_NAME = f"decode_chunk__{SAFE}__kv384__s16"
LOOP_NAME = f"prefill_chunks_loop__{SAFE}__kv2048"


def batcher(**counters) -> dict:
    return {"batchers": {JUDGE: counters}, "device": {"engines": {JUDGE: {"devices": [0]}}}}


def ctx(after: dict, programs: dict, runs=(), before=None, cfg=CONFIG) -> dict:
    return {
        "config": cfg, "peaks": V5E, "ok": list(runs), "failed": [],
        "stats_before": before or batcher(), "stats_after": after,
        "trace": {"chips": {"/device:TPU:0": {"programs": programs}}},
    }


def program(runs: int, total_s: float) -> dict:
    return {"runs": runs, "total_s": total_s, "mean_ms": total_s / runs * 1e3}


def run_with(judge_prompt_tokens: int) -> dict:
    return {"prompt_tokens": 100,
            "doc": {"timings": {"judge_prompt_tokens": judge_prompt_tokens}}}


# 1,600 steps of six rows at 300 live slots a row; 5.6 distinct held experts
# hit a layer a step; prefill programs that covered 38,000 token slots and
# sent one held pair a slot a layer through the four expert halves
COUNTERS = dict(
    decode_steps=1600, decode_kv_slots_live=1600 * 6 * 300,
    ssm_state_row_steps=1600 * 6, moe_layer_steps=1600 * 4,
    moe_expert_reads=int(1600 * 4 * 5.6), moe_pairs_total=1_000_000,
    moe_pairs_held=125_000, moe_prefill_pairs_held=38_000 * 4,
    ssm_positions_swept=40_000, ssm_positions_live=30_000,
    admit_tokens=30_000, prefill_slot_tokens=38_000,
)


def test_the_count_of_bytes_is_the_table_of_the_issue():
    assert decode_roof.kinds(SPEC) == {"K": 3, "E": 4, "*": 1}
    # a delta layer 137.7 M; the attention layer 109.0 M; an expert half
    # outside its experts 17.04 M; an expert 15.73 M (each with its norm)
    assert decode_roof.delta_params(SPEC) == 137_736_384
    assert decode_roof.delta_matmul_params(SPEC) == (
        4 * 4096 * 8192 + 2 * 128 * (4096 + 8192) + 4096 * 64)
    assert decode_roof.gated_attention_matmul_params(SPEC) + 4096 == 109_056_000
    assert decode_roof.expert_fixed_params(SPEC) == 17_043_776
    assert decode_roof.expert_params(SPEC) == 15_728_640
    fixed = (3 * 137_736_384 + 4 * 17_043_776 + 109_056_000
             + 4096 + 4096 * 24_576)                   # no embedding
    assert decode_roof.fixed_params(SPEC) == fixed
    row = decode_roof.state_bytes_per_row(SPEC, "bfloat16")
    assert row == 3 * (64 * 128 * 128 * 4 + 3 * 3 * 8192 * 2)   # the DELTA layers
    step = decode_roof.step_bytes(SPEC, "bfloat16", 5.6, 6 * 300.0, 6.0)
    experts = 4 * 5.6 * 15_728_640
    cache = 6 * 300 * 2 * 8 * 128 * 1                   # the ATTENTION layer
    assert step == pytest.approx(2 * (fixed + experts + cache) + 2 * 6 * row)
    # the issue's reckoning: 2.29 GB a step, of which the delta layers'
    # leaves and the six rows' states in and out 0.98, the expert halves 0.84
    assert 2.2e9 < step < 2.3e9
    assert 0.97e9 < 2 * 3 * 137_736_384 + 2 * 6 * row < 0.99e9
    assert 0.83e9 < 2 * (4 * 17_043_776 + experts) < 0.85e9
    # what the accepted one-part reader would claim: it knows no K layer
    assert hybrid_latent_moe_decode_roofline.stated(ctx(batcher(**COUNTERS), {})) is None


def test_decode_roofline_reads_and_cannot_top_100_at_the_roofline():
    step = decode_roof.step_bytes(SPEC, "bfloat16", 5.6, 6 * 300.0, 6.0)
    at_roofline_s = step / V5E["hbm_bytes_per_s"]
    c = ctx(batcher(**COUNTERS), {DECODE_NAME: program(10, 10 * 16 * at_roofline_s)})
    assert decode_roof.read(c) == pytest.approx(100.0)
    slower = ctx(batcher(**COUNTERS),
                 {DECODE_NAME: program(10, 10 * 16 * at_roofline_s * 1.25)})
    assert decode_roof.read(slower) == pytest.approx(80.0)
    # counters that claim every held expert read every step, where the
    # step's time allows for the 5.6 that were hit, read over 100: that is
    # how a wrong count shows
    wrong = dict(COUNTERS, moe_expert_reads=1600 * 4 * 40)
    assert decode_roof.read(
        ctx(batcher(**wrong), {DECODE_NAME: program(10, 10 * 16 * at_roofline_s)})) > 105


def test_prefill_roofline_reads_and_cannot_top_100_at_the_roofline():
    runs = [run_with(1700), run_with(1900)]  # mean 1,800 real tokens
    ops = prefill_roof.prefill_ops(SPEC, 1800.0, 1.0)
    rule = 64 * (3 * 128 * 128 + 2.5 * 64 * 128 + 64 * 64 / 6) + 3 * 8192 * 4
    assert prefill_roof.rule_macs_per_token(SPEC) == pytest.approx(rule)
    per_token = (
        3 * (4 * 4096 * 8192 + 2 * 128 * (4096 + 8192) + 4096 * 64 + rule)
        + 4 * (4096 * 320 + 3 * 4096 * 1280 + 1.0 * 15_728_640)
        + 3 * 4096 * 8192 + 2 * 4096 * 1024)
    causal = 1800 * 1801 / 2 * 64 * 2 * 128              # ONE attention layer
    assert ops == pytest.approx(2 * (1800 * per_token + causal + 4096 * 24_576))
    # the chunked rule is a thirtieth of a delta layer's operations
    assert 0.03 < rule / (decode_roof.delta_matmul_params(SPEC) + rule) < 0.04
    at_roofline_s = ops / V5E["bf16_flops_per_s"]
    c = ctx(batcher(**COUNTERS), {LOOP_NAME: program(3, 3 * at_roofline_s)}, runs)
    assert prefill_roof.read(c) == pytest.approx(100.0)
    c = ctx(batcher(**COUNTERS), {LOOP_NAME: program(3, 3 * at_roofline_s * 4)}, runs)
    assert prefill_roof.read(c) == pytest.approx(25.0)
    # runs that claim longer prompts than the time allows for read over 100
    c = ctx(batcher(**COUNTERS), {LOOP_NAME: program(3, 3 * at_roofline_s)},
            [run_with(3600)])
    assert prefill_roof.read(c) > 105
    # bare chunks in the window are parts of prompts: not read
    bare = LOOP_NAME.replace("prefill_chunks_loop", "prefill_chunk")
    c = ctx(batcher(**COUNTERS), {LOOP_NAME: program(3, 1.0), bare: program(2, 0.1)}, runs)
    assert prefill_roof.read(c) is None
    # no run says how long its judge prompt was: not read
    c = ctx(batcher(**COUNTERS), {LOOP_NAME: program(3, 1.0)}, [{"prompt_tokens": 9}])
    assert prefill_roof.read(c) is None


def test_the_accepted_counter_readers_read_this_cell():
    c = ctx(batcher(**COUNTERS), {})
    assert moe_experts_hit_per_step.read(c) == pytest.approx(5.6)
    assert moe_held_pair_share.read(c) == pytest.approx(12.5)
    assert ssm_scan_live_share.read(c) == pytest.approx(75.0)


NOTHING = {
    "no-counters": (batcher(decode_steps=5, decode_kv_slots_live=9), True),
    "the-states-counters-alone": (
        batcher(decode_steps=5, decode_kv_slots_live=9, ssm_state_row_steps=30,
                ssm_positions_swept=9, prefill_slot_tokens=9), True),
    "no-trace-programs": (batcher(**COUNTERS), False),
}


@pytest.mark.parametrize("case", NOTHING)
def test_readers_find_nothing_and_do_not_raise(case):
    """A program without this family's counters; a window that holds no
    judge program."""
    after, with_programs = NOTHING[case]
    programs = {DECODE_NAME: program(4, 0.4), LOOP_NAME: program(2, 0.4)}
    c = ctx(after, programs if with_programs else {}, [run_with(1800)])
    assert decode_roof.read(c) is None and prefill_roof.read(c) is None
    no_trace = dict(c, trace=None)
    assert decode_roof.read(no_trace) is None and prefill_roof.read(no_trace) is None


OTHER_CELLS = (
    "qwen25-trio-bf16", "mistral7b-trio-int8", "mistral7b-trio-bf16-x4",
    "deepseek-v2-ep8-trio-bf16", "falcon-h1-34b-pp8-trio-bf16",
    "nemotron3-super-ep8-trio-bf16")


@pytest.mark.parametrize("name", OTHER_CELLS)
def test_another_cells_judge_reads_nothing(name):
    """Whatever the counters and the trace hold, a judge that states no
    delta-rule layer is not this reader's."""
    other = config(name)
    judge = other["judge"]
    safe = trace_spans.name_safe(judge)
    after = {"batchers": {judge: COUNTERS},
             "device": {"engines": {judge: {"devices": [0]}}}}
    programs = {f"decode_chunk__{safe}__kv384__s16": program(4, 0.4),
                f"prefill_chunks_loop__{safe}__kv2048": program(2, 0.4)}
    c = ctx(after, programs, [run_with(1800)], before={"batchers": {judge: {}}}, cfg=other)
    assert decode_roof.read(c) is None and prefill_roof.read(c) is None


def test_the_names_are_the_programs_names():
    assert trace_spans.program_of(DECODE_NAME)[1] == SAFE
    assert trace_spans.program_of(LOOP_NAME)[1] == SAFE
