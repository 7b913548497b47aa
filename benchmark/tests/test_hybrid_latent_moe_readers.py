"""The two readers that came with the Nemotron-3-Super cell (PR 41), whose
counts go BY LAYER KIND: each on hand-made contexts (the count against hand
numbers at the cell's sizes, a reading, a count that cannot top 100% on a
made-up step at the roofline and reads over it when the counters claim more
than the time allows, nothing without the counters or the named programs or
for another cell's judge)."""

import json
import os

import pytest

from benchmark import peaks, trace_spans
from benchmark.layer_metrics import (
    hybrid_latent_moe_decode_roofline as decode_roof,
    hybrid_latent_moe_prefill_roofline as prefill_roof,
    hybrid_ssm_decode_roofline, latent_moe_decode_roofline,
    moe_experts_hit_per_step, moe_held_pair_share, ssm_scan_live_share)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
JUDGE = "nemotron-3-super"
V5E = peaks.peaks_of("TPU v5 lite")


def config(name: str) -> dict:
    with open(os.path.join(REPO, f"benchmark/configs/{name}.json")) as f:
        return json.load(f)


CONFIG = config("nemotron3-super-ep8-trio-bf16")
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


# 1,600 steps of six rows at 300 live slots a row; 14.6 distinct held experts
# hit a layer a step; prefill programs that covered 38,000 token slots and
# sent 2.75 held pairs a slot a layer through the five expert layers
COUNTERS = dict(
    decode_steps=1600, decode_kv_slots_live=1600 * 6 * 300,
    ssm_state_row_steps=1600 * 6, moe_layer_steps=1600 * 5,
    moe_expert_reads=int(1600 * 5 * 14.6), moe_pairs_total=1_000_000,
    moe_pairs_held=125_000, moe_prefill_pairs_held=int(38_000 * 5 * 2.75),
    ssm_positions_swept=40_000, ssm_positions_live=30_000,
    admit_tokens=30_000, prefill_slot_tokens=38_000,
)


def test_the_count_of_bytes_is_the_table_of_the_issue():
    assert decode_roof.kinds(SPEC) == {"M": 5, "E": 5, "*": 1}
    assert decode_roof.conv_channels(SPEC) == 10_240
    # a mixer 109.64 M; an expert layer outside its experts 54.53 M; an
    # expert 5.505 M; the attention layer 35.66 M with its norm
    assert decode_roof.mixer_params(SPEC) == 109_640_064
    assert decode_roof.mixer_matmul_params(SPEC) == 4096 * 18_560 + 8192 * 4096
    assert decode_roof.expert_fixed_params(SPEC) == 54_530_560
    assert decode_roof.expert_params(SPEC) == 5_505_024
    assert decode_roof.attention_matmul_params(SPEC) + 4096 == 35_655_680
    fixed = (5 * 109_640_064 + 5 * 54_530_560 + 35_655_680
             + 4096 + 4096 * 16_384)                   # no embedding
    assert decode_roof.fixed_params(SPEC) == fixed
    row = decode_roof.state_bytes_per_row(SPEC, "bfloat16")
    assert row == 5 * (128 * 64 * 128 * 4 + 3 * 10_240 * 2)      # the MIXER layers
    step = decode_roof.step_bytes(SPEC, "bfloat16", 14.6, 6 * 300.0, 6.0)
    experts = 5 * 14.6 * 5_505_024
    cache = 6 * 300 * 2 * 2 * 128 * 1                   # the ATTENTION layer
    assert step == pytest.approx(2 * (fixed + experts + cache) + 2 * 6 * row)
    # the issue's reckoning: mixers 1.10 GB, expert layers outside their
    # experts 0.55, the experts hit 0.80, state in and out 0.26, head 0.13,
    # attention 0.07 + keys and values: about 2.9 GB a step
    assert 2.85e9 < step < 3.0e9
    assert 0.78e9 < 2 * experts < 0.82e9 and 0.25e9 < 2 * 6 * row < 0.26e9
    # what counting every layer as every kind would claim (the accepted
    # readers' ``n_layers`` x): 11 layers of state, 11 of keys and values
    assert hybrid_ssm_decode_roofline.state_bytes_per_row(SPEC, "bfloat16") == row * 11 / 5


def test_decode_roofline_reads_and_cannot_top_100_at_the_roofline():
    step = decode_roof.step_bytes(SPEC, "bfloat16", 14.6, 6 * 300.0, 6.0)
    at_roofline_s = step / V5E["hbm_bytes_per_s"]
    c = ctx(batcher(**COUNTERS), {DECODE_NAME: program(10, 10 * 16 * at_roofline_s)})
    assert decode_roof.read(c) == pytest.approx(100.0)
    slower = ctx(batcher(**COUNTERS),
                 {DECODE_NAME: program(10, 10 * 16 * at_roofline_s * 1.25)})
    assert decode_roof.read(slower) == pytest.approx(80.0)
    # counters that claim every held expert read every step, where the
    # step's time allows for the 14.6 that were hit, read over 100: that is
    # how a wrong count shows
    wrong = dict(COUNTERS, moe_expert_reads=1600 * 5 * 64)
    assert decode_roof.read(
        ctx(batcher(**wrong), {DECODE_NAME: program(10, 10 * 16 * at_roofline_s)})) > 105


def test_prefill_roofline_reads_and_cannot_top_100_at_the_roofline():
    runs = [run_with(1700), run_with(1900)]  # mean 1,800 real tokens
    ops = prefill_roof.prefill_ops(SPEC, 1800.0, 2.75)
    scan = 8 * 128 * 128 + 128 * (128 * 64 + 2 * 64 * 128) + 4 * 10_240
    assert prefill_roof.scan_macs_per_token(SPEC) == scan
    per_token = (
        5 * (4096 * 18_560 + 8192 * 4096 + scan)
        + 5 * (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 + 2.75 * 5_505_024)
        + 2 * 4096 * 4096 + 2 * 4096 * 256)
    causal = 1800 * 1801 / 2 * 32 * 2 * 128              # ONE attention layer
    assert ops == pytest.approx(2 * (1800 * per_token + causal + 4096 * 16_384))
    # held pairs x 2 x 2 x 1,024 x 2,688: a twelfth of a token's operations
    assert 0.07 < 5 * 2.75 * 5_505_024 / per_token < 0.09
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
    assert moe_experts_hit_per_step.read(c) == pytest.approx(14.6)
    assert moe_held_pair_share.read(c) == pytest.approx(12.5)
    assert ssm_scan_live_share.read(c) == pytest.approx(75.0)


NOTHING = {
    "no-counters": (batcher(decode_steps=5, decode_kv_slots_live=9), True),
    "the-mixers-counters-alone": (
        batcher(decode_steps=5, decode_kv_slots_live=9, ssm_state_row_steps=30,
                ssm_positions_swept=9, prefill_slot_tokens=9), True),
    "no-trace-programs": (batcher(**COUNTERS), False),
}


@pytest.mark.parametrize("case", NOTHING)
def test_readers_find_nothing_and_do_not_raise(case):
    """The parent has named programs and none of this family's counters; a
    window can hold no judge program."""
    after, with_programs = NOTHING[case]
    programs = {DECODE_NAME: program(4, 0.4), LOOP_NAME: program(2, 0.4)}
    c = ctx(after, programs if with_programs else {}, [run_with(1800)])
    assert decode_roof.read(c) is None and prefill_roof.read(c) is None
    no_trace = dict(c, trace=None)
    assert decode_roof.read(no_trace) is None and prefill_roof.read(no_trace) is None


OTHER_CELLS = (
    "qwen25-trio-bf16", "mistral7b-trio-int8", "mistral7b-trio-bf16-x4",
    "deepseek-v2-ep8-trio-bf16", "falcon-h1-34b-pp8-trio-bf16")


@pytest.mark.parametrize("name", OTHER_CELLS)
def test_another_cells_judge_reads_nothing(name):
    """Whatever the counters and the trace hold, a judge that states no
    pattern of one-part layers is not this reader's."""
    other = config(name)
    judge = other["judge"]
    safe = trace_spans.name_safe(judge)
    after = {"batchers": {judge: COUNTERS},
             "device": {"engines": {judge: {"devices": [0]}}}}
    programs = {f"decode_chunk__{safe}__kv384__s16": program(4, 0.4),
                f"prefill_chunks_loop__{safe}__kv2048": program(2, 0.4)}
    c = ctx(after, programs, [run_with(1800)], before={"batchers": {judge: {}}}, cfg=other)
    assert decode_roof.read(c) is None and prefill_roof.read(c) is None


def test_the_accepted_rooflines_read_nothing_of_this_cell_but_its_hybrid_one():
    """Why the cell is on no accepted roofline's list: the latent one asks
    for a latent cache and reads nothing; the hybrid one would READ, with
    every layer counted as a mixer beside attention and an MLP of width 0."""
    step_s = decode_roof.step_bytes(
        SPEC, "bfloat16", 14.6, 6 * 300.0, 6.0) / V5E["hbm_bytes_per_s"]
    c = ctx(batcher(**COUNTERS), {DECODE_NAME: program(10, 10 * 16 * step_s)})
    assert latent_moe_decode_roofline.read(c) is None
    assert hybrid_ssm_decode_roofline.read(c) > 105


def test_the_names_are_the_programs_names():
    assert trace_spans.program_of(DECODE_NAME)[1] == SAFE
    assert trace_spans.program_of(LOOP_NAME)[1] == SAFE
