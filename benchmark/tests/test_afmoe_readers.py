"""The three readers that came with the Trinity-Mini cell (PR 48), whose
counts go BY LAYER KIND (window and full attention layers, a dense MLP,
expert layers that hold every expert): each on hand-made contexts (the count
against hand numbers at the cell's sizes, a reading, a count that cannot top
100% on a made-up step at the roofline and reads over it when the counters
claim more than the time allows, nothing without the counters or the named
programs or for another cell's judge)."""

import json
import os

import pytest

from benchmark import peaks, trace_spans
from benchmark.layer_metrics import (
    afmoe_decode_roofline as decode_roof, afmoe_prefill_roofline as prefill_roof,
    decode_kv_live_share, delta_moe_decode_roofline,
    hybrid_latent_moe_decode_roofline, moe_experts_hit_per_step,
    moe_held_pair_share, window_sweep_share)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
JUDGE = "trinity-mini"
V5E = peaks.peaks_of("TPU v5 lite")


def config(name: str) -> dict:
    with open(os.path.join(REPO, f"benchmark/configs/{name}.json")) as f:
        return json.load(f)


CONFIG = config("trinity-mini-pp8-trio-bf16")
SPEC = CONFIG["models"][JUDGE]
SAFE = trace_spans.name_safe(JUDGE)
DECODE_NAME = f"decode_chunk__{SAFE}__kv2048__s16"
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


# 1,600 steps of six rows at 2,100 live slots a row for a full layer, of which
# a window layer sweeps 2,048; 41 distinct experts hit a layer a step, every
# pair held; prefill programs that covered 38,000 token slots and sent 8 held
# pairs a slot a layer through the four expert layers
COUNTERS = dict(
    decode_steps=1600, decode_kv_slots_live=1600 * 6 * 2100,
    decode_kv_slots_swept=1600 * 6 * 2176,
    decode_kv_slots_window_layer=1600 * 6 * 2048, moe_layer_steps=1600 * 4,
    moe_expert_reads=1600 * 4 * 41, moe_pairs_total=1_000_000,
    moe_pairs_held=1_000_000, moe_prefill_pairs_held=38_000 * 4 * 8,
    admit_tokens=30_000, prefill_slot_tokens=38_000,
)


def test_the_count_of_bytes_is_the_table_of_the_issue():
    assert decode_roof.kinds(SPEC) == {"W": 4, "*": 1, "D": 1, "E": 4}
    # an attention part 27.27 M, the dense MLP 37.75 M, an expert layer
    # outside its experts 6.56 M, an expert 6.29 M (each with its two norms)
    assert decode_roof.attention_params(SPEC) == 27_267_328
    assert decode_roof.gated_attention_matmul_params(SPEC) == (
        3 * 2048 * 4096 + 2 * 2048 * 512)
    assert decode_roof.dense_matmul_params(SPEC) + 2 * 2048 == 37_752_832
    assert decode_roof.expert_fixed_params(SPEC) == (
        2048 * 128 + 128 + 3 * 2048 * 1024 + 2 * 2048)
    assert decode_roof.expert_params(SPEC) == 6_291_456
    assert decode_roof.expert_fixed_params(SPEC) + 128 * 6_291_456 == 811_864_192
    fixed = (5 * 27_267_328 + 37_752_832 + 4 * decode_roof.expert_fixed_params(SPEC)
             + 2048 + 2048 * 25_024)                   # no embedding
    assert decode_roof.fixed_params(SPEC) == fixed
    step = decode_roof.step_bytes(SPEC, "bfloat16", 41.0, 6 * 2100.0, 6 * 2048.0)
    experts = 4 * 41 * 6_291_456
    cache = 2 * 4 * 128 * (1 * 6 * 2100 + 4 * 6 * 2048)   # a live window a KIND
    assert step == pytest.approx(2 * (fixed + experts + cache))
    # the issue's reckoning: about 2.6 GB a step, the expert layers 2.1 of it
    assert 2.5e9 < step < 2.7e9
    assert 2.0e9 < 2 * (4 * decode_roof.expert_fixed_params(SPEC) + experts) < 2.2e9
    assert cache * 2 < 0.13e9
    # what the accepted one-part readers would claim: they know no W layer
    c = ctx(batcher(**COUNTERS), {})
    assert hybrid_latent_moe_decode_roofline.stated(c) is None
    assert delta_moe_decode_roofline.stated(c) is None


def test_decode_roofline_reads_and_cannot_top_100_at_the_roofline():
    step = decode_roof.step_bytes(SPEC, "bfloat16", 41.0, 6 * 2100.0, 6 * 2048.0)
    at_roofline_s = step / V5E["hbm_bytes_per_s"]
    c = ctx(batcher(**COUNTERS), {DECODE_NAME: program(10, 10 * 16 * at_roofline_s)})
    assert decode_roof.read(c) == pytest.approx(100.0)
    slower = ctx(batcher(**COUNTERS),
                 {DECODE_NAME: program(10, 10 * 16 * at_roofline_s * 1.25)})
    assert decode_roof.read(slower) == pytest.approx(80.0)
    # counters that claim every held expert read every step, where the
    # step's time allows for the 41 that were hit, read over 100: that is
    # how a wrong count shows
    wrong = dict(COUNTERS, moe_expert_reads=1600 * 4 * 128)
    assert decode_roof.read(
        ctx(batcher(**wrong), {DECODE_NAME: program(10, 10 * 16 * at_roofline_s)})) > 105


def test_prefill_roofline_takes_the_larger_of_the_two_bounds():
    runs = [run_with(1700), run_with(1900)]  # mean 1,800 real tokens: 4 chunks
    ops = prefill_roof.prefill_ops(SPEC, 1800.0, 8.0)
    per_token = (
        5 * (3 * 2048 * 4096 + 2 * 2048 * 512) + 3 * 2048 * 6144
        + 4 * (2048 * 128 + 3 * 2048 * 1024 + 8.0 * 6_291_456))
    pairs = 1 * 1800 * 1801 / 2 + 4 * 1800 * 1801 / 2      # all under 2,048
    assert ops == pytest.approx(
        2 * (1800 * per_token + pairs * 32 * 2 * 128 + 2048 * 25_024))
    assert prefill_roof.window_pairs(3000, 2048) == 2048 * 2049 / 2 + 952 * 2048
    assert prefill_roof.window_pairs(2048, 2048) == 2048 * 2049 / 2
    # bytes ONCE A PROMPT, however the program cuts it into chunks: every held
    # leaf outside the embedding, all 128 experts of each expert layer
    moved = prefill_roof.prefill_bytes(SPEC, "bfloat16")
    assert moved == 2 * (decode_roof.fixed_params(SPEC) + 4 * 128 * 6_291_456)
    assert 6.9e9 < moved < 7.0e9
    # at 1,800 tokens the two bounds lie within a tenth: bytes the larger
    ops_s, bytes_s = ops / V5E["bf16_flops_per_s"], moved / V5E["hbm_bytes_per_s"]
    assert 1.0 < bytes_s / ops_s < 1.1
    c = ctx(batcher(**COUNTERS), {LOOP_NAME: program(3, 3 * bytes_s)}, runs)
    assert prefill_roof.read(c) == pytest.approx(100.0)
    c = ctx(batcher(**COUNTERS), {LOOP_NAME: program(3, 3 * bytes_s * 4)}, runs)
    assert prefill_roof.read(c) == pytest.approx(25.0)
    # (a program that streams the experts again for each of four chunks, each
    # at the memory's rate, reads that quarter: the gap the metric shows)
    # a longer prompt is bound by its operations: the larger bound, and runs
    # that claim longer prompts than the time allows read over 100
    long_ops_s = prefill_roof.prefill_ops(SPEC, 3600.0, 8.0) / V5E["bf16_flops_per_s"]
    assert long_ops_s > 1.9 * bytes_s
    c = ctx(batcher(**COUNTERS), {LOOP_NAME: program(3, 3 * bytes_s)}, [run_with(3600)])
    assert prefill_roof.read(c) == pytest.approx(long_ops_s / bytes_s * 100.0)
    assert prefill_roof.read(c) > 105
    # the program's chunk is no part of the count
    chunked = dict(CONFIG, env=dict(CONFIG.get("env") or {}, LLMC_PREFILL_CHUNK="128"))
    assert prefill_roof.read(
        ctx(batcher(**COUNTERS), {LOOP_NAME: program(3, 3 * bytes_s)}, runs, cfg=chunked)
    ) == pytest.approx(100.0)
    # bare chunks in the window are parts of prompts: not read
    bare = LOOP_NAME.replace("prefill_chunks_loop", "prefill_chunk")
    c = ctx(batcher(**COUNTERS), {LOOP_NAME: program(3, 1.0), bare: program(2, 0.1)}, runs)
    assert prefill_roof.read(c) is None
    # no run says how long its judge prompt was: not read
    c = ctx(batcher(**COUNTERS), {LOOP_NAME: program(3, 1.0)}, [{"prompt_tokens": 9}])
    assert prefill_roof.read(c) is None


def test_window_sweep_share_and_the_accepted_counter_readers():
    c = ctx(batcher(**COUNTERS), {})
    assert window_sweep_share.read(c) == pytest.approx(2048 / 2100 * 100)
    assert moe_experts_hit_per_step.read(c) == pytest.approx(41.0)
    assert moe_held_pair_share.read(c) == pytest.approx(100.0)
    # decode_kv_live_share keeps its meaning: what a full layer sweeps
    assert decode_kv_live_share.read(c) == pytest.approx(2100 / 2176 * 100)
    # no row past its window: both kinds sweep alike
    alike = dict(COUNTERS, decode_kv_slots_window_layer=COUNTERS["decode_kv_slots_live"])
    assert window_sweep_share.read(ctx(batcher(**alike), {})) == pytest.approx(100.0)
    none_yet = dict(COUNTERS, decode_kv_slots_live=0, decode_kv_slots_window_layer=0)
    assert window_sweep_share.read(ctx(batcher(**none_yet), {})) is None


NOTHING = {
    "no-counters": (batcher(decode_steps=5, decode_kv_slots_live=9), True),
    "the-parents-counters-alone": (
        batcher(decode_steps=5, decode_kv_slots_live=9, moe_layer_steps=20,
                moe_expert_reads=90, moe_prefill_pairs_held=9,
                prefill_slot_tokens=9), True),
    "no-trace-programs": (batcher(**COUNTERS), False),
}


@pytest.mark.parametrize("case", NOTHING)
def test_readers_find_nothing_and_do_not_raise(case):
    """A program without this family's counters (the parent commit's, which
    cannot state the model at all); a window that holds no judge program."""
    after, with_programs = NOTHING[case]
    programs = {DECODE_NAME: program(4, 0.4), LOOP_NAME: program(2, 0.4)}
    c = ctx(after, programs if with_programs else {}, [run_with(1800)])
    assert decode_roof.read(c) is None
    # the prefill's count needs no counter of this family's own
    assert (prefill_roof.read(c) is None) == (case != "the-parents-counters-alone")
    if case != "no-trace-programs":
        assert window_sweep_share.read(c) is None
    no_trace = dict(c, trace=None)
    assert decode_roof.read(no_trace) is None and prefill_roof.read(no_trace) is None


OTHER_CELLS = (
    "qwen25-trio-bf16", "mistral7b-trio-int8", "mistral7b-trio-bf16-x4",
    "deepseek-v2-ep8-trio-bf16", "falcon-h1-34b-pp8-trio-bf16",
    "nemotron3-super-ep8-trio-bf16", "solar-open2-ep8-trio-bf16")


@pytest.mark.parametrize("name", OTHER_CELLS)
def test_another_cells_judge_reads_nothing(name):
    """Whatever the counters and the trace hold, a judge that states no
    window layer beside a full one is not these readers'."""
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
