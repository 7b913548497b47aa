"""The reduction with the device programs' own scopes beneath the spans
(PR 37, benchmark/trace_scopes.py): a program's time by part; what
benchmark/trace_spans.py says is unchanged. And the six readers that came
with the panel side of `timings`, on hand-made contexts."""

import json
import os

import pytest

from benchmark import trace_scopes, trace_spans
from benchmark.layer_metrics import (
    admit_dispatch_block_ms, judge_prepare_p50_ms, panel_gate_ms_per_step_p50,
    panel_gate_prefill_p50_ms, panel_skew_p50_ms, reply_tail_mean_ms)
from benchmark.tests.test_trace_spans import planes_with_spans

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "traces")
US = 1e3  # ns
DECODE = "decode_chunk__big_3b__kv256__s4"
LOOP = "prefill_chunks_loop__big_3b__kv2048"
JIT = f"jit({DECODE})"


def op(name, start_us, dur_us, path="", nbytes=0):
    """One operation of the `XLA Ops` line as `read_device_lines` gives it."""
    op_name = f"{JIT}/{path}" if path else ""
    return (f"%{name} = bf16[8]{{0}} fusion()", start_us * US, dur_us * US,
            op_name, nbytes)


def decode_run(start_us):
    """One whole run of the decode chunk, 100 us: an entry copy without a
    scope (5), a `while` of 90 that holds the steps' operations (an inner
    `while` over the layers among them, with its own body) and 4 us in
    which nothing runs, then the tail (5)."""
    s = start_us
    return [
        op("copy.1", s, 5, "", 64),
        op("while.9", s + 5, 90, "while"),
        op("fusion.1", s + 5, 10, "while/body/llmc.embed/gather", 100),
        op("while.3", s + 15, 60, "while/body/while"),
        op("fusion.2", s + 15, 20, "while/body/while/body/llmc.attn.proj/dot_general", 2000),
        op("fusion.3", s + 35, 30, "while/body/while/body/llmc.mlp/dot_general", 3000),
        # 10 us of the inner while's own: its condition and bookkeeping
        op("fusion.4", s + 75, 10, "while/body/llmc.head/dot_general", 500),
        op("fusion.5", s + 85, 6, "while/body/llmc.sample/llmc.chunk.tail/argmax", 8),
        # 4 us of the outer while in which no operation ran
        op("fusion.6", s + 95, 5, "llmc.chunk.tail/add", 8),
    ]


def chip(runs, extra_modules=(), extra_ops=()):
    modules = [(f"jit_{DECODE}(77)", s * US, 100 * US) for s in runs]
    modules += list(extra_modules)
    ops = [o for s in runs for o in decode_run(s)] + list(extra_ops)
    return {"name": "/device:TPU:0", "scoped": {"modules": modules, "ops": ops},
            "lines": [{"name": "XLA Ops", "events": [o[:3] for o in ops]},
                      {"name": "XLA Modules", "events": modules}]}


def test_self_time_under_nested_whiles_counts_nothing_twice():
    out, cut = trace_scopes.program_scopes([chip([1000, 2000])])
    p = out["/device:TPU:0"][f"jit_{DECODE}"]
    assert (p["runs"], p["cut_runs"], cut) == (2, 0, 0)
    assert p["total_s"] == pytest.approx(200e-6)
    per_run = {k: v / 2 * 1e6 for k, v in p["scopes"].items()}
    assert per_run == pytest.approx({
        # the entry copy 5, the inner while's own 10, the outer while's 4
        "unscoped": 19.0, "embed": 10.0, "attn.proj": 20.0, "mlp": 30.0,
        "head": 10.0,
        # the innermost llmc. part of a path names it: the tail inside the
        # sampler is the tail's
        "chunk.tail": 11.0, "between_ops": 0.0})
    assert sum(p["scopes"].values()) == pytest.approx(p["total_s"])
    # bytes are the innermost operations' alone: a while counts none
    assert p["bytes"] == {"unscoped": 128, "embed": 200, "attn.proj": 4000,
                          "mlp": 6000, "head": 1000, "chunk.tail": 32}
    assert p["unscoped_top"][0] == ["while.3 bf16[8]", pytest.approx(20e-6)]


def test_time_inside_a_run_with_no_operation_is_between_ops():
    """The module event outlasts its operations: what is left is counted,
    so that the scopes still sum to the program's time."""
    plane = chip([1000])
    name, start, _ = plane["scoped"]["modules"][0]
    plane["scoped"]["modules"] = [
        (name, start, 108 * US), (name, 5000 * US, 108 * US)]
    plane["scoped"]["ops"] += decode_run(5000)
    p = trace_scopes.program_scopes([plane])[0]["/device:TPU:0"][f"jit_{DECODE}"]
    assert p["scopes"]["between_ops"] == pytest.approx(16e-6)
    assert sum(p["scopes"].values()) == pytest.approx(p["total_s"])


def test_a_run_cut_by_the_windows_edge_is_left_out_and_counted():
    """The window opened inside the first run (its first operations are
    missing) and closed inside the last (its tail is)."""
    plane = chip([1000, 2000, 3000])
    ops = plane["scoped"]["ops"]
    head_cut = [o for o in ops if not (o[1] < 1070 * US)]
    both_cut = [o for o in head_cut if not (o[1] >= 3090 * US)]
    plane["scoped"]["ops"] = both_cut
    out, cut = trace_scopes.program_scopes([plane])
    p = out["/device:TPU:0"][f"jit_{DECODE}"]
    assert (p["runs"], p["cut_runs"], cut) == (1, 2, 2)
    assert p["total_s"] == pytest.approx(100e-6)
    # a program's one run that touches the plane's edge cannot be told
    # whole from cut: left out
    lone = chip([1000])
    assert trace_scopes.program_scopes([lone])[1] == 1
    # a run with no operation at all is cut
    empty = chip([1000, 2000])
    empty["scoped"]["modules"].append((f"jit_{DECODE}(77)", 9000 * US, 50 * US))
    assert trace_scopes.program_scopes([empty])[1] == 1


def test_step_split_is_ms_a_step_for_decode_and_ms_a_run_for_prefill():
    loop_runs = [(f"jit_{LOOP}(5)", s * US, 400 * US) for s in (4000, 5000)]
    loop_ops = []
    for s in (4000, 5000):
        loop_ops += [
            (f"%fusion.7 = bf16[8]{{0}} fusion()", s * US, 300 * US,
             f"jit({LOOP})/while/body/llmc.attn.sweep/dot_general", 1_000_000),
            (f"%fusion.8 = bf16[8]{{0}} fusion()", (s + 300) * US, 100 * US,
             f"jit({LOOP})/llmc.head/dot_general", 2_000_000),
        ]
    # a helper program: in program_scopes, not in step_split
    helper = [("jit_broadcast_in_dim(3)", 100 * US, 2 * US)] * 1
    plane = chip([1000, 2000], loop_runs + helper, loop_ops)
    r = trace_scopes.reduce([plane])
    assert set(r["step_split"]) == {f"jit_{DECODE}", f"jit_{LOOP}"}
    d = r["step_split"][f"jit_{DECODE}"]
    assert (d["runs"], d["steps"], d["chips"]) == (2, 4, 1)
    # 100 us a run of four steps
    assert d["total_ms"] == pytest.approx(0.025)
    assert d["ms"]["mlp"] == pytest.approx(0.0075)
    assert d["mb"]["mlp"] == pytest.approx(3000 / 4 / 1e6)
    loop = r["step_split"][f"jit_{LOOP}"]
    assert (loop["runs"], loop["steps"]) == (2, 1)
    assert loop["ms"] == pytest.approx(
        {"attn.sweep": 0.3, "head": 0.1, "between_ops": 0.0})
    assert loop["total_ms"] == pytest.approx(0.4)
    assert "jit_broadcast_in_dim" in r["program_scopes"]["/device:TPU:0"]


def test_a_program_on_two_chips_is_the_mean_of_them():
    a, b = chip([1000, 2000]), chip([1000, 2000, 3000, 4000])
    b["name"] = "/device:TPU:1"
    d = trace_scopes.reduce([a, b])["step_split"][f"jit_{DECODE}"]
    assert (d["chips"], d["runs"]) == (2, 3)
    assert d["total_ms"] == pytest.approx(0.025)
    assert d["ms"]["head"] == pytest.approx(0.0025)


def test_every_key_of_trace_spans_comes_out_unchanged():
    planes = planes_with_spans()
    plain = trace_spans.reduce(planes_with_spans())
    ours = trace_scopes.reduce(planes)
    for key, value in plain.items():
        assert json.dumps(ours[key]) == json.dumps(value), key
    assert set(ours) - set(plain) == {"program_scopes", "step_split", "cut_runs"}
    # planes without the operations' op_name add nothing and break nothing
    assert ours["program_scopes"] == {} and ours["cut_runs"] == 0


@pytest.mark.parametrize("op_name,scope", [
    ("jit(decode_chunk__m__kv128__s16)/while/body/llmc.mlp/dot_general", "mlp"),
    ("jit(f)/llmc.attn.sweep/while/body/llmc.attn.kv_write/dus", "attn.kv_write"),
    ("jit(f)/while/body/closed_call/llmc.ssm.state_write/dynamic_slice",
     "ssm.state_write"),
    ("jit(f)/while/body/dot_general", "unscoped"), ("", "unscoped"),
    ("jit(f)/llmc.moe.experts/ragged_dot:", "moe.experts"),
    # what the chip's compiler makes of a grouped product: its own name
    ("ragged-dot-none", "moe.experts"), ("ragged-dot-metadata", "moe.experts"),
])
def test_the_innermost_part_of_a_path_names_an_operation(op_name, scope):
    assert trace_scopes.scope_of(op_name) == scope


# -- one recorded window ----------------------------------------------------------


TINY = os.path.join(DATA, "tiny_llama_tpu_scopes.xplane.pb")


@pytest.fixture(scope="module")
def tiny():
    return trace_scopes.reduce(trace_scopes.load_xplane(TINY))


def test_the_recorded_window_reads_its_scopes_from_the_files_own_bytes():
    """A TPU window over a two-row pool of `tiny-llama` (PR 37, by hand):
    every operation's `op_name` is in the event metadata's `tf_op` stat."""
    planes = trace_scopes.read_device_lines(TINY)
    assert list(planes) == ["/device:TPU:0"]
    ops = planes["/device:TPU:0"]["ops"]
    named = [o for o in ops if "llmc." in o[3]]
    assert len(named) > 0.8 * len(ops)
    assert any("llmc.attn.sweep" in o[3] for o in ops)


def test_the_recorded_windows_decode_step_splits_into_its_parts(tiny):
    name, = [n for n in tiny["step_split"] if "decode_chunk__tiny_llama" in n]
    split = tiny["step_split"][name]
    assert split["steps"] == 4 and split["runs"] >= 2
    assert sum(split["ms"].values()) == pytest.approx(split["total_ms"])
    for part in ("embed", "norm", "attn.proj", "attn.kv_write", "attn.sweep",
                 "attn.out", "mlp", "head", "layers", "chunk.tail"):
        assert split["ms"].get(part, 0) > 0, part
    # what the older reductions say of the same file is all there
    plain = trace_spans.reduce(trace_spans.load_xplane(TINY))
    for key, value in plain.items():
        assert json.dumps(tiny[key]) == json.dumps(value), key
    chip_programs = tiny["chips"]["/device:TPU:0"]["programs"]
    whole = tiny["program_scopes"]["/device:TPU:0"][name]
    assert whole["runs"] + whole["cut_runs"] == chip_programs[
        next(k for k in chip_programs if k.startswith(name))]["runs"]


# -- the six readers ----------------------------------------------------------------


def run(gate="tpu:b", steps=100, **more):
    panel = [
        {"model": "tpu:a", "lead_in_ms": 0.2, "wall_ms": 900.0, "queue_ms": 30.0,
         "prefill_ms": 170.0, "decode_ms": 690.0, "decode_steps": 112},
        {"model": "tpu:b", "lead_in_ms": 0.4, "wall_ms": 2000.0, "queue_ms": 31.0,
         "prefill_ms": 200.0, "decode_ms": 1750.0, "decode_steps": steps},
    ]
    timings = {"panel_ms": 2035.0, "panel": panel, "panel_gate": gate,
               "panel_skew_ms": 1100.0, "judge_prepare_ms": 34.6, **more}
    return {"doc": {"timings": timings}}


def ctx(ok):
    return {"ok": ok, "config": {"judge": "big"},
            "stats_before": {}, "stats_after": {}}


def test_panel_readers_take_the_gates_entry_of_every_run():
    runs = [run(), run("tpu:a"), run()]
    assert panel_gate_prefill_p50_ms.read(ctx(runs)) == 231.0
    assert panel_gate_ms_per_step_p50.read(ctx(runs)) == 17.5
    assert panel_skew_p50_ms.read(ctx(runs)) == 1100.0
    assert judge_prepare_p50_ms.read(ctx(runs)) == 34.6


@pytest.mark.parametrize("reader", [
    panel_gate_prefill_p50_ms, panel_gate_ms_per_step_p50, panel_skew_p50_ms,
    judge_prepare_p50_ms])
def test_panel_readers_find_nothing_without_timings_panel(reader):
    old = {"doc": {"timings": {"panel_ms": 2035.0, "judge_queue_ms": 33.0}}}
    assert reader.read(ctx([old, {"doc": {}}, {}])) is None
    assert reader.read(ctx([])) is None


def test_a_gate_without_marks_or_without_steps_is_left_out():
    walls_only = run()
    walls_only["doc"]["timings"]["panel"][1] = {
        "model": "tpu:b", "lead_in_ms": 0.4, "wall_ms": 2000.0}
    assert panel_gate_prefill_p50_ms.read(ctx([walls_only])) is None
    assert panel_gate_ms_per_step_p50.read(ctx([walls_only])) is None
    assert panel_gate_ms_per_step_p50.read(ctx([run(steps=0)])) is None
    # the other runs of the window still count
    assert panel_gate_ms_per_step_p50.read(
        ctx([walls_only, run(steps=0), run()])) == 17.5


def stats(singles, dispatch_s, tails, tail_s):
    return {"batchers": {"big": {
        "admit_single_dispatches": singles, "admit_dispatch_s": dispatch_s,
        "admit_alloc_s": 0.01 * singles, "admit_splice_s": 0.001 * singles}},
        "serve": {"reply_tails": tails, "reply_tail_s": tail_s}}


def test_counter_readers_divide_the_windows_deltas():
    c = ctx([])
    c["stats_before"], c["stats_after"] = stats(6, 1.2, 6, 0.2), stats(66, 14.4, 72, 2.84)
    assert admit_dispatch_block_ms.read(c) == pytest.approx(220.0)
    assert reply_tail_mean_ms.read(c) == pytest.approx(40.0)


def test_counter_readers_find_nothing_on_a_program_without_the_counters():
    c = ctx([])
    c["stats_before"] = c["stats_after"] = {
        "batchers": {"big": {"admit_s": 3.0, "prefill_waves": 12}}}
    assert admit_dispatch_block_ms.read(c) is None
    assert reply_tail_mean_ms.read(c) is None
    # the counters are there and nothing went one by one in the window
    c["stats_before"] = c["stats_after"] = stats(6, 1.2, 6, 0.2)
    assert admit_dispatch_block_ms.read(c) is None
    assert reply_tail_mean_ms.read(c) is None
