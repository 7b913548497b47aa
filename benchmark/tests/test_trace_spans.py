"""The reduction with the program's spans on the trace's clock (PR 23,
benchmark/trace_spans.py): each idle gap gets a cause; what
benchmark/trace_reduce.py says is unchanged."""

import json
import os

import pytest

from benchmark import trace_reduce, trace_spans
from benchmark.layer_metrics import (
    decode_row_fill, judge_model_decode_step_dev_ms,
    judge_prefill_p50_ms, judge_prefill_pad_share, judge_queue_p50_ms)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "traces")
MS = 1e6  # ns
BIG, SMALL = "decode_chunk__big_3b__kv256__s16", "decode_chunk__small__kv128__s16"


def span(name, start_ms, dur_ms, **args):
    return {"name": "llmc." + name, "start": start_ms * MS, "dur": dur_ms * MS,
            "args": args}


def planes_with_spans():
    """One chip, 100 ms. Busy 10-30 (big), 40-50 (small), 60-80 (big), so it
    idles 0-10, 30-40, 50-60, 80-100."""
    tpu = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ("fusion.1", 10 * MS, 20 * MS), ("fusion.2", 40 * MS, 10 * MS),
            ("fusion.1", 60 * MS, 20 * MS)]},
        {"name": "XLA Modules", "events": [
            (f"jit_{BIG}(11)", 10 * MS, 20 * MS),
            (f"jit_{SMALL}(22)", 40 * MS, 10 * MS),
            (f"jit_{BIG}(11)", 60 * MS, 20 * MS)]},
    ]}
    host = {"name": "/host:CPU", "lines": [
        # big's scheduler thread: it waits for work until 5, dispatches at
        # 5-8 and 52-56; between, it sits at a gate with no span of its own
        {"name": "python", "events": [("extent", 0.0, 100 * MS)], "spans": [
            span("pool.wait", 0, 5, model="big-3b"),
            span("pool.decode", 5, 3, model="big-3b", steps=16),
            span("pool.decode", 52, 4, model="big-3b", steps=16),
        ]},
        # big's fetch thread: a fetch that blocks across the small program
        # and an emit after it
        {"name": "python", "events": [("extent", 8 * MS, 60 * MS)], "spans": [
            span("pool.fetch", 8, 40, model="big-3b", pure=1),
            span("pool.emit", 48, 4, model="big-3b", tokens=16),
        ]},
        # small's scheduler: admits through the first gap before its program
        {"name": "python", "events": [("extent", 20 * MS, 30 * MS)], "spans": [
            span("pool.admit", 28, 11, model="small", rows_real=1),
            span("worker", 20, 30, model="tpu:small", role="panel", trace="t1"),
        ]},
    ]}
    return [host, tpu]


def test_each_gap_gets_the_span_open_across_it():
    r = trace_spans.reduce(planes_with_spans())
    gaps = {k: v for k, v in r["idle_gaps"]}
    # 0-10 before big's first program: its scheduler waited (no work) until
    # 5, then dispatched 5-8; 8-10 only the fetch thread has a span open
    assert gaps[f"no_work tpu0 none->jit_{BIG}"] == pytest.approx(0.005)
    assert gaps[f"decode tpu0 none->jit_{BIG}"] == pytest.approx(0.003)
    assert gaps[f"fetch tpu0 none->jit_{BIG}"] == pytest.approx(0.002)
    # 30-40 before small's program: small's own scheduler was admitting,
    # and big's fetch (another pool's) does not count
    assert gaps[f"admit tpu0 jit_{BIG}->jit_{SMALL}"] == pytest.approx(0.009)
    assert gaps[f"unattributed tpu0 jit_{BIG}->jit_{SMALL}"] == pytest.approx(0.001)
    # 50-60 before big's next: emit 50-52 (the fetch thread, no scheduler
    # span there), its dispatch 52-56, then nothing
    assert gaps[f"emit tpu0 jit_{SMALL}->jit_{BIG}"] == pytest.approx(0.002)
    assert gaps[f"decode tpu0 jit_{SMALL}->jit_{BIG}"] == pytest.approx(0.004)
    assert gaps[f"unattributed tpu0 jit_{SMALL}->jit_{BIG}"] == pytest.approx(0.004)
    # 80-100 after the last program: no span at all
    assert gaps[f"unattributed tpu0 jit_{BIG}->none"] == pytest.approx(0.020)
    by_cause = r["idle_by_cause"]
    assert sum(by_cause.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert by_cause == pytest.approx({
        "no_work": 0.005, "decode": 0.007, "fetch": 0.002, "admit": 0.009,
        "emit": 0.002, "unattributed": 0.025})
    assert r["idle_attributed_share"] == pytest.approx(50.0)
    # what trace_reduce.py says of the same planes is all there, as it says it
    plain = trace_reduce.reduce(planes_with_spans())
    for key in set(plain) - {"idle_gaps", "idle_gap_detail"}:
        assert json.dumps(r[key]) == json.dumps(plain[key]), key
    assert sum(v for _, v in plain["idle_gaps"]) == pytest.approx(
        sum(by_cause.values()))


def test_a_gap_beside_no_hot_program_looks_in_every_pool():
    """The window opens on an idle chip and the first program is a helper
    (no pool in its name): every pool waits until one absorbs a burst and
    admits; the waits are no_work for as long as nothing else is open."""
    tpu = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [("fusion.1", 80 * MS, 20 * MS)]},
        {"name": "XLA Modules", "events": [("jit_broadcast_in_dim(5)", 80 * MS, 20 * MS)]},
    ]}
    host = {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [("extent", 0.0, 100 * MS)], "spans": [
            span("pool.wait", 0, 50, model="big-3b"),
            span("pool.absorb", 50, 20, model="big-3b", queued=1),
            span("pool.admit", 70, 15, model="big-3b", rows_real=1),
        ]},
        {"name": "python", "events": [("extent", 0.0, 100 * MS)], "spans": [
            span("pool.wait", 0, 100, model="small"),
        ]},
    ]}
    by_cause = trace_spans.reduce([host, tpu])["idle_by_cause"]
    assert by_cause == pytest.approx(
        {"no_work": 0.050, "absorb": 0.020, "admit": 0.010})


def test_host_spans_count_total_and_self_time():
    spans = trace_spans.reduce(planes_with_spans())["host_spans"]
    assert spans["pool.decode big-3b"] == pytest.approx(
        {"count": 2, "total_s": 0.007, "self_s": 0.007})
    # the admit (28-39) is nested in the worker (20-50) on its thread
    assert spans["worker tpu:small"] == pytest.approx(
        {"count": 1, "total_s": 0.030, "self_s": 0.019})
    assert spans["pool.admit small"]["self_s"] == pytest.approx(0.011)


def test_a_trace_without_spans_reads_as_before():
    """A program that writes no spans (the parent of PR 23): every gap is
    unattributed, and the share has nothing to read."""
    planes = planes_with_spans()
    for line in planes[0]["lines"]:
        del line["spans"]
    r = trace_spans.reduce(planes)
    assert set(r["idle_by_cause"]) == {"unattributed"}
    assert all(k.startswith("unattributed tpu0 ") for k, _ in r["idle_gaps"])
    assert r["host_spans"] == {}
    assert r["idle_attributed_share"] is None
    # the parent's only annotation (arg-less, no pool) changes nothing
    planes[0]["lines"][0]["spans"] = [span("admit_prefill", 28, 11)]
    r = trace_spans.reduce(planes)
    assert set(r["idle_by_cause"]) == {"unattributed"}
    assert r["idle_attributed_share"] is None


def test_old_keys_are_unchanged_on_the_recorded_chip_trace():
    """What PR 22's reduction printed for the recorded trace, key for key
    and byte for byte; its gaps keep their labels (no span, no cause)."""
    with open(os.path.join(DATA, "trio_saturated_250ms.reduced_pr22.json")) as f:
        before = json.load(f)
    r = trace_spans.reduce(trace_spans.load_xplane(
        os.path.join(DATA, "trio_saturated_250ms.xplane.pb")))
    for key in ("window_s", "busy_s", "chips", "device_ops", "device_programs"):
        assert json.dumps(r[key]) == json.dumps(before[key]), key
    assert [k for k, _ in r["idle_gaps"]] == [k for k, _ in before["idle_gaps"]]
    assert r["host_spans"] == {} and set(r["idle_by_cause"]) == {"unattributed"}


def test_a_cpu_trace_recorded_with_program_spans():
    """A DeepProfiler window over a tiny-llama pool on the CPU (PR 23): the
    llmc.* events come out of the .xplane.pb with their arguments, each on
    its thread's line, and the host lines keep their extent."""
    planes = trace_spans.load_xplane(
        os.path.join(DATA, "tiny_pool_cpu_spans.xplane.pb"))
    lines = [ln for p in planes for ln in p["lines"] if ln.get("spans")]
    assert len(lines) == 3  # the caller, the pool's scheduler, its fetch thread
    assert all(len(ln["events"]) == 1 and ln["events"][0][0] == "extent"
               for ln in lines)
    decode = [sp for ln in lines for sp in ln["spans"]
              if sp["name"] == "llmc.pool.decode"]
    assert len(decode) == 2
    assert decode[0]["args"] == {
        "model": "tiny-llama", "steps": 4, "kv_width": 128, "rows_live": 1,
        "rows": 2, "pos": decode[0]["args"]["pos"]}
    spans = trace_spans.reduce(planes)["host_spans"]
    assert spans["pool.fetch tiny-llama"]["count"] == 2
    assert spans["pool.admit tiny-llama"]["count"] == 1
    run, worker = spans["consensus_run"], spans["worker tpu:tiny-llama"]
    assert run["self_s"] == pytest.approx(run["total_s"] - worker["total_s"])


def test_run_by_hand_on_a_trace_file(tmp_path):
    """run.py does not call trace_spans.py: it is run on a window's trace."""
    out = tmp_path / "spans.json"
    assert trace_spans.main(["trace_spans.py", os.path.join(
        DATA, "tiny_pool_cpu_spans.xplane.pb"), str(out)]) == 0
    r = json.loads(out.read_text())
    assert "pool.decode tiny-llama" in r["host_spans"]
    # a CPU trace has no device plane: no gap, so no share
    assert r["idle_by_cause"] == {} and r["idle_attributed_share"] is None
    assert trace_spans.main(["trace_spans.py"]) == 2


# -- the readers ---------------------------------------------------------------


def test_program_names_say_model_width_and_steps():
    assert trace_spans.program_of("jit_decode_chunk__qwen2_5_3b__kv2048__s16(77)") == (
        "decode_chunk", "qwen2_5_3b", 2048, 16)
    assert trace_spans.program_of("jit_prefill_chunks_loop__mistral_7b__kv0") == (
        "prefill_chunks_loop", "mistral_7b", 0, None)
    assert trace_spans.program_of("jit_prefill_chunk__tiny_llama__kv512(3)")[0] == "prefill_chunk"
    assert trace_spans.program_of("jit__decode_chunk(11)") is None
    assert trace_spans.name_safe("qwen2.5-3b") == "qwen2_5_3b"


def ctx_with(programs=None, before=None, after=None, ok=()):
    return {
        "trace": {"chips": {"/device:TPU:0": {"programs": programs or {}}}},
        "config": {"judge": "big-3b", "panel": ["small", "big-3b"],
                   "serve": {"max_batch": 6}},
        "stats_before": {"batchers": {"big-3b": before or {}}},
        "stats_after": {"batchers": {"big-3b": after or {}},
                        "device": {"engines": {"big-3b": {"devices": [0]}}}},
        "ok": list(ok),
    }


def test_judge_decode_step_is_read_by_name_and_agrees_with_the_step_counter():
    """Two widths at 16 steps and one chunk clamped to 8, beside another
    model's slower-looking programs: only the judge's names count, and the
    steps the names say are the steps the pool counted."""
    programs = {
        "jit_decode_chunk__big_3b__kv256__s16(1)": {"runs": 3, "total_s": 0.480, "mean_ms": 160.0},
        "jit_decode_chunk__big_3b__kv2048__s16(2)": {"runs": 2, "total_s": 0.352, "mean_ms": 176.0},
        "jit_decode_chunk__big_3b__kv2048__s8(3)": {"runs": 1, "total_s": 0.088, "mean_ms": 88.0},
        "jit_decode_chunk__small__kv128__s16(4)": {"runs": 9, "total_s": 1.8, "mean_ms": 200.0},
        "jit_prefill_chunk__big_3b__kv2048(5)": {"runs": 4, "total_s": 0.8, "mean_ms": 200.0},
    }
    ctx = ctx_with(programs, before={"decode_steps": 1000},
                   after={"decode_steps": 1088})
    step_ms = judge_model_decode_step_dev_ms.read(ctx)
    assert step_ms == pytest.approx(0.920 / 88 * 1e3)
    steps = sum(s * runs for _, s, runs, _ in
                judge_model_decode_step_dev_ms.judge_decode_programs(ctx))
    assert steps == 1088 - 1000
    assert step_ms * steps / 1e3 == pytest.approx(0.920, rel=0.02)
    # a parent's trace names no model: nothing to read, no raise
    old = ctx_with({"jit__decode_chunk(11)": {"runs": 1, "total_s": 0.1, "mean_ms": 100.0}})
    assert judge_model_decode_step_dev_ms.read(old) is None
    assert judge_model_decode_step_dev_ms.read(dict(ctx, trace=None)) is None


def test_pad_share_and_row_fill_come_from_the_dispatch_site_counters():
    before = {"admit_tokens": 100, "prefill_slot_tokens": 1000,
              "decode_steps": 160, "decode_row_steps": 320}
    after = {"admit_tokens": 100 + 1700, "prefill_slot_tokens": 1000 + 6 * 4 * 512,
             "decode_steps": 160 + 320, "decode_row_steps": 320 + 960}
    ctx = ctx_with(before=before, after=after)
    assert judge_prefill_pad_share.read(ctx) == pytest.approx(
        (1 - 1700 / 12288) * 100)
    assert decode_row_fill.read(ctx) == pytest.approx(960 / (320 * 6) * 100)
    # the parent's /statsz has neither counter
    old = ctx_with(before={"admit_tokens": 1}, after={"admit_tokens": 9})
    assert judge_prefill_pad_share.read(old) is None
    assert decode_row_fill.read(old) is None
    # an idle window: nothing dispatched, nothing to divide by
    assert judge_prefill_pad_share.read(ctx_with(before=after, after=after)) is None
    assert decode_row_fill.read(ctx_with(before=after, after=after)) is None


def test_judge_waits_are_medians_over_the_completed_runs_timings():
    runs = [{"doc": {"timings": {"judge_queue_ms": q, "judge_prefill_ms": p}}}
            for q, p in ((30.0, 850.0), (10.0, 870.0), (250.0, 900.0))]
    runs.append({"doc": {"consensus": "a run of the parent: no timings"}})
    ctx = ctx_with(ok=runs)
    assert judge_queue_p50_ms.read(ctx) == 30.0
    assert judge_prefill_p50_ms.read(ctx) == 870.0
    assert judge_queue_p50_ms.read(ctx_with(ok=runs[-1:])) is None
    assert judge_prefill_p50_ms.read(ctx_with()) is None
