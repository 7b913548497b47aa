import os

import pytest

from benchmark import trace_reduce
from benchmark.layer_metrics import decode_dev_ms_per_step, device_idle_share

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "traces")
MS = 1e6  # ns


def plane(name, ops, modules):
    return {"name": name, "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": modules},
    ]}


def hand_made():
    host = {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [("extent", 0.0, 100 * MS)]}]}
    tpu0 = plane(
        "/device:TPU:0",
        ops=[("fusion.1", 10 * MS, 20 * MS), ("fusion.2", 25 * MS, 15 * MS),
             ("fusion.1", 60 * MS, 20 * MS)],
        modules=[("jit__decode_chunk(11)", 10 * MS, 30 * MS),
                 ("jit__prefill_chunk(22)", 60 * MS, 20 * MS)],
    )
    tpu1 = plane("/device:TPU:1", ops=[("fusion.9", 0.0, 100 * MS)],
                 modules=[("jit__decode_chunk(33)", 0.0, 100 * MS)])
    return [host, tpu0, tpu1]


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_busy_idle_and_programs_on_hand_made_planes():
    r = trace_reduce.reduce(hand_made())
    assert r["window_s"] == pytest.approx(0.1)
    assert r["device_planes"] == 2
    c0 = r["chips"]["/device:TPU:0"]
    assert c0["busy_s"] == pytest.approx(0.05)  # 10-40 overlapping, 60-80
    assert r["busy_s"] == pytest.approx((0.05 + 0.1) / 2)
    p = c0["programs"]["jit__decode_chunk(11)"]
    assert p["runs"] == 1 and p["mean_ms"] == pytest.approx(30.0)
    gaps = dict(r["idle_gaps"])
    assert gaps["unattributed tpu0 jit__decode_chunk->jit__prefill_chunk"] == pytest.approx(0.02)
    assert gaps["unattributed tpu0 none->jit__decode_chunk"] == pytest.approx(0.01)
    assert gaps["unattributed tpu0 jit__prefill_chunk->none"] == pytest.approx(0.02)
    assert r["device_ops"][0] == ["fusion.9", pytest.approx(0.1)]
    assert device_idle_share.read({"trace": r}) == pytest.approx(25.0)
    assert device_idle_share.read({"trace": None}) is None


def test_decode_step_time_picks_the_judge_chip_and_its_slowest_decode_programs():
    r = trace_reduce.reduce(hand_made())
    ctx = {"trace": r, "config": {"judge": "big"},
           "stats_after": {"device": {"engines": {"big": {"devices": [0]}}}}}
    assert decode_dev_ms_per_step.read(ctx) == pytest.approx(30.0 / 16)
    ctx["stats_after"]["device"]["engines"]["big"]["devices"] = [1]
    assert decode_dev_ms_per_step.read(ctx) == pytest.approx(100.0 / 16)
    assert decode_dev_ms_per_step.read(dict(ctx, trace=None)) is None
    # the judge model at two decode widths (30 and 20 ms a chunk) beside a
    # smaller model that ran more often (12 ms): the judge's are the slow ones
    programs = r["chips"]["/device:TPU:1"]["programs"]
    programs.clear()
    programs.update({
        "jit__decode_chunk(1)": {"runs": 2, "total_s": 0.060, "mean_ms": 30.0},
        "jit__decode_chunk(2)": {"runs": 1, "total_s": 0.020, "mean_ms": 20.0},
        "jit__decode_chunk(3)": {"runs": 9, "total_s": 0.108, "mean_ms": 12.0},
    })
    assert decode_dev_ms_per_step.read(ctx) == pytest.approx(80.0 / 3 / 16)


def test_a_trace_recorded_on_the_chip():
    """One short window of `rehearsal:qwen25-trio-bf16:short-saturated:8`
    on a TPU v5 lite (PR 22): the reduction finds the device plane, ops and
    the decode-chunk programs under the names the trace gives them."""
    path = os.path.join(DATA, "trio_saturated_250ms.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded trace checked in")
    r = trace_reduce.reduce(trace_reduce.load_xplane(path))
    assert r["device_planes"] == 1
    assert 0.0 < r["busy_s"] <= r["window_s"]
    assert 0.1 < r["window_s"] < 2.0
    programs = r["chips"]["/device:TPU:0"]["programs"]
    assert any("decode_chunk" in name for name in programs)
    assert r["device_ops"] and r["idle_gaps"]
    idle = device_idle_share.read({"trace": r})
    assert 0.0 <= idle < 100.0
