"""BENCHMARK.json against the files it names, and the small tables."""

import json
import os
import re

import pytest

from benchmark import peaks, traffic
from benchmark.layer_metrics import decode_weights_roof_share

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")

with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def test_every_name_has_its_file():
    for cfg in BENCH["configs"]:
        assert NAME.match(cfg["name"])
        with open(os.path.join(REPO, cfg["file"])) as f:
            doc = json.load(f)
        assert doc["name"] == cfg["name"] and doc["reduced"] == cfg["reduced"]
        assert set(doc["panel"]) | {doc["judge"]} == set(doc["models"])
        assert doc["guarantees"] and doc["assumed"]
    configs = {c["name"]: c for c in BENCH["configs"]}
    for cell in BENCH["workloads"]:
        assert cell["config"] in configs and len(cell["why"]) <= 200
        mix = traffic.load_mix(cell["traffic"])
        plan = traffic.generate(mix, traffic.load_cell(cell["name"]), 1, BENCH["run_seconds"])
        assert plan.arrivals or plan.clients
    for kind, package in (("end_to_end", "end_to_end"), ("per_layer", "layer_metrics")):
        for m in BENCH[kind]:
            assert NAME.match(m["name"])
            assert os.path.exists(os.path.join(REPO, "benchmark", package, m["name"] + ".py")), m["name"]
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])
    four = [c for c in BENCH["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_of("TPU v9")


def test_weight_bytes_of_the_published_sizes():
    with open(os.path.join(REPO, "benchmark/configs/mistral7b-trio-int8.json")) as f:
        models = json.load(f)["models"]
    wb = decode_weights_roof_share.weight_bytes
    assert wb(models["mistral-7b"], "bfloat16") == pytest.approx(7.24e9 * 2, rel=0.01)
    assert wb(models["mistral-7b"], "int8") == pytest.approx(7.4e9, rel=0.03)
    assert wb(models["qwen2.5-0.5b"], "bfloat16") == pytest.approx(0.494e9 * 2, rel=0.01)
