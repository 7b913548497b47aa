"""The reader that came with the four-chip cell (PR 25), on hand-made
contexts, and the weight bytes its roofline share divides."""

import json
import os

import pytest

from benchmark.layer_metrics import decode_weights_roof_share, judge_engine_build_s

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def ctx(build_s=None):
    engine = {"devices": [2, 3]}
    if build_s is not None:
        engine.update(tp=2, param_bytes_per_chip=7.24e9, build_s=build_s)
    return {
        "config": {"judge": "mistral-7b"},
        "stats_after": {"device": {"engines": {
            "mistral-7b": engine, "qwen2.5-0.5b": {"devices": [0], "build_s": 1.5},
        }}},
    }


def test_build_seconds_are_the_judge_engines():
    assert judge_engine_build_s.read(ctx(build_s=12.25)) == 12.25
    # a program that does not report them (the parent): nothing, no raise
    assert judge_engine_build_s.read(ctx()) is None
    assert judge_engine_build_s.read({"config": {"judge": "m"}, "stats_after": {}}) is None


def test_weight_bytes_of_mistral_7b_in_bf16():
    with open(os.path.join(REPO, "benchmark/configs/mistral7b-trio-bf16-x4.json")) as f:
        doc = json.load(f)
    assert doc["weights"] == "bfloat16" and doc["chips"] == 4 and doc["reduced"] == []
    wb = decode_weights_roof_share.weight_bytes
    # 7,241,732,096 parameters, two bytes each
    assert wb(doc["models"]["mistral-7b"], doc["weights"]) == 14_483_464_192
    assert wb(doc["models"]["mistral-7b"], doc["weights"]) / 1e9 == pytest.approx(14.48, abs=0.005)
    # the other two configurations state the same models with the same sizes
    for sibling in ("mistral7b-trio-int8", "qwen25-trio-bf16"):
        with open(os.path.join(REPO, f"benchmark/configs/{sibling}.json")) as f:
            theirs = json.load(f)["models"]
        for name in set(theirs) & set(doc["models"]):
            assert theirs[name] == doc["models"][name], name
