"""decode_kv_live_share: the judge pool's live slots over swept slots."""

import pytest

from benchmark.layer_metrics import decode_kv_live_share


def ctx_with(before=None, after=None):
    return {
        "config": {"judge": "big-3b", "serve": {"max_batch": 6}},
        "stats_before": {"batchers": {"big-3b": before or {}}},
        "stats_after": {"batchers": {"big-3b": after or {}, "small": {
            "decode_kv_slots_swept": 10, "decode_kv_slots_live": 10}}},
    }


def test_share_is_the_judge_pools_delta_of_live_over_swept():
    # 112 steps of one live row of six at 1,920 slots, about 1,800 valid
    before = {"decode_kv_slots_swept": 5000, "decode_kv_slots_live": 700}
    after = {"decode_kv_slots_swept": 5000 + 112 * 6 * 1920,
             "decode_kv_slots_live": 700 + 112 * 1800}
    assert decode_kv_live_share.read(ctx_with(before, after)) == pytest.approx(
        1800 / (6 * 1920) * 100)


def test_nothing_to_read_is_none_and_never_a_raise():
    # the parent's /statsz has no such counters
    assert decode_kv_live_share.read(
        ctx_with({"decode_steps": 1}, {"decode_steps": 9})) is None
    assert decode_kv_live_share.read({
        "config": {"judge": "big-3b"}, "stats_before": {}, "stats_after": {},
    }) is None
    # an idle window: nothing dispatched, nothing to divide by
    same = {"decode_kv_slots_swept": 7, "decode_kv_slots_live": 3}
    assert decode_kv_live_share.read(ctx_with(same, same)) is None
