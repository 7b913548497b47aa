"""prefill_kv_live_share: the judge pool's causal pairs over swept pairs."""

import pytest

from benchmark.layer_metrics import prefill_kv_live_share


def ctx_with(before=None, after=None):
    return {
        "config": {"judge": "big-moe", "serve": {"max_batch": 6}},
        "stats_before": {"batchers": {"big-moe": before or {}}},
        "stats_after": {"batchers": {"big-moe": after or {}, "small": {
            "prefill_kv_pairs_swept": 10, "prefill_kv_pairs_live": 10}}},
    }


@pytest.mark.parametrize("swept_a_prompt,share", [
    (4 * 512 * 2048, 41.7),                       # the whole bucket, every chunk
    (512 * (512 + 1024 + 1536 + 2048), 66.7),     # up to each chunk's frontier
])
def test_share_is_the_judge_pools_delta_of_live_over_swept(swept_a_prompt, share):
    # six judge prompts of 1,870 tokens, four 512-token chunks each
    live = 6 * (1870 * 1871 // 2)
    before = {"prefill_kv_pairs_swept": 9000, "prefill_kv_pairs_live": 4000}
    after = {"prefill_kv_pairs_swept": 9000 + 6 * swept_a_prompt,
             "prefill_kv_pairs_live": 4000 + live}
    got = prefill_kv_live_share.read(ctx_with(before, after))
    assert got == pytest.approx(live / (6 * swept_a_prompt) * 100)
    assert got == pytest.approx(share, abs=0.1)


def test_nothing_to_read_is_none_and_never_a_raise():
    # the parent's /statsz has no such counters
    assert prefill_kv_live_share.read(
        ctx_with({"prefill_waves": 1}, {"prefill_waves": 9})) is None
    assert prefill_kv_live_share.read({
        "config": {"judge": "big-moe"}, "stats_before": {}, "stats_after": {},
    }) is None
    # a window without an admission: nothing to divide by
    same = {"prefill_kv_pairs_swept": 7, "prefill_kv_pairs_live": 3}
    assert prefill_kv_live_share.read(ctx_with(same, same)) is None
