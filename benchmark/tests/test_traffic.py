import dataclasses

import pytest

from benchmark import traffic

MIXES = ["short-steady", "short-saturated", "short-bursty", "tiny-rehearsal"]


def plan_of(name, seed, seconds=40.0, **cell):
    cell = cell or {"rate_per_s": 0.5, "clients": 3}
    return traffic.generate(traffic.load_mix(name), cell, seed, seconds)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests_other_seed_others(name):
    a, b, c = plan_of(name, 7), plan_of(name, 7), plan_of(name, 8)
    assert a.to_doc() == b.to_doc()
    assert a.to_doc() != c.to_doc()
    assert a.warmup_sequential


@pytest.mark.parametrize("name", MIXES)
def test_lengths_inside_the_mix_and_prompts_unique(name):
    mix = traffic.load_mix(name)
    plan = plan_of(name, 3)
    reqs = plan.arrivals + [r for c in plan.clients for r in c]
    assert reqs
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    assert all(lo <= r.prompt_tokens <= hi for r in reqs)
    assert all(r.max_tokens == mix["max_tokens"] for r in reqs)
    warm = plan.warmup_sequential + [r for _, g in plan.warmup_concurrent for r in g]
    prompts = [r.prompt for r in reqs + warm]
    assert len(set(prompts)) == len(prompts)  # no cache hit, no coalescing
    assert all(r.prompt.isascii() for r in reqs)


def test_open_loop_rate_and_order():
    plan = plan_of("short-steady", 5, seconds=4000.0, rate_per_s=0.5)
    due = [r.due_s for r in plan.arrivals]
    assert due == sorted(due) and 0.0 <= due[0] and due[-1] < 4000.0
    assert len(due) / 4000.0 == pytest.approx(0.5, rel=0.02)  # paced
    gaps = [b - a for a, b in zip(due, due[1:])]
    assert 1.79 < min(gaps) and max(gaps) < 2.21  # 1/rate +- 10%


def test_strata_give_every_run_the_same_lengths_in_another_order():
    a = [r.prompt_tokens for r in plan_of("short-steady", 1, seconds=32.0).arrivals[:8]]
    b = [r.prompt_tokens for r in plan_of("short-steady", 2, seconds=32.0).arrivals[:8]]
    assert sorted(a) == sorted(b) and a != b and len(set(a)) == 8


def test_bursty_mix_keeps_the_mean_rate_and_arrives_in_bursts():
    mix = traffic.load_mix("short-bursty")
    plan = traffic.generate(mix, {}, 11, 4000.0)
    due = [r.due_s for r in plan.arrivals]
    assert len(due) / 4000.0 == pytest.approx(mix["rate_per_s"], rel=0.15)
    sizes = {}
    for t in due:
        sizes[t] = sizes.get(t, 0) + 1
    assert min(sizes.values()) >= mix["burst"]["min"]
    assert max(sizes.values()) <= mix["burst"]["max"]


def test_closed_loop_clients_come_from_the_cell_or_the_mix():
    assert len(plan_of("short-saturated", 1, clients=6).clients) == 6
    four = dict(traffic.load_mix("short-saturated"), clients=4)
    assert len(traffic.generate(four, {}, 1, 10).clients) == 4
    with pytest.raises(ValueError):
        traffic.generate(traffic.load_mix("short-saturated"), {}, 1, 10)
    with pytest.raises(ValueError):
        traffic.generate(traffic.load_mix("short-steady"), {}, 1, 10)


def test_parameters_no_first_cell_sets_are_read():
    mix = dict(traffic.load_mix("short-steady"), repeat_share=0.5,
               shared_system_tokens=300, second_round=True, stream=False,
               priority={"low": 1.0, "high": 1.0})
    plan = traffic.generate(mix, {"rate_per_s": 2.0}, 2, 200.0)
    prompts = [r.prompt for r in plan.arrivals]
    repeats = len(prompts) - len(set(prompts))
    assert 0.3 < repeats / len(prompts) < 0.7
    assert {r.priority for r in plan.arrivals} == {"low", "high"}
    assert all(len(r.system) == 300 and r.follow_up and not r.stream
               for r in plan.arrivals)
    assert len({r.system for r in plan.arrivals}) == 1
    body = plan.arrivals[0].body()
    assert body["system"] and body["stream"] is False


def test_unknown_mix_names_what_exists():
    with pytest.raises(FileNotFoundError, match="short-steady"):
        traffic.load_mix("no-such-mix")


def test_request_is_frozen():
    r = plan_of("short-steady", 1).arrivals[0]
    with pytest.raises(dataclasses.FrozenInstanceError):
        r.prompt = "x"
