"""A start asks JAX for every program once.

With the program's defaults (attribution on, as ``serve`` has it) an engine
of each family whose programs ``tests/data/lowered_text_pins.json`` pins runs
a six-row wave, a judge prompt's chunk loop and decode chunks, twice each,
and ``jax.monitoring`` reports ONE trace and ONE lowering under each
program's name: the call's own. Until PR 47 ``obs/roofline.py`` asked for
every program again after its first call (``fn.lower(*args)`` for a
``cost_analysis``): JAX 0.9 serves that from its caches once the call has
run (a second trace event, no second lowering), but whatever asks again is a
second place a program can be traced FROM, and a Pallas kernel's serialized
body carries the frames it was traced under into the persistent compile
cache's key (PR 44: each start compiled a different handful anew).
"""

from __future__ import annotations

import dataclasses
import types
from collections import Counter

import jax
import jax.monitoring
import pytest

from llm_consensus_tpu import obs
from llm_consensus_tpu.engine import engine as E
from llm_consensus_tpu.models.config import MODEL_PRESETS
from tests.test_nemotron_h import OLDER
from tests.test_solar_open2 import PINNED

FAMILIES = {**OLDER, **{f: (name, None) for f, name in PINNED.items()},
            "afmoe": ("tiny-afmoe", None)}
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
STEMS = ("_prefill_step", "prefill_chunks_loop__", "decode_chunk__")

# jax.monitoring has no public way to take a listener back: one listener for
# the process, which counts only while a test has handed it a counter.
_counting: list[Counter] = []


def _on_duration(event: str, duration_s: float, fun_name: str = "", **_kw) -> None:
    if _counting and event in (TRACE, LOWERING):
        # a lowering's name is the traced function's inside "jit(...)"
        name = fun_name[4:-1] if event == LOWERING else fun_name
        _counting[-1][event, name] += 1


@pytest.fixture(scope="module", autouse=True)
def _listener():
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def _reset_planes() -> None:
    """Every plane of ``obs`` resolves anew, from the environment."""
    for name in obs.__all__:
        plane = getattr(obs, name)
        if isinstance(plane, types.ModuleType):
            plane.reset()


@pytest.fixture
def lowerings():
    """(event, program) counts, under the planes' defaults."""
    _reset_planes()
    counts: Counter = Counter()
    _counting.append(counts)
    yield counts
    _counting.pop()
    _reset_planes()


@pytest.mark.parametrize("family", FAMILIES)
def test_each_program_of_a_family_is_lowered_once(family, lowerings):
    preset, quant = FAMILIES[family]
    # A name of its own: the programs are process-wide, and another test of
    # this worker may have lowered the preset's at these shapes already.
    cfg = dataclasses.replace(
        MODEL_PRESETS[preset], name=f"{preset}-lowered-once")
    eng = E.Engine(cfg, max_seq=256, seed=0, prefill_chunk=32, quant=quant)
    assert obs.attrib.ledger() is not None  # the default, as serve has it
    wave = [f"row {i} of the six-row wave" for i in range(6)]
    sampling = E.SamplingParams(max_new_tokens=20, temperature=0.0)
    for turn in range(2):
        eng.generate_batch(wave, sampling)
        # 75 tokens in chunks of 32 and no prefix shared between the turns
        eng._prefill_ids([(turn + 3 + i) % 200 for i in range(75)])
        assert eng.last_prefill.chunks == 3 and eng.last_prefill.reused == 0
    named = {
        key: n for key, n in lowerings.items() if key[1].startswith(STEMS)}
    for event in (TRACE, LOWERING):
        for stem in STEMS:
            assert any(key[0] == event and key[1].startswith(stem)
                       for key in named), (event, stem, lowerings)
    assert all(n == 1 for n in named.values()), named
