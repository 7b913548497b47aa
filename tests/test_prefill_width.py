"""The width of a one-row prefill's chunks follows from the model (PR 49).

``Engine.prefill_chunk`` stays the LENGTH past which a prompt leaves the
one-shot program and a wave's rows are admitted one by one; the WIDTH of the
chunks such a prompt prefills in is the engine's own
(``Engine.prefill_width``, utils/flops.py ``prefill_ridge_width`` and
``prefill_chunk_width``): wide enough that a chunk feeds the least-fed stored
matrix of the stack to the device's ridge, or, where no prompt can, as wide
as the prompt's bucket and the score transient's cap allow. Asked here: the
rule from the benchmark's configuration files alone, what the constructor
makes of it, which program a prompt runs, and that the numbers do not depend
on the width.
"""

import dataclasses
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import server
from llm_consensus_tpu.engine import (
    ContinuousBatcher, Engine, SamplingParams, engine as E)
from llm_consensus_tpu.engine.batcher import singles_cover_fewer
from llm_consensus_tpu.models import get_config
from llm_consensus_tpu.utils import flops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V5E = "TPU v5 lite"

# -- the rule, from the configuration files alone ----------------------------------

# model -> (the width at which its least-fed matrix reaches a v5e's ridge,
# the width of a judge prompt's chunks: a 2,048-slot bucket); every model
# that is not here is dense or hybrid-dense: every matrix sees the whole
# chunk, and 512 it stays.
ROUTED = {
    "trinity-mini": (4096, 2048),      # w / 16 an expert; 32 heads
    "nemotron-3-super": (8192, 2048),  # w / 23; 32 heads
    "solar-open2": (16384, 2048),      # w / 40; 64 heads: 1 GiB of scores
    "deepseek-v2": (8192, 1024),       # w / 27; 128 heads: the cap binds
}


def _served():
    cases = []
    for path in sorted(glob.glob(os.path.join(ROOT, "benchmark/configs/*.json"))):
        name = os.path.basename(path)[:-len(".json")]
        if name.startswith("tiny-"):
            continue  # the rehearsals
        with open(path) as f:
            doc = json.load(f)
        cases += [(name, model) for model in doc["models"]]
    return cases


SERVED = _served()


def test_the_benchmark_serves_eight_configurations():
    assert len({c for c, _ in SERVED}) == 8 and len(SERVED) == 24
    assert {m for _, m in SERVED} >= set(ROUTED)


@pytest.mark.parametrize("config,model", SERVED, ids=[f"{c}:{m}" for c, m in SERVED])
def test_the_width_of_every_served_model_from_its_file(config, model):
    with open(os.path.join(ROOT, "benchmark/configs", f"{config}.json")) as f:
        doc = json.load(f)
    cfg = server.model_config(model, doc["models"][model])
    itemsize = {"bfloat16": 2, "int8": 1}[doc["weights"]]
    ridge = flops.prefill_ridge_width(cfg, V5E, itemsize)
    want_ridge, want = ROUTED.get(model, (512, 512))
    assert ridge == want_ridge
    assert flops.prefill_chunk_width(ridge, cfg.n_heads, 2048) == want
    # never wider than the prompt's bucket, never under today's 512
    assert flops.prefill_chunk_width(ridge, cfg.n_heads, 1024) == min(want, 1024)
    assert flops.prefill_chunk_width(ridge, cfg.n_heads, 1 << 17) == 512


def test_an_int8_tree_halves_the_ridges_rows():
    assert flops.ridge_rows(V5E, 2) == pytest.approx(197e12 / 819e9)  # 240.5
    assert flops.ridge_rows(V5E, 1) == pytest.approx(flops.ridge_rows(V5E, 2) / 2)
    assert flops.ridge_rows("cpu") is None
    with pytest.raises(flops.UnknownDeviceError):
        flops.ridge_rows("TPU v9 imaginary")


@pytest.mark.parametrize("itemsize,want", [(2, 1024), (1, 512)])
def test_a_mixtral_gets_a_chunk_that_feeds_two_of_eight_experts(itemsize, want):
    """8 experts, 2 a token: an expert sees w / 4 rows, 240 of them at 962
    tokens; an int8 tree's 120 at 481, under the floor."""
    cfg = get_config("mixtral-8x7b")
    assert flops.prefill_ridge_width(cfg, V5E, itemsize) == want
    assert flops.prefill_ridge_width(cfg, "cpu", itemsize) == 512
    assert flops.prefill_ridge_width(cfg, V5E, itemsize, floor=2048) == 2048


def test_the_cap_counts_heads_by_chunk_by_bucket_scores():
    assert flops.PREFILL_SCORE_BYTES == 64 * 2048 * 2048 * 4
    for heads, bucket, want in [(32, 2048, 2048), (32, 4096, 2048), (64, 2048, 2048),
                                (64, 4096, 1024), (128, 2048, 1024), (128, 4096, 512),
                                (256, 4096, 512)]:
        assert flops.prefill_chunk_width(1 << 14, heads, bucket) == want
        assert heads * want * bucket * 4 <= flops.PREFILL_SCORE_BYTES or want == 512


# -- what the constructor makes of it ----------------------------------------------


@pytest.fixture
def v5e_peaks(monkeypatch):
    """The CPU the tests run on, given a v5e's two peaks."""
    monkeypatch.setattr(
        flops, "ridge_rows", lambda kind, itemsize=2: 197e12 / 819e9 * itemsize / 2)


def _engine(name="tiny-mixtral", **kw):
    kw.setdefault("max_seq", 4096)
    kw.setdefault("dtype", jnp.float32)
    return Engine(get_config(name), seed=0, **kw)


def test_off_a_tpu_the_width_is_the_chunk():
    eng = _engine()
    assert (eng.prefill_chunk, eng.prefill_width) == (512, 512)
    assert eng._chunk_width(1733) == 512
    assert eng.build_stats["prefill_width"] == 512


def test_on_a_tpu_the_width_is_the_models(v5e_peaks, monkeypatch):
    """4 experts: at 2 a token an expert sees w / 2 rows, 240 of a bf16
    matrix's at 481 tokens; at 1 a token w / 4, at 962; float32 leaves
    double the rows, int8 leaves halve them."""
    eng = _engine(dtype=jnp.bfloat16)
    assert (eng.prefill_chunk, eng.prefill_width) == (512, 512)
    assert _engine().prefill_width == 1024  # float32
    top1 = dataclasses.replace(
        get_config("tiny-mixtral"), experts_per_token=1, name="tiny-top1")
    eng = Engine(top1, max_seq=4096)
    assert (eng.prefill_chunk, eng.prefill_width) == (512, 1024)
    assert eng.build_stats["prefill_width"] == 1024
    assert [eng._chunk_width(n) for n in (513, 1024, 1025, 4000)] == [1024] * 4
    assert Engine(top1, max_seq=4096, quant="int8").prefill_width == 512
    assert _engine("tiny-llama").prefill_width == 512
    # the old knob keeps its meaning: given, it does both jobs
    given = Engine(top1, params=eng.params, max_seq=4096, prefill_chunk=512)
    assert (given.prefill_chunk, given.prefill_width) == (512, 512)
    monkeypatch.setenv("LLMC_PREFILL_CHUNK", "128")
    env = Engine(top1, params=eng.params, max_seq=4096)
    assert (env.prefill_chunk, env.prefill_width) == (128, 128)
    assert env._chunk_width(1733) == 128


def test_the_width_alone_can_be_given():
    eng = _engine(prefill_width=2048)
    assert (eng.prefill_chunk, eng.prefill_width) == (512, 2048)
    assert [eng._chunk_width(n) for n in (513, 1024, 1025, 1733, 2048, 2049, 4096)] == [
        1024, 1024, 2048, 2048, 2048, 2048, 2048]
    assert _engine(prefill_width=64).prefill_width == 512  # never under the chunk
    off = _engine(prefill_chunk=0, prefill_width=2048)
    assert off.prefill_chunk == 0  # chunking off stays off


def test_a_capacity_that_is_no_power_of_two_narrows_the_width():
    """3,000 slots hold 2,100 tokens as five chunks of 512, not as two of
    2,048 or three of 1,024: the last chunk's padding must fit."""
    eng = _engine("tiny-llama", max_seq=3000, prefill_width=2048)
    assert eng._chunk_width(2100) == 512
    assert eng._chunk_width(2900) == 512 and eng._chunk_width(1500) == 2048
    eng._prefill_ids(list(range(1, 2101)))
    assert (eng.last_prefill.chunks, eng.last_prefill.slot_tokens) == (5, 2560)


# -- which program a prompt runs ---------------------------------------------------


class _Counted:
    """A program family that notes the name each call ran under."""

    def __init__(self, programs):
        self.programs, self.ran = programs, []

    def __call__(self, *args, **kwargs):
        self.ran.append(self.programs.program(*args, **kwargs).__name__)
        return self.programs(*args, **kwargs)


@pytest.fixture(scope="module")
def wide():
    return Engine(get_config("tiny-llama"), dtype=jnp.float32, seed=0,
                  max_seq=4096, prefill_width=2048)


@pytest.mark.parametrize("n,bucket", [(513, 1024), (1733, 2048), (2048, 2048)])
def test_a_long_prompt_is_one_run_of_the_loop_program(wide, monkeypatch, n, bucket):
    loop, bare = _Counted(E._prefill_chunks_loop), _Counted(E._prefill_chunk)
    monkeypatch.setattr(E, "_prefill_chunks_loop", loop)
    monkeypatch.setattr(E, "_prefill_chunk", bare)
    wide._prefill_ids(list(range(1, n + 1)))
    assert loop.ran == [f"prefill_chunks_loop__tiny_llama__kv{bucket}"]
    assert bare.ran == []
    did = wide.last_prefill
    assert (did.chunks, did.slot_tokens, did.pairs_swept) == (1, bucket, bucket * bucket)


@pytest.mark.parametrize("n", [40, 512])
def test_a_one_shot_prompt_is_untouched(wide, monkeypatch, n):
    loop, step = _Counted(E._prefill_chunks_loop), []
    monkeypatch.setattr(E, "_prefill_chunks_loop", loop)
    real = E._prefill_step
    monkeypatch.setattr(
        E, "_prefill_step", lambda *a, **k: step.append(a[2].shape) or real(*a, **k))
    wide._prefill_ids(list(range(1, n + 1)))
    assert loop.ran == [] and step == [(1, E._bucket(n, 4096))]
    assert wide.last_prefill.chunks == 1


WAVES = [[1733] * 6, [1733], [1800, 1700, 1900, 2000, 1750, 1650], [600] * 6,
         [513, 2048], [512, 1733], [40, 90, 200], [1733] * 2, [3000] * 3, [700] * 8]


@pytest.mark.parametrize("lens", WAVES, ids=[f"{len(w)}x{max(w)}" for w in WAVES])
def test_the_same_waves_go_row_by_row_as_at_512(wide, lens):
    """``singles_cover_fewer`` reads the LENGTH (``prefill_chunk``, and the
    bucket ``_rows_bucket`` pads to by it), which the width leaves alone."""
    plain = Engine(wide.cfg, params=wide.params, dtype=jnp.float32, max_seq=4096)
    assert plain.prefill_width == 512
    got = singles_cover_fewer(lens, 6, wide.prefill_chunk, wide._rows_bucket)
    assert got == singles_cover_fewer(lens, 6, 512, plain._rows_bucket)
    assert [wide._rows_bucket(n) for n in lens] == [plain._rows_bucket(n) for n in lens]
    if lens == [1733] * 6:
        assert got  # six judge prompts: one row each


# -- the numbers do not depend on the width ----------------------------------------


def _rehearsal(config: str, model: str):
    with open(os.path.join(ROOT, "benchmark/configs", f"{config}.json")) as f:
        return server.model_config(model, json.load(f)["models"][model])


FAMILIES = {  # a routed preset of each family
    "mixtral": lambda: get_config("tiny-mixtral"),
    "afmoe": lambda: _rehearsal("tiny-afmoe-rehearsal", "tiny-afmoe-top8"),
    "latent": lambda: get_config("tiny-deepseek-v2"),
    "state-space-latent": lambda: get_config("tiny-nemotron-h"),
    "delta-rule": lambda: get_config("tiny-solar-open2"),
}
N_PROMPT = 1100  # past 512 and 1,024: a 2,048-slot bucket, 948 pads in one chunk


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("family", FAMILIES)
def test_a_prompt_reads_the_same_at_every_width(family):
    """A prompt past 512 tokens through ``_prefill_ids`` at 512 (three
    chunks), at 1,024 (two), at its bucket (one, nearly half of it padding)
    and in the one-shot form: the last position's logits agree within the
    family's own tests' limit, the greedy continuation is equal, and a
    model that keeps a state holds the same state after the padded tail."""
    cfg = FAMILIES[family]()
    ids = [int(i) for i in np.random.default_rng(49).integers(1, cfg.vocab_size, N_PROMPT)]
    base = Engine(cfg, dtype=jnp.float32, seed=0, max_seq=2048 + 16, prefill_chunk=0)
    sampling = SamplingParams(max_new_tokens=8, temperature=0.0, ignore_eos=True)
    with jax.default_matmul_precision("highest"):
        want, cache = base._prefill_ids(ids)
        assert base.last_prefill.chunks == 1  # one shot
        want_state = cache.get("ssm")
        want_ids = base.generate_ids(ids, sampling).token_ids
        for width, chunks in ((512, 3), (1024, 2), (2048, 1)):
            eng = Engine(cfg, params=base.params, dtype=jnp.float32,
                         max_seq=2048 + 16, prefill_width=width)
            got, cache = eng._prefill_ids(ids)
            assert eng.last_prefill.chunks == chunks, width
            assert eng.last_prefill.slot_tokens == chunks * width
            assert rel_err(got, want) < 3e-5, width
            if cfg.has_state:
                for name, leaf in want_state.items():
                    assert rel_err(cache["ssm"][name], leaf) < 3e-5, (width, name)
            assert eng.generate_ids(ids, sampling).token_ids == want_ids, width
    assert (want_state is not None) == cfg.has_state


@pytest.mark.parametrize("name", ["tiny-nemotron-h", "tiny-solar-open2", "tiny-falcon-h1"])
def test_a_state_keeping_part_in_segments_is_the_part_whole(name, monkeypatch):
    """A chunk wider than ``STATE_SEGMENT`` passes through a mixer or a
    delta-rule layer in segments (models/transformer.py ``_in_segments``):
    rows that start inside the second segment, end inside the third, end
    on a seam, and a row that fills the chunk read what the part run whole
    reads, logits at every real position, state and tail."""
    from llm_consensus_tpu.models import forward, init_kv_cache, init_params
    from llm_consensus_tpu.models import transformer

    cfg = get_config(name)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    t, seg = 256, 64
    starts, ends = [0, 70, 10, 0], [256, 256, 150, 128]
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(1, cfg.vocab_size, (4, t)), jnp.int32)

    def run(segment):
        monkeypatch.setattr(transformer, "STATE_SEGMENT", segment)
        with jax.default_matmul_precision("highest"):
            return forward(
                params, cfg, tokens, init_kv_cache(cfg, 4, t, jnp.float32), 0,
                row_start=jnp.asarray(starts, jnp.int32),
                row_end=jnp.asarray(ends, jnp.int32))

    (whole, whole_cache), (cut, cut_cache) = run(1 << 20), run(seg)
    for row, (lo, hi) in enumerate(zip(starts, ends)):
        assert rel_err(cut[row, lo:hi], whole[row, lo:hi]) < 2e-5, row
    for leaf in ("state", "conv"):
        assert rel_err(cut_cache["ssm"][leaf], whole_cache["ssm"][leaf]) < 2e-5, leaf


# -- the counter that says it engaged ----------------------------------------------


@pytest.mark.parametrize("width,chunks", [(None, 4), (1024, 2), (2048, 1)])
def test_a_judge_sized_prompt_counts_the_chunks_the_width_implies(width, chunks):
    from llm_consensus_tpu.obs import blackbox as bb_mod
    from llm_consensus_tpu.obs.blackbox import FlightRecorder

    ring = FlightRecorder(capacity=512)
    bb_mod.install(ring)
    eng = Engine(get_config("tiny-mixtral"), max_seq=4096, stream_interval=4,
                 prefill_width=width)
    pool = ContinuousBatcher(eng, max_batch=2)
    try:
        sampling = SamplingParams(max_new_tokens=4, ignore_eos=True)
        out = pool.submit("judge this: " + "word " * 370, sampling)
        n = out.result(timeout=600).prompt_tokens
        pool.submit("a panel prompt", sampling).result(timeout=600)
        st = pool.snapshot()
    finally:
        pool.close()
    assert 1536 < n <= 2048
    # the judge prompt's chunks and the short prompt's one one-shot wave
    assert st["prefill_chunks"] == chunks + 1 and st["prefill_waves"] == 2
    assert st["prefill_slot_tokens"] == 2048 + 2 * 16
    admits = [e.args for e in ring.snapshot()
              if e.name == "pool.admit" and e.tid == "pool:tiny-mixtral"]
    assert [(a["route"], a["chunks"]) for a in admits] == [
        ("single", chunks), ("rows", 1)]
    # every pair is on a held expert, whatever the width
    assert st["moe_prefill_pairs_held"] == 2 * st["prefill_slot_tokens"] * 2
