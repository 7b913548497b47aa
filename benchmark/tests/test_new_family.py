"""What lets a configuration bring a model of a new family (PR 29): any field
of the program's ModelConfig under ``more_fields``, a reference named by the
entry, parity lengths stated by the file; proven with ``tiny-mixtral``, which
the program serves and the harness could not state."""

import copy
import glob
import json
import os

import numpy as np
import pytest

from benchmark import parity, server
from benchmark.layer_metrics import decode_weights_roof_share

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIGS = sorted(glob.glob(os.path.join(REPO, "benchmark/configs/*.json")))


def config(name: str) -> dict:
    with open(os.path.join(REPO, f"benchmark/configs/{name}.json")) as f:
        return json.load(f)


def mixtral(**changes) -> dict:
    spec = copy.deepcopy(config("tiny-moe-rehearsal")["models"]["tiny-mixtral"])
    spec.update(changes)
    return spec


ROPE = [8.0, 1.0, 4.0, 8192]
INSTALL_CASES = {
    # name: (model name, entry, the key a SystemExit must name or None)
    "preset-matches": ("tiny-mixtral", mixtral(), None),
    "preset-differs-outside-the-core": (
        "tiny-mixtral", mixtral(more_fields={}), "n_experts"),
    "preset-differs-in-one-more-field": (
        "tiny-mixtral", mixtral(more_fields={"n_experts": 4}), "experts_per_token"),
    "key-is-no-field": (
        "tiny-mixtral", mixtral(more_fields={"n_expert": 4}), "more_fields.n_expert"),
    "key-is-a-core-field": (
        "tiny-mixtral", mixtral(more_fields={"d_ff": 256}), "more_fields.d_ff"),
    "name-is-not-settable": (
        "tiny-mixtral", mixtral(more_fields={"name": "x"}), "more_fields.name"),
    "inserted-with-a-list": (
        "made-up-moe", mixtral(preset=False, more_fields={
            "n_experts": 4, "experts_per_token": 2, "rope_scaling": ROPE}), None),
}


@pytest.mark.parametrize("case", INSTALL_CASES)
def test_install_models_takes_more_fields(case, presets):
    name, spec, named = INSTALL_CASES[case]
    if named is not None:
        with pytest.raises(SystemExit) as stop:
            server.install_models({name: spec})
        assert named in str(stop.value)
        return
    server.install_models({name: spec})
    cfg = presets[name]
    assert (cfg.n_experts, cfg.experts_per_token, cfg.is_moe) == (4, 2, True)
    if "rope_scaling" in spec["more_fields"]:
        # JSON's list is the frozen dataclass's tuple, and the config hashes
        assert cfg.rope_scaling == tuple(ROPE) and isinstance(cfg.rope_scaling, tuple)
        assert hash(cfg) == hash(server.model_config(name, spec))


@pytest.mark.parametrize("path", CONFIGS, ids=[os.path.basename(p) for p in CONFIGS])
def test_every_configuration_file_installs(path, presets):
    """The program's ModelConfig and the files cannot drift apart unseen:
    every file under benchmark/configs goes through install_models, its
    references import and its parity lengths hold."""
    with open(path) as f:
        cfg = json.load(f)
    server.install_models(cfg["models"])
    for name, spec in cfg["models"].items():
        assert presets[name] == server.model_config(name, spec)
        assert parity.reference_for(name, spec).FAMILIES
    assert parity.lengths(cfg)["cache_slots"] <= int(cfg["env"]["LLMC_MAX_SEQ"])


REFERENCE_CASES = {
    "default": ({}, "decoder"),
    "named": ({"reference": "moe_decoder"}, "moe_decoder"),
    "capital": ({"reference": "Decoder"}, None),
    "a-path": ({"reference": "../parity"}, None),
    "dotted": ({"reference": "reference.decoder"}, None),
    "not-a-string": ({"reference": 3}, None),
    "missing-module": ({"reference": "no_such_family"}, None),
}


@pytest.mark.parametrize("case", REFERENCE_CASES)
def test_the_reference_is_chosen_by_name(case):
    spec, module = REFERENCE_CASES[case]
    if module is None:
        with pytest.raises(SystemExit) as stop:
            parity.reference_for("m", spec)
        assert str(spec["reference"]) in str(stop.value)
        return
    got = parity.reference_for("m", spec)
    assert got.__name__ == f"benchmark.reference.{module}"
    # the contract of benchmark/reference/__init__.py
    assert callable(got.forward) and callable(got.compared)
    assert callable(got.hidden) and callable(got.logits)  # the blocked form
    assert "Lengths the limit" in got.compared.__doc__
    assert got.TOLERANCE > 0 and got.STORED_LEAVES
    assert list(got.compared(np.asarray([0.01, 0.02]), 1))[0] == "rel_err_max"


def test_default_lengths_are_the_ids_every_run_compared():
    """Absent a "parity" object the three cells compare what they compared:
    128 positions, the last 32 through the cache, 256 slots, drawn as PR 22
    drew them (ids written down from the parent's expression)."""
    assert parity.lengths({"env": {"LLMC_MAX_SEQ": "4096"}}) == {
        "seq_len": 128, "decoded": 32, "cache_slots": 256}
    ids = parity.draw_ids(2900000101, "qwen2.5-3b", 151936, 128)
    rng = np.random.default_rng([2900000101, len("qwen2.5-3b")])
    assert ids.tolist() == rng.integers(0, 151936, 128, dtype=np.int64).tolist()
    assert ids[:4].tolist() == [145608, 4464, 31269, 135606]
    # a longer sequence starts with the same ids: one generator, one draw
    longer = parity.draw_ids(2900000101, "qwen2.5-3b", 151936, 192)
    assert longer[:128].tolist() == ids.tolist()


LENGTH_CASES = {
    "stated": ({"seq_len": 192, "decoded": 48, "cache_slots": 256},
               {"seq_len": 192, "decoded": 48, "cache_slots": 256}),
    "partly-stated": ({"cache_slots": 512},
                      {"seq_len": 128, "decoded": 32, "cache_slots": 512}),
    "at-the-edges": ({"seq_len": 4096, "decoded": 4095, "cache_slots": 4096},
                     {"seq_len": 4096, "decoded": 4095, "cache_slots": 4096}),
    "nothing-decoded": ({"decoded": 0}, None),
    "all-decoded": ({"seq_len": 64, "decoded": 64}, None),
    "longer-than-the-cache": ({"seq_len": 320}, None),
    "cache-over-max-seq": ({"cache_slots": 8192}, None),
    "no-such-length": ({"seq": 192}, None),
    "not-whole": ({"seq_len": 128.5}, None),
}


@pytest.mark.parametrize("case", LENGTH_CASES)
def test_parity_lengths(case):
    stated, want = LENGTH_CASES[case]
    cfg = {"env": {"LLMC_MAX_SEQ": "4096"}, "parity": stated}
    if want is None:
        with pytest.raises(SystemExit):
            parity.lengths(cfg)
    else:
        assert parity.lengths(cfg) == want


def test_moe_reference_matches_the_program_in_float32():
    """Whole sequence, the form of test_reference.py: two independent
    computations of the published sparse block agree to float32 rounding
    (the program's capacity dispatch drops nothing at E=4, k=2, factor 2)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import moe_decoder
    from llm_consensus_tpu.models import forward, get_config, init_params

    cfg = get_config("tiny-mixtral")
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, 192)
    with jax.default_matmul_precision("highest"):
        want, _ = forward(params, cfg, jnp.asarray(ids[None], jnp.int32))
    want = np.asarray(want[0])
    got = np.asarray(moe_decoder.forward(params, mixtral(), ids))
    err = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert err.max() < 1e-4
    with pytest.raises(ValueError):  # the file's count against the served router
        moe_decoder.forward(params, mixtral(more_fields={
            "n_experts": 8, "experts_per_token": 2}), ids)
    with pytest.raises(ValueError):
        moe_decoder.forward(params, config("tiny-rehearsal")["models"]["tiny-mistral"], ids)


def engine(model: str, **how):
    from llm_consensus_tpu.engine.engine import Engine
    from llm_consensus_tpu.models import get_config

    return Engine(get_config(model), max_seq=512, seed=0, **how)


def test_moe_reference_through_prefill_and_cached_decode_in_float32():
    import jax.numpy as jnp

    cfg = config("tiny-moe-rehearsal")
    sizes = parity.lengths(cfg)
    out = parity.check_engine(
        engine("tiny-mixtral", dtype=jnp.float32),
        cfg["models"]["tiny-mixtral"], "float32", 5, sizes)
    assert out["ok"] and out["rel_err_max"] < 1e-4 and out["rel_err_decoded_max"] < 1e-4
    assert (out["reference"], out["seq_len"], out["decoded"], out["cache_slots"]) == (
        "moe_decoder", 192, 48, 256)
    assert set(out["compared"]) == {
        "rel_err_max", "rel_err_median", "rel_err_decoded_median"}


BROKEN = {
    # the control: what must come out as not ok, through check_engine
    "dense-int8-weights-under-a-bf16-file": ("tiny-qwen2", {"quant": "int8"}, None),
    "moe-int8-weights-under-a-bf16-file": ("tiny-mixtral", {"quant": "int8"}, None),
    "dense-a-token-altered": ("tiny-qwen2", {}, "token"),
    "moe-a-token-altered": ("tiny-mixtral", {}, "token"),
    "moe-the-cache-not-written": ("tiny-mixtral", {}, "cache"),
}


@pytest.mark.parametrize("case", BROKEN)
def test_check_engine_fails_a_broken_timed_path(case, monkeypatch):
    """bfloat16 engines as the rehearsal serves them. Sound, every model is
    ok; with weights stored one precision under the file's, with the token
    at one decoded position altered where the program reads it, or with the
    decode steps' cache writes dropped, it is not."""
    import llm_consensus_tpu.models as models

    model, how, fault = BROKEN[case]
    cfg = config("tiny-moe-rehearsal")
    spec, sizes = cfg["models"][model], parity.lengths(cfg)
    eng = engine(model, **how)
    if not how:
        assert parity.check_engine(eng, spec, cfg["weights"], 8, sizes)["ok"]
    program = models.forward

    def broken(params, mcfg, tokens, cache, pos, **kw):
        if fault == "token" and tokens.shape[1] == 1:
            tokens = (tokens + 1) % mcfg.vocab_size
        logits, new = program(params, mcfg, tokens, cache, pos, **kw)
        if fault == "cache" and tokens.shape[1] == 1:
            new = cache
        return logits, new

    if fault:
        monkeypatch.setattr(models, "forward", broken)
    out = parity.check_engine(eng, spec, cfg["weights"], 8, sizes)
    assert not out["ok"]
    if fault:
        assert out["stored_as_stated"]
        assert any(value > limit for value, limit in out["compared"].values())
    else:
        assert not out["stored_as_stated"]


WEIGHT_CASES = {
    "more-fields": mixtral(family="mistral"),
    "another-family": mixtral(more_fields={}),
    "both": mixtral(),
}


@pytest.mark.parametrize("case", WEIGHT_CASES)
def test_weight_bytes_refuses_what_it_cannot_count(case):
    with pytest.raises(ValueError):
        decode_weights_roof_share.weight_bytes(WEIGHT_CASES[case], "bfloat16")
