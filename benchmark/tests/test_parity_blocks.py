"""The comparison in blocks of positions, each model at lengths of its own
(PR 39): the block path against the whole path on every family the harness
states, that the length alone decides between them, the order the lengths
fall back in, what stops the child, and what neither side may hold (a
``[T, V]`` array past the block, an ``[H, T, T]`` table of scores)."""

import copy
import json
import os

import numpy as np
import pytest

from benchmark import parity, server

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# 144 prefilled positions in blocks of 64: two seams and a last block of 16.
SIZES = {"seq_len": 192, "decoded": 48, "cache_slots": 256}
BLOCK = 64


def config(name: str) -> dict:
    with open(os.path.join(REPO, f"benchmark/configs/{name}.json")) as f:
        return json.load(f)


def engine(file: str, model: str, max_seq: int = 512, **how):
    """``model`` as the rehearsal ``file`` states it, and its entry there."""
    from llm_consensus_tpu.engine.engine import Engine
    from llm_consensus_tpu.models import get_config

    spec = config(file)["models"][model]
    server.install_models({model: spec})
    return Engine(get_config(model), max_seq=max_seq, seed=0, **how), spec


@pytest.fixture
def short_blocks(monkeypatch):
    """The harness's two numbers at CI size: whole up to 128 positions, in
    blocks of 64 past that, so ``check_engine`` takes SIZES in blocks."""
    monkeypatch.setattr(parity, "WHOLE_UP_TO", 128)
    monkeypatch.setattr(parity, "BLOCK", BLOCK)


FAMILIES = {
    # model: the rehearsal that states it (and what the case is there for)
    "tiny-qwen2": "tiny-rehearsal",                    # q/k/v bias, no window
    "tiny-mistral": "tiny-rehearsal",                  # window 32 across the seams
    "tiny-mixtral": "tiny-moe-rehearsal",              # routed experts
    "tiny-deepseek-v2-share": "tiny-dsv2-rehearsal",   # latent cache, width rule
    "tiny-falcon-h1-mup": "tiny-falcon-h1-rehearsal",  # state carried across seams
}


@pytest.mark.parametrize("model", FAMILIES)
def test_block_path_equals_whole_path_in_float32(model, presets, short_blocks):
    """Both paths against the same reference, float32 on both sides: each
    agrees to float32 rounding at every position, so the prefill fed through
    the cache a block at a time (a shorter last block, a window and a
    recurrent state across the seams) is the prefill taken whole."""
    import jax.numpy as jnp

    eng, spec = engine(FAMILIES[model], model, dtype=jnp.float32)
    reference = parity.reference_for(model, spec)
    ids = parity.draw_ids(39, model, eng.cfg.vocab_size, SIZES["seq_len"])
    whole, *_ = parity.errors_whole(eng, reference, spec, ids, SIZES)
    blocked, *_ = parity.errors_blocked(eng, reference, spec, ids, SIZES, BLOCK)
    assert whole.shape == blocked.shape == (192,)
    assert whole.max() < 1e-4 and blocked.max() < 1e-4
    out = parity.check_engine(eng, spec, "float32", 39, SIZES)
    assert out["ok"] and out["block"] == 64 and out["rel_err_max"] == blocked.max()


def test_block_path_equals_whole_path_on_a_tensor_parallel_mesh(presets):
    """The four-chip cell's judge is sharded over two chips: under a ``tp``
    mesh of two (virtual) devices the start of a block is replicated, each
    block of the reference's logits goes where the program's lie, and both
    paths read the same, in float32 to rounding and in bfloat16 as served."""
    import jax
    import jax.numpy as jnp

    from llm_consensus_tpu.parallel.mesh import make_mesh

    if jax.device_count() < 4:
        pytest.skip("needs virtual devices (benchmark/tests/conftest.py asks for four)")
    mesh = make_mesh({"dp": 1, "tp": 2}, jax.devices()[2:4])
    for dtype, same in ((jnp.float32, 1e-6), (jnp.bfloat16, 2e-3)):
        eng, spec = engine("tiny-rehearsal", "tiny-mistral", mesh=mesh, dtype=dtype)
        reference = parity.reference_for("tiny-mistral", spec)
        ids = parity.draw_ids(39, "tiny-mistral", eng.cfg.vocab_size, SIZES["seq_len"])
        whole, *_ = parity.errors_whole(eng, reference, spec, ids, SIZES)
        blocked, *_ = parity.errors_blocked(eng, reference, spec, ids, SIZES, BLOCK)
        assert np.abs(whole - blocked).max() < same
        assert blocked.max() < (1e-4 if dtype == jnp.float32 else 0.022)


SELECTED = {
    # name: (positions, whole up to, the block the record states or None)
    "at-the-last-whole-length": (192, 192, None),
    "one-position-past-it": (193, 192, BLOCK),
    "the-defaults-under-the-real-numbers": (128, None, None),
}


@pytest.mark.parametrize("case", SELECTED)
def test_the_length_alone_decides_whole_or_blocks(case, presets, monkeypatch):
    """No key of a file chooses the path: a sequence of at most WHOLE_UP_TO
    positions goes whole (and the record has no ``block``), one position
    more goes in blocks of BLOCK; and the record says which route each of
    the check's own programs traced."""
    seq_len, whole_up_to, block = SELECTED[case]
    if whole_up_to is not None:
        monkeypatch.setattr(parity, "WHOLE_UP_TO", whole_up_to)
        monkeypatch.setattr(parity, "BLOCK", BLOCK)
    called = []
    for path in ("errors_whole", "errors_blocked"):
        monkeypatch.setattr(parity, path, lambda *a, _p=path, _f=getattr(parity, path): (
            called.append(_p), _f(*a))[1])
    eng, spec = engine("tiny-rehearsal", "tiny-mistral")
    out = parity.check_engine(
        eng, spec, "bfloat16", 5, {**SIZES, "seq_len": seq_len})
    assert called == ["errors_whole" if block is None else "errors_blocked"]
    assert out.get("block") == block and out["seq_len"] == seq_len
    assert out["attention"] == {"prefill": ["xla"], "decode": ["xla"]}


def test_the_real_numbers_send_the_rehearsals_long_model_through_blocks(presets):
    """``tiny-long-parity-rehearsal`` as the child runs it: nothing patched,
    tiny-mistral's 1,200 positions are over the 1,024 and go in blocks of
    512 (two of them and one of 128), and compare ``ok`` in bfloat16."""
    from benchmark import reference

    assert (reference.WHOLE_UP_TO, reference.BLOCK) == (1024, 512)
    assert (parity.WHOLE_UP_TO, parity.BLOCK) == (1024, 512)
    cfg = config("tiny-long-parity-rehearsal")
    sizes = parity.stated(cfg)["tiny-mistral"]
    eng, spec = engine("tiny-long-parity-rehearsal", "tiny-mistral", max_seq=2048)
    out = parity.check_engine(eng, spec, cfg["weights"], 3900000001, sizes)
    assert out["ok"] and out["block"] == 512 and out["seq_len"] == 1200, out


@pytest.mark.parametrize("model", ["tiny-qwen2", "tiny-falcon-h1-mup"])
def test_the_check_never_holds_more_than_a_block_of_logits(
        model, presets, monkeypatch, short_blocks):
    """Bfloat16 as served. Past the whole length the program is never given,
    and never returns, more than a block of positions; the reference's head
    is never applied to more; its ``forward`` (the sequence whole) is never
    called; and one float64 a position comes back."""
    import llm_consensus_tpu.models as models

    eng, spec = engine(FAMILIES[model], model)
    reference = parity.reference_for(model, spec)
    seen = {"program": [], "reference": [], "row_end": []}
    program, head = models.forward, reference.logits

    def forward(params, cfg, tokens, cache, pos, **kw):
        logits, new = program(params, cfg, tokens, cache, pos, **kw)
        seen["program"].append(logits.shape)
        seen["row_end"].append(kw.get("row_end") is not None)
        return logits, new

    def logits(params, spec, rows):
        out = head(params, spec, rows)
        seen["reference"].append(out.shape)
        return out

    def whole(*_):
        raise AssertionError("the reference took the sequence whole")

    monkeypatch.setattr(models, "forward", forward)
    monkeypatch.setattr(reference, "logits", logits)
    monkeypatch.setattr(reference, "forward", whole)
    out = parity.check_engine(eng, spec, "bfloat16", 7, SIZES)
    assert out["ok"] and out["block"] == BLOCK, out
    vocab = eng.cfg.vocab_size
    # traced once a shape: two prefill programs (64 and 16 positions), one step
    assert sorted(set(seen["program"])) == [(1, 1, vocab), (1, 16, vocab), (1, 64, vocab)]
    assert seen["reference"] == [(64, vocab), (64, vocab), (16, vocab), (48, vocab)]
    # a state-space model is told where each block's real tokens end
    told = [r for shape, r in zip(seen["program"], seen["row_end"]) if shape[1] > 1]
    assert told == [eng.cfg.has_ssm] * 2


def test_a_long_sequence_needs_a_reference_that_computes_in_blocks(monkeypatch):
    cfg = config("tiny-long-parity-rehearsal")
    assert parity.stated(cfg)["tiny-mistral"]["seq_len"] > parity.WHOLE_UP_TO
    from benchmark.reference import decoder

    monkeypatch.delattr(decoder, "hidden")
    with pytest.raises(SystemExit, match="tiny-mistral: parity states 1200 positions"):
        parity.stated(cfg)
    # a model at or under the whole length needs no blocked form
    del cfg["models"]["tiny-mistral"]["parity"]
    assert parity.stated(cfg)["tiny-mistral"] == parity.DEFAULT_LENGTHS


def test_the_long_parity_rehearsal_compares_its_two_models_at_different_lengths():
    cfg = config("tiny-long-parity-rehearsal")
    assert parity.stated(cfg) == {
        "tiny-qwen2": parity.DEFAULT_LENGTHS,
        "tiny-mistral": {"seq_len": 1200, "decoded": 48, "cache_slots": 1280},
    }
    assert cfg["models"]["tiny-mistral"]["sliding_window"] < 512  # binds inside a block


def lengths_of(file: dict | None, model: dict | None) -> dict:
    cfg = {"env": {"LLMC_MAX_SEQ": "4096"}, "models": {"m": {}}}
    if file is not None:
        cfg["parity"] = file
    if model is not None:
        cfg["models"]["m"]["parity"] = model
    return parity.lengths(cfg, "m")


FALLBACK = {
    # name: (the file's object, the model's own, what the model is compared at)
    "nothing-stated": (None, None, {"seq_len": 128, "decoded": 32, "cache_slots": 256}),
    "the-file-alone": ({"seq_len": 192, "decoded": 48}, None,
                       {"seq_len": 192, "decoded": 48, "cache_slots": 256}),
    "the-model-alone": (None, {"seq_len": 192, "cache_slots": 512},
                        {"seq_len": 192, "decoded": 32, "cache_slots": 512}),
    "the-model-over-the-file": (
        {"seq_len": 1024, "decoded": 64, "cache_slots": 1024},
        {"seq_len": 3072, "cache_slots": 4096},
        {"seq_len": 3072, "decoded": 64, "cache_slots": 4096}),
    "the-files-decoded-reaches-the-model": (
        {"decoded": 48}, {"seq_len": 192},
        {"seq_len": 192, "decoded": 48, "cache_slots": 256}),
    "a-why-is-free-text": (
        {"why": "the file's"}, {"seq_len": 160, "why": "past a 128-token chunk"},
        {"seq_len": 160, "decoded": 32, "cache_slots": 256}),
}


@pytest.mark.parametrize("case", FALLBACK)
def test_a_models_lengths_fall_back_to_the_files_then_to_the_defaults(case):
    file, model, want = FALLBACK[case]
    assert lengths_of(file, model) == want
    # another model of the same file is not touched by this one's object
    cfg = {"parity": file or {}, "models": {"m": {"parity": model or {}}, "other": {}}}
    assert parity.lengths(cfg, "other") == parity.lengths({"parity": file or {}})


REFUSED = {
    # name: (the file's object, the model's own, what the message must name)
    "a-bad-key-of-the-model": (None, {"seq": 192}, "m: parity: no such length ['seq']"),
    "a-bad-key-of-the-file": ({"blocks": 64}, None, "no such length ['blocks']"),
    "a-block-is-no-files-to-state": (None, {"block": 512}, "m: parity: no such length ['block']"),
    "a-block-of-the-file": ({"block": 0}, None, "no such length ['block']"),
    "a-length-not-whole": (None, {"seq_len": 192.0}, "m: parity: lengths are whole numbers"),
    "nothing-decoded": (None, {"decoded": 0}, "m: parity: need 0 < decoded"),
    "all-decoded": ({"seq_len": 64}, {"decoded": 64}, "m: parity: need 0 < decoded"),
    "longer-than-its-cache": ({"cache_slots": 512}, {"seq_len": 1024}, "seq_len <= cache_slots"),
    "a-cache-past-max-seq": (None, {"seq_len": 6144, "decoded": 64, "cache_slots": 8192},
                             "cache_slots <= LLMC_MAX_SEQ"),
}


@pytest.mark.parametrize("case", REFUSED)
def test_a_malformed_parity_object_stops_the_child_by_name(case):
    file, model, named = REFUSED[case]
    with pytest.raises(SystemExit) as stop:
        lengths_of(file, model)
    assert named in str(stop.value)
    # and through the start-up check server.py makes, with real entries
    cfg = copy.deepcopy(config("tiny-rehearsal"))
    if file is not None:
        cfg["parity"] = file
    if model is not None:
        cfg["models"]["tiny-mistral"]["parity"] = model
    with pytest.raises(SystemExit) as stop:
        parity.stated(cfg)
    assert named.replace("m: parity", "tiny-mistral: parity") in str(stop.value)


def widest(jaxpr) -> int:
    """The most elements any value inside ``jaxpr`` has, sub-programs too."""
    import jax

    most = 0
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            most = max(most, int(np.prod(getattr(var.aval, "shape", ()) or (1,))))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            most = max(most, widest(sub))
    return most


def qkv(t: int, heads: int, kv_heads: int, dim: int):
    rng = np.random.default_rng(t)
    draw = lambda h: np.asarray(rng.normal(size=(t, h, dim)), np.float32)  # noqa: E731
    return draw(heads), draw(kv_heads), draw(kv_heads)


WINDOWS = {"none": None, "inside-a-block": 32, "across-two-blocks": 700,
           "wider-than-the-sequence": 4096}


@pytest.mark.parametrize("case", WINDOWS)
def test_blocked_attention_equals_its_whole_form(case):
    """``decoder.attention`` (what ``decoder``, ``moe_decoder`` and
    ``falcon_h1`` attend with) on a sequence of four blocks of queries
    against the one table of scores, with and without a window; and no value
    it makes is as large as ``[H, T, T]``."""
    import jax

    from benchmark.reference import decoder

    window, t, heads = WINDOWS[case], 4 * decoder.QUERY_BLOCK, 4
    assert t > decoder.WHOLE_UP_TO
    q, k, v = qkv(t, heads, 2, 16)
    with jax.default_matmul_precision("highest"):
        got = decoder.attention(q, k, v, window)
        want = decoder._attend(
            q, np.repeat(k, 2, axis=1), np.repeat(v, 2, axis=1), window, 0, 0)
        made = jax.make_jaxpr(lambda *a: decoder.attention(*a, window))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)
    assert widest(made.jaxpr) <= heads * decoder.QUERY_BLOCK * t < heads * t * t
    # at or under WHOLE_UP_TO positions it is the one table, as it always was
    short = qkv(decoder.WHOLE_UP_TO, heads, 2, 16)
    made = jax.make_jaxpr(lambda *a: decoder.attention(*a, window))(*short)
    assert widest(made.jaxpr) == heads * decoder.WHOLE_UP_TO ** 2


def test_latent_attention_is_computed_in_blocks_of_queries_and_heads(monkeypatch):
    """``deepseek_v2`` has attended in blocks since PR 31: every call of its
    ``_attend`` on a sequence of four blocks takes at most a block of queries
    and a block of heads, and the blocks together are the whole form."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import deepseek_v2

    spec = config("tiny-dsv2-rehearsal")["models"]["tiny-deepseek-v2-share"]
    more, t = spec["more_fields"], 4 * deepseek_v2.QUERY_BLOCK
    calls, attend = [], deepseek_v2._attend

    def recorded(q_nope, q_rope, k_nope, k_rope, v, q0, scale):
        calls.append((q_nope.shape[0], q_nope.shape[1], k_nope.shape[0]))
        return attend(q_nope, q_rope, k_nope, k_rope, v, q0, scale=scale)

    monkeypatch.setattr(deepseek_v2, "_attend", recorded)
    rng = np.random.default_rng(2)
    d, heads = spec["d_model"], spec["n_heads"]
    nope, rope, vdim, rank = (
        more["qk_nope_dim"], more["qk_rope_dim"], more["v_head_dim"], more["kv_lora_rank"])
    draw = lambda *shape: jnp.asarray(  # noqa: E731
        rng.normal(size=shape) * shape[0] ** -0.5, jnp.float32)
    w = {
        "attn_norm": jnp.ones((d,)), "wq_a": draw(d, more["q_lora_rank"]),
        "q_norm": jnp.ones((more["q_lora_rank"],)),
        "wq_b": draw(more["q_lora_rank"], heads * (nope + rope)),
        "wkv_a": draw(d, rank + rope), "kv_norm": jnp.ones((rank,)),
        "wkv_b": draw(rank, heads * (nope + vdim)), "wo": draw(heads * vdim, d),
    }
    x = draw(t, d)
    with jax.default_matmul_precision("highest"):
        cos, sin = deepseek_v2.rotary_tables(more, float(spec["rope_theta"]), t)
        got = deepseek_v2.attention_block(x, w, spec, cos, sin)
        blocks = list(calls)
        monkeypatch.setattr(deepseek_v2, "QUERY_BLOCK", t)
        want = deepseek_v2.attention_block(x, w, spec, cos, sin)
    assert len(blocks) == 4 * -(-heads // deepseek_v2.HEAD_BLOCK)
    assert all(tq <= 512 and hb <= deepseek_v2.HEAD_BLOCK and tk == t
               for tq, hb, tk in blocks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)


HEAD_MODELS = {
    "decoder": "tiny-mistral", "moe_decoder": "tiny-mixtral",
    "deepseek_v2": "tiny-deepseek-v2-share", "falcon_h1": "tiny-falcon-h1-mup",
}


@pytest.mark.parametrize("module", HEAD_MODELS)
def test_forward_is_the_head_on_every_row_of_hidden(module, presets):
    """The contract's blocked form: ``forward`` is ``logits`` of ``hidden``,
    and ``logits`` of some rows is those rows of ``forward``."""
    import jax.numpy as jnp

    model = HEAD_MODELS[module]
    eng, spec = engine(FAMILIES[model], model, dtype=jnp.float32)
    reference = parity.reference_for(model, spec)
    assert reference.__name__.endswith(module)
    ids = parity.draw_ids(3, model, eng.cfg.vocab_size, 80)
    whole = np.asarray(reference.forward(eng.params, spec, ids))
    hidden = reference.hidden(eng.params, spec, ids)
    assert hidden.shape == (80, eng.cfg.d_model) and hidden.dtype == jnp.float32
    some = np.asarray(reference.logits(eng.params, spec, hidden[48:64]))
    np.testing.assert_allclose(some, whole[48:64], rtol=1e-5, atol=1e-6)
