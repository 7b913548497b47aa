"""The Nemotron-H stack (PR 41): every layer ONE part (a Mamba-2 mixer, a
LatentMoE layer, or attention without rotary embedding), a parameter stack
and a cache a layer kind, and what the family is refused.

The yardstick is ``benchmark/reference/nemotron_h.py``, which imports nothing
of the program: the plain recurrence (a position a step), the held experts
one at a time, no cache. The model is ``tiny-nemotron-h``: one whole period
``MEMEMEM*EME`` of the published pattern at CI size (5 mixers of 6 heads of 8
and state 16; 5 expert layers of 16 ungated relu2 experts of 40 in a latent
of 56, 3 a token by sigmoid score + bias, a shared expert of 72 on the full
width; 1 attention layer of 4/2 heads of 24).

Last: the programs of the families that were here before lower to the text
they lowered to at the parent commit, byte for byte
(``tests/data/lowered_text_pins.json``; ``python tests/test_nemotron_h.py
<out.json>`` from another checkout's root writes that checkout's).
"""

import copy
import dataclasses
import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":  # the checkout in the working directory, not this file's
    sys.path.insert(0, os.getcwd())

from llm_consensus_tpu.engine import engine as E  # noqa: E402
from llm_consensus_tpu.models import (  # noqa: E402
    forward, get_config, init_kv_cache, init_params)
from llm_consensus_tpu.models.config import MODEL_PRESETS  # noqa: E402
from llm_consensus_tpu.ops.quant import (  # noqa: E402
    init_params_quantized, quantize_params)

if __name__ != "__main__":  # a parent checkout has neither
    from benchmark import parity, server
    from benchmark.reference import nemotron_h as reference
    from tests.test_falcon_h1 import (
        IN_PLACE_ROWS, in_place_step_is_the_sliced_step)
else:
    IN_PLACE_ROWS = ()

NAME = "tiny-nemotron-h"
PINS = os.path.join(REPO, "tests", "data", "lowered_text_pins.json")
PUBLISHED_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEM*EMEMEMEME")


def config(name: str) -> dict:
    with open(os.path.join(REPO, f"benchmark/configs/{name}.json")) as f:
        return json.load(f)


def tiny_spec(share: bool = False) -> dict:
    """The preset as the harness states a model: the rehearsal's entry (the
    preset's sizes with 8 experts a token, under which the reference's chip
    limits hold at CI size) with the preset's own 3 put back; or a strict
    share of it: experts 4-5 of the 16 the router scores."""
    spec = copy.deepcopy(
        config("tiny-nemotron-h-rehearsal")["models"][f"{NAME}-top8"])
    spec["more_fields"]["experts_per_token"] = 3
    if share:
        spec["more_fields"].update(n_experts=2, router_width=16, first_expert=4)
    return spec


def rel_err(got, want) -> np.ndarray:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


IDS = np.random.default_rng(0).integers(0, 512, 80)


@pytest.fixture(scope="module")
def model():
    cfg = get_config(NAME)
    return cfg, init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)


def through_the_cache(cfg, params, ids, n_pre, dtype, slots=96):
    """Prefill ``n_pre`` positions at once, the rest a token a step."""
    cache = init_kv_cache(cfg, 1, slots, dtype)
    logits, cache = forward(
        params, cfg, jnp.asarray(ids[None, :n_pre], jnp.int32), cache, 0)
    rows = [logits[0]]
    for p in range(n_pre, len(ids)):
        step, cache = forward(
            params, cfg, jnp.asarray(ids[None, p:p + 1], jnp.int32), cache,
            jnp.asarray(p, jnp.int32))
        rows.append(step[0])
    return jnp.concatenate(rows, axis=0), cache


# -- the preset, its stacks and its caches -------------------------------------


def test_the_rehearsals_entry_is_the_preset():
    cfg = get_config(NAME)
    assert server.model_config(NAME, tiny_spec()) == cfg
    # what the rehearsal runs: the preset with 8 of its 16 experts a token
    stated = config("tiny-nemotron-h-rehearsal")["models"][f"{NAME}-top8"]
    assert server.model_config(f"{NAME}-top8", stated) == dataclasses.replace(
        cfg, name=f"{NAME}-top8", experts_per_token=8)
    assert cfg.layer_kinds == PUBLISHED_PATTERN[:11] == "MEMEMEM*EME"
    assert (cfg.n_ssm_layers, cfg.n_expert_layers, cfg.n_attn_layers) == (5, 5, 1)
    assert cfg.kind_layers("E") == (1, 3, 5, 8, 10) and cfg.kind_layers("*") == (7,)
    assert cfg.has_ssm and cfg.is_moe and not cfg.is_latent and not cfg.rotary
    assert (cfg.moe_latent, cfg.shared_width, cfg.gated_experts) == (56, 72, False)
    # the older presets: every new field off, the counts what they were
    old = get_config("tiny-falcon-h1")
    assert (old.layer_kinds, old.rotary, old.moe_latent, old.d_shared) == (
        "", True, 0, 0)
    assert (old.n_attn_layers, old.n_ssm_layers) == (2, 2)
    assert get_config("tiny-llama").n_ssm_layers == 0
    with pytest.raises(ValueError, match="layer_kinds"):
        get_config(NAME, layer_kinds="MEX")


def test_a_stack_and_a_cache_a_layer_kind():
    cfg = get_config(NAME)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert set(shapes) == {
        "embed", "final_norm", "lm_head", "layers_ssm", "layers_moe", "layers_attn"}
    assert shapes["layers_ssm"]["ssm_in"].shape == (5, 96, 48 + 112 + 6)
    moe = shapes["layers_moe"]
    assert "w_gate" not in moe and "ws_gate" not in moe
    assert (moe["w_up"].shape, moe["w_down"].shape) == (
        (5, 16, 56, 40), (5, 16, 40, 56))
    assert (moe["w_latent_in"].shape, moe["ws_up"].shape, moe["router_bias"].shape) == (
        (5, 96, 56), (5, 96, 72), (5, 16))
    assert shapes["layers_attn"]["wk"].shape == (1, 96, 48)
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 3, 64, jnp.bfloat16))
    assert cache["k"].shape == cache["v"].shape == (1, 3, 64, 2, 24)
    assert cache["ssm"]["state"].shape == (5, 3, 6, 8, 16)
    assert cache["ssm"]["state"].dtype == jnp.float32   # whatever the served type
    assert cache["ssm"]["conv"].shape == (5, 3, 3, 112)
    assert cache["ssm"]["conv"].dtype == jnp.bfloat16


# -- the model against the reference -------------------------------------------

# float32 is tight. In bfloat16 a routed model's worst position is a routing
# flip (another expert than the float32 reference picks, on scores that
# nearly tie): what is held is the median, 3 times what this size reads.
PRECISIONS = {"float32": (jnp.float32, 2e-5, 2e-5), "bfloat16": (jnp.bfloat16, 1.2, 0.06)}


@pytest.mark.parametrize("precision", PRECISIONS)
def test_forward_matches_the_reference_whole_sequence(precision):
    dtype, worst, median = PRECISIONS[precision]
    cfg = get_config(NAME)
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=dtype)
    want = reference.forward(params, tiny_spec(), IDS)
    with jax.default_matmul_precision("highest"):
        got, _ = forward(params, cfg, jnp.asarray(IDS[None], jnp.int32))
    err = rel_err(got[0], want)
    assert err.max() < worst and np.median(err) < median


@pytest.mark.parametrize("precision", PRECISIONS)
def test_prefill_then_decode_through_the_cache_matches_the_reference(precision):
    """The timed path's shape through the harness's own check: one prefill
    (the chunked scan, the sorted dispatch), then decode steps through both
    caches (the one-step recurrence), against the reference's whole forward;
    on a strict share of the experts."""
    dtype, worst, median = PRECISIONS[precision]
    spec = tiny_spec(share=True)
    cfg = server.model_config(f"tiny-nemotron-h-{precision}", spec)
    eng = E.Engine(cfg, max_seq=256, seed=0, dtype=dtype)
    sizes = {"seq_len": 96, "decoded": 32, "cache_slots": 128}
    out = parity.check_engine(eng, spec, precision, 5, sizes)
    assert out["reference"] == "nemotron_h" and out["stored_as_stated"]
    assert out["rel_err_max"] < worst and out["rel_err_median"] < median
    assert out["compared"]["rel_err_decoded_median"][0] < median
    assert out["attention"] == {"prefill": ["xla"], "decode": ["xla"]}


def test_chunked_prefill_with_a_padded_last_chunk_then_decode(model):
    """The judge prompt's path: every chunk in one ``_prefill_chunks_loop``
    program (five chunks of 16, the state carried across four seams, five
    pads at the end that must not advance it), then decode steps."""
    cfg, params = model
    eng = E.Engine(cfg, params=params, dtype=jnp.float32, max_seq=256,
                   prefill_chunk=16)
    n_pre = 75
    with jax.default_matmul_precision("highest"):
        last, cache = eng._prefill_ids([int(i) for i in IDS[:n_pre]])
        assert eng.last_prefill.chunks == 5 and eng.last_prefill.reused == 0
        assert E.scan_positions_swept(cfg, eng.last_prefill, 1) == 80
        rows = [last]
        for p in range(n_pre, len(IDS)):
            logits, cache = forward(
                params, cfg, jnp.asarray(IDS[None, p:p + 1], jnp.int32), cache,
                jnp.asarray(p, jnp.int32))
            rows.append(logits[0])
    want = reference.forward(params, tiny_spec(), IDS)[n_pre - 1:]
    assert rel_err(jnp.concatenate(rows), want).max() < 2e-5


def test_rows_with_different_starts_and_a_dead_row(model):
    """A left-padded wave: each row's first real token is its position 0
    (no rotary embedding to say so: the mask and the mixers' spans do), a
    row without a stream neither reads nor disturbs the others."""
    from llm_consensus_tpu.engine.batcher import DEAD_ROW

    cfg, params = model
    starts, t = [0, 5, 11, DEAD_ROW], 24
    tokens = np.stack([IDS[i * 3:i * 3 + t] for i in range(4)])
    cache = init_kv_cache(cfg, 4, 64, jnp.float32)
    with jax.default_matmul_precision("highest"):
        logits, cache = forward(
            params, cfg, jnp.asarray(tokens, jnp.int32), cache, 0,
            row_start=jnp.asarray(starts, jnp.int32))
        step, cache = forward(
            params, cfg, jnp.asarray(tokens[:, :1], jnp.int32), cache,
            jnp.asarray(t, jnp.int32), row_start=jnp.asarray(starts, jnp.int32))
    spec = tiny_spec()
    for row, start in enumerate(starts[:3]):
        own = np.concatenate([tokens[row, start:], tokens[row, :1]])
        want = reference.forward(params, spec, own)
        got = jnp.concatenate([logits[row, start:], step[row]])
        assert rel_err(got, want).max() < 2e-5, row
    for leaf in jax.tree.leaves(cache["ssm"]):
        assert bool(jnp.isfinite(leaf).all())
    assert not np.asarray(cache["ssm"]["state"])[:, 3].any()  # the dead row's


def test_remat_walks_the_same_layers(model):
    """``forward(remat=True)`` (training, no cache) checkpoints each one-part
    layer: the same logits, and a gradient reaches every leaf of every stack
    but the correction bias, which chooses and does not weigh."""
    cfg, params = model
    tokens = jnp.asarray(IDS[None, :24], jnp.int32)

    def loss(p, remat):
        return jnp.mean(forward(p, cfg, tokens, remat=remat)[0] ** 2)

    np.testing.assert_allclose(loss(params, True), loss(params, False), rtol=1e-6)
    grads = jax.grad(loss)(params, True)
    assert not np.asarray(grads["layers_moe"].pop("router_bias")).any()
    for stack in ("layers_ssm", "layers_moe", "layers_attn"):
        for name, g in grads[stack].items():
            assert float(jnp.abs(g).max()) > 0, (stack, name)


def test_the_state_is_float32_under_a_bfloat16_model():
    cfg = get_config(NAME)
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.bfloat16)
    _, cache = through_the_cache(cfg, params, IDS[:40], 32, jnp.bfloat16)
    assert cache["ssm"]["state"].dtype == jnp.float32
    assert cache["k"].dtype == cache["ssm"]["conv"].dtype == jnp.bfloat16
    assert float(jnp.abs(cache["ssm"]["state"]).max()) > 0


# -- the expert layer: shares, the bias, the forms -----------------------------


def moe_layer(cfg, lp, h, first=0, held=None, shared=True, **kw):
    """ops/moe.py's layer on one layer's leaves, the experts ``[first, first
    + held)`` held."""
    from llm_consensus_tpu.ops.moe import moe_block

    held = cfg.n_experts if held is None else held
    return moe_block(
        h, lp["w_router"], None, lp["w_up"][first:first + held],
        lp["w_down"][first:first + held], top_k=cfg.experts_per_token,
        activation=cfg.activation, first_expert=first, norm_topk=cfg.norm_topk,
        routed_scale=cfg.routed_scale, scoring=cfg.router_scoring,
        router_bias=lp["router_bias"],
        latent=(lp["w_latent_in"], lp["w_latent_out"]),
        shared=(None, lp["ws_up"], lp["ws_down"]) if shared else None, **kw)


def test_the_shares_add_up_to_the_uncut_layer(model):
    """The test that ties the share to the model: the routed parts of the
    eight shares of two experts AFTER the latent out-projection (linear, no
    bias), plus the shared expert once, are the uncut reference's layer."""
    from llm_consensus_tpu.ops.mlp import plain_mlp

    cfg, params = model
    lp = jax.tree.map(lambda a: a[2], params["layers_moe"])
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 24, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want = reference.experts(
            h.reshape(-1, cfg.d_model), lp, tiny_spec()["more_fields"])
        routed = sum(
            moe_layer(cfg, lp, h, first, 2, shared=False)
            for first in range(0, 16, 2))
        shared = plain_mlp(h, lp["ws_up"], lp["ws_down"], "relu2")
        whole = moe_layer(cfg, lp, h)
    got = (routed + shared).reshape(-1, cfg.d_model)
    assert rel_err(got, want).max() < 1e-5
    assert rel_err(whole.reshape(-1, cfg.d_model), want).max() < 1e-5
    # and no single share is the whole layer
    one = moe_layer(cfg, lp, h, 4, 2)
    assert rel_err(one.reshape(-1, cfg.d_model), want).max() > 1e-2


def test_the_bias_changes_the_choice_and_not_the_weights():
    from llm_consensus_tpu.ops.moe import route

    logits = np.full((2, 8), -4.0, np.float32)
    logits[0, [1, 2, 5]] = [2.0, 1.0, 0.5]    # token 0: 1, 2, then 5 by score
    logits[1, [0, 3, 6]] = [3.0, 2.0, 1.0]
    bias = np.zeros((8,), np.float32)
    bias[7] = 0.9                             # lifts expert 7 over 5 and over 6
    s = 1 / (1 + np.exp(-logits))
    plain, w_plain = route(jnp.asarray(logits), 3, norm_topk=True,
                           routed_scale=2.5, bias=jnp.zeros((8,)))
    assert sorted(plain[0].tolist()) == [1, 2, 5]
    idx, weights = route(jnp.asarray(logits), 3, norm_topk=True,
                         routed_scale=2.5, bias=jnp.asarray(bias))
    assert sorted(idx[0].tolist()) == [1, 2, 7] and sorted(idx[1].tolist()) == [0, 3, 7]
    for row in range(2):
        chosen = s[row, np.asarray(idx[row])]            # WITHOUT the bias
        np.testing.assert_allclose(
            weights[row], 2.5 * chosen / chosen.sum(), rtol=1e-6)
    raw, w_raw = route(jnp.asarray(logits), 3, norm_topk=False, bias=jnp.asarray(bias))
    np.testing.assert_allclose(w_raw[0], s[0, np.asarray(raw[0])], rtol=1e-6)
    # the preset's own bias moves the choice of some tokens of a layer
    cfg = get_config(NAME)
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    lp = jax.tree.map(lambda a: a[0], params["layers_moe"])
    h = jax.random.normal(jax.random.PRNGKey(5), (64, cfg.d_model))
    scores = h @ lp["w_router"]
    with_b, _ = route(scores, 3, bias=lp["router_bias"])
    without, _ = route(scores, 3, bias=jnp.zeros_like(lp["router_bias"]))
    moved = (np.sort(with_b, -1) != np.sort(without, -1)).any(-1).mean()
    assert 0.05 < moved < 0.95


FORMS = {
    # what is asked of moe_block, and the words of its refusal
    "softmax-with-a-bias": (
        dict(scoring="softmax", router_bias=jnp.zeros((4,))), "takes no correction bias"),
    "sigmoid-without-one": (dict(scoring="sigmoid_bias"), "needs correction bias"),
    "sigmoid-over-groups": (
        dict(scoring="sigmoid_bias", router_bias=jnp.zeros((4,)), n_groups=2),
        "over expert groups is not computed"),
    "another-scoring": (dict(scoring="noisy_top_k"), "is not computed"),
}


@pytest.mark.parametrize("case", FORMS)
def test_a_scoring_that_is_not_computed_is_refused(case):
    from llm_consensus_tpu.ops.moe import moe_block

    how, words = FORMS[case]
    x, w = jnp.zeros((1, 2, 8)), jnp.zeros((4, 8, 8))
    with pytest.raises(ValueError, match=words):
        moe_block(x, jnp.zeros((8, 4)), None, w, w, top_k=2, **how)


def test_relu2_and_the_ungated_form():
    from llm_consensus_tpu.ops.mlp import _activate, plain_mlp

    x = jnp.asarray([[-2.0, 0.5, 3.0]])
    np.testing.assert_allclose(_activate(x, "relu2"), [[0.0, 0.25, 9.0]])
    w1, w2 = jnp.eye(3) * 2.0, jnp.ones((3, 2))
    np.testing.assert_allclose(plain_mlp(x, w1, w2, "relu2"), [[37.0, 37.0]])


def test_int8_weights_for_the_new_leaves_are_computed(model):
    """Every matmul leaf of the three stacks stored int8 (the latent
    projections among them): the program computes with codes times scales,
    which is what the reference reads from the same tree."""
    cfg, params = model
    q = quantize_params(params)
    for stack, leaf in reference.STORED_LEAVES:
        assert set(q[stack][leaf]) == {"q8", "s"}, (stack, leaf)
    assert not isinstance(q["layers_moe"]["w_router"], dict)
    assert not isinstance(q["layers_ssm"]["ssm_conv"], dict)
    want = reference.forward(q, tiny_spec(), IDS[:48])
    with jax.default_matmul_precision("highest"):
        got, _ = through_the_cache(cfg, q, IDS[:48], 40, jnp.float32)
    assert rel_err(got, want).max() < 2e-5
    streamed = jax.eval_shape(lambda: init_params_quantized(cfg, jax.random.PRNGKey(0)))
    assert set(streamed["layers_moe"]["w_latent_out"]) == {"q8", "s"}


# -- through the engine and the pool, token for token ---------------------------

GREEDY = dict(temperature=0.0, ignore_eos=True)


def test_a_pool_of_unequal_rows_books_what_it_routed_and_scanned(model, monkeypatch):
    """A wave of unequal rows in a pool of four (one row never has a
    tenant), a latecomer, a row reused: each stream token for token what the
    engine generates alone; the ``moe_*`` and ``ssm_*`` counters are booked
    for this family's pool, and ``engine.build`` says how many layers of
    each kind there are."""
    from llm_consensus_tpu.engine import ContinuousBatcher, SamplingParams

    monkeypatch.setenv("LLMC_KV_POOL", "0")
    cfg, params = model
    eng = E.Engine(cfg, params=params, dtype=jnp.float32, max_seq=256,
                   stream_interval=8, prefill_chunk=16)
    assert not eng.prefix_cache_enabled
    assert {k: eng.build_stats[k] for k in (
        "ssm_layers", "attn_layers", "expert_layers", "experts_held",
        "router_width", "cache_bytes_per_token", "state_bytes_per_row")} == {
            "ssm_layers": 5, "attn_layers": 1, "expert_layers": 5,
            "experts_held": 16, "router_width": 16,
            "cache_bytes_per_token": 1 * 2 * 2 * 24 * 4,
            "state_bytes_per_row": 5 * (6 * 8 * 16 * 4 + 3 * 112 * 4)}
    s = SamplingParams(max_new_tokens=20, **GREEDY)
    prompts = ["short", "a prompt of middling length for the wave",
               "the longest of the three rows of this wave by some margin, "
               "long enough to take more than one prefill chunk"]
    pool = ContinuousBatcher(eng, max_batch=4)
    try:
        assert not pool._prefix_enabled
        futs = [pool.submit(p, s) for p in prompts]
        for p, f in zip(prompts, futs):
            assert f.result(timeout=300).token_ids == eng.generate(p, s).token_ids, p
        again = pool.submit(prompts[1] + " again", s)
        assert again.result(timeout=300).token_ids == eng.generate(
            prompts[1] + " again", s).token_ids
        st = pool.snapshot()
        for leaf in jax.tree.leaves(pool._cache["ssm"]):
            assert bool(jnp.isfinite(leaf).all())
    finally:
        pool.close()
    assert st["moe_layer_steps"] == st["decode_steps"] * 5
    assert 0 < st["moe_pairs_held"] == st["moe_pairs_total"]  # every expert held
    assert 0 < st["moe_expert_reads"] <= 16 * st["moe_layer_steps"]
    assert st["ssm_state_row_steps"] == st["decode_steps"] * 4
    assert 0 < st["ssm_positions_live"] <= st["ssm_positions_swept"]


def test_the_tree_helpers_take_leaves_of_different_depths():
    """Splice, compaction and a shrink map over a cache whose leaves count
    different layers (1 of keys and values, 5 of state): rows on axis 1,
    slots on axis 2, whatever axis 0 holds."""
    from llm_consensus_tpu.engine.batcher import (
        _compact_cache, _shrink_rows, _splice, _splice_rows)

    cfg = get_config(NAME)

    def marked(rows, slots, base):
        cache = init_kv_cache(cfg, rows, slots, jnp.float32)
        return jax.tree.map(
            lambda a: a + (base + jnp.arange(rows, dtype=a.dtype)).reshape(
                1, rows, *(1,) * (a.ndim - 2)), cache)

    pool, one = marked(4, 32, 10.0), marked(1, 16, 50.0)
    out = _splice(jax.tree.map(jnp.copy, pool), one, 2, 5, 16)
    state = np.asarray(out["ssm"]["state"])
    assert state.shape[0] == 5 and state[:, 2].min() == 50.0 == state[:, 2].max()
    assert state[:, 1].max() == 11.0
    k = np.asarray(out["k"])
    assert k.shape[0] == 1 and k[0, 2, 5:21].min() == 50.0 and k[0, 2, :5].max() == 12.0
    wave = marked(2, 16, 70.0)
    out = _splice_rows(
        jax.tree.map(jnp.copy, pool), wave, jnp.asarray([1, 0]),
        jnp.asarray([0, 3]), jnp.asarray([4, 8]), 2, 16)
    conv = np.asarray(out["ssm"]["conv"])
    assert conv[4, 0].max() == 71.0 and conv[4, 3].max() == 70.0 and conv[4, 1].max() == 11.0
    out = _compact_cache(jax.tree.map(jnp.copy, pool), jnp.asarray(6))
    np.testing.assert_array_equal(out["ssm"]["state"], pool["ssm"]["state"])
    out = _shrink_rows(jax.tree.map(jnp.copy, pool), 2)
    assert out["ssm"]["state"].shape[:2] == (5, 2) and out["v"].shape[:2] == (1, 2)


# -- what the family is refused, by its message ---------------------------------


def _engine(**how):
    return E.Engine(get_config(NAME), max_seq=128, **how)


def _refuse_radix_arena(monkeypatch):
    monkeypatch.setenv("LLMC_KV_POOL", "1")
    _engine()


def _refuse_mesh():
    from llm_consensus_tpu.parallel import make_mesh

    _engine(mesh=make_mesh({"dp": 1, "tp": 2}, jax.devices()[:2]))


def _refuse_pool_speculation():
    from llm_consensus_tpu.engine import ContinuousBatcher
    from llm_consensus_tpu.engine.speculative import SpecConfig

    ContinuousBatcher(_engine(), max_batch=2, spec=SpecConfig(kind="lookup"))


def _refuse_engine_speculation():
    from llm_consensus_tpu.engine.speculative import (
        PromptLookupDrafter, SpeculativeEngine)

    SpeculativeEngine(_engine(), PromptLookupDrafter())


def _refuse_shared_prefix_admission():
    from llm_consensus_tpu.engine import ContinuousBatcher

    b = ContinuousBatcher(_engine(), max_batch=2)
    try:
        assert not b._prefix_enabled
        b._establish_prefix(list(range(40)))
    finally:
        b.close()


def _refuse_handoff():
    from llm_consensus_tpu.engine.handoff import KVHandoff

    eng = _engine()
    KVHandoff(eng, eng)


def _refuse_checkpoint():
    from llm_consensus_tpu.engine.checkpoint import load_hf_safetensors

    load_hf_safetensors(get_config(NAME), "/nonexistent")


def _forward_with(**kw):
    cfg = get_config(NAME)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    cache = init_kv_cache(cfg, 1, 32)
    forward(params, cfg, jnp.zeros((1, 4), jnp.int32), cache, 0, **kw)


REFUSALS = {
    # name: (what is tried, words the message must hold)
    "int8-cache": (lambda: _engine(kv_quant="int8"),
                   "no int8 cache for a state-space model"),
    "int8-cache-shape": (
        lambda: init_kv_cache(get_config(NAME), 1, 32, quant="int8"),
        "no quantized cache for a state-space model"),
    "radix-arena": (_refuse_radix_arena, "radix KV arena"),
    "mesh-tp": (_refuse_mesh, "runs on one chip"),
    "pool-speculation": (_refuse_pool_speculation, "no speculative pool decode"),
    "engine-speculation": (_refuse_engine_speculation, "no speculative decoding"),
    "speculative-bitmap": (
        lambda: _forward_with(kv_mask=jnp.ones((1, 32), bool),
                              row_start=jnp.zeros((1,), jnp.int32)),
        "no speculative decoding"),
    "shared-prefix": (
        lambda: _forward_with(prefix={"k": None}, prefix_len=jnp.asarray(2)),
        "no shared-prefix attention"),
    "shared-prefix-admission": (
        _refuse_shared_prefix_admission, "no pooled shared-prefix admission"),
    "ring-prefill": (lambda: _forward_with(attn_impl="ring"), "no sequence-parallel"),
    "prefill-session": (
        lambda: _engine().prefill_session(), "no incremental prefill session"),
    "handoff": (_refuse_handoff, "no cross-mesh handoff"),
    "checkpoint-import": (_refuse_checkpoint, "no checkpoint importer"),
}


@pytest.mark.parametrize("case", REFUSALS)
def test_what_the_family_does_not_get_is_refused_by_name(case, monkeypatch):
    attempt, words = REFUSALS[case]
    with pytest.raises(ValueError) as stop:
        attempt(monkeypatch) if attempt is _refuse_radix_arena else attempt()
    assert words in str(stop.value) and NAME in str(stop.value)


def test_the_family_is_never_sharded_and_never_snapshots_a_prefix():
    from llm_consensus_tpu.parallel.mesh import best_tp
    from llm_consensus_tpu.parallel.sharding import cache_specs, param_specs

    cfg = get_config(NAME)
    assert best_tp(cfg, 4) == 1
    specs = param_specs(cfg)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert jax.tree.structure(specs, is_leaf=lambda x: not isinstance(x, dict)) \
        == jax.tree.structure(shapes)
    assert all(ax is None for spec in jax.tree.leaves(
        specs, is_leaf=lambda x: not isinstance(x, dict)) for ax in spec)
    assert set(cache_specs(cfg)) == {"k", "v", "ssm"}
    assert not _engine().prefix_cache_enabled


# -- counts ----------------------------------------------------------------------


def published():
    """The model at its published sizes, and the cell's cut of it."""
    spec = config("nemotron3-super-ep8-trio-bf16")["models"]["nemotron-3-super"]
    cut = server.model_config("cut", spec)
    whole = server.model_config("whole", {
        **spec, **{k: spec["published"][k] for k in ("n_layers", "vocab_size")},
        "more_fields": {**spec["more_fields"], "n_experts": 512, "router_width": 0,
                        "layer_kinds": spec["published"]["layer_kinds"]}})
    return whole, cut


def test_param_count_at_the_published_sizes():
    from llm_consensus_tpu.utils.flops import (
        cache_bytes_per_token, param_count, state_bytes_per_row)

    whole, cut = published()
    assert whole.layer_kinds == PUBLISHED_PATTERN and len(PUBLISHED_PATTERN) == 88
    assert (whole.n_ssm_layers, whole.n_expert_layers, whole.n_attn_layers) == (40, 40, 8)
    assert round(param_count(whole) / 1e9, 2) == 120.67
    assert round(param_count(whole, active_only=True) / 1e9, 2) == 12.77
    # by layer kind, the issue's hand numbers
    mixer = 4096 * 18560 + 10240 * 5 + 3 * 128 + 8192 + 8192 * 4096 + 4096
    outside = 4096 * 512 + 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 + 4096
    expert = 2 * 1024 * 2688
    attn = 2 * 4096 * 4096 + 2 * 4096 * 256 + 4096
    assert (mixer, outside, expert, attn) == (109_640_064, 54_530_560, 5_505_024, 35_655_680)
    assert param_count(whole) == (
        40 * mixer + 40 * (outside + 512 * expert) + 8 * attn
        + 2 * 131072 * 4096 + 4096)
    # the cell's cut: one period, an eighth of the experts and of the vocabulary
    shapes = jax.eval_shape(lambda: init_params(cut, jax.random.PRNGKey(0)))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert param_count(cut) == n == (
        5 * mixer + 5 * (outside + 64 * expert) + attn + 2 * 16384 * 4096 + 4096)
    assert round(2 * n / 1e9, 2) == 5.50
    assert cache_bytes_per_token(cut) == 1024
    assert state_bytes_per_row(cut) == 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    cache = jax.eval_shape(lambda: init_kv_cache(cut, 6, 4096))
    assert cache["k"].shape == (1, 6, 4096, 2, 128)
    assert cache["ssm"]["state"].shape == (5, 6, 128, 64, 128)
    assert cache["ssm"]["conv"].shape == (5, 6, 3, 10240)


def test_the_cells_file_states_the_catalogs_numbers():
    """Every number of the published config.json stands in the cell's file
    under its key, but the three the file lists as reduced."""
    doc = config("nemotron3-super-ep8-trio-bf16")
    assert doc["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert doc["published"]["hybrid_override_pattern"] == PUBLISHED_PATTERN
    assert doc["hybrid_override_pattern"] == PUBLISHED_PATTERN[:doc["num_hidden_layers"]]
    assert (doc["num_hidden_layers"], doc["n_routed_experts"], doc["vocab_size"]) == (
        11, 64, 16384)
    for key, value in {
            "hidden_size": 4096, "num_attention_heads": 32, "num_key_value_heads": 2,
            "head_dim": 128, "mamba_num_heads": 128, "mamba_head_dim": 64,
            "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4, "chunk_size": 128,
            "moe_intermediate_size": 2688, "moe_latent_size": 1024,
            "moe_shared_expert_intermediate_size": 5376, "num_experts_per_tok": 22,
            "routed_scaling_factor": 5, "n_group": 1, "topk_group": 1}.items():
        assert doc[key] == value, key
    for word in ("rotary", "mtp", "serving_peak"):
        assert word in doc["assumed"]


@pytest.mark.parametrize("case", IN_PLACE_ROWS)
def test_the_in_place_step_is_the_sliced_step_to_the_last_bit(case):
    """The five mixers' rows advance where they lie in the state stack, each
    at a STATIC index (``_walk_kinds`` unrolls the pattern), by the numbers
    of slice -> ``ssd_step`` -> update (tests/test_falcon_h1.py has the
    cases, and a traced index)."""
    in_place_step_is_the_sliced_step(NAME, False, case)


# -- the older families' programs: the parent's text, byte for byte -------------

OLDER = {
    # family: (preset, weight and cache quantisation)
    "qwen2": ("tiny-qwen2", None),
    "mistral-int8": ("tiny-mistral", "int8"),
    "deepseek_v2": ("tiny-deepseek-v2", None),
    "falcon_h1": ("tiny-falcon-h1", None),
    "mixtral": ("tiny-mixtral", None),
}
PROGRAMS = ("decode_chunk", "six_row_wave", "judge_prompt_loop")
LOWERED = [(f, p) for f in OLDER for p in PROGRAMS]
ROWS, SLOTS, CHUNK = 6, 256, 64


def lowered_text(family: str, program: str) -> str:
    """The text a program of an older family lowers to, on abstract
    operands of a pool of six: the decode chunk, the six-row prefill wave,
    the judge prompt's chunk loop."""
    name, quant = OLDER[family]
    cfg = MODEL_PRESETS[name]
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(
        (lambda: init_params_quantized(cfg, key)) if quant
        else (lambda: init_params(cfg, key)))
    rows = 1 if program == "judge_prompt_loop" else ROWS
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, rows, SLOTS, quant=quant))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    if program == "decode_chunk":
        lowered = E._decode_chunk.lower(
            params, cfg, i32(ROWS), i32(), cache,
            jax.ShapeDtypeStruct((2,), jnp.uint32), 16, 0.0, None, None,
            row_start=i32(ROWS), kv_width=128, attn_impl="flash",
            sentinel=True, moe_stats=True)
    elif program == "six_row_wave":
        lowered = E._prefill_step.lower(
            params, cfg, i32(ROWS, CHUNK), i32(ROWS), cache,
            attn_impl="flash", row_start=i32(ROWS), kv_width=CHUNK,
            moe_stats=True, row_end=i32(ROWS) if cfg.has_ssm else None)
    else:
        lowered = E._prefill_chunks_loop.lower(
            params, cfg, i32(4, 1, CHUNK), i32(), i32(), i32(1), cache, 4,
            SLOTS, moe_stats=True)
    return lowered.as_text()


def digest(family: str, program: str) -> str:
    return hashlib.sha256(lowered_text(family, program).encode()).hexdigest()


@pytest.mark.parametrize("family,program", LOWERED)
def test_an_older_familys_program_lowers_to_the_parents_text(family, program):
    with open(PINS) as f:
        pins = json.load(f)
    assert digest(family, program) == pins[f"{family}.{program}"]


if __name__ == "__main__":
    # python tests/test_nemotron_h.py <out.json>, from a checkout's root:
    # the digests of that checkout's lowered text, the pins above.
    with open(sys.argv[1], "w") as out:
        json.dump({f"{f}.{p}": digest(f, p) for f, p in LOWERED}, out, indent=1)
        out.write("\n")
