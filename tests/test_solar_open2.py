"""The Solar-Open2 stack (PR 44): Kimi-Delta-Attention layers (a matrix state
a row under a gated delta rule) beside output-gated attention without rotary
embedding, every layer followed by routed experts, as one-part layers
``*EKEKEKE``; and what the family is refused.

The yardstick is ``benchmark/reference/solar_open2.py``, which imports
nothing of the program: the plain recurrence (a position a step), the held
experts one at a time, no cache. The model is ``tiny-solar-open2``: one whole
period of four published layers at CI size (1 attention layer of 4/2 heads of
24 with its gate; 3 delta layers of 3 heads of 16, two sub-chunks a chunk of
32, beta in (0, 2); 4 expert halves of 16 gated experts of 40, 3 a token by
sigmoid score + bias, one shared expert of 40).

Last: Nemotron-H's programs, which walk the same ``_walk_kinds``, and this
family's own lower to their pinned text (``tests/data/
lowered_text_pins.json``, keys ``nemotron_h.*`` and ``solar_open2.*``; the
older families' pins are tests/test_nemotron_h.py's).
"""

import copy
import dataclasses
from functools import partial
import glob
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

if __name__ != "__main__":  # a parent checkout has neither
    from benchmark import parity, server
    from benchmark.reference import solar_open2 as reference
    from llm_consensus_tpu.ops import delta
    from llm_consensus_tpu.ops.quant import quantize_params

NAME = "tiny-solar-open2"
PINS = os.path.join(REPO, "tests", "data", "lowered_text_pins.json")
CONFIGS = sorted(glob.glob(os.path.join(REPO, "benchmark/configs/*.json")))


def config(name: str) -> dict:
    with open(os.path.join(REPO, f"benchmark/configs/{name}.json")) as f:
        return json.load(f)


def tiny_spec(share: bool = False) -> dict:
    """The preset as the harness states a model: the rehearsal's entry (the
    preset's first two published layers, ``*EKE``, with 8 experts a token)
    with the preset's own eight layers and 3 experts put back; or a strict
    share of it: experts 4-5 of the 16 the router scores."""
    spec = copy.deepcopy(
        config("tiny-solar-open2-rehearsal")["models"][f"{NAME}-top8"])
    spec["more_fields"].update(experts_per_token=3, layer_kinds="*EKEKEKE")
    spec["n_layers"] = 8
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


@partial(jax.jit, static_argnames=("cfg", "remat"))
def run(params, cfg, tokens, cache=None, start=0, row_start=None, remat=False):
    """``forward`` as one program (an eager walk compiles every operation of
    the chunked rule apart), at ``highest`` precision."""
    with jax.default_matmul_precision("highest"):
        return forward(params, cfg, tokens, cache, start, remat=remat,
                       row_start=row_start)


def through_the_cache(cfg, params, ids, n_pre, dtype, slots=96):
    """Prefill ``n_pre`` positions at once, the rest a token a step."""
    cache = init_kv_cache(cfg, 1, slots, dtype)
    logits, cache = run(
        params, cfg, jnp.asarray(ids[None, :n_pre], jnp.int32), cache)
    rows = [logits[0]]
    for p in range(n_pre, len(ids)):
        step, cache = run(
            params, cfg, jnp.asarray(ids[None, p:p + 1], jnp.int32), cache,
            jnp.asarray(p, jnp.int32))
        rows.append(step[0])
    return jnp.concatenate(rows, axis=0), cache


# -- the rule itself: the chunked form against the plain recurrence ------------


def plain_rule(q, k, v, g, beta, state):
    """The recurrence a position a step, by ``kda_step``."""
    def step(s, at):
        o, s = delta.kda_step(*at, s)
        return s, o

    with jax.default_matmul_precision("highest"):
        state, o = jax.lax.scan(
            step, state, tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def drawn(t: int, decay: str, neg_eigval: bool, b=2, h=3, p=16, seed=0):
    """Operands of the rule as the layer makes them: unit keys, queries at
    ``p^-1/2``, beta in (0, 1) or (0, 2), a log decay a channel."""
    keys = jax.random.split(jax.random.PRNGKey(seed + t), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (b, t, h, p))) * p ** -0.5
    k = unit(jax.random.normal(keys[1], (b, t, h, p)))
    v = jax.random.normal(keys[2], (b, t, h, p))
    u = jax.random.uniform(keys[3], (b, t, h, p))
    if decay == "mild":             # dt in [1e-3, 1e-1], A in [1, 16]
        g = -jnp.exp(u * np.log(100.0) + np.log(1e-3)) * (1 + 15 * u[..., :1])
    elif decay == "initialiser":    # the strongest it draws: A 16, dt 0.1
        g = jnp.full_like(u, -1.6)
    else:                           # a gate far open: e^-64 a position, and
        g = -16.0 * jax.nn.softplus(4.0 + u)  # keys that repeat under beta 2
        k = unit(0.1 * k + k[:, :1])
    beta = jax.random.uniform(keys[4], (b, t, h)) * (2.0 if neg_eigval else 1.0)
    state = jax.random.normal(keys[5], (b, h, p, p))
    return q, k, v, g, beta, state


RULE_CASES = [
    # T, chunk, decay, neg_eigval: one chunk, several, a ragged last one, a
    # chunk of one sub-chunk, and every decay under both ranges of beta
    (32, 32, "mild", True), (70, 32, "mild", True), (200, 64, "mild", False),
    (16, 8, "mild", True), (5, 32, "mild", False),
    (64, 64, "initialiser", True), (130, 64, "initialiser", False),
    (64, 64, "strongest", True), (130, 64, "strongest", False),
    (96, 32, "strongest", True),
]


@pytest.mark.parametrize("t,chunk,decay,neg_eigval", RULE_CASES)
def test_the_chunked_rule_is_the_plain_recurrence(t, chunk, decay, neg_eigval):
    args = drawn(t, decay, neg_eigval)
    want_o, want_s = plain_rule(*args)
    got_o, got_s = jax.jit(delta.kda_chunked, static_argnums=6)(*args, chunk)
    assert bool(jnp.isfinite(got_o).all()) and bool(jnp.isfinite(got_s).all())
    scale = float(jnp.abs(want_o).max())
    assert float(jnp.abs(got_o - want_o).max()) < 1e-4 * max(scale, 1.0)
    assert float(jnp.abs(got_s - want_s).max()) < 1e-3


def test_no_factor_is_the_exponential_of_a_positive_number():
    """Decay sums of -64 a position: e^(G_t - G_i) taken apart as e^(G_t)
    e^(-G_i) would be 0 times infinity. Every entry of the two tables is a
    sum of products of factors that are at most 1."""
    q, k, _, g, _, _ = drawn(64, "strongest", True)
    to_chunk = lambda x: jnp.moveaxis(x, 1, 2)  # noqa: E731 — [B, H, C, P]
    cum = jnp.cumsum(to_chunk(g), axis=-2)
    for strict in (True, False):
        table = delta.decayed_products(to_chunk(q), to_chunk(k), cum, strict)
        assert bool(jnp.isfinite(table).all())
        assert float(jnp.abs(table).max()) <= 1.0
        upper = jnp.triu(jnp.ones((64, 64), bool), 0 if strict else 1)
        assert not np.asarray(jnp.where(upper, table, 0.0)).any()


def test_the_unit_lower_inverse_where_powers_of_the_table_would_cancel():
    """Keys that all repeat under beta 2 and no decay: ``A`` is 2 below the
    diagonal, its 32nd power 1e27, its inverse's entries +-2."""
    a = 2.0 * jnp.tril(jnp.ones((64, 64)), -1)
    inv = delta.unit_lower_inverse(a[None])[0]
    np.testing.assert_allclose(inv @ (jnp.eye(64) + a), np.eye(64), atol=1e-4)
    assert float(jnp.abs(inv).max()) == 2.0
    # block row by block row is row by row: the same inverse of a drawn table
    drawn_table = 0.3 * jnp.tril(jax.random.normal(jax.random.PRNGKey(7), (2, 3, 64, 64)), -1)
    np.testing.assert_allclose(
        delta.unit_lower_inverse(drawn_table), delta._substitute(drawn_table),
        rtol=1e-4, atol=1e-5)


# -- the layer: spans, the state and the tail ----------------------------------


@partial(jax.jit, static_argnames=("cfg",))
def kda_layer(cfg, lp, u, state=None, tail=None, lo=None, hi=None):
    b = u.shape[0]
    p = cfg.kda_head_dim
    state = jnp.zeros((b, cfg.kda_heads, p, p)) if state is None else state
    tail = jnp.zeros((b, cfg.kda_conv - 1, cfg.kda_conv_width)) if tail is None else tail
    with jax.default_matmul_precision("highest"):
        return delta.kda(cfg, u, lp, state, tail, lo, hi)


@pytest.mark.parametrize("neg_eigval", [True, False])
def test_padding_on_either_side_leaves_state_and_tail_alone(model, neg_eigval):
    """A ragged ``row_end`` and a left-padded start: state and tail after a
    padded call are those after the row's real positions alone, and the real
    positions' outputs are the same."""
    cfg, params = model
    cfg = dataclasses.replace(cfg, kda_neg_eigval=neg_eigval)
    lp = jax.tree.map(lambda a: a[1], params["layers_kda"])
    u = jax.random.normal(jax.random.PRNGKey(1), (3, 40, cfg.d_model))
    lo, hi = jnp.asarray([0, 7, 12]), jnp.asarray([40, 29, 33])
    out, state, tail = kda_layer(cfg, lp, u, lo=lo, hi=hi)
    for row in range(3):
        a, b = int(lo[row]), int(hi[row])
        own, own_state, own_tail = kda_layer(cfg, lp, u[row:row + 1, a:b])
        np.testing.assert_allclose(out[row, a:b], own[0], atol=2e-5)
        np.testing.assert_allclose(state[row], own_state[0], atol=2e-5)
        np.testing.assert_allclose(tail[row], own_tail[0], atol=1e-6)
    # a call of padding alone changes nothing
    none = jnp.asarray([0, 0, 0])
    _, state2, tail2 = kda_layer(cfg, lp, u, state, tail, lo=none, hi=none)
    np.testing.assert_array_equal(state2, state)
    np.testing.assert_array_equal(tail2, tail)
    # and so does a step on a row that is not live
    _, state3, tail3 = kda_layer(
        cfg, lp, u[:, :1], state, tail, lo=none, hi=jnp.asarray([1, 0, 1]))
    np.testing.assert_array_equal(state3[1], state[1])
    np.testing.assert_array_equal(tail3[1], tail[1])
    assert float(jnp.abs(state3[0] - state[0]).max()) > 0


def test_steps_through_the_cache_are_the_chunked_prefill(model):
    """T = 1 steps through the cache against the chunked prefill of the
    same tokens: logits, state and tail."""
    cfg, params = model
    stepped, cache_s = through_the_cache(cfg, params, IDS[:72], 1, jnp.float32)
    whole, cache_w = through_the_cache(cfg, params, IDS[:72], 72, jnp.float32)
    mixed, cache_m = through_the_cache(cfg, params, IDS[:72], 37, jnp.float32)
    assert rel_err(stepped, whole).max() < 2e-5
    assert rel_err(mixed, whole).max() < 2e-5
    for other in (cache_s, cache_m):
        np.testing.assert_allclose(
            other["ssm"]["state"], cache_w["ssm"]["state"], rtol=1e-4, atol=2e-5)
        np.testing.assert_allclose(
            other["ssm"]["conv"], cache_w["ssm"]["conv"], rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(cache_w["ssm"]["state"]).max()) > 0


# -- the preset, its stacks and its caches -------------------------------------


def test_the_rehearsals_entry_is_the_preset():
    cfg = get_config(NAME)
    assert server.model_config(NAME, tiny_spec()) == cfg
    stated = config("tiny-solar-open2-rehearsal")["models"][f"{NAME}-top8"]
    # what the rehearsal runs: the preset's first two published layers with
    # 8 of its 16 experts a token (under the reference's chip limits at CI size)
    assert server.model_config(f"{NAME}-top8", stated) == dataclasses.replace(
        cfg, name=f"{NAME}-top8", experts_per_token=8, n_layers=4,
        layer_kinds="*EKE")
    assert cfg.layer_kinds == "*EKEKEKE"
    assert (cfg.n_kda_layers, cfg.n_expert_layers, cfg.n_attn_layers) == (3, 4, 1)
    assert cfg.kind_layers("K") == (2, 4, 6) and cfg.kind_layers("*") == (0,)
    assert cfg.has_state and cfg.has_kda and not cfg.has_ssm and cfg.n_ssm_layers == 0
    assert cfg.is_moe and not cfg.is_latent and not cfg.rotary and cfg.attn_out_gate
    assert (cfg.scan_chunk, cfg.kda_inner, cfg.kda_conv_width) == (32, 48, 144)
    # the older presets: every new field off, the one question answered
    for name, state in (("tiny-falcon-h1", True), ("tiny-nemotron-h", True),
                        ("tiny-llama", False), ("tiny-deepseek-v2", False)):
        old = get_config(name)
        assert (old.kda_heads, old.attn_out_gate, old.n_kda_layers) == (0, False, 0)
        assert old.has_state == old.has_ssm == state and not old.has_kda
    assert get_config("tiny-nemotron-h").scan_chunk == 8
    for how, words in (
            (dict(layer_kinds="*EKEKEKX"), "layer_kinds"),
            (dict(layer_kinds="*E*E*E*E"), "disagree"),
            (dict(kda_heads=0), "disagree"),
            (dict(ssm_heads=2, ssm_head_dim=8, ssm_state=8,
                  layer_kinds="*EKEKEME"), "one kind of state")):
        with pytest.raises(ValueError, match=words):
            get_config(NAME, **how)
    with pytest.raises(ValueError, match="one-part layer"):
        get_config("tiny-llama", kda_heads=2)


def test_a_stack_and_a_cache_a_layer_kind():
    cfg = get_config(NAME)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert set(shapes) == {
        "embed", "final_norm", "lm_head", "layers_kda", "layers_moe", "layers_attn"}
    kda = shapes["layers_kda"]
    assert kda["wq"].shape == kda["wk"].shape == kda["wv"].shape == (3, 96, 48)
    assert (kda["wo"].shape, kda["kda_conv"].shape) == ((3, 48, 96), (3, 144, 4))
    assert (kda["kda_f_a"].shape, kda["kda_f_b"].shape) == ((3, 96, 8), (3, 8, 48))
    assert (kda["kda_g_a"].shape, kda["kda_g_b"].shape) == ((3, 96, 8), (3, 8, 48))
    assert (kda["kda_beta"].shape, kda["kda_dt_bias"].shape, kda["kda_a_log"].shape,
            kda["kda_norm"].shape) == ((3, 96, 3), (3, 48), (3, 3), (3, 16))
    assert shapes["layers_attn"]["w_ogate"].shape == (1, 96, 96)
    moe = shapes["layers_moe"]
    assert (moe["w_gate"].shape, moe["ws_gate"].shape, moe["router_bias"].shape) == (
        (4, 16, 96, 40), (4, 96, 40), (4, 16))
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 3, 64, jnp.bfloat16))
    assert cache["k"].shape == cache["v"].shape == (1, 3, 64, 2, 24)
    assert cache["ssm"]["state"].shape == (3, 3, 3, 16, 16)
    assert cache["ssm"]["state"].dtype == jnp.float32   # whatever the served type
    assert cache["ssm"]["conv"].shape == (3, 3, 3, 144)
    assert cache["ssm"]["conv"].dtype == jnp.bfloat16


def test_the_initialiser_draws_the_decay_in_the_published_ranges(model):
    _, params = model
    kda = params["layers_kda"]
    a = np.exp(np.asarray(kda["kda_a_log"]))
    dt = np.log1p(np.exp(np.asarray(kda["kda_dt_bias"])))
    assert 1.0 <= a.min() and a.max() <= 16.0
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001


# -- the model against the reference -------------------------------------------

# float32 is tight. In bfloat16 a routed model's worst position is a routing
# flip (another expert than the float32 reference picks, on scores that
# nearly tie): what is held is the median, about twice what this size reads.
PRECISIONS = {"float32": (jnp.float32, 3e-5, 3e-5), "bfloat16": (jnp.bfloat16, 1.2, 0.12)}


@pytest.mark.parametrize("precision", PRECISIONS)
def test_forward_matches_the_reference_whole_sequence(precision):
    dtype, worst, median = PRECISIONS[precision]
    cfg = get_config(NAME)
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=dtype)
    want = reference.forward(params, tiny_spec(), IDS)
    got, _ = run(params, cfg, jnp.asarray(IDS[None], jnp.int32))
    err = rel_err(got[0], want)
    assert err.max() < worst and np.median(err) < median


@pytest.mark.parametrize("precision", PRECISIONS)
def test_prefill_then_decode_through_the_engine_matches_the_reference(precision):
    """The timed path's shape through the harness's own check: one prefill
    (the chunked rule over three chunks, the sorted dispatch), then decode
    steps through both caches (the one-step rule), against the reference's
    whole forward, logits and not tokens; on a strict share of the experts."""
    dtype, worst, median = PRECISIONS[precision]
    spec = tiny_spec(share=True)
    cfg = server.model_config(f"tiny-solar-open2-{precision}", spec)
    eng = E.Engine(cfg, max_seq=256, seed=0, dtype=dtype)
    sizes = {"seq_len": 96, "decoded": 32, "cache_slots": 128}
    out = parity.check_engine(eng, spec, precision, 5, sizes)
    assert out["reference"] == "solar_open2" and out["stored_as_stated"]
    assert out["rel_err_max"] < worst and out["rel_err_median"] < median
    assert out["compared"]["rel_err_decoded_median"][0] < median
    assert out["attention"] == {"prefill": ["xla"], "decode": ["xla"]}


def test_the_comparison_in_blocks_tells_the_rule_where_a_block_ends(model):
    """Past ``WHOLE_UP_TO`` positions the harness feeds the prefill through
    the cache a block at a time: the state carried over each seam."""
    cfg, params = model
    eng = E.Engine(cfg, params=params, dtype=jnp.float32, max_seq=256)
    sizes = {"seq_len": 80, "decoded": 10, "cache_slots": 96}
    ids = parity.draw_ids(3, cfg.name, cfg.vocab_size, 80)
    with jax.default_matmul_precision("highest"):
        err, _, _ = parity.errors_blocked(eng, reference, tiny_spec(), ids, sizes, 24)
    assert err.shape == (80,) and err.max() < 3e-5


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
        # each 16-slot program's rule runs over one whole chunk of 32
        assert E.scan_positions_swept(cfg, eng.last_prefill, 1) == 160
        rows = [last]
        for p in range(n_pre, len(IDS)):
            logits, cache = run(
                params, cfg, jnp.asarray(IDS[None, p:p + 1], jnp.int32), cache,
                jnp.asarray(p, jnp.int32))
            rows.append(logits[0])
    want = reference.forward(params, tiny_spec(), IDS)[n_pre - 1:]
    assert rel_err(jnp.concatenate(rows), want).max() < 3e-5


def test_rows_with_different_starts_and_a_dead_row(model):
    """A left-padded wave: each row's first real token is its position 0
    (no rotary embedding to say so: the mask and the rule's spans do), a row
    without a stream neither reads nor disturbs the others."""
    from llm_consensus_tpu.engine.batcher import DEAD_ROW

    cfg, params = model
    starts, t = [0, 5, 11, DEAD_ROW], 24
    tokens = np.stack([IDS[i * 3:i * 3 + t] for i in range(4)])
    cache = init_kv_cache(cfg, 4, 64, jnp.float32)
    logits, cache = run(
        params, cfg, jnp.asarray(tokens, jnp.int32), cache, 0,
        jnp.asarray(starts, jnp.int32))
    step, cache = run(
        params, cfg, jnp.asarray(tokens[:, :1], jnp.int32), cache,
        jnp.asarray(t, jnp.int32), jnp.asarray(starts, jnp.int32))
    spec = tiny_spec()
    for row, start in enumerate(starts[:3]):
        own = np.concatenate([tokens[row, start:], tokens[row, :1]])
        want = reference.forward(params, spec, own)
        got = jnp.concatenate([logits[row, start:], step[row]])
        assert rel_err(got, want).max() < 3e-5, row
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
        return jnp.mean(run(p, cfg, tokens, remat=remat)[0] ** 2)

    np.testing.assert_allclose(loss(params, True), loss(params, False), rtol=1e-6)
    grads = jax.jit(jax.grad(partial(loss, remat=True)))(params)
    assert not np.asarray(grads["layers_moe"].pop("router_bias")).any()
    for stack in ("layers_kda", "layers_moe", "layers_attn"):
        for name, g in grads[stack].items():
            assert float(jnp.abs(g).max()) > 0, (stack, name)


def test_the_state_is_float32_under_a_bfloat16_model():
    cfg = get_config(NAME)
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.bfloat16)
    _, cache = through_the_cache(cfg, params, IDS[:40], 32, jnp.bfloat16)
    assert cache["ssm"]["state"].dtype == jnp.float32
    assert cache["k"].dtype == cache["ssm"]["conv"].dtype == jnp.bfloat16
    assert float(jnp.abs(cache["ssm"]["state"]).max()) > 0


def test_the_output_gate_gates(model):
    """The attention layer's output passes ``sigmoid(h W_gate)`` before
    ``wo``: a gate whose weights are zero halves it, which is the ungated
    layer with half of ``wo``; the drawn gate is neither that nor absent."""
    cfg, params = model
    tokens = jnp.asarray(IDS[None, :16], jnp.int32)
    attn = params["layers_attn"]
    ungated = {k: v for k, v in attn.items() if k != "w_ogate"}

    def logits(cfg, layers_attn):
        return run({**params, "layers_attn": layers_attn}, cfg, tokens)[0]

    plain = dataclasses.replace(cfg, attn_out_gate=False)
    half = logits(cfg, {**attn, "w_ogate": jnp.zeros_like(attn["w_ogate"])})
    np.testing.assert_allclose(
        half, logits(plain, {**ungated, "wo": 0.5 * attn["wo"]}), atol=2e-5)
    drawn_gate = logits(cfg, attn)
    assert float(jnp.abs(drawn_gate - half).max()) > 1e-2
    assert float(jnp.abs(drawn_gate - logits(plain, ungated)).max()) > 1e-2


# -- the expert layer: the shares ----------------------------------------------


def moe_layer(cfg, lp, h, first=0, held=None, shared=True):
    """ops/moe.py's layer on one layer's leaves, the experts ``[first, first
    + held)`` held."""
    from llm_consensus_tpu.ops.moe import moe_block

    held = cfg.n_experts if held is None else held
    cut = lambda name: lp[name][first:first + held]  # noqa: E731
    return moe_block(
        h, lp["w_router"], cut("w_gate"), cut("w_up"), cut("w_down"),
        top_k=cfg.experts_per_token, activation=cfg.activation,
        first_expert=first, norm_topk=cfg.norm_topk,
        routed_scale=cfg.routed_scale, scoring=cfg.router_scoring,
        router_bias=lp["router_bias"],
        shared=(lp["ws_gate"], lp["ws_up"], lp["ws_down"]) if shared else None)


def test_the_shares_add_up_to_the_uncut_layer(model):
    """The test that ties the share to the model: the routed parts of the
    eight shares of two experts, plus the shared expert counted once, are
    the uncut reference's expert layer."""
    from llm_consensus_tpu.ops.mlp import gated_mlp

    cfg, params = model
    lp = jax.tree.map(lambda a: a[2], params["layers_moe"])
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 24, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want = reference.experts(
            h.reshape(-1, cfg.d_model), lp, tiny_spec()["more_fields"])
        routed = sum(
            moe_layer(cfg, lp, h, first, 2, shared=False)
            for first in range(0, 16, 2))
        shared = gated_mlp(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"], "silu")
        whole = moe_layer(cfg, lp, h)
    got = (routed + shared).reshape(-1, cfg.d_model)
    assert rel_err(got, want).max() < 1e-5
    assert rel_err(whole.reshape(-1, cfg.d_model), want).max() < 1e-5
    # and no single share is the whole layer
    one = moe_layer(cfg, lp, h, 4, 2)
    assert rel_err(one.reshape(-1, cfg.d_model), want).max() > 1e-2


def test_int8_weights_for_the_new_leaves_are_computed(model):
    """Every matmul leaf the reference names stored int8 (the attention
    gate and the delta layer's four projections among them): the program
    computes with codes times scales, which is what the reference reads from
    the same tree; the low-rank gates and the convolution stay as made."""
    cfg, params = model
    q = quantize_params(params)
    for stack, leaf in reference.STORED_LEAVES:
        assert set(q[stack][leaf]) == {"q8", "s"}, (stack, leaf)
    for name in ("kda_conv", "kda_f_a", "kda_g_b", "kda_beta"):
        assert not isinstance(q["layers_kda"][name], dict)
    want = reference.forward(q, tiny_spec(), IDS[:48])
    got, _ = through_the_cache(cfg, q, IDS[:48], 40, jnp.float32)
    assert rel_err(got, want).max() < 3e-5


# -- through the engine and the pool, token for token ---------------------------

GREEDY = dict(temperature=0.0, ignore_eos=True)


def test_a_pool_of_unequal_rows_books_what_it_routed_and_scanned(model, monkeypatch):
    """A wave of unequal rows in a pool of four (one row never has a
    tenant), a latecomer, a row reused: each stream token for token what the
    engine generates alone; the ``moe_*`` counters and the counters a state
    layer already books (``ssm_*``) are booked for this family's pool, and
    ``engine.build`` says how many layers of each kind there are."""
    from llm_consensus_tpu.engine import ContinuousBatcher, SamplingParams

    monkeypatch.setenv("LLMC_KV_POOL", "0")
    cfg, params = model
    eng = E.Engine(cfg, params=params, dtype=jnp.float32, max_seq=256,
                   stream_interval=8, prefill_chunk=16)
    assert not eng.prefix_cache_enabled
    assert {k: eng.build_stats[k] for k in (
        "ssm_layers", "kda_layers", "attn_layers", "expert_layers",
        "experts_held", "router_width", "cache_bytes_per_token",
        "state_bytes_per_row")} == {
            "ssm_layers": 0, "kda_layers": 3, "attn_layers": 1,
            "expert_layers": 4, "experts_held": 16, "router_width": 16,
            "cache_bytes_per_token": 1 * 2 * 2 * 24 * 4,
            "state_bytes_per_row": 3 * (3 * 16 * 16 * 4 + 3 * 144 * 4)}
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
    assert st["moe_layer_steps"] == st["decode_steps"] * 4
    assert 0 < st["moe_pairs_held"] == st["moe_pairs_total"]  # every expert held
    assert 0 < st["moe_expert_reads"] <= 16 * st["moe_layer_steps"]
    assert st["ssm_state_row_steps"] == st["decode_steps"] * 4
    assert 0 < st["ssm_positions_live"] <= st["ssm_positions_swept"]


def test_a_splice_replaces_a_rows_state_whole():
    """Splice, compaction and a shrink map over a cache whose leaves count
    different layers (1 of keys and values, 3 of state): a spliced row's
    state and tail are the newcomer's, whole, and nobody else's move."""
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
    assert state.shape == (3, 4, 3, 16, 16)
    assert state[:, 2].min() == 50.0 == state[:, 2].max()
    assert state[:, 1].max() == 11.0 and state[:, 3].min() == 13.0
    k = np.asarray(out["k"])
    assert k.shape[0] == 1 and k[0, 2, 5:21].min() == 50.0 and k[0, 2, :5].max() == 12.0
    wave = marked(2, 16, 70.0)
    out = _splice_rows(
        jax.tree.map(jnp.copy, pool), wave, jnp.asarray([1, 0]),
        jnp.asarray([0, 3]), jnp.asarray([4, 8]), 2, 16)
    conv = np.asarray(out["ssm"]["conv"])
    assert conv[2, 0].max() == 71.0 and conv[2, 3].max() == 70.0 and conv[2, 1].max() == 11.0
    out = _compact_cache(jax.tree.map(jnp.copy, pool), jnp.asarray(6))
    np.testing.assert_array_equal(out["ssm"]["state"], pool["ssm"]["state"])
    out = _shrink_rows(jax.tree.map(jnp.copy, pool), 2)
    assert out["ssm"]["state"].shape[:2] == (3, 2) and out["v"].shape[:2] == (1, 2)


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
    # name: (what is tried, words the message must hold): what every family
    # whose rows hold a state is refused, by the one property ``has_state``
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
    spec = config("solar-open2-ep8-trio-bf16")["models"]["solar-open2"]
    cut = server.model_config("cut", spec)
    whole = server.model_config("whole", {
        **spec, **{k: spec["published"][k] for k in ("n_layers", "vocab_size")},
        "more_fields": {**spec["more_fields"], "n_experts": 320, "router_width": 0,
                        "layer_kinds": spec["published"]["layer_kinds"]}})
    return whole, cut


def test_param_count_is_the_tree_at_the_tiny_size():
    from llm_consensus_tpu.utils.flops import param_count

    cfg = get_config(NAME)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert param_count(cfg) == sum(x.size for x in jax.tree.leaves(shapes))
    share = server.model_config("share", tiny_spec(share=True))
    shapes = jax.eval_shape(lambda: init_params(share, jax.random.PRNGKey(0)))
    assert param_count(share) == sum(x.size for x in jax.tree.leaves(shapes))


def test_param_count_at_the_published_sizes():
    from llm_consensus_tpu.utils.flops import (
        cache_bytes_per_token, param_count, state_bytes_per_row)

    whole, cut = published()
    assert whole.layer_kinds == "*EKEKEKE" * 12 and whole.n_layers == 96
    assert (whole.n_kda_layers, whole.n_expert_layers, whole.n_attn_layers) == (36, 48, 12)
    # the published 250B-A15B: the check that the layers are read right
    assert round(param_count(whole) / 1e9, 2) == 250.29
    assert round(param_count(whole, active_only=True) / 1e9, 2) == 14.74
    # by layer kind, the issue's hand numbers (each with its norm)
    d, inner = 4096, 8192
    kda = (4 * d * inner + 3 * inner * 4 + 2 * 128 * (d + inner) + d * 64
           + inner + 64 + 128 + d)
    attn = 3 * d * inner + 2 * d * 1024 + d
    outside = d * 320 + 320 + 3 * d * 1280 + d
    expert = 3 * d * 1280
    assert (kda, attn, outside, expert) == (
        137_736_384, 109_056_000, 17_043_776, 15_728_640)
    assert param_count(whole) == (
        36 * kda + 12 * attn + 48 * (outside + 320 * expert)
        + 2 * 196608 * d + d)
    # the cell's cut: one period, an eighth of the experts and of the vocabulary
    shapes = jax.eval_shape(lambda: init_params(cut, jax.random.PRNGKey(0)))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert param_count(cut) == n == (
        3 * kda + attn + 4 * (outside + 40 * expert) + 2 * 24576 * d + d)
    assert round(2 * n / 1e9, 2) == 6.62
    assert cache_bytes_per_token(cut) == 4096          # ONE attention layer
    assert state_bytes_per_row(cut) == 3 * (64 * 128 * 128 * 4 + 3 * 3 * 8192 * 2)
    cache = jax.eval_shape(lambda: init_kv_cache(cut, 6, 4096))
    assert cache["k"].shape == (1, 6, 4096, 8, 128)
    assert cache["ssm"]["state"].shape == (3, 6, 64, 128, 128)
    assert cache["ssm"]["conv"].shape == (3, 6, 3, 3 * 8192)


def test_the_cells_file_states_the_catalogs_numbers():
    """Every number of the published config.json stands in the cell's file
    under its key, but the three the file lists as reduced."""
    doc = config("solar-open2-ep8-trio-bf16")
    assert doc["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert doc["published"] == {
        "num_hidden_layers": 48, "n_routed_experts": 320, "vocab_size": 196608}
    assert (doc["num_hidden_layers"], doc["n_routed_experts"], doc["vocab_size"]) == (
        4, 40, 24576)
    for key, value in {
            "hidden_size": 4096, "num_attention_heads": 64, "num_key_value_heads": 8,
            "head_dim": 128, "moe_intermediate_size": 1280, "intermediate_size": 10240,
            "num_experts_per_tok": 8, "n_shared_experts": 1, "routed_scaling_factor": 1,
            "first_k_dense_replace": 0, "gqa_interval": 3, "rms_norm_eps": 1e-05,
            "use_rope": False, "use_gqa_gate": True, "kda_use_full_proj": False,
            "kda_allow_neg_eigval": True, "norm_topk_prob": True}.items():
        assert doc[key] == value, key
    assert doc["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert doc["gqa_layers"] == list(range(0, 48, 4))
    more = doc["models"]["solar-open2"]["more_fields"]
    assert (more["kda_heads"], more["kda_head_dim"], more["kda_conv"],
            more["router_width"], more["n_experts"], more["experts_per_token"],
            more["d_expert"]) == (64, 128, 4, 320, 40, 8, 1280)
    for word in ("router", "attention_gate", "delta_rule", "state", "chunk",
                 "d_ff", "serving_peak"):
        assert word in doc["assumed"]


@pytest.mark.parametrize("path", CONFIGS, ids=[os.path.basename(p) for p in CONFIGS])
def test_every_configuration_still_loads_through_install_models(path, monkeypatch):
    with open(path) as f:
        doc = json.load(f)
    monkeypatch.setattr(
        "llm_consensus_tpu.models.config.MODEL_PRESETS", dict(MODEL_PRESETS))
    server.install_models(doc["models"])
    from llm_consensus_tpu.models import config as C

    for name, spec in doc["models"].items():
        cfg = C.MODEL_PRESETS[name]
        assert cfg.family == spec["family"] and cfg.n_layers == spec["n_layers"]
        hash(cfg)
    assert set(parity.stated(doc)) == set(doc["models"])


# -- the one-part families' programs: the parent's text, byte for byte -----------

PINNED = {"nemotron_h": "tiny-nemotron-h", "solar_open2": "tiny-solar-open2"}
PROGRAMS = ("decode_chunk", "six_row_wave", "judge_prompt_loop")
LOWERED = [(f, p) for f in PINNED for p in PROGRAMS]
ROWS, SLOTS, CHUNK = 6, 256, 64


def lowered_text(family: str, program: str) -> str:
    """The text a program of a family that walks ``_walk_kinds`` lowers to
    on its CI-size preset, on abstract operands of a pool of six
    (tests/test_nemotron_h.py ``lowered_text`` for the older families)."""
    cfg = MODEL_PRESETS[PINNED[family]]
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    rows = 1 if program == "judge_prompt_loop" else ROWS
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, rows, SLOTS))
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
            moe_stats=True, row_end=i32(ROWS))
    else:
        lowered = E._prefill_chunks_loop.lower(
            params, cfg, i32(4, 1, CHUNK), i32(), i32(), i32(1), cache, 4,
            SLOTS, moe_stats=True)
    return lowered.as_text()


def digest(family: str, program: str) -> str:
    return hashlib.sha256(lowered_text(family, program).encode()).hexdigest()


@pytest.mark.parametrize("family,program", LOWERED)
def test_a_one_part_familys_program_lowers_to_the_parents_text(family, program):
    """Nemotron-H's decode chunk was pinned anew in PR 45 (its mixers' step
    went into the state stack in place); the delta rule's kept its path, so
    Solar-Open2's three are the texts of PR 45's parent."""
    with open(PINS) as f:
        pins = json.load(f)
    assert digest(family, program) == pins[f"{family}.{program}"]


if __name__ == "__main__":
    # python tests/test_solar_open2.py <out.json>, from a checkout's root:
    # the digests of that checkout's lowered text, the pins above.
    with open(sys.argv[1], "w") as out:
        json.dump({f"{f}.{p}": digest(f, p) for f, p in LOWERED}, out, indent=1)
        out.write("\n")
