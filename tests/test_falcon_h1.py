"""The Falcon-H1 block (PR 34): a Mamba-2 mixer beside attention in every
layer, the fixed multipliers, recurrent state beside keys and values in one
cache tree, and what the family is refused.

The yardstick is ``benchmark/reference/falcon_h1.py``, which imports nothing
of the program and runs the PLAIN recurrence (a position a step); the program
runs the chunked form for a prefill and the one-step form through its cache.
The model is ``tiny-falcon-h1`` (2 layers, 4 mixer heads of 8, state 16, 2
groups, convolution 4, scan chunk 8, every multiplier different from 1).

State has no sequence axis: it exists at ONE length. So the cases that
matter are those where a program runs over positions that are not the row's:
left padding, right padding, a chunk's padded tail, a wave of unequal rows, a
row whose last tenant left its state behind.
"""

import dataclasses
import functools
import http.client
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import falcon_h1 as reference
from llm_consensus_tpu.engine import ContinuousBatcher, Engine, SamplingParams
from llm_consensus_tpu.engine.batcher import (
    DEAD_ROW, _compact_cache, _move_row, _shrink_rows, _splice, _splice_rows)
from llm_consensus_tpu.models import (
    forward, get_config, init_kv_cache, init_params)
from llm_consensus_tpu.ops import ssm
from llm_consensus_tpu.ops.pallas.ssm_step import ssd_step_in_place
from llm_consensus_tpu.ops.quant import STATE_KEY, kv_tree_map
from llm_consensus_tpu.pressure import PRIORITY_HIGH, PRIORITY_LOW
from llm_consensus_tpu.utils.flops import (
    decode_bytes_per_token, param_count, state_bytes_per_row)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "tiny-falcon-h1"


def config(name: str) -> dict:
    with open(os.path.join(REPO, f"benchmark/configs/{name}.json")) as f:
        return json.load(f)


def tiny_spec() -> dict:
    """The preset as the harness states a model: the rehearsal's entry (the
    preset's sizes under the PUBLISHED multipliers, which is what the
    reference's chip limits were read under) with the preset's own
    multipliers, each far from the published one, put back."""
    spec = config("tiny-falcon-h1-rehearsal")["models"][f"{NAME}-mup"]
    cfg = get_config(NAME)
    spec["more_fields"].update(
        {k: getattr(cfg, k) for k in spec["more_fields"] if "multiplier" in k})
    return spec


def rel_err(got, want) -> np.ndarray:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def tree_close(got, want, atol=2e-5):
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=atol),
        got, want)


@pytest.fixture(scope="module")
def model():
    cfg = get_config(NAME)
    return cfg, init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)


IDS = np.random.default_rng(0).integers(0, 512, 80)


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


def test_the_rehearsals_entry_is_the_preset_under_the_published_multipliers():
    from benchmark import server

    assert server.model_config(NAME, tiny_spec()) == get_config(NAME)
    cfg = get_config(NAME)
    # what the rehearsal runs: the preset under the cell's multipliers
    stated = config("tiny-falcon-h1-rehearsal")["models"][f"{NAME}-mup"]
    cell = server.model_config("m", config(
        "falcon-h1-34b-pp8-trio-bf16")["models"]["falcon-h1-34b"])
    assert server.model_config(f"{NAME}-mup", stated) == dataclasses.replace(
        cfg, name=f"{NAME}-mup", **{
            k: getattr(cell, k) for k in stated["more_fields"]
            if "multiplier" in k})
    assert cfg.has_ssm and not get_config("tiny-llama").has_ssm
    assert all(m != 1.0 for m in (
        cfg.embedding_multiplier, cfg.lm_head_multiplier,
        cfg.attention_in_multiplier, cfg.attention_out_multiplier,
        cfg.key_multiplier, cfg.ssm_in_multiplier, cfg.ssm_out_multiplier,
        *cfg.ssm_multipliers, *cfg.mlp_multipliers))


# -- the model against the reference ------------------------------------------

PRECISIONS = {"float32": (jnp.float32, 2e-5), "bfloat16": (jnp.bfloat16, 0.08)}


@pytest.mark.parametrize("precision", PRECISIONS)
def test_forward_matches_the_reference_whole_sequence(precision):
    dtype, limit = PRECISIONS[precision]
    cfg = get_config(NAME)
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=dtype)
    with jax.default_matmul_precision("highest"):
        got, _ = forward(params, cfg, jnp.asarray(IDS[None], jnp.int32))
        want = reference.forward(params, tiny_spec(), IDS)
    assert rel_err(got[0], want).max() < limit


@pytest.mark.parametrize("precision", PRECISIONS)
def test_prefill_then_decode_through_the_cache_matches_the_reference(precision):
    """53 positions through the chunked form (six whole scan chunks and five
    positions), 27 through the one-step form on the carried state."""
    dtype, limit = PRECISIONS[precision]
    cfg = get_config(NAME)
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=dtype)
    with jax.default_matmul_precision("highest"):
        got, cache = through_the_cache(cfg, params, IDS, 53, dtype)
        want = reference.forward(params, tiny_spec(), IDS)
    err = rel_err(got, want)
    assert err.max() < limit and np.isfinite(err).all()
    assert cache[STATE_KEY]["state"].dtype == jnp.float32  # whatever is served


def test_the_rehearsals_model_is_within_the_chips_limits_on_the_cpu():
    """The fourth rehearsal compares ``ok``: the preset's sizes under the
    published multipliers, served in bfloat16 on the CPU at the rehearsal's
    parity lengths, read under both limits the chip's readings set."""
    from benchmark import parity, server

    doc = config("tiny-falcon-h1-rehearsal")
    spec = doc["models"][f"{NAME}-mup"]
    engine = Engine(
        server.model_config(f"{NAME}-mup", spec), dtype=jnp.bfloat16, max_seq=512)
    got = parity.check_engine(
        engine, spec, doc["weights"], 1, parity.lengths(doc))
    assert got["ok"], got["compared"]


def test_a_bfloat16_state_is_a_lower_precision_the_comparison_sees(model):
    """What holds the state's type. On the chip a bfloat16 state hides under
    bfloat16 activations (benchmark/reference/falcon_h1.py has the readings:
    no statistic of the logits separates it); in float32 it does not: the
    same program with its carried state rounded to bfloat16 after every step
    reads an order and more worse than with the float32 state."""
    cfg, params = model
    want = np.asarray(reference.forward(params, tiny_spec(), IDS))

    def decode(round_state):
        cache = init_kv_cache(cfg, 1, 96, jnp.float32)
        rows = []
        for p in range(len(IDS)):
            step, cache = forward(
                params, cfg, jnp.asarray(IDS[None, p:p + 1], jnp.int32), cache,
                jnp.asarray(p, jnp.int32))
            if round_state:
                cache[STATE_KEY]["state"] = cache[STATE_KEY]["state"].astype(
                    jnp.bfloat16).astype(jnp.float32)
            rows.append(step[0])
        return rel_err(jnp.concatenate(rows, axis=0), want)

    with jax.default_matmul_precision("highest"):
        sound, rounded = decode(False), decode(True)
    assert sound.max() < 2e-5 and np.median(rounded) > 1e-4


# -- the chunked form against the recurrence ----------------------------------

SCAN_LENGTHS = {
    "whole-chunks": 32, "a-chunk-and-a-bit": 11, "under-a-chunk": 5,
    "one-over": 9, "many-and-a-bit": 45,
}


def scan_inputs(t, b=2, h=4, p=8, g=2, n=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        xs=jax.random.normal(ks[0], (b, t, h, p)),
        dt=jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)) - 1.0),
        a=-jnp.exp(jax.random.normal(ks[2], (h,))),
        bm=jax.random.normal(ks[3], (b, t, g, n)),
        cm=jax.random.normal(ks[4], (b, t, g, n)),
        d=jax.random.normal(ks[5], (h,)),
        state=jax.random.normal(ks[6], (b, h, p, n)),  # carried in
    )


def recurrence(xs, dt, a, bm, cm, d, state):
    """A position a step through ``ssd_step``."""
    ys = []
    for t in range(xs.shape[1]):
        y, state = ssm.ssd_step(
            xs[:, t], dt[:, t], a, bm[:, t], cm[:, t], d, state)
        ys.append(y)
    return jnp.stack(ys, axis=1), state


@pytest.mark.parametrize("case", SCAN_LENGTHS)
def test_the_chunked_form_is_the_recurrence(case):
    inputs = scan_inputs(SCAN_LENGTHS[case])
    y, state = ssm.ssd_chunked(**inputs, chunk=8)
    want_y, want_state = recurrence(**inputs)
    np.testing.assert_allclose(y, want_y, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(state, want_state, rtol=2e-4, atol=2e-4)


def test_a_position_with_dt_zero_leaves_the_state_alone():
    inputs = scan_inputs(19)
    keep = jnp.arange(19) < 13
    masked = dict(inputs, dt=jnp.where(keep[None, :, None], inputs["dt"], 0.0))
    _, state = ssm.ssd_chunked(**masked, chunk=8)
    short = {k: (v[:, :13] if k in ("xs", "dt", "bm", "cm") else v)
             for k, v in inputs.items()}
    _, want = ssm.ssd_chunked(**short, chunk=8)
    np.testing.assert_allclose(state, want, rtol=1e-5, atol=1e-5)


@functools.cache
def in_place_against_sliced(name: str, traced: bool):
    """Three decode steps of every mixer layer of the preset ``name`` over a
    pool of four rows (two live, one with a state whose position is not
    live, one that never had a tenant), the layer's index traced (a scan
    over the stack, as ``forward`` walks a hybrid's layers) or static
    (unrolled, as ``_walk_kinds`` does): the step in place in the stack, and
    slice -> ``ssd_step`` -> update. Returns ``(the stack before, (y a step
    a layer, stack) in place, the same sliced)``."""
    cfg = get_config(name)
    h, p, n, g = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    layers = cfg.layer_kinds.count("M") or cfg.n_layers
    keys = iter(jax.random.split(jax.random.PRNGKey(45), 8))
    normal = lambda *shape: jax.random.normal(next(keys), shape, jnp.float32)  # noqa: E731
    stack = normal(layers, 4, h, p, n).at[:, 3].set(0.0)
    dt = jax.nn.softplus(normal(4, h)).at[2:].set(0.0)
    step = (normal(4, h, p), dt, -jnp.exp(normal(h)), normal(4, g, n),
            normal(4, g, n), normal(h))

    def sliced(stack, layer, *step):
        state = jax.lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)
        y, state = ssm.ssd_step(*step, state)
        return y, jax.lax.dynamic_update_index_in_dim(stack, state, layer, 0)

    def three_steps(one):
        def every_layer(stack, _):
            if traced:
                return jax.lax.scan(
                    lambda st, li: one(st, li, *step)[::-1], stack,
                    jnp.arange(layers, dtype=jnp.int32))
            ys = []
            for li in range(layers):
                y, stack = one(stack, li, *step)
                ys.append(y)
            return stack, jnp.stack(ys)
        return jax.jit(lambda st: jax.lax.scan(every_layer, st, None, length=3))

    return (stack, three_steps(ssd_step_in_place)(stack),
            three_steps(sliced)(stack))


IN_PLACE_ROWS = {
    # name: (the rows, what holds of them beside equality with the sliced step)
    "live": ((0, 1), "advance"), "position-not-live": ((2,), "keep"),
    "never-a-tenant": ((3,), "stay zero"),
}


def in_place_step_is_the_sliced_step(name: str, traced: bool, case: str):
    before, (stack, ys), (want_stack, want_ys) = in_place_against_sliced(name, traced)
    rows, held = IN_PLACE_ROWS[case]
    rows = list(rows)
    stack, was = np.asarray(stack)[:, rows], np.asarray(before)[:, rows]
    np.testing.assert_array_equal(np.asarray(ys)[:, :, rows], np.asarray(want_ys)[:, :, rows])
    np.testing.assert_array_equal(stack, np.asarray(want_stack)[:, rows])
    if held == "advance":
        assert (stack != was).mean() > 0.99
    else:
        np.testing.assert_array_equal(stack, was)
        assert was.any() == (held == "keep")


@pytest.mark.parametrize("case", IN_PLACE_ROWS)
def test_the_in_place_step_is_the_sliced_step_to_the_last_bit(case):
    """T = 1: a mixer's rows advance where they lie in the cache's state
    stack (ops/pallas/ssm_step.py), by the numbers of the step it replaced.
    Here under a traced layer index; tests/test_nemotron_h.py a static one."""
    in_place_step_is_the_sliced_step(NAME, True, case)


IN_PLACE_BLOCKS = {
    # name: ((heads, head size, state size), heads a block)
    "falcon-h1-34b": ((32, 128, 256), 16), "nemotron-3-super": ((128, 64, 128), 64),
    "tiny-falcon-h1": ((4, 8, 16), 4), "no-whole-sublane-tile-divides": ((12, 8, 16), 12),
}


@pytest.mark.parametrize("case", IN_PLACE_BLOCKS)
def test_the_in_place_step_takes_two_mebibytes_of_a_rows_state_a_block(case):
    from llm_consensus_tpu.ops.pallas.ssm_step import _block_heads

    shape, heads = IN_PLACE_BLOCKS[case]
    assert _block_heads(*shape) == heads


@pytest.mark.parametrize("case", ["position-not-live", "never-a-tenant"])
def test_a_decode_step_keeps_state_and_tail_of_a_row_that_does_not_advance(case, model):
    """Through ``forward`` at T = 1 over a pool of four: the row whose
    position is not live keeps its state and its tail to the bit, the row
    that never had a tenant stays zero, and the live rows advance."""
    cfg, params = model
    tokens = jnp.asarray(IDS[:36].reshape(4, 9), jnp.int32)
    _, cache = forward(
        params, cfg, tokens[:, :8], init_kv_cache(cfg, 4, 32, jnp.float32), 0,
        row_end=jnp.asarray([8, 8, 8, 0], jnp.int32))
    _, after = forward(
        params, cfg, tokens[:, 8:], cache, jnp.asarray(8, jnp.int32),
        row_start=jnp.asarray([0, 0, DEAD_ROW, DEAD_ROW], jnp.int32))
    row = 2 if case == "position-not-live" else 3
    for leaf in ("state", "conv"):
        was, now = (np.asarray(c[STATE_KEY][leaf]) for c in (cache, after))
        np.testing.assert_array_equal(now[:, row], was[:, row])
        assert was[:, row].any() == (row == 2)
        assert (now[:, :2] != was[:, :2]).any()


CONV_SPANS = {
    # name: (T, lo, hi) of the row's real positions
    "all-real": (12, 0, 12), "left-padded": (12, 5, 12),
    "right-padded": (12, 0, 7), "both": (12, 3, 9), "two-real": (12, 4, 6),
    "none-real": (12, 12, 12), "one-position-dead": (1, 1, 1),
}


@pytest.mark.parametrize("case", CONV_SPANS)
def test_the_convolution_runs_over_real_positions_alone(case):
    t, lo, hi = CONV_SPANS[case]
    c, k = 6, 4
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    x = jax.random.normal(ks[0], (1, t, c))
    tail = jax.random.normal(ks[1], (1, k - 1, c))
    w, b = jax.random.normal(ks[2], (c, k)), jax.random.normal(ks[3], (c,))
    out, new_tail = ssm.causal_conv(
        x, tail, w, b, jnp.asarray([lo]), jnp.asarray([hi]))
    want, want_tail = ssm.causal_conv(x[:, lo:hi], tail, w, b)
    np.testing.assert_allclose(out[:, lo:hi], want, atol=1e-5)
    np.testing.assert_allclose(new_tail, want_tail, atol=1e-6)
    if t == 1:
        _, step_tail = ssm.conv_step(x, tail, w, b, jnp.asarray([lo == 0]))
        np.testing.assert_allclose(step_tail, want_tail, atol=1e-6)


# -- padding: state, tail and logits of the unpadded row ----------------------

N_REAL = 21


@pytest.fixture(scope="module")
def unpadded(model):
    cfg, params = model
    cache = init_kv_cache(cfg, 1, 64, jnp.float32)
    with jax.default_matmul_precision("highest"):
        logits, cache = forward(
            params, cfg, jnp.asarray(IDS[None, :N_REAL], jnp.int32), cache, 0)
    return logits[0], cache[STATE_KEY]


def padded_left(cfg, params):
    tokens = np.concatenate([IDS[40:51], IDS[:N_REAL]])
    cache = init_kv_cache(cfg, 1, 64, jnp.float32)
    logits, cache = forward(
        params, cfg, jnp.asarray(tokens[None], jnp.int32), cache, 0,
        row_start=jnp.asarray([11]))
    return logits[0, 11:], cache


def padded_right(cfg, params):
    tokens = np.concatenate([IDS[:N_REAL], IDS[40:51]])
    cache = init_kv_cache(cfg, 1, 64, jnp.float32)
    logits, cache = forward(
        params, cfg, jnp.asarray(tokens[None], jnp.int32), cache, 0,
        row_end=jnp.asarray([N_REAL]))
    return logits[0, :N_REAL], cache


def chunks_with_a_padded_tail(cfg, params):
    """Three chunks of 8 at a traced start, the last with three pads."""
    tokens = np.concatenate([IDS[:N_REAL], IDS[40:43]])
    cache = init_kv_cache(cfg, 1, 64, jnp.float32)
    step = jax.jit(lambda tok, c, at: forward(
        params, cfg, tok, c, at, kv_width=32, row_end=jnp.asarray([N_REAL])))
    rows = []
    for i in range(3):
        logits, cache = step(
            jnp.asarray(tokens[None, i * 8:(i + 1) * 8], jnp.int32), cache,
            jnp.asarray(i * 8, jnp.int32))
        rows.append(logits[0])
    return jnp.concatenate(rows, axis=0)[:N_REAL], cache


PADDINGS = {
    "left-padded": padded_left, "right-padded": padded_right,
    "a-last-chunk-with-a-padded-tail": chunks_with_a_padded_tail,
}


@pytest.mark.parametrize("case", PADDINGS)
def test_padding_does_not_advance_the_state(case, model, unpadded):
    cfg, params = model
    want_logits, want_state = unpadded
    with jax.default_matmul_precision("highest"):
        logits, cache = PADDINGS[case](cfg, params)
        np.testing.assert_allclose(logits, want_logits, atol=2e-5)
        tree_close(cache[STATE_KEY], want_state)
    assert float(jnp.abs(cache[STATE_KEY]["state"]).max()) > 0


def test_without_row_end_a_padded_tail_does_corrupt_the_state(model, unpadded):
    """What ``row_end`` is for: the same padded call without it ends in
    another state."""
    cfg, params = model
    tokens = np.concatenate([IDS[:N_REAL], IDS[40:51]])
    cache = init_kv_cache(cfg, 1, 64, jnp.float32)
    _, cache = forward(
        params, cfg, jnp.asarray(tokens[None], jnp.int32), cache, 0)
    off = jnp.abs(cache[STATE_KEY]["state"] - unpadded[1]["state"]).max()
    assert float(off) > 1e-3


# -- the cache tree's helpers: state is per row, told apart by key ------------


def marked_cache(cfg, rows, slots, base):
    """A cache whose every leaf holds ``base + row`` in row ``row``."""
    cache = init_kv_cache(cfg, rows, slots, jnp.float32)
    return jax.tree.map(
        lambda a: a + (base + jnp.arange(rows, dtype=a.dtype)).reshape(
            1, rows, *(1,) * (a.ndim - 2)), cache)


def test_the_tree_helpers_treat_state_per_row():
    cfg = get_config(NAME)
    pool = marked_cache(cfg, 4, 32, 10.0)            # rows read 10..13
    one = marked_cache(cfg, 1, 16, 50.0)
    # a one-row splice replaces the pool row's state and tail whole
    out = _splice(jax.tree.map(jnp.copy, pool), one, 2, 5, 16)
    for leaf in jax.tree.leaves(out[STATE_KEY]):
        assert np.asarray(leaf)[:, 2].min() == 50.0 == np.asarray(leaf)[:, 2].max()
        assert np.asarray(leaf)[:, 1].max() == 11.0
    assert np.asarray(out["k"])[0, 2, 5:21].min() == 50.0
    assert np.asarray(out["k"])[0, 2, :5].max() == 12.0
    # a wave splice copies row for row
    wave = marked_cache(cfg, 2, 16, 70.0)
    out = _splice_rows(
        jax.tree.map(jnp.copy, pool), wave, jnp.asarray([1, 0]),
        jnp.asarray([0, 3]), jnp.asarray([4, 8]), 2, 16)
    state = np.asarray(out[STATE_KEY]["state"])
    assert state[0, 0].max() == 71.0 and state[0, 3].max() == 70.0
    assert state[0, 1].max() == 11.0
    # a compaction slides the slots and leaves the state alone
    ramp = jax.tree.map(jnp.copy, pool)
    ramp["k"] = ramp["k"] + jnp.arange(32.0).reshape(1, 1, 32, 1, 1)
    out = _compact_cache(ramp, jnp.asarray(6))
    tree_close(out[STATE_KEY], pool[STATE_KEY], atol=0)
    assert float(out["k"][0, 1, 0, 0, 0]) == 11.0 + 6.0
    # rows move, shrink and grow on axis 1, state leaves too
    out = _move_row(jax.tree.map(jnp.copy, pool), 3, 0)
    assert float(out[STATE_KEY]["conv"][0, 0].max()) == 13.0
    out = _shrink_rows(jax.tree.map(jnp.copy, pool), 2)
    assert out[STATE_KEY]["state"].shape[1] == 2 == out["v"].shape[1]
    # and the rule's owner keeps the state leaves away from the slot rule
    seen = []
    kv_tree_map(lambda leaf: seen.append(leaf.shape) or leaf, pool)
    assert len(seen) == 2 and all(len(s) == 5 and s[2] == 32 for s in seen)


# -- through the engine and the pool, token for token --------------------------

GREEDY = dict(temperature=0.0, ignore_eos=True)


@pytest.fixture(scope="module")
def engine():
    cfg = get_config(NAME)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return Engine(cfg, params=params, dtype=jnp.float32, max_seq=256,
                  stream_interval=8, prefill_chunk=16)


def test_chunked_prefill_across_seams_equals_one_shot(engine):
    """A 75-token prompt through the loop (five chunks of 16, state carried
    across four seams, five pads at the end), through the per-chunk program,
    and in one shot: the same first token and the same state."""
    from llm_consensus_tpu.engine.engine import (
        _prefill_chunk, scan_positions_swept)

    ids = [int(t) for t in IDS[:75]]
    logits, cache = engine._prefill_ids(ids)
    assert engine.last_prefill.chunks == 5 and engine.last_prefill.reused == 0
    assert scan_positions_swept(engine.cfg, engine.last_prefill, 1) == 80
    one = init_kv_cache(engine.cfg, 1, 256, jnp.float32)
    want_logits, one = forward(
        engine.params, engine.cfg, jnp.asarray([ids], jnp.int32), one, 0)
    np.testing.assert_allclose(logits[0], want_logits[0, -1], atol=3e-5)
    tree_close(cache[STATE_KEY], one[STATE_KEY], atol=3e-5)
    per = init_kv_cache(engine.cfg, 1, 256, jnp.float32)
    padded = ids + [0] * 5
    for i in range(5):
        got, per = _prefill_chunk(
            engine.params, engine.cfg,
            jnp.asarray([padded[i * 16:(i + 1) * 16]], jnp.int32),
            jnp.asarray(i * 16, jnp.int32), jnp.asarray([10]), per,
            kv_width=128, row_end=jnp.asarray([75], jnp.int32))
    np.testing.assert_allclose(got[0], want_logits[0, -1], atol=3e-5)
    tree_close(per[STATE_KEY], one[STATE_KEY], atol=3e-5)


def test_two_prompts_with_a_common_prefix_back_to_back(engine):
    """Judge prompts share their template: the second prompt must not start
    from the first's state (the prefix snapshot is off for the family)."""
    s = SamplingParams(max_new_tokens=12, **GREEDY)
    stem = "the template every judge prompt opens with, word for word; "
    first, second = stem + "then answer A", stem + "then another answer, B"
    alone = Engine(engine.cfg, params=engine.params, dtype=jnp.float32,
                   max_seq=256, stream_interval=8, prefill_chunk=16)
    want = alone.generate(second, s).token_ids
    assert not engine.prefix_cache_enabled
    engine.generate(first, s)
    assert engine.generate(second, s).token_ids == want
    assert engine.last_prefill.reused == 0 and engine._prefix_cache is None


def pool_case_wave(engine):
    """A wave of unequal rows, then a late admission into the decoding
    pool, then a row reused after its tenant retired."""
    s = SamplingParams(max_new_tokens=24, **GREEDY)
    prompts = ["short", "a prompt of middling length for the wave",
               "the longest of the three rows of this wave by some margin, "
               "long enough to take more than one prefill chunk"]
    b = ContinuousBatcher(engine, max_batch=4)
    try:
        futs = [b.submit(p, s) for p in prompts]
        while not any(st is not None for st in b._slots):
            time.sleep(0.005)
        late = b.submit("a latecomer joins the decoding pool", s)
        for p, f in zip(prompts, futs):
            assert f.result(timeout=300).token_ids == engine.generate(
                p, s).token_ids, p
        assert late.result(timeout=300).token_ids == engine.generate(
            "a latecomer joins the decoding pool", s).token_ids
        # every slot has had a tenant: the next rows start from their own
        # prefill's state, not from what was left
        again = [b.submit(p + " again", s) for p in prompts]
        for p, f in zip(prompts, again):
            assert f.result(timeout=300).token_ids == engine.generate(
                p + " again", s).token_ids, p
        st = b.snapshot()
        assert st["ssm_positions_live"] == st["admit_tokens"]
        assert st["ssm_positions_swept"] >= st["prefill_slot_tokens"]
        assert st["ssm_state_row_steps"] == st["decode_steps"] * 4
    finally:
        b.close()


def pool_case_compaction(engine):
    """Staggered streams push the shared frontier past capacity: the slide
    moves every row's slots and must leave its state where it is.

    All the streams are queued at once: three short ones free their rows at
    three frontiers, and from then on the pool itself admits the next stream
    as a row ends, where the frontier then stands, so the pool is never idle
    before the queue is (an idle pool resets its frontier). A prompt of 32
    tokens is admitted up to frontier 224 (``fits``) and a stream of 96
    tokens admitted past 160 is still live at 256: a row frees every 24
    steps or so, so some stream is, whenever the host's threads run. (This
    thread used to submit the next stream of 60 tokens when it saw one end:
    on a loaded machine it came late and the pool went idle, or no stream
    was admitted between 196 and 224, and the frontier never reached
    capacity: no slide, nothing tested.)"""
    lengths = [24, 48, 72] + [96] * 10
    prompts = [f"staggered stream {i} of the slide" for i in range(len(lengths))]
    params = [SamplingParams(max_new_tokens=n, **GREEDY) for n in lengths]
    wants = [engine.generate(p, s).token_ids for p, s in zip(prompts, params)]
    b = ContinuousBatcher(engine, max_batch=4)
    slides = []
    compact = b._compact
    b._compact = lambda: slides.append(b._pos) or compact()
    try:
        futs = [b.submit(p, s) for p, s in zip(prompts, params)]
        for i, f in enumerate(futs):
            assert f.result(timeout=600).token_ids == wants[i], i
        assert slides, "the frontier never reached capacity"
    finally:
        b.close()


def pool_case_shrink_and_regrow(engine):
    b = ContinuousBatcher(engine, max_batch=16)
    try:
        assert b._rows_bucket_enabled
        s_short = SamplingParams(max_new_tokens=6, **GREEDY)
        s_long = SamplingParams(max_new_tokens=48, **GREEDY)
        shorts = [f"short stream number {i}" for i in range(12)]
        longs = [f"long running stream {i}" for i in range(4)]
        futs_s = [b.submit(p, s_short) for p in shorts]
        futs_l = [b.submit(p, s_long) for p in longs]
        for p, f in zip(shorts + longs, futs_s + futs_l):
            want = engine.generate(p, s_short if p in shorts else s_long)
            assert f.result(timeout=600).token_ids == want.token_ids, p
        assert b._rows_cap == 8
        assert b._cache[STATE_KEY]["state"].shape[1] == 8
        burst = [f"second burst stream {i}" for i in range(12)]
        futs = [b.submit(p, s_short) for p in burst]
        for p, f in zip(burst, futs):
            assert f.result(timeout=600).token_ids == engine.generate(
                p, s_short).token_ids, p
        assert b._rows_cap == 16
    finally:
        b.close()


def pool_case_preempt_and_resume(engine):
    s_low = SamplingParams(max_new_tokens=48, **GREEDY)
    s_hi = SamplingParams(max_new_tokens=10, **GREEDY)
    lows = [f"low class resident {i} body" for i in range(2)]
    b = ContinuousBatcher(engine, max_batch=2)
    try:
        for _attempt in range(4):
            before = b.snapshot()["preemptions"]
            futs = [b.submit(p, s_low, priority=PRIORITY_LOW) for p in lows]
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline and sum(
                    1 for st in b._slots if st is not None) < 2:
                time.sleep(0.005)
            r_hi = b.submit("high class latecomer", s_hi,
                            priority=PRIORITY_HIGH).result(timeout=300)
            assert r_hi.token_ids == engine.generate(
                "high class latecomer", s_hi).token_ids
            for p, f in zip(lows, futs):
                assert f.result(timeout=300).token_ids == engine.generate(
                    p, s_low).token_ids, p
            if b.snapshot()["preemptions"] > before:
                break
        assert b.snapshot()["preemptions"] >= 1
    finally:
        b.close()


POOL_CASES = {
    "wave-late-admission-and-reuse": pool_case_wave,
    "compaction-shift": pool_case_compaction,
    "shrink-and-regrow": pool_case_shrink_and_regrow,
    "preempt-and-resume": pool_case_preempt_and_resume,
}


@pytest.mark.parametrize("case", POOL_CASES)
def test_a_pool_row_carries_its_own_state(case, engine, monkeypatch):
    monkeypatch.setenv("LLMC_KV_POOL", "0")
    POOL_CASES[case](engine)


def test_dead_rows_and_ended_rows_keep_finite_state(engine):
    """One short stream beside a long one in a pool of four with the
    finite-logit sentinel on: the short row runs on inside its last chunk and
    is then dead, two rows never had a tenant; every state stays finite."""
    from llm_consensus_tpu import integrity

    os.environ["LLMC_INTEGRITY"] = "1"
    integrity.reset()
    try:
        b = ContinuousBatcher(engine, max_batch=4)
        try:
            long_ = b.submit("the long neighbour", SamplingParams(
                max_new_tokens=40, **GREEDY))
            short = b.submit("short", SamplingParams(max_new_tokens=3, **GREEDY))
            assert len(short.result(timeout=300).token_ids) == 3
            assert len(long_.result(timeout=300).token_ids) == 40
            for leaf in jax.tree.leaves(b._cache[STATE_KEY]):
                assert bool(jnp.isfinite(leaf).all())
            assert not np.asarray(b._cache[STATE_KEY]["state"])[:, 2:].any()
        finally:
            b.close()
    finally:
        os.environ.pop("LLMC_INTEGRITY", None)
        integrity.reset()


# -- what the family is refused, by its message -------------------------------


def _engine(**how):
    return Engine(get_config(NAME), max_seq=128, **how)


def _refuse_radix_arena(monkeypatch):
    monkeypatch.setenv("LLMC_KV_POOL", "1")
    _engine()


def _refuse_mesh():
    from llm_consensus_tpu.parallel import make_mesh

    _engine(mesh=make_mesh({"dp": 1, "tp": 2}, jax.devices()[:2]))


def _refuse_pool_speculation():
    from llm_consensus_tpu.engine.speculative import SpecConfig

    ContinuousBatcher(_engine(), max_batch=2, spec=SpecConfig(kind="lookup"))


def _refuse_engine_speculation():
    from llm_consensus_tpu.engine.speculative import (
        PromptLookupDrafter, SpeculativeEngine)

    SpeculativeEngine(_engine(), PromptLookupDrafter())


def _refuse_shared_prefix_admission():
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
}


@pytest.mark.parametrize("case", REFUSALS)
def test_what_the_family_does_not_get_is_refused_by_name(case, monkeypatch):
    attempt, words = REFUSALS[case]
    with pytest.raises(ValueError) as stop:
        attempt(monkeypatch) if attempt is _refuse_radix_arena else attempt()
    assert words in str(stop.value) and NAME in str(stop.value)


# -- counts -------------------------------------------------------------------


def test_param_count_is_the_init_params_tree():
    cfg = get_config(NAME)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert shapes["layers"]["ssm_in"].shape == (2, 96, 32 + 32 + 64 + 4)
    assert shapes["layers"]["ssm_conv"].shape == (2, 96, 4)
    assert param_count(cfg) == sum(x.size for x in jax.tree.leaves(shapes))
    assert cfg.n_params() == param_count(cfg)


def test_param_count_at_the_cells_widths():
    from benchmark import server

    spec = config("falcon-h1-34b-pp8-trio-bf16")["models"]["falcon-h1-34b"]
    cfg = server.model_config("m", spec)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert param_count(cfg) == n
    per_layer = (n - 2 * 32640 * 5120 - 5120) // cfg.n_layers
    assert per_layer == 430_120_032  # 0.860 GB in bf16: the published widths
    assert shapes["layers"]["ssm_in"].shape[1:] == (5120, 9248)


STATE_CASES = {
    # name: (model, bytes a row with a bf16 tail)
    "tiny-falcon-h1": (NAME, 2 * (4 * 8 * 16 * 4 + 3 * 96 * 2)),
    "tiny-llama": ("tiny-llama", 0),
    "the-cells": (None, None),
}


@pytest.mark.parametrize("case", STATE_CASES)
def test_state_bytes_a_row(case):
    from benchmark import server

    model, want = STATE_CASES[case]
    if model is None:
        cfg = server.model_config("m", config(
            "falcon-h1-34b-pp8-trio-bf16")["models"]["falcon-h1-34b"])
        want = cfg.n_layers * (32 * 128 * 256 * 4 + 3 * 5120 * 2)
    else:
        cfg = get_config(model)
    assert state_bytes_per_row(cfg, 2) == want == cfg.state_bytes_per_row
    leaves = jax.tree.leaves(jax.eval_shape(
        lambda: init_kv_cache(cfg, 3, 64, jnp.bfloat16)).get(STATE_KEY, {}))
    assert sum(x.size * x.dtype.itemsize for x in leaves) == want * 3
    plain = decode_bytes_per_token(cfg, 100) - 2 * want
    assert plain == param_count(cfg) * 2 + 100 * 2 * cfg.n_layers * cfg.cache_width


# -- served --------------------------------------------------------------------


def _post(port: int, body: dict):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        conn.request("POST", "/v1/consensus", json.dumps(body),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        data = r.read()
    finally:
        conn.close()
    return r.status, json.loads(data)


def test_a_consensus_run_with_it_as_panelist_and_judge_through_serve(tmp_path):
    from llm_consensus_tpu import serve
    from llm_consensus_tpu.providers import Registry
    from llm_consensus_tpu.providers.tpu import TPUProvider

    prov = TPUProvider(ignore_eos=True, stream_interval=4, batch_streams=4)
    panel, judge = [f"tpu:{NAME}", "tpu:tiny-qwen2"], f"tpu:{NAME}"
    registry = Registry()
    for m in panel:
        registry.register(m, prov)
    gw = serve.build_gateway(
        registry, panel, judge, timeout=300.0, max_concurrency=2,
        max_tokens=8, data_dir=os.path.join(str(tmp_path), "data"),
    )
    gw.start()
    try:
        _, port = gw.address
        status, doc = _post(port, {"prompt": "what does a state remember?"})
        stats = prov.batcher_stats()[NAME]
        built = prov._engine_for(f"tpu:{NAME}").build_stats
    finally:
        gw.close(drain=False, timeout=10.0)
        prov.release()
    assert status == 200, doc
    assert {r["model"] for r in doc["responses"]} == set(panel)
    assert doc["judge"] == judge and isinstance(doc["consensus"], str)
    assert not doc.get("failed_models")
    assert doc["timings"]["judge_tokens"] == 8
    # a panel prompt and a judge prompt went through the pool's scans
    assert stats["ssm_positions_live"] == stats["admit_tokens"] > 0
    assert stats["ssm_state_row_steps"] >= stats["decode_steps"] > 0
    assert built["ssm_layers"] == 2 and built["state_bytes_per_row"] > 0
