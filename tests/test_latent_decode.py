"""The latent (MLA) decode step (PR 35): the absorbed form writes each row's
latent where it lies and sweeps the pool from where it is.

A pool of four rows at ``tiny-dsv2-rehearsal``'s widths (both stacks: one
dense layer, two expert layers, a strict share of the experts held):
left-padded rows with a ``row_start`` each, one of them dead. After a
two-chunk prefill at a traced start, a 16-step decode chunk gives

  * the tokens and logits of the NON-absorbed form over the same cache
    (float32 under ``highest`` to 1e-4; bf16 and int8 weight leaves fed the
    same tokens, inside every limit ``benchmark/reference/deepseek_v2.py``
    holds the chip's runs to);
  * the tokens and logits the parent commit's program gave for this seed
    (``tests/data/latent_decode_pins.npz``, written by this file run as a
    script from a checkout of that commit): to the token with the experts'
    products as the pins had them (``ragged_dot``), and within a rounding
    as the program ships (the kernel over the sorted pairs, PR 46);
  * through the engine's own ``_decode_chunk`` the same tokens, a finite
    verdict for every row, and a pool that differs from the pool before it
    in the 16 written slots a row a layer and nowhere else.
"""

import contextlib
import functools
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

from benchmark import server  # noqa: E402
from benchmark.reference import deepseek_v2 as reference  # noqa: E402
from llm_consensus_tpu.engine.batcher import DEAD_ROW  # noqa: E402
from llm_consensus_tpu.engine.engine import _decode_chunk  # noqa: E402
from llm_consensus_tpu.models import forward, init_kv_cache, init_params  # noqa: E402
from llm_consensus_tpu.models import transformer  # noqa: E402
from llm_consensus_tpu.ops import moe  # noqa: E402
from llm_consensus_tpu.ops.quant import quantize_params  # noqa: E402

PINS = os.path.join(REPO, "tests", "data", "latent_decode_pins.npz")
ROW_START = (0, 5, 11, DEAD_ROW)   # left-padded rows; the last has no stream
LIVE = slice(0, 3)
CHUNK, PROMPT, STEPS, WIDTH, SLOTS = 16, 32, 16, 64, 96
WEIGHTS = {
    "float32": (jnp.float32, False),
    "bf16": (jnp.bfloat16, False),
    "int8-leaves": (jnp.bfloat16, True),
}


@contextlib.contextmanager
def the_grouped_product():
    """The experts' products as ``ragged_dot``, which the pins were made
    with, in place of the kernel over the sorted pairs that a buffer this
    small takes (ops/moe.py). The switch is read while a program is traced:
    nothing traced under the other value is kept, either way."""
    patch = pytest.MonkeyPatch()
    patch.setattr(moe, "PAIRS_KERNEL_MAX", 0)
    jax.clear_caches()
    try:
        yield
    finally:
        patch.undo()
        jax.clear_caches()


def rel_err(got, want) -> np.ndarray:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def model(weights: str):
    with open(os.path.join(REPO, "benchmark/configs/tiny-dsv2-rehearsal.json")) as f:
        spec = json.load(f)["models"]["tiny-deepseek-v2-share"]
    cfg = server.model_config(f"latent-decode-{weights}", spec)
    dtype, int8 = WEIGHTS[weights]
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=dtype)
    return cfg, quantize_params(params) if int8 else params, dtype


def prefilled(cfg, params, dtype):
    """The pool after a two-chunk prefill at a traced start, and the rows'
    first decode tokens."""
    ids = jnp.asarray(np.random.default_rng(35).integers(
        0, cfg.vocab_size, (len(ROW_START), PROMPT)), jnp.int32)
    rs = jnp.asarray(ROW_START, jnp.int32)
    cache = init_kv_cache(cfg, len(ROW_START), SLOTS, dtype=dtype)
    chunk = jax.jit(lambda toks, cache, start: forward(
        params, cfg, toks, cache, start, kv_width=WIDTH, row_start=rs))
    for start in range(0, PROMPT, CHUNK):
        logits, cache = chunk(
            ids[:, start:start + CHUNK], cache, jnp.asarray(start, jnp.int32))
    return cache, jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), rs


@functools.partial(jax.jit, static_argnames=("cfg",))
def steps(params, cfg, cache, token, rs, forced=None):
    """``STEPS`` greedy decode steps as one scan, as ``_decode_chunk`` runs
    them, with every step's logits kept; ``forced`` [STEPS, B] feeds another
    run's tokens instead of its own."""
    def body(carry, fed):
        token, pos, cache = carry
        logits, cache = forward(
            params, cfg, token[:, None], cache, start_pos=pos, row_start=rs,
            kv_width=WIDTH)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return (nxt if fed is None else fed, pos + 1, cache), (nxt, logits[:, -1])

    (_, _, cache), (toks, logits) = jax.lax.scan(
        body, (token, jnp.asarray(PROMPT, jnp.int32), cache), forced,
        length=STEPS)
    return toks, logits, cache


@functools.lru_cache(maxsize=None)
def decoded(weights: str, grouped: bool = False):
    """The pool, the first tokens and sixteen steps: of the program as it
    ships, or (``grouped``) with the experts' products as the pins' were."""
    with the_grouped_product() if grouped else contextlib.nullcontext():
        cfg, params, dtype = model(weights)
        cache, first, rs = prefilled(cfg, params, dtype)
        return cfg, params, cache, first, rs, jax.block_until_ready(
            steps(params, cfg, cache, first, rs))


@pytest.mark.parametrize("weights", WEIGHTS)
def test_the_absorbed_form_is_the_prefill_form_over_the_same_cache(
        weights, monkeypatch):
    cfg, params, cache, first, rs, (toks, logits, _) = decoded(weights)
    absorbed = transformer.latent_attention
    monkeypatch.setattr(
        transformer, "latent_attention",
        lambda *a, **kw: absorbed(*a, **{**kw, "absorbed": False}))
    exact = weights == "float32"
    with jax.default_matmul_precision("highest" if exact else "default"):
        steps.clear_cache()
        want_toks, want, _ = steps(
            params, cfg, cache, first, rs, forced=None if exact else toks)
        steps.clear_cache()
    assert np.isfinite(np.asarray(want)).all()
    err = rel_err(logits[:, LIVE], want[:, LIVE])
    if exact:
        assert np.array_equal(toks[:, LIVE], want_toks[:, LIVE])
        assert err.max() < 1e-4
    else:
        # Fed the same tokens, two bf16 forms differ by rounding (1-2% a
        # position) and, where a token's routing flips, by an expert: the
        # reference's limits say how far either may go.
        for name, (value, limit) in reference.compared(err.ravel(), 0).items():
            assert value < limit, (name, value, limit)
        # A token may differ only where the best two logits lie closer
        # than the two forms' logits do.
        got, want = np.asarray(logits, np.float64), np.asarray(want, np.float64)
        for step, row in zip(*np.nonzero(np.asarray(toks != want_toks)[:, LIVE])):
            gap = want[step, row, want_toks[step, row]] - want[step, row, toks[step, row]]
            assert gap <= 2 * np.abs(got[step, row] - want[step, row]).max()


@pytest.mark.parametrize("weights", ["bf16", "int8-leaves"])
def test_the_decode_chunk_gives_what_the_parent_gave(weights):
    """Tokens and logits pinned from the parent commit's program (one
    fused write a layer, no barrier): the same mathematics in the same
    types, so on one CPU the same numbers up to a fusion's rounding."""
    *_, (toks, logits, _) = decoded(weights, grouped=True)
    pins = np.load(PINS)
    assert np.array_equal(toks[:, LIVE], pins[f"{weights}.tokens"][:, LIVE])
    assert rel_err(
        logits[:, LIVE], pins[f"{weights}.logits"][:, LIVE]).max() < 2e-3


# Two bfloat16 forms of one step differ by 1-2% a position (the absorbed form
# against the prefill form, above); the kernel against the pins reads 1.2%.
KERNEL_BOUND = 0.03


@pytest.mark.parametrize("weights", ["bf16", "int8-leaves"])
def test_the_chunk_that_ships_is_within_a_rounding_of_the_pins(weights):
    """The same sixteen steps as the program ships them: the experts of a
    buffer this small run in the kernel over the sorted pairs (PR 46; int8
    leaves keep the grouped product, and give the pins' numbers). Fed its
    own tokens, a bfloat16 step is the pins' up to the roundings the two
    forms place differently (the kernel rounds a gated expert's activation
    once, the CPU's grouped path an operation): logits inside
    ``KERNEL_BOUND`` of the pins' while the tokens are, and a token differs
    only where the pins' best two logits lie closer than the two runs'."""
    *_, (toks, logits, _) = decoded(weights)
    pins = np.load(PINS)
    want_toks = pins[f"{weights}.tokens"]
    got, want = np.asarray(logits, np.float64), pins[f"{weights}.logits"].astype(np.float64)
    toks = np.asarray(toks)
    for row in range(LIVE.stop):
        differs = np.nonzero(toks[:, row] != want_toks[:, row])[0]
        same = len(toks) if not len(differs) else differs[0] + 1  # same input
        assert rel_err(got[:same, row], want[:same, row]).max() < KERNEL_BOUND
        if len(differs):
            step = differs[0]
            gap = (want[step, row, want_toks[step, row]]
                   - want[step, row, toks[step, row]])
            assert gap <= 2 * np.abs(got[step, row] - want[step, row]).max()


@pytest.mark.parametrize("weights", ["bf16", "int8-leaves"])
def test_the_engines_chunk_writes_sixteen_slots_a_row_and_nothing_else(weights):
    cfg, params, cache, first, rs, (toks, _, scanned) = decoded(weights)
    before = np.asarray(cache["kv"], np.float32)
    last, chunk_toks, after, ok, moe = _decode_chunk(
        params, cfg, first, jnp.asarray(PROMPT, jnp.int32),
        jax.tree.map(jnp.copy, cache), jax.random.PRNGKey(0), n_steps=STEPS,
        temperature=0.0, top_k=None, top_p=None, row_start=rs, kv_width=WIDTH,
        sentinel=True, moe_stats=True)
    assert np.array_equal(chunk_toks, toks) and np.array_equal(last, toks[-1])
    assert bool(np.asarray(ok).all())  # a dead row's logits are finite too
    assert int(moe[0]) == STEPS * len(ROW_START) * cfg.experts_per_token * (
        cfg.n_layers - cfg.n_dense_layers)
    after = np.asarray(after["kv"], np.float32)
    assert after.shape == before.shape == (
        cfg.n_layers, len(ROW_START), SLOTS, 1, cfg.cache_width)
    assert np.array_equal(after, np.asarray(scanned["kv"], np.float32))
    written = slice(PROMPT, PROMPT + STEPS)
    assert np.array_equal(after[:, :, :PROMPT], before[:, :, :PROMPT])
    assert np.array_equal(after[:, :, written.stop:], before[:, :, written.stop:])
    # Every layer of both stacks wrote every row's latent at every step.
    assert (np.abs(after[:, :, written]).max(axis=(-1, -2)) > 0).all()
    assert not before[:, :, written].any()


if __name__ == "__main__":
    # python tests/test_latent_decode.py <out.npz>, from a checkout's root:
    # that checkout's tokens and logits, the pins of the test above.
    out = {}
    for name in ("bf16", "int8-leaves"):
        *_, (toks, logits, _) = decoded(name)
        out[f"{name}.tokens"] = np.asarray(toks)
        out[f"{name}.logits"] = np.asarray(logits, np.float32)
    np.savez_compressed(sys.argv[1], **out)
