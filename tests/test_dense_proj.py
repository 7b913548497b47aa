"""The dense attention block's input projections (PR 38): ``wq`` / ``wk`` /
``wv`` are read where they lie, behind an ``optimization_barrier`` that
stands between the three products and their split into heads. The barrier
is for the chip's layout assignment (tests/test_tpu_compile.py holds what
the chip's compiler then materialises) and is the identity, so on one CPU

  * a pool of four left-padded rows (a ``row_start`` each, one of them
    dead) gives, after a two-chunk prefill at a traced start and 16 decode
    steps, the tokens and logits of the parent commit's program TO THE LAST
    BIT, for a model with biases (``tiny-qwen2``), with a sliding window the
    decode steps cross (``tiny-mistral``) and with a key multiplier and a
    mixer beside attention (``tiny-falcon-h1``), bf16 and int8 weight
    leaves alike (``tests/data/dense_proj_pins.npz``, written by this file
    run as a script from a checkout of that commit);
  * the engine's own ``_decode_chunk`` samples the same tokens;
  * the lowered text of ``_decode_chunk`` is the parent's once the barrier
    is taken for the identity it is, and with it differs by the barrier's
    own lines and nothing else (``tiny-falcon-h1``'s two digests were made
    anew in PR 45, whose mixer step advances the state stack in place: the
    text without the barrier is that PR's; tokens and logits are still
    PR 38's parent's to the last bit).
"""

import functools
import hashlib
import os
import re
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":  # the checkout in the working directory, not this file's
    sys.path.insert(0, os.getcwd())

from llm_consensus_tpu.engine import engine as E  # noqa: E402
from llm_consensus_tpu.engine.batcher import DEAD_ROW  # noqa: E402
from llm_consensus_tpu.models import forward, init_kv_cache, init_params  # noqa: E402
from llm_consensus_tpu.models.config import MODEL_PRESETS  # noqa: E402
from llm_consensus_tpu.ops.quant import quantize_params  # noqa: E402

PINS = os.path.join(REPO, "tests", "data", "dense_proj_pins.npz")
ROW_START = (0, 5, 11, DEAD_ROW)   # left-padded rows; the last has no stream
CHUNK, PROMPT, STEPS, WIDTH, SLOTS = 16, 32, 16, 64, 96
PRESETS = ("tiny-qwen2", "tiny-mistral", "tiny-falcon-h1")
WEIGHTS = ("bf16", "int8-leaves")
CASES = [(p, w) for p in PRESETS for w in WEIGHTS]
BARRIER = "optimization_barrier"


def model(preset: str, weights: str):
    cfg = MODEL_PRESETS[preset]
    params = init_params(cfg, jax.random.PRNGKey(38), dtype=jnp.bfloat16)
    return cfg, quantize_params(params) if weights == "int8-leaves" else params


def prefilled(cfg, params):
    """The pool after a two-chunk prefill at a traced start, the last
    chunk's logits, and the rows' first decode tokens."""
    ids = jnp.asarray(np.random.default_rng(38).integers(
        0, cfg.vocab_size, (len(ROW_START), PROMPT)), jnp.int32)
    rs = jnp.asarray(ROW_START, jnp.int32)
    cache = init_kv_cache(cfg, len(ROW_START), SLOTS, dtype=jnp.bfloat16)
    chunk = jax.jit(lambda toks, cache, start: forward(
        params, cfg, toks, cache, start, kv_width=WIDTH, row_start=rs))
    for start in range(0, PROMPT, CHUNK):
        logits, cache = chunk(
            ids[:, start:start + CHUNK], cache, jnp.asarray(start, jnp.int32))
    first = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    return cache, logits[:, -1], first, rs


@functools.partial(jax.jit, static_argnames=("cfg",))
def steps(params, cfg, cache, token, rs):
    """``STEPS`` greedy decode steps as one scan, as ``_decode_chunk`` runs
    them, with every step's logits kept."""
    def body(carry, _):
        token, pos, cache = carry
        logits, cache = forward(
            params, cfg, token[:, None], cache, start_pos=pos, row_start=rs,
            kv_width=WIDTH)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return (nxt, pos + 1, cache), (nxt, logits[:, -1])

    _, (toks, logits) = jax.lax.scan(
        body, (token, jnp.asarray(PROMPT, jnp.int32), cache), None,
        length=STEPS)
    return toks, logits


@functools.lru_cache(maxsize=None)
def decoded(preset: str, weights: str):
    cfg, params = model(preset, weights)
    cache, prefill_logits, first, rs = prefilled(cfg, params)
    toks, logits = steps(params, cfg, cache, first, rs)
    return cfg, params, cache, first, rs, {
        "prefill_logits": np.asarray(prefill_logits, np.float32),
        "tokens": np.asarray(toks),
        "logits": np.asarray(logits, np.float32),
    }


def digest(array) -> str:
    return hashlib.sha256(np.ascontiguousarray(array).tobytes()).hexdigest()


def lowered(preset: str, weights: str) -> str:
    """The text of ``_decode_chunk`` lowered for abstract operands of the
    pool above, under a jit of its own (so that two traces share no cache)."""
    cfg = MODEL_PRESETS[preset]
    params = jax.eval_shape(lambda: model(preset, weights)[1])
    cache = jax.eval_shape(
        lambda: init_kv_cache(cfg, len(ROW_START), SLOTS, dtype=jnp.bfloat16))
    fn = E._decode_chunk._fn
    copy = types.FunctionType(
        fn.__code__, fn.__globals__, fn.__name__, fn.__defaults__, fn.__closure__)
    copy.__kwdefaults__ = fn.__kwdefaults__
    rows = jax.ShapeDtypeStruct((len(ROW_START),), jnp.int32)
    return jax.jit(copy, static_argnames=E._decode_chunk._static).lower(
        params, cfg, rows, jax.ShapeDtypeStruct((), jnp.int32), cache,
        jax.ShapeDtypeStruct((2,), jnp.uint32), STEPS, 0.0, None, None,
        row_start=rows, kv_width=WIDTH, attn_impl="flash", sentinel=True,
    ).as_text()


def pinned(preset: str, weights: str) -> dict:
    """What a checkout gives for one case: the pins' entries."""
    *_, got = decoded(preset, weights)
    return {
        "tokens": got["tokens"],
        "prefill_logits": got["prefill_logits"],
        "last_logits": got["logits"][-1],
        "logits_sha256": np.asarray(digest(got["logits"])),
        "lowered_sha256": np.asarray(
            hashlib.sha256(lowered(preset, weights).encode()).hexdigest()),
    }


@pytest.fixture(scope="module")
def pins():
    return np.load(PINS)


@pytest.mark.parametrize("preset,weights", CASES)
def test_tokens_and_logits_are_the_parents_to_the_last_bit(preset, weights, pins):
    *_, got = decoded(preset, weights)
    want = {k: pins[f"{preset}.{weights}.{k}"] for k in (
        "tokens", "prefill_logits", "last_logits", "logits_sha256")}
    assert np.isfinite(got["logits"]).all()  # a dead row's are finite too
    assert np.array_equal(got["tokens"], want["tokens"])
    # The kept logits say HOW far a failing tree is; the digest holds all.
    assert np.array_equal(got["prefill_logits"], want["prefill_logits"])
    assert np.array_equal(got["logits"][-1], want["last_logits"])
    assert digest(got["logits"]) == str(want["logits_sha256"])


@pytest.mark.parametrize("preset,weights", CASES)
def test_the_engines_chunk_samples_the_same_tokens(preset, weights):
    cfg, params, cache, first, rs, got = decoded(preset, weights)
    last, toks, _, ok = E._decode_chunk(
        params, cfg, first, jnp.asarray(PROMPT, jnp.int32),
        jax.tree.map(jnp.copy, cache), jax.random.PRNGKey(0), n_steps=STEPS,
        temperature=0.0, top_k=None, top_p=None, row_start=rs, kv_width=WIDTH,
        sentinel=True)
    assert np.array_equal(toks, got["tokens"])
    assert np.array_equal(last, got["tokens"][-1])
    assert bool(np.asarray(ok).all())


def _unnamed(text: str) -> list:
    """The lines of a lowered text with every value's name taken out."""
    return [re.sub(r"%[\w#:]+", "%", line) for line in text.splitlines()]


@pytest.mark.parametrize("preset,weights", CASES)
def test_the_lowered_chunk_differs_from_the_parents_by_the_barrier_alone(
        preset, weights, pins, monkeypatch):
    with_barrier = lowered(preset, weights)
    # One barrier a traced layer body: the layer scan's.
    assert with_barrier.count(f"stablehlo.{BARRIER}") == 1
    monkeypatch.setattr(jax.lax, BARRIER, lambda x: x)
    without = lowered(preset, weights)
    assert BARRIER not in without
    assert hashlib.sha256(without.encode()).hexdigest() == str(
        pins[f"{preset}.{weights}.lowered_sha256"])
    assert [line for line in _unnamed(with_barrier) if BARRIER not in line
            ] == _unnamed(without)


if __name__ == "__main__":
    # python tests/test_dense_proj.py <out.npz>, from a checkout's root:
    # that checkout's tokens, logits and lowered text, the pins above.
    np.savez_compressed(sys.argv[1], **{
        f"{preset}.{weights}.{key}": value
        for preset, weights in CASES
        for key, value in pinned(preset, weights).items()})
