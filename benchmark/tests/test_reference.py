"""The plain reference against the program's forward, in float32 on the CPU
at CI sizes: the two computations are independent, so agreement to float32
rounding says both compute the published block (sliding window included:
tiny-mistral's window of 32 is shorter than the sequence)."""

import json
import os

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.mark.parametrize("name", ["tiny-qwen2", "tiny-mistral"])
def test_reference_matches_the_program_in_float32(name):
    import jax
    import jax.numpy as jnp

    from benchmark.reference import decoder
    from llm_consensus_tpu.models import forward, get_config, init_params

    with open(os.path.join(REPO, "benchmark/configs/tiny-rehearsal.json")) as f:
        spec = json.load(f)["models"][name]
    cfg = get_config(name)
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    if "bq" in params["layers"]:  # zero at init: make the bias matter
        for k in ("bq", "bk", "bv"):
            params["layers"][k] = 0.1 * jax.random.normal(
                jax.random.PRNGKey(len(k)), params["layers"][k].shape)
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, 96)
    with jax.default_matmul_precision("highest"):
        want, _ = forward(params, cfg, jnp.asarray(ids[None], jnp.int32))
    got = decoder.forward(params, spec, ids)
    err = np.linalg.norm(np.asarray(got) - np.asarray(want[0]), axis=-1) / np.linalg.norm(np.asarray(want[0]), axis=-1)
    assert err.max() < 1e-4


def test_int8_leaves_are_dequantized():
    import jax.numpy as jnp

    from benchmark.reference import decoder

    leaf = {"q8": jnp.asarray([[1, -2], [3, 4]], jnp.int8),
            "s": jnp.asarray([[0.5, 0.25]], jnp.bfloat16)}
    np.testing.assert_allclose(
        np.asarray(decoder.dense(leaf)), [[0.5, -0.5], [1.5, 1.0]])
    with pytest.raises(ValueError):
        decoder.dense({"q4": leaf["q8"]})
