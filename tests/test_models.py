"""Model-core tests: every family, causality, cache consistency, ops.

Runs on CPU (conftest pins JAX_PLATFORMS=cpu with an 8-device virtual mesh);
tiny configs keep compiles fast.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_consensus_tpu.models import forward, get_config, init_kv_cache, init_params
from llm_consensus_tpu.ops import rms_norm, sample_token
from llm_consensus_tpu.ops.moe import moe_block
from llm_consensus_tpu.ops.rope import apply_rope, rope_angles, rope_inv_freq

FAMILIES = ["tiny-llama", "tiny-gemma", "tiny-qwen2", "tiny-mistral", "tiny-mixtral"]


def setup_model(name, dtype=jnp.float32):
    cfg = get_config(name)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=dtype)
    return cfg, params


@pytest.mark.parametrize("name", FAMILIES)
def test_forward_shapes_all_families(name):
    cfg, params = setup_model(name)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    logits, cache = forward(params, cfg, tokens)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert cache is None
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("name", ["tiny-llama", "tiny-mistral"])
def test_causality(name):
    # Logits at position t must not depend on tokens after t.
    cfg, params = setup_model(name)
    key = jax.random.PRNGKey(2)
    tokens = jax.random.randint(key, (1, 12), 0, cfg.vocab_size)
    altered = tokens.at[0, -1].set((tokens[0, -1] + 7) % cfg.vocab_size)
    la, _ = forward(params, cfg, tokens)
    lb, _ = forward(params, cfg, altered)
    np.testing.assert_allclose(la[0, :-1], lb[0, :-1], rtol=1e-5, atol=1e-5)
    assert not np.allclose(la[0, -1], lb[0, -1])


@pytest.mark.parametrize("name", FAMILIES)
def test_cache_decode_matches_full_forward(name):
    # prefill + stepwise decode through the KV cache must reproduce the
    # no-cache forward logits — the core correctness invariant of the engine.
    cfg, params = setup_model(name)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 10), 0, cfg.vocab_size)
    full_logits, _ = forward(params, cfg, tokens)

    cache = init_kv_cache(cfg, batch=1, max_seq=32, dtype=jnp.float32)
    prefill_len = 6
    logits_pre, cache = forward(params, cfg, tokens[:, :prefill_len], cache, start_pos=0)
    np.testing.assert_allclose(
        full_logits[:, :prefill_len], logits_pre, rtol=2e-4, atol=2e-4
    )
    for i in range(prefill_len, 10):
        step_logits, cache = forward(params, cfg, tokens[:, i : i + 1], cache, start_pos=i)
        np.testing.assert_allclose(
            full_logits[:, i : i + 1], step_logits, rtol=2e-4, atol=2e-4
        )


def test_sliding_window_masks_far_tokens():
    cfg = get_config("tiny-mistral")  # window 32 > test len; shrink it
    from dataclasses import replace

    cfg = replace(cfg, sliding_window=4)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, 16), 0, cfg.vocab_size)
    # Changing a token > window steps in the past must not affect current logits.
    altered = tokens.at[0, 2].set((tokens[0, 2] + 3) % cfg.vocab_size)
    la, _ = forward(params, cfg, tokens)
    lb, _ = forward(params, cfg, altered)
    np.testing.assert_allclose(la[0, -1], lb[0, -1], rtol=1e-5, atol=1e-5)


def test_gemma_embed_scaling_applied():
    cfg, params = setup_model("tiny-gemma")
    tokens = jnp.zeros((1, 4), jnp.int32)
    logits, _ = forward(params, cfg, tokens)
    assert bool(jnp.all(jnp.isfinite(logits)))
    # tied embeddings: no separate lm_head in the pytree
    assert "lm_head" not in params


def test_qwen_bias_params_exist():
    cfg, params = setup_model("tiny-qwen2")
    assert "bq" in params["layers"] and "bk" in params["layers"]


# -- ops ---------------------------------------------------------------------


def test_rms_norm_unit_scale():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 64))
    out = rms_norm(x, jnp.ones((64,)))
    rms = jnp.sqrt(jnp.mean(out**2, axis=-1))
    np.testing.assert_allclose(rms, jnp.ones_like(rms), rtol=1e-3)


def test_rms_norm_gemma_offset():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
    # stored weight 0 with offset 1 == stored weight 1 with offset 0
    a = rms_norm(x, jnp.zeros((64,)), offset=1.0)
    b = rms_norm(x, jnp.ones((64,)), offset=0.0)
    np.testing.assert_allclose(a, b, rtol=1e-6)


def test_rope_preserves_norm_and_relative_angle():
    inv = rope_inv_freq(32, 10000.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 6, 2, 32))
    pos = jnp.arange(6)[None, :]
    cos, sin = rope_angles(pos, inv)
    rotated = apply_rope(x, cos, sin)
    np.testing.assert_allclose(
        jnp.linalg.norm(rotated, axis=-1), jnp.linalg.norm(x, axis=-1), rtol=1e-5
    )
    # position 0 is the identity rotation
    np.testing.assert_allclose(rotated[:, 0], x[:, 0], rtol=1e-6)


def test_rope_llama3_scaling_changes_long_wavelengths():
    base = rope_inv_freq(64, 500000.0)
    scaled = rope_inv_freq(
        64, 500000.0,
        {"factor": 8.0, "low_freq_factor": 1.0, "high_freq_factor": 4.0,
         "original_max_position_embeddings": 8192},
    )
    assert not np.allclose(base, scaled)
    np.testing.assert_allclose(base[0], scaled[0], rtol=1e-6)  # highest freq kept


def test_moe_routes_all_tokens():
    key = jax.random.PRNGKey(0)
    e, d, f, k = 4, 32, 64, 2
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (2, 8, d))
    wr = jax.random.normal(ks[1], (d, e)) * 0.1
    wg = jax.random.normal(ks[2], (e, d, f)) * 0.1
    wu = jax.random.normal(ks[3], (e, d, f)) * 0.1
    wd = jax.random.normal(ks[4], (e, f, d)) * 0.1
    out, stats = moe_block(x, wr, wg, wu, wd, top_k=k, with_stats=True)
    assert out.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(out)))
    # Dropless, every expert held: all 16 * 2 pairs are computed here.
    assert stats.tolist()[:2] == [32, 32]
    assert float(jnp.abs(out).mean()) > 0


def test_sample_greedy_is_argmax():
    logits = jnp.array([[0.1, 5.0, -2.0], [3.0, 0.0, 1.0]])
    out = sample_token(logits, jax.random.PRNGKey(0), temperature=0.0)
    np.testing.assert_array_equal(out, jnp.array([1, 0]))


def test_sample_top_k_restricts_support():
    logits = jnp.array([[10.0, 9.0, -50.0, -50.0]])
    for seed in range(20):
        tok = sample_token(logits, jax.random.PRNGKey(seed), temperature=1.0, top_k=2)
        assert int(tok[0]) in (0, 1)


def test_sample_top_p_restricts_support():
    logits = jnp.log(jnp.array([[0.6, 0.3, 0.05, 0.05]]))
    for seed in range(20):
        tok = sample_token(logits, jax.random.PRNGKey(seed), temperature=1.0, top_p=0.5)
        assert int(tok[0]) == 0  # 0.6 ≥ 0.5 → only the top token survives


def test_n_params_plausible():
    cfg = get_config("llama-3-8b")
    assert 7.5e9 < cfg.n_params() < 8.5e9
    cfg70 = get_config("llama-3-70b")
    assert 6.5e10 < cfg70.n_params() < 7.5e10


def test_forward_logits_index_matches_full():
    """logits_index must be a pure FLOP-saving slice: equal to selecting
    from the full logits after the fact."""
    import jax
    import jax.numpy as jnp

    from llm_consensus_tpu.models import forward, get_config, init_kv_cache, init_params

    cfg = get_config("tiny-llama")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    tokens = jnp.arange(12, dtype=jnp.int32)[None, :] % cfg.vocab_size
    cache = init_kv_cache(cfg, batch=1, max_seq=32, dtype=jnp.float32)
    full, _ = forward(params, cfg, tokens,
                      init_kv_cache(cfg, batch=1, max_seq=32, dtype=jnp.float32),
                      start_pos=0)
    idx = jnp.asarray([7])
    sliced, _ = forward(params, cfg, tokens, cache, start_pos=0, logits_index=idx)
    assert sliced.shape == (1, 1, cfg.vocab_size)
    assert jnp.allclose(sliced[:, 0], full[:, 7], atol=1e-6)
