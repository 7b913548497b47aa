"""FLOPs accounting (utils/flops.py): analytic counts vs real param trees.

The reference's only throughput signal is a chars/4 estimate
(/root/reference/internal/ui/ui.go:142); these tests pin the real
accounting that replaces it.
"""

import jax
import jax.numpy as jnp
import pytest

from llm_consensus_tpu.models import get_config, init_params
from llm_consensus_tpu.utils.flops import (
    decode_mfu,
    device_peak_flops,
    flops_per_token,
    param_count,
)


@pytest.mark.parametrize(
    "preset", ["tiny-llama", "tiny-gemma", "tiny-qwen2", "tiny-mistral", "tiny-mixtral",
               "tiny-deepseek-v2"]
)
def test_param_count_matches_init_params(preset):
    cfg = get_config(preset)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    actual = sum(x.size for x in jax.tree.leaves(params))
    assert param_count(cfg) == actual


def test_active_param_count_moe():
    cfg = get_config("tiny-mixtral")
    assert param_count(cfg, active_only=True) < param_count(cfg)
    dense = get_config("tiny-llama")
    assert param_count(dense, active_only=True) == param_count(dense)


def test_flops_per_token_grows_with_context():
    cfg = get_config("tiny-llama")
    assert flops_per_token(cfg, 1024) > flops_per_token(cfg, 0)
    # At zero context the count is the classic 2N rule over non-embedding
    # weights; embedding lookup is not a matmul.
    n_weights = param_count(cfg, active_only=True) - cfg.vocab_size * cfg.d_model
    assert flops_per_token(cfg, 0) == 2.0 * n_weights


def test_flops_per_token_tied_embeddings_count_unembed():
    """Gemma ties embed/unembed: the shared table is a real output matmul,
    so its FLOPs must not be subtracted with the lookup."""
    cfg = get_config("tiny-gemma")
    assert cfg.tie_embeddings
    assert flops_per_token(cfg, 0) == 2.0 * param_count(cfg, active_only=True)


def test_n_params_delegates_to_param_count():
    cfg = get_config("tiny-qwen2")  # qkv_bias: the term the old dup missed
    assert cfg.n_params() == param_count(cfg)
    moe = get_config("tiny-mixtral")
    assert moe.n_params(active_only=True) == param_count(moe, active_only=True)


def test_device_peak_lookup():
    assert device_peak_flops("TPU v5 lite") == pytest.approx(197e12)
    assert device_peak_flops("TPU v5p chip") == pytest.approx(459e12)
    assert device_peak_flops("TPU v4") == pytest.approx(275e12)
    assert device_peak_flops("cpu") is None


def test_decode_mfu():
    cfg = get_config("llama-3-8b")
    mfu = decode_mfu(cfg, tokens_per_sec=100.0, device_kind="TPU v5 lite")
    assert mfu is not None and 0 < mfu < 0.05  # 8B @ 100 tok/s on v5e ~0.8%
    assert decode_mfu(cfg, 100.0, "cpu") is None
    # TP over 4 chips divides utilization by the slice size.
    mfu4 = decode_mfu(cfg, 100.0, "TPU v5 lite", n_devices=4)
    assert mfu4 == pytest.approx(mfu / 4)


def test_decode_mbu_accounting():
    from llm_consensus_tpu.models import get_config
    from llm_consensus_tpu.utils.flops import (
        decode_bytes_per_token,
        decode_mbu,
        device_peak_hbm_bw,
        param_count,
    )

    cfg = get_config("consensus-1b")
    # bf16 weights, no context: exactly 2 bytes per active param.
    assert decode_bytes_per_token(cfg, 0, weight_bytes=2, kv_bytes=2) == (
        2 * param_count(cfg, active_only=True)
    )
    # int8 halves the weight term; KV term scales with context and width.
    int8 = decode_bytes_per_token(cfg, 1024, weight_bytes=1, kv_bytes=1)
    bf16 = decode_bytes_per_token(cfg, 1024, weight_bytes=2, kv_bytes=2)
    assert abs(bf16 - 2 * int8) < 1e-6
    assert device_peak_hbm_bw("TPU v5 lite") == 819e9
    assert device_peak_hbm_bw("cpu") is None
    # 500 tok/s of int8 consensus-1b on v5e ≈ 54% of the 819 GB/s roofline.
    mbu = decode_mbu(cfg, 500.0, "TPU v5 lite", weight_bytes=1, kv_bytes=1)
    assert 0.4 < mbu < 0.7


def test_int8_peak_is_double_bf16():
    """MXU int8×int8 runs at 2× the dense bf16 rate; the helper is the
    single owner of the W8A8 MFU normalization convention."""
    from llm_consensus_tpu.utils.flops import (
        device_peak_flops, device_peak_int8_ops)

    assert device_peak_int8_ops("TPU v5 lite") == 2 * device_peak_flops(
        "TPU v5 lite"
    )
    # v4 publishes equal int8 TOPS and bf16 TFLOPS; v2/v3 have no int8
    # MXU rate at all — the helper must not invent a 2x peak there.
    assert device_peak_int8_ops("TPU v4") == device_peak_flops("TPU v4")
    assert device_peak_int8_ops("TPU v3") is None
    assert device_peak_int8_ops("some cpu") is None
