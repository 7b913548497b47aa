"""Engine safety net: a Pallas kernel that fails to lower must degrade
the engine to the XLA attention path, never kill the run — and never
quietly.

Round 1's decode kernel shipped with a Mosaic-invalid BlockSpec and was
on by default on TPU backends — every hardware run crashed at first
dispatch and the bench recorded rc=1. The runner's contract is
best-effort (reference runner.go:75-83: a model failure is a warning);
these tests pin the guard that keeps a serving process answering, and
the count (``flash_fallbacks`` / ``attention_stats``) that lets
chip_smoke.py and bench.py turn the same event into a failure.
"""

import warnings

import jax.numpy as jnp
import pytest

import llm_consensus_tpu.ops.pallas as pallas_pkg
from llm_consensus_tpu.engine import Engine, SamplingParams
from llm_consensus_tpu.engine.engine import _is_pallas_lowering_error
from llm_consensus_tpu.models import get_config

MOSAIC_MSG = (
    "The Pallas TPU lowering currently requires that the last two "
    "dimensions of your block shape are divisible by 8 and 128"
)


def _broken_kernel(*args, **kwargs):
    raise ValueError(MOSAIC_MSG)


def test_lowering_error_detector():
    assert _is_pallas_lowering_error(ValueError(MOSAIC_MSG))
    assert _is_pallas_lowering_error(RuntimeError("Mosaic failed to compile"))
    assert not _is_pallas_lowering_error(ValueError("empty prompt"))
    assert not _is_pallas_lowering_error(MemoryError("oom"))

    # XlaRuntimeError is retryable ONLY in its compile-time form (the
    # Mosaic compiler rejecting a kernel, before any executable runs);
    # a runtime fault means donated buffers may be consumed, so even a
    # Mosaic-flavored message must propagate.
    class XlaRuntimeError(Exception):
        pass

    assert _is_pallas_lowering_error(
        XlaRuntimeError("INTERNAL: Mosaic failed to compile TPU kernel")
    )
    assert not _is_pallas_lowering_error(
        XlaRuntimeError("Mosaic custom call faulted at runtime")
    )


def test_decode_kernel_failure_falls_back_to_xla(monkeypatch):
    """A broken decode kernel pins the engine to XLA mid-run and the
    generation still produces the exact greedy tokens."""
    cfg = get_config("tiny-llama", head_dim=128)  # decode_flash-eligible
    # max_seq distinct from every other dh=128 engine test: the cache
    # shape must force a fresh trace, or a jit-cache hit from an earlier
    # test would dispatch a cached good program and never reach the
    # patched kernel.
    ref = Engine(cfg, dtype=jnp.float32, max_seq=160, attn_impl="xla")
    eng = Engine(
        cfg, params=ref.params, dtype=jnp.float32, max_seq=160,
        attn_impl="flash",
    )
    monkeypatch.setattr(pallas_pkg, "decode_attention", _broken_kernel)
    sampling = SamplingParams(max_new_tokens=8, ignore_eos=True)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        out = eng.generate("hello world consensus", sampling)
    assert eng.attn_impl == "xla"
    assert any("falling back to XLA" in str(w.message) for w in caught)
    assert out.token_ids == ref.generate("hello world consensus", sampling).token_ids
    # Loud, not just logged: built vs running impl and the count.
    stats = eng.attention_stats()
    assert (stats["built"], stats["impl"], stats["fallbacks"]) == ("flash", "xla", 1)


def test_prefill_kernel_failure_falls_back_to_xla(monkeypatch):
    """Same guard on the one-shot prefill dispatch (flash prefill path)."""
    cfg = get_config("tiny-llama")
    # max_seq distinct from other tiny-llama engine tests (see decode
    # test above for why the shapes must force a fresh trace).
    ref = Engine(
        cfg, dtype=jnp.float32, max_seq=96, attn_impl="xla",
        prefill_chunk=0,  # force the one-shot per-bucket prefill program
    )
    eng = Engine(
        cfg, params=ref.params, dtype=jnp.float32, max_seq=96,
        attn_impl="flash", prefill_chunk=0,
    )
    monkeypatch.setattr(pallas_pkg, "flash_attention", _broken_kernel)
    sampling = SamplingParams(max_new_tokens=4, ignore_eos=True)
    # 320 bytes under the byte tokenizer; _budget_prompt middle-out
    # truncates to fit max_seq=96 and the result pads to bucket 96,
    # whose block sizes flash_supported admits.
    prompt = "word " * 64
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        out = eng.generate(prompt, sampling)
    assert eng.attn_impl == "xla"
    assert any("falling back to XLA" in str(w.message) for w in caught)
    assert out.token_ids == ref.generate(prompt, sampling).token_ids
    assert eng.flash_fallbacks == 1 and eng.attn_built == "flash"


def test_non_pallas_errors_propagate():
    """The guard must not swallow genuine errors (e.g. bad prompts)."""
    cfg = get_config("tiny-llama")
    eng = Engine(cfg, dtype=jnp.float32, max_seq=32, attn_impl="flash")
    with pytest.raises(ValueError, match="empty prompt"):
        eng.generate_ids([], SamplingParams(max_new_tokens=4))
    assert eng.attn_impl == "flash"  # untouched by unrelated failures
    assert eng.flash_fallbacks == 0
