"""Rotary position embeddings.

Uses the half-split ("rotate_half") convention matching HuggingFace weight
layouts for Llama/Mistral/Qwen/Gemma, so imported checkpoints work without
permuting projection weights. Supports Llama-3-style NTK frequency scaling
and YaRN (the frequency blend and the softmax-scale correction).

TPU notes: angles are computed from integer positions inside the jitted
function (cheap VPU work, avoids carrying a [max_seq, dim] table in HBM), and
everything stays static-shaped so decode steps hit the same compiled program.
"""

from __future__ import annotations

from typing import Optional

import math

import jax
import jax.numpy as jnp


def rope_inv_freq(
    head_dim: int,
    theta: float = 10000.0,
    llama3_scaling: Optional[dict] = None,
) -> jax.Array:
    """Inverse frequencies [head_dim/2], fp32.

    ``llama3_scaling`` (factor, low_freq_factor, high_freq_factor,
    original_max_position_embeddings) applies Llama-3.1's piecewise NTK
    wavelength remap.
    """
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    inv_freq = 1.0 / (theta ** exponents)
    if llama3_scaling:
        factor = llama3_scaling["factor"]
        low = llama3_scaling["low_freq_factor"]
        high = llama3_scaling["high_freq_factor"]
        orig = llama3_scaling["original_max_position_embeddings"]
        wavelen = 2.0 * jnp.pi / inv_freq
        low_wavelen = orig / low
        high_wavelen = orig / high
        # long wavelengths fully scaled, short kept, middle interpolated
        smooth = (orig / wavelen - low) / (high - low)
        smooth = jnp.clip(smooth, 0.0, 1.0)
        scaled = inv_freq / factor
        interp = (1.0 - smooth) * scaled + smooth * inv_freq
        inv_freq = jnp.where(wavelen > low_wavelen, scaled,
                             jnp.where(wavelen < high_wavelen, inv_freq, interp))
    return inv_freq


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature correction: ``0.1 * mscale * ln(factor)
    + 1`` for a factor above 1."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1.0 else 1.0


def yarn_inv_freq(
    head_dim: int, theta: float, factor: float, beta_fast: float,
    beta_slow: float, original_max_position: int,
) -> jax.Array:
    """YaRN's inverse frequencies [head_dim/2], fp32 (arXiv 2309.00071, as
    DeepSeek-V2's published modelling code computes them).

    Per frequency a blend of ``theta^(-2i/d)`` (kept: it turns more than
    ``beta_fast`` times within the original context) and the same ÷
    ``factor`` (interpolated: fewer than ``beta_slow`` turns), by a linear
    ramp over the dimensions in between.
    """
    def turns_dim(turns: float) -> float:
        # the dimension whose wavelength makes `turns` rotations over the
        # original context
        return (
            head_dim * math.log(original_max_position / (turns * 2 * math.pi))
        ) / (2 * math.log(theta))

    low = max(math.floor(turns_dim(beta_fast)), 0)
    high = min(math.ceil(turns_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001  # the published guard against a zero-width ramp
    kept = rope_inv_freq(head_dim, theta)
    ramp = jnp.clip(
        (jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / (high - low),
        0.0, 1.0,
    )
    return (kept / factor) * ramp + kept * (1.0 - ramp)


def rope_angles(positions: jax.Array, inv_freq: jax.Array) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for integer ``positions`` [..., T] → [..., T, head_dim/2]."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate ``x`` [B, T, H, head_dim] by per-position angles [B, T, hd/2].

    Half-split convention: (x1, x2) → (x1·cos − x2·sin, x2·cos + x1·sin).
    """
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    # broadcast cos/sin over the heads axis
    c = cos[..., None, :]
    s = sin[..., None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)
