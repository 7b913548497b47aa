"""Multi-head latent attention (MLA, DeepSeek-V2; arXiv 2405.04434 §2.1).

With ``h`` the normed hidden state of one token:

  * **Queries.** ``c_q = RMSNorm(h · W_qa)``; ``q = c_q · W_qb``, per head
    split into ``q_nope`` and ``q_rope``; ``q_rope`` is rotated.
  * **Latent.** ``[c_kv ; k_r] = h · W_kva``; ``c_kv ← RMSNorm(c_kv)``;
    ``k_rope`` = rotated ``k_r``, ONE head shared by every query head. The
    cache holds ``c_kv ‖ k_rope`` a token a layer and nothing else.
  * **Prefill form.** ``[k_nope ; v]_head = c_kv · W_kvb``; scores
    ``(q_nope · k_nope + q_rope · k_rope) · scale``, masked softmax in
    float32, ``o_head = Σ p · v``.
  * **Decode form (absorbed).** ``q̃_head = q_nope · W_uk,headᵀ``; scores
    ``(q̃ · c_kv + q_rope · k_rope) · scale``; ``o_lat = Σ p · c_kv``;
    ``o_head = o_lat · W_uv,head``. ``W_uk`` and ``W_uv`` are the two halves
    of ``W_kvb``: the same mathematics with the products reassociated, so no
    per-head key or value is ever written, only the latent is swept.

Both forms are plain einsums (XLA); the mask is the one every XLA route
uses (causality, the frontier, ``row_start``, a dead row's mark). The decode
form writes its one token a row (``_write_token``) and sweeps the ``kv_width``
bucket of the cache from where the pool lies. The prefill form sweeps a
static width chosen at run time by the chunk's frontier (``prefill_sweep``):
one program serves every chunk of a prompt at a traced start, so it holds a
branch for T, 2T, ... up to the bucket, and a chunk runs the narrowest that
covers the slots it may attend. ``wo`` and the residual are the caller's.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from llm_consensus_tpu.obs.scopes import scope
from llm_consensus_tpu.ops.attention import NEG_INF
from llm_consensus_tpu.ops.norms import rms_norm
from llm_consensus_tpu.ops.quant import (
    dequantize, kv_layer, kv_write_rows, qeinsum)
from llm_consensus_tpu.ops.rope import apply_rope


# The most branches a prefill program holds; past it the widths step coarser.
MAX_SWEEP_BRANCHES = 8


def prefill_sweep(t: int, kv_width: int, end):
    """THE width rule of the prefill form, written once: ``(widths, at)``
    for a ``t``-token chunk in a ``kv_width``-slot bucket whose last token
    is written at slot ``end - 1``. ``widths`` are the static widths the
    program holds a branch for (``t``, 2 ``t``, ... and the bucket last;
    steps of 2 ``t``, 4 ``t``, ... where that would pass
    ``MAX_SWEEP_BRANCHES``), ``at`` the index of the narrowest that covers
    ``end`` slots, counted past the last for an ``end`` beyond the bucket
    (``jax.lax.switch`` clamps, ``prefill_sweep_width`` too). ``end`` may
    be traced. The program's branch list and the pool's
    ``prefill_kv_pairs_swept`` both come from here."""
    step = t
    while -(-kv_width // step) > MAX_SWEEP_BRANCHES:
        step *= 2
    widths = (*range(step, kv_width, step), kv_width)
    return widths, (end - 1) // step


def prefill_sweep_width(t: int, kv_width: int, end: int) -> int:
    """The width a chunk that ends at slot ``end`` sweeps (host arithmetic)."""
    widths, at = prefill_sweep(t, kv_width, end)
    return widths[min(at, len(widths) - 1)]


def _write_token(cache: jax.Array, latent: jax.Array, layer_idx, pos):
    """One token's latents ``[B, rank + rope]`` into the full stack at
    (``layer_idx``, every row, slot ``pos``), in place, ONE WRITE A ROW. A
    single ``[1, B, 1, 1, C]`` update has rows and latent as its two real
    axes, and the chip's layout assignment then lays the whole POOL rows
    second-minor around it, against the sweeps, which want slots there:
    the layer loop and the leading dense layer disagreed and the pool was
    copied twice a decode step. A row's update has one real axis and leaves
    the pool as the sweeps read it (tests/test_tpu_compile.py holds that)."""
    for row in range(latent.shape[0]):
        cache = jax.lax.dynamic_update_slice(
            cache, latent[row][None, None, None, None, :].astype(cache.dtype),
            (layer_idx, row, pos, 0, 0))
    return cache


def latent_attention(
    h: jax.Array,               # [B, T, D], already normed
    lp: dict,                   # wq_a, q_norm, wq_b, wkv_a, kv_norm, wkv_b
    cos: jax.Array,             # [B, T, qk_rope_dim / 2]
    sin: jax.Array,
    mask: jax.Array,            # [B, T, S] bool
    cache: Optional[jax.Array],  # FULL latent stack [L, B, S, 1, rank + rope]
    start_pos,
    layer_idx,
    *,
    n_heads: int,
    kv_lora_rank: int,
    qk_nope_dim: int,
    qk_rope_dim: int,
    v_head_dim: int,
    scale: float,
    rms_eps: float,
    kv_width: Optional[int] = None,
    absorbed: bool = False,
) -> tuple[jax.Array, Optional[jax.Array]]:
    """Attention output [B, T, H * v_head_dim] and the cache with this
    call's latents written at (``layer_idx``, ``start_pos``)."""
    b, t, _ = h.shape
    with scope("mla.q"):
        c_q = rms_norm(
            qeinsum("btd,dr->btr", h, lp["wq_a"]), lp["q_norm"], rms_eps)
        # The barrier stands between the product and its split into heads:
        # the chip's layout assignment otherwise carries the split back onto
        # the weight and relays the layer's whole ``wq_b`` before every
        # product (75 MB a layer a decode step at DeepSeek-V2's widths)
        # where a few activations would do. It changes no value.
        q = jax.lax.optimization_barrier(
            qeinsum("btr,rk->btk", c_q, lp["wq_b"])).reshape(
            b, t, n_heads, qk_nope_dim + qk_rope_dim)
        q_nope = q[..., :qk_nope_dim]
        q_rope = apply_rope(q[..., qk_nope_dim:], cos, sin)

    with scope("mla.kv_a"):
        ckr = qeinsum("btd,dr->btr", h, lp["wkv_a"])
        c_kv = rms_norm(ckr[..., :kv_lora_rank], lp["kv_norm"], rms_eps)
        k_rope = apply_rope(ckr[..., None, kv_lora_rank:], cos, sin)[:, :, 0]
        latent = jnp.concatenate([c_kv, k_rope], axis=-1)    # [B, T, rank+rope]
        if cache is not None:
            cache = (
                _write_token(cache, latent[:, 0], layer_idx, start_pos)
                if t == 1 else kv_write_rows(
                    cache, latent[:, :, None, :], layer_idx, start_pos))

    with scope("mla.absorb"):
        w_kvb = dequantize(lp["wkv_b"], h.dtype).reshape(
            kv_lora_rank, n_heads, qk_nope_dim + v_head_dim)
        w_uk, w_uv = w_kvb[..., :qk_nope_dim], w_kvb[..., qk_nope_dim:]
    f32 = dict(preferred_element_type=jnp.float32)

    def attend(width: Optional[int]) -> jax.Array:
        """[B, T, H, v_head_dim] over the first ``width`` slots of this
        layer's latents (the bucket, or this call's own without a cache)."""
        with scope("mla.sweep"):
            if cache is None:
                lat, keep = latent, mask
            else:
                lat = kv_layer(
                    cache, layer_idx, width)[:, :, 0, :].astype(h.dtype)
                keep = mask[..., :lat.shape[1]]
            c_all, r_all = lat[..., :kv_lora_rank], lat[..., kv_lora_rank:]
            rope_scores = jnp.einsum("bthr,bsr->bhts", q_rope, r_all, **f32)
        with scope("mla.absorb"):
            if absorbed:
                q_lat = jnp.einsum("bthd,chd->bthc", q_nope, w_uk)
            else:
                k_nope = jnp.einsum("bsc,chd->bshd", c_all, w_uk)
        with scope("mla.sweep"):
            if absorbed:
                scores = jnp.einsum("bthc,bsc->bhts", q_lat, c_all, **f32)
            else:
                scores = jnp.einsum("bthd,bshd->bhts", q_nope, k_nope, **f32)
            scores = jnp.where(
                keep[:, None], (scores + rope_scores) * scale, NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1).astype(h.dtype)
            if absorbed:
                o_lat = jnp.einsum("bhts,bsc->bthc", probs, c_all)
        if absorbed:
            with scope("mla.absorb"):
                return jnp.einsum("bthc,chd->bthd", o_lat, w_uv)
        with scope("mla.absorb"):
            v = jnp.einsum("bsc,chd->bshd", c_all, w_uv)
        with scope("mla.sweep"):
            return jnp.einsum("bhts,bshd->bthd", probs, v)

    if cache is None or absorbed:
        out = attend(kv_width)
    else:
        # A slot at or past the frontier is masked to NEG_INF and adds
        # exactly 0 to every sum, so the narrowest branch that covers the
        # frontier gives the whole bucket's result up to a reduction's order.
        slots = mask.shape[-1]
        with scope("mla.sweep"):
            widths, at = prefill_sweep(t, slots, start_pos + t)
        out = jax.lax.switch(at, [partial(attend, w) for w in widths])
    with scope("mla.sweep"):
        return out.reshape(b, t, n_heads * v_head_dim), cache
