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

Both forms are plain einsums (XLA) over the ``kv_width`` bucket of the
cache; the mask is the one every XLA route uses (causality, the frontier,
``row_start``, a dead row's mark). ``wo`` and the residual are the caller's.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from llm_consensus_tpu.ops.attention import NEG_INF
from llm_consensus_tpu.ops.norms import rms_norm
from llm_consensus_tpu.ops.quant import (
    dequantize, kv_layer, kv_write_rows, qeinsum)
from llm_consensus_tpu.ops.rope import apply_rope


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
    c_q = rms_norm(qeinsum("btd,dr->btr", h, lp["wq_a"]), lp["q_norm"], rms_eps)
    q = qeinsum("btr,rk->btk", c_q, lp["wq_b"]).reshape(
        b, t, n_heads, qk_nope_dim + qk_rope_dim)
    q_nope = q[..., :qk_nope_dim]
    q_rope = apply_rope(q[..., qk_nope_dim:], cos, sin)

    ckr = qeinsum("btd,dr->btr", h, lp["wkv_a"])
    c_kv = rms_norm(ckr[..., :kv_lora_rank], lp["kv_norm"], rms_eps)
    k_rope = apply_rope(ckr[..., None, kv_lora_rank:], cos, sin)[:, :, 0]
    latent = jnp.concatenate([c_kv, k_rope], axis=-1)        # [B, T, rank+rope]
    if cache is not None:
        cache = kv_write_rows(cache, latent[:, :, None, :], layer_idx, start_pos)
        latent = kv_layer(cache, layer_idx, kv_width)[:, :, 0, :].astype(h.dtype)
    c_all, r_all = latent[..., :kv_lora_rank], latent[..., kv_lora_rank:]

    w_kvb = dequantize(lp["wkv_b"], h.dtype).reshape(
        kv_lora_rank, n_heads, qk_nope_dim + v_head_dim)
    w_uk, w_uv = w_kvb[..., :qk_nope_dim], w_kvb[..., qk_nope_dim:]
    f32 = dict(preferred_element_type=jnp.float32)
    rope_scores = jnp.einsum("bthr,bsr->bhts", q_rope, r_all, **f32)
    if absorbed:
        q_lat = jnp.einsum("bthd,chd->bthc", q_nope, w_uk)
        scores = jnp.einsum("bthc,bsc->bhts", q_lat, c_all, **f32)
    else:
        k_nope = jnp.einsum("bsc,chd->bshd", c_all, w_uk)
        scores = jnp.einsum("bthd,bshd->bhts", q_nope, k_nope, **f32)
    scores = jnp.where(mask[:, None], (scores + rope_scores) * scale, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(h.dtype)
    if absorbed:
        o_lat = jnp.einsum("bhts,bsc->bthc", probs, c_all)
        out = jnp.einsum("bthc,chd->bthd", o_lat, w_uv)
    else:
        v = jnp.einsum("bsc,chd->bshd", c_all, w_uv)
        out = jnp.einsum("bhts,bshd->bthd", probs, v)
    return out.reshape(b, t, n_heads * v_head_dim), cache
