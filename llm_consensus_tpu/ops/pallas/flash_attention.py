"""Fused flash attention for TPU (Pallas/Mosaic).

This is the prefill hot op: the XLA path (ops/attention.py) materializes the
full [B, Hq, T, S] score tensor in HBM, which for a judge prefill over the
whole cache is O(T·S_max) memory traffic per head. The kernel below streams
KV blocks through VMEM with an online softmax (running max / sum / output
accumulator in scratch), so scores never leave the chip and the work is
bounded by the causal frontier (q_offset + T), not the cache capacity.

Design notes, TPU-first:
  * Layout [B, H, S, dh]: the last two dims of every block are
    (block, head_dim), which lands on the (sublane, lane) tiling the MXU
    and VPU want; the wrapper transposes from the model's [B, S, H, dh].
  * Grid (B, Hq, q_blocks, kv_blocks), kv innermost — TPU grids run
    sequentially in row-major order, so VMEM scratch carries the online
    softmax state across the kv sweep of each q block; the output block is
    written once, on the last kv step. Default blocks are 256×256: at
    batch-128 serving prefill the 128×128 grid ran 4× the iterations for
    the same bytes (measured ~8% slower end-to-end), and the bigger
    blocks still fit VMEM with wide margins.
  * GQA is handled by the index map: q head h reads kv head h·Hkv/Hq —
    no repeated/materialized KV heads.
  * Both matmuls (q·kᵀ and p·v) keep bf16 inputs with fp32 accumulation
    (`preferred_element_type`), matching the XLA reference numerics.
  * Causal + sliding-window block skipping via `pl.when`: kv blocks wholly
    above the diagonal (or wholly below the window) cost ~nothing.

The reference has no analog for any of this — its "attention" is on the
other side of an HTTPS call (/root/reference/internal/provider/openai.go:97).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_consensus_tpu.utils.backend import pallas_interpret

NEG_INF = -1e30  # large-negative f32; exp(NEG_INF - m) underflows to exactly 0

_LANES = 128  # TPU lane width: scratch rows are broadcast across it


def _pow2_block(n: int, cap: int) -> int:
    """Largest power-of-two ≤ cap that divides n (n itself need not be pow2)."""
    b = 1
    while b * 2 <= cap and n % (b * 2) == 0:
        b *= 2
    return b


def flash_supported(t: int, n_heads: int, n_kv_heads: int) -> bool:
    """Whether the kernel handles this shape (caller falls back to XLA if not)."""
    return t > 1 and n_heads % n_kv_heads == 0 and _pow2_block(t, 128) >= 8


def _kernel(
    q_ref,  # [1, 1, block_q, dh]
    k_ref,  # [1, 1, block_k, dh]
    v_ref,  # [1, 1, block_k, dh]
    o_ref,  # [1, 1, block_q, dh]
    m_ref,  # [block_q, LANES] f32 scratch: running row max (broadcast)
    l_ref,  # [block_q, LANES] f32 scratch: running row sum (broadcast)
    acc_ref,  # [block_q, dh] f32 scratch: unnormalized output accumulator
    *,
    scale: float,
    q_offset: int,
    block_q: int,
    block_k: int,
    n_kv_blocks: int,
    sliding_window: Optional[int],
    logit_softcap: Optional[float],
):
    i = pl.program_id(2)  # q block
    j = pl.program_id(3)  # kv block (innermost: scratch carries across it)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start = q_offset + i * block_q  # absolute position of this block's 1st row
    k_start = j * block_k

    # Causal frontier: skip kv blocks entirely above the diagonal.
    live = k_start <= q_start + block_q - 1
    if sliding_window is not None:
        # ...and entirely below the window of even the earliest row.
        live = jnp.logical_and(
            live, k_start + block_k > q_start - sliding_window + 1
        )

    @pl.when(live)
    def _block():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * scale
        if logit_softcap is not None:
            s = logit_softcap * jnp.tanh(s / logit_softcap)

        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = cols <= rows
        if sliding_window is not None:
            mask = jnp.logical_and(mask, cols > rows - sliding_window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)  # correction for the old accumulator
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1)[:, None]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_kv_blocks - 1)
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked row (can't happen causally)
        o_ref[0, 0, :, :] = (acc_ref[:] / l).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # [B, T, Hq, dh]
    k: jax.Array,  # [B, S, Hkv, dh]
    v: jax.Array,  # [B, S, Hkv, dh]
    *,
    q_offset: int = 0,
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    block_q: int = 256,
    block_k: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Causal GQA flash attention → [B, T, Hq, dh].

    Query row r attends kv positions p with ``p <= q_offset + r`` (and
    ``p > q_offset + r - sliding_window`` when windowed) — the same
    semantics as ``make_attention_mask`` over a cache whose valid region is
    exactly the causal frontier. KV beyond ``q_offset + T`` (unwritten
    cache capacity) is never read.
    """
    b, t, hq, dh = q.shape
    _, s, hkv, _ = k.shape
    if hq % hkv:
        raise ValueError(f"n_heads {hq} not a multiple of n_kv_heads {hkv}")
    scale = dh**-0.5 if scale is None else scale
    if interpret is None:
        interpret = pallas_interpret()

    block_q = _pow2_block(t, min(block_q, t))
    # Work is bounded by the causal frontier, not cache capacity.
    s_eff = min(s, q_offset + t)
    bk = 1  # smallest power of two covering s_eff, capped at block_k
    while bk < s_eff and bk < block_k:
        bk *= 2
    block_k = bk
    n_kv_blocks = pl.cdiv(s_eff, block_k)
    s_pad = n_kv_blocks * block_k

    # [B, S, H, dh] → [B, H, S, dh] so blocks tile as (seq, head_dim).
    qt = q.transpose(0, 2, 1, 3)
    kt = k[:, :s_eff].transpose(0, 2, 1, 3)
    vt = v[:, :s_eff].transpose(0, 2, 1, 3)
    if s_pad != s_eff:
        # Padded keys sit at positions ≥ q_offset+T, so the causal mask
        # already excludes them; zeros keep the matmul well-defined.
        pad = ((0, 0), (0, 0), (0, s_pad - s_eff), (0, 0))
        kt, vt = jnp.pad(kt, pad), jnp.pad(vt, pad)

    grid = (b, hq, t // block_q, n_kv_blocks)
    group = hq // hkv

    kernel = functools.partial(
        _kernel,
        scale=scale,
        q_offset=q_offset,
        block_q=block_q,
        block_k=block_k,
        n_kv_blocks=n_kv_blocks,
        sliding_window=sliding_window,
        logit_softcap=logit_softcap,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, dh), lambda b_, h, i, j: (b_, h, i, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, dh), lambda b_, h, i, j: (b_, h // group, j, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, dh), lambda b_, h, i, j: (b_, h // group, j, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, dh), lambda b_, h, i, j: (b_, h, i, 0),
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, t, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=4 * b * hq * t * s_eff * dh,
            bytes_accessed=2 * (qt.size + kt.size + vt.size) * q.dtype.itemsize,
            transcendentals=b * hq * t * s_eff,
        ),
        interpret=interpret,
        name="llmc_flash_attention",
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
