"""Ring attention: sequence/context parallelism over an ICI ring.

Long-context path for the judge: a consensus judge prompt concatenates the
user prompt plus every panel answer (consensus/judge.py, mirroring the
reference template at /root/reference/internal/consensus/judge.go:21-25),
so judge prefill length grows with panel size — past a single chip's HBM,
the sequence dimension itself must shard.

Design (Ring Attention, Liu et al. 2023 — re-derived for shard_map):
  * Q, K, V shard over mesh axis ``axis_name`` on the sequence dim. Each
    device keeps its Q block resident and circulates K/V blocks around the
    ring with ``ppermute`` — every device sees every KV block after
    ``axis_size`` hops, so peak memory is O(S/n) while the math equals
    full attention.
  * Blocks combine with the online-softmax recurrence (running row max
    ``m``, normalizer ``l``, unnormalized accumulator ``out`` — fp32),
    the same update flash attention uses across KV tiles; a block is just
    a very large tile that happens to live on another chip.
  * Causality rides on absolute positions: each KV block carries its
    position vector around the ring, so masking needs no step/rank
    arithmetic and sliding windows compose for free.
  * ``lax.scan`` drives the hops: XLA sees a static ring of
    collective-permutes and overlaps each hop's transfer with the current
    block's matmuls on the MXU.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from llm_consensus_tpu.ops.attention import NEG_INF


def _block_attention(
    q: jax.Array,        # [B, T, Hkv, G, dh]  (GQA-grouped queries)
    k: jax.Array,        # [B, S, Hkv, dh]
    v: jax.Array,        # [B, S, Hkv, dh]
    mask: jax.Array,     # [B, T, S] bool
    scale: float,
    logit_softcap: Optional[float],
) -> tuple[jax.Array, jax.Array]:
    """One KV block's (scores-max, exp-weighted sums) for online softmax."""
    scores = jnp.einsum(
        "btkgd,bskd->bkgts", q, k, preferred_element_type=jnp.float32
    ) * scale
    if logit_softcap is not None:
        # Gemma-family softcap; applied pre-mask exactly as ops.attention.
        scores = logit_softcap * jnp.tanh(scores / logit_softcap)
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    block_max = jnp.max(scores, axis=-1)                       # [B,Hkv,G,T]
    p = jnp.exp(scores - block_max[..., None])
    p = jnp.where(mask[:, None, None, :, :], p, 0.0)
    block_sum = jnp.sum(p, axis=-1)                            # [B,Hkv,G,T]
    block_out = jnp.einsum("bkgts,bskd->btkgd", p.astype(v.dtype), v)
    return block_max, (block_sum, block_out)


def _ring_attention_local(
    q: jax.Array,          # [B, Tl, Hq, dh] local query shard
    k: jax.Array,          # [B, Tl, Hkv, dh] local KV shard
    v: jax.Array,
    axis_name: str,
    scale: float,
    sliding_window: Optional[int],
    logit_softcap: Optional[float],
    vary_axes: tuple = (),  # every shard_map axis the inputs vary over
) -> jax.Array:
    """Per-device body (runs under shard_map over ``axis_name``)."""
    axis_size = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, tl, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv

    local_pos = jnp.arange(tl, dtype=jnp.int32)
    q_pos = jnp.broadcast_to((idx * tl + local_pos)[None, :], (b, tl))
    kv_pos0 = q_pos

    qg = q.reshape(b, tl, hkv, g, dh)
    # Ring: device i sends its current KV block to i+1, receives from i-1.
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def hop(carry, _):
        k_blk, v_blk, kv_pos, out, m, l = carry
        causal = kv_pos[:, None, :] <= q_pos[:, :, None]
        if sliding_window is not None:
            causal &= kv_pos[:, None, :] > (q_pos[:, :, None] - sliding_window)
        blk_max, (blk_sum, blk_out) = _block_attention(
            qg, k_blk, v_blk, causal, scale, logit_softcap
        )
        m_new = jnp.maximum(m, blk_max)
        corr = jnp.exp(m - m_new)
        blk_corr = jnp.exp(blk_max - m_new)
        l_new = l * corr + blk_sum * blk_corr
        # out layout [B,T,Hkv,G,dh]; factors come in [B,Hkv,G,T]
        corr_t = jnp.moveaxis(corr, -1, 1)[..., None]
        blk_corr_t = jnp.moveaxis(blk_corr, -1, 1)[..., None]
        out_new = out * corr_t + blk_out.astype(jnp.float32) * blk_corr_t
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        kv_pos = jax.lax.ppermute(kv_pos, axis_name, perm)
        return (k_blk, v_blk, kv_pos, out_new, m_new, l_new), None

    # pcast: mark the accumulator inits as device-varying over every bound
    # axis so the scan carry types match (they combine with varying data —
    # the ring axis always, plus the head axis when heads are sharded).
    axes = tuple(vary_axes) or (axis_name,)

    def varying(x):
        return jax.lax.pcast(x, axes, to="varying")

    out0 = varying(jnp.zeros((b, tl, hkv, g, dh), jnp.float32))
    m0 = varying(jnp.full((b, hkv, g, tl), NEG_INF, jnp.float32))
    l0 = varying(jnp.zeros((b, hkv, g, tl), jnp.float32))
    (_, _, _, out, _, l), _ = jax.lax.scan(
        hop, (k, v, kv_pos0, out0, m0, l0), None, length=axis_size
    )
    l_t = jnp.moveaxis(l, -1, 1)[..., None]                    # [B,T,Hkv,G,1]
    out = out / jnp.maximum(l_t, 1e-30)
    return out.reshape(b, tl, hq, dh).astype(q.dtype)


def ring_attention(
    q: jax.Array,          # [B, S, Hq, dh] (sequence-sharded over axis_name)
    k: jax.Array,          # [B, S, Hkv, dh]
    v: jax.Array,
    mesh: Mesh,
    axis_name: str = "sp",
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    head_axis: Optional[str] = None,
) -> jax.Array:
    """Causal GQA attention with the sequence dim sharded over ``axis_name``.

    Equals ``ops.attention`` with a causal mask, computed without any
    device ever holding the full sequence. S must divide evenly by the
    axis size (pad prompts to the shard multiple — static shapes anyway).

    ``head_axis`` additionally shards the head dim (TP): rings then run
    per head-shard — attention is per-head, so the two compositions never
    communicate, and SP×TP meshes work with one shard_map. The local body
    sees per-shard head counts, so GQA grouping requires the head axis to
    divide both Hq and Hkv.
    """
    if q.shape[1] % mesh.shape[axis_name] != 0:
        raise ValueError(
            f"sequence length {q.shape[1]} not divisible by "
            f"{axis_name}={mesh.shape[axis_name]}"
        )
    if head_axis is not None:
        h = mesh.shape[head_axis]
        if q.shape[2] % h or k.shape[2] % h:
            raise ValueError(
                f"head counts {q.shape[2]}/{k.shape[2]} not divisible by "
                f"{head_axis}={h}"
            )
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    seq_spec = P(None, axis_name, head_axis, None)
    vary_axes = (axis_name,) if head_axis is None else (axis_name, head_axis)
    fn = jax.shard_map(
        partial(
            _ring_attention_local,
            axis_name=axis_name,
            scale=scale,
            sliding_window=sliding_window,
            logit_softcap=logit_softcap,
            vary_axes=vary_axes,
        ),
        mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec),
        out_specs=seq_spec,
    )
    return fn(q, k, v)
