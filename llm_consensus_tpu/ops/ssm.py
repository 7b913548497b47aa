"""Mamba-2 selective state-space mixer in plain XLA, but for a decode step's
recurrence in a cache (ops/pallas/ssm_step.py).

The mixer of a hybrid block (``ModelConfig.has_ssm``), beside attention on
the same normed input ``u`` [T, D]::

    p = (u * in_mult) @ W_in * mup            # z | x | B | C | dt
    xBC = silu(causal_conv(x | B | C))         # depthwise, K taps
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t[h] (outer) B_t[g]      # [P, N] a head
    y_t[h] = S_t C_t[g] + D_h x_t[h]
    out = (group_rms_norm(y * silu(z)) @ W_out) * out_mult

Two forms of the recurrence, the same numbers: ``ssd_chunked`` for T > 1 (the
state-space-duality form over chunks of ``chunk`` positions: the products
inside a chunk, each chunk's end state, the recurrence over chunk states and
the carried-in state's share) and ``ssd_step`` for T = 1, which a state in
a cache's stack takes in place (ops/pallas/ssm_step.py). State and decay
sums are float32, and every product here runs at ``highest`` precision: the
scan is a few percent of a block's operations and its state is carried
through thousands of steps.

A row's state has no sequence axis: it exists at ONE length. So every
function takes the span of real positions ``[lo, hi)`` of each row inside
the call's T (``None``: all of them). A position outside it has ``dt`` = 0
and adds nothing to the convolution tail: state and tail after a padded call
equal those after its real positions alone, whichever side the padding is on.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from llm_consensus_tpu.obs.scopes import scope
from llm_consensus_tpu.ops.quant import qeinsum

_HI = jax.lax.Precision.HIGHEST


def span_mask(t: int, lo: jax.Array, hi: jax.Array) -> jax.Array:
    """[B, T] bool: the positions of each row's real span ``[lo, hi)``."""
    idx = jnp.arange(t, dtype=jnp.int32)[None, :]
    return jnp.logical_and(idx >= lo[:, None], idx < hi[:, None])


def causal_conv(x: jax.Array, tail: jax.Array, w: jax.Array, b: jax.Array,
                lo: Optional[jax.Array] = None,
                hi: Optional[jax.Array] = None):
    """Depthwise causal convolution over each row's REAL positions.

    ``x`` [B, T, C]; ``tail`` [B, K-1, C], the K-1 real inputs before this
    call, oldest first (zeros for a fresh row); ``w`` [C, K], tap K-1 on the
    current position; ``b`` [C]. Returns ``(out [B, T, C], new tail)``: the
    tail after the row's last real position. Outputs at positions outside
    the span are junk nobody reads.
    """
    bsz, t, c = x.shape
    k = w.shape[-1]
    if lo is None:
        ext = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
        new_tail = ext[:, t:]
    else:
        # ext[j] is position j - (K-1). Padding is zeroed, and the carried
        # tail is laid directly before the row's first real position, so
        # left padding does not separate a row from its past.
        xm = jnp.where(span_mask(t, lo, hi)[..., None], x, 0)
        ext = jnp.concatenate([jnp.zeros((bsz, k - 1, c), x.dtype), xm], axis=1)
        ext = jax.vmap(
            lambda e, tl, at: jax.lax.dynamic_update_slice(e, tl, (at, 0))
        )(ext, tail.astype(x.dtype), lo)
        new_tail = jax.vmap(
            lambda e, at: jax.lax.dynamic_slice(e, (at, 0), (k - 1, c))
        )(ext, hi)
    wf = w.astype(jnp.float32)
    out = b.astype(jnp.float32) + sum(
        ext[:, j:j + t].astype(jnp.float32) * wf[:, j] for j in range(k))
    return out.astype(x.dtype), new_tail.astype(tail.dtype)


def conv_step(x: jax.Array, tail: jax.Array, w: jax.Array, b: jax.Array,
              live: Optional[jax.Array] = None):
    """``causal_conv`` for T = 1: ``x`` [B, 1, C]; ``live`` [B] bool says
    which rows' one position is real (a row that is not keeps its tail)."""
    ext = jnp.concatenate([tail.astype(x.dtype), x], axis=1)  # [B, K, C]
    out = b.astype(jnp.float32) + jnp.einsum(
        "bkc,ck->bc", ext.astype(jnp.float32), w.astype(jnp.float32),
        precision=_HI)
    new_tail = ext[:, 1:].astype(tail.dtype)
    if live is not None:
        new_tail = jnp.where(live[:, None, None], new_tail, tail)
    return out[:, None].astype(x.dtype), new_tail


def ssd_step(xs, dt, a, bm, cm, d, state):
    """One position of the recurrence. ``xs`` [B, H, P]; ``dt`` [B, H]
    float32 (0 on a row that does not advance); ``a`` [H] float32, negative;
    ``bm``, ``cm`` [B, G, N]; ``d`` [H]; ``state`` [B, H, P, N] float32.
    Returns ``(y [B, H, P] float32, new state)``."""
    h, g = xs.shape[1], bm.shape[1]
    xf = xs.astype(jnp.float32)
    bh = jnp.repeat(bm.astype(jnp.float32), h // g, axis=1)  # [B, H, N]
    ch = jnp.repeat(cm.astype(jnp.float32), h // g, axis=1)
    decay = jnp.exp(dt * a)[..., None, None]
    state = state * decay + (dt[..., None] * xf)[..., None] * bh[:, :, None, :]
    y = jnp.sum(state * ch[:, :, None, :], axis=-1) + d[:, None] * xf
    return y, state


def ssd_chunked(xs, dt, a, bm, cm, d, state, chunk: int):
    """The recurrence over T positions in chunks of ``chunk``.

    ``xs`` [B, T, H, P]; ``dt`` [B, T, H] float32 (0 at positions that do
    not advance the state); ``a`` [H] float32, negative; ``bm``, ``cm``
    [B, T, G, N]; ``d`` [H]; ``state`` [B, H, P, N] float32, carried in.
    Returns ``(y [B, T, H, P] float32, state after position T-1)``. T need
    not be a multiple of ``chunk``: the tail is padded with ``dt`` = 0.
    """
    bsz, t, h, p = xs.shape
    g, n = bm.shape[2], bm.shape[3]
    hg = h // g
    nc = -(-t // chunk)
    pad = nc * chunk - t

    def chunks(v):  # [B, T, ...] -> float32 [B, nc, chunk, ...]
        v = v.astype(jnp.float32)
        if pad:
            v = jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
        return v.reshape(bsz, nc, chunk, *v.shape[2:])

    x = chunks(xs).reshape(bsz, nc, chunk, g, hg, p)
    bc, cc = chunks(bm), chunks(cm)                      # [B, nc, Q, G, N]
    # Per-head scalars ride head-major [B, nc, G, Hg, Q]: positions on the
    # minor axis, where a [.., Q, Q] table of them tiles without padding.
    dth = jnp.transpose(
        chunks(dt).reshape(bsz, nc, chunk, g, hg), (0, 1, 3, 4, 2))
    cum = jnp.cumsum(dth * a.reshape(g, hg, 1), axis=-1)  # decay sums, <= 0

    def by_position(v):  # [B, nc, G, Hg, Q] -> [B, nc, Q, G, Hg, 1]
        return jnp.transpose(v, (0, 1, 4, 2, 3))[..., None]

    # Inside a chunk: y_q += sum_{s<=q} (C_q . B_s) exp(cum_q - cum_s) dt_s x_s
    cb = jnp.einsum("bcqgn,bcsgn->bcgqs", cc, bc, precision=_HI)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(
        causal, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    scores = decay * dth[..., None, :] * cb[:, :, :, None]  # [B, nc, G, Hg, Q, S]
    y = jnp.einsum("bcghqs,bcsghp->bcqghp", scores, x, precision=_HI)
    # Each chunk's own end state, from a zero start.
    to_end = by_position(jnp.exp(cum[..., -1:] - cum) * dth)
    ends = jnp.einsum(
        "bcqghp,bcqgn->bcghpn", x * to_end, bc, precision=_HI)
    chunk_decay = jnp.exp(cum[..., -1])                  # [B, nc, G, Hg]

    def carry(s, per_chunk):
        end, dec = per_chunk
        return s * dec[..., None, None] + end, s         # emit the state carried IN

    state, carried = jax.lax.scan(
        carry, state.reshape(bsz, g, hg, p, n).astype(jnp.float32),
        (jnp.moveaxis(ends, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    carried = jnp.moveaxis(carried, 0, 1)                # [B, nc, G, Hg, P, N]
    y = y + jnp.einsum(
        "bcqgn,bcghpn->bcqghp", cc, carried, precision=_HI
    ) * by_position(jnp.exp(cum))
    y = y + d.reshape(g, hg, 1) * x
    y = y.reshape(bsz, nc * chunk, h, p)[:, :t]
    return y, state.reshape(bsz, h, p, n)


def gated_group_norm(y, z, weight, groups: int, eps: float):
    """``rms_norm`` over each of ``groups`` equal slices of ``y * silu(z)``
    (the gate first, then the norm). ``y``, ``z`` [..., inner]."""
    v = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    vg = v.reshape(*v.shape[:-1], groups, v.shape[-1] // groups)
    vg = vg * jax.lax.rsqrt(jnp.mean(vg * vg, axis=-1, keepdims=True) + eps)
    return vg.reshape(v.shape) * weight.astype(jnp.float32)


def mixer(cfg, u: jax.Array, lp: dict, state: jax.Array, tail: jax.Array,
          lo: Optional[jax.Array] = None, hi: Optional[jax.Array] = None,
          layer: Optional[jax.Array] = None):
    """The mixer branch of one layer on the normed input ``u`` [B, T, D].

    ``lp`` holds the layer's ``ssm_*`` leaves; ``state`` [B, H, P, N] float32
    and ``tail`` [B, K-1, C] are the row's carried state; ``lo``, ``hi`` [B]
    bound each row's real positions inside T (``None``: all real). Returns
    ``(out [B, T, D], new state, new tail)``. With ``layer`` (T = 1 only)
    ``state`` is a cache's whole stack [L, B, H, P, N]: that layer's rows
    advance where they lie, and the stack comes back.
    """
    b, t, _ = u.shape
    h, p, n, g = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    inner, gn = cfg.ssm_inner, cfg.ssm_groups * cfg.ssm_state
    mz, mx, mb, mc, mdt = cfg.ssm_multipliers
    # From the in-projection's accumulator to the out-projection's input the
    # branch stays float32: the convolution, the gate and the scan are
    # elementwise work on [T, 2 inner], and each bf16 rounding spared here
    # is one the carried state does not inherit.
    with scope("ssm.in_proj"):
        proj = qeinsum("btd,dk->btk", u * cfg.ssm_in_multiplier, lp["ssm_in"],
                       preferred_element_type=jnp.float32)
        z, xbc, dt = jnp.split(
            proj, [inner, inner + cfg.ssm_conv_width], axis=-1)
        # mup: one multiplier a segment of the in-projection
        z = z * mz
        xbc = xbc * jnp.concatenate([
            jnp.full((inner,), mx, xbc.dtype), jnp.full((gn,), mb, xbc.dtype),
            jnp.full((gn,), mc, xbc.dtype)])
        dt = jax.nn.softplus(dt * mdt + lp["ssm_dt_bias"].astype(jnp.float32))
        a = -jnp.exp(lp["ssm_a_log"].astype(jnp.float32))
        d = lp["ssm_d"].astype(jnp.float32)
    with scope("ssm.conv"):
        if t == 1:
            live = None if lo is None else jnp.logical_and(lo == 0, hi == 1)
            xbc, tail = conv_step(
                xbc, tail, lp["ssm_conv"], lp["ssm_conv_bias"], live)
            if live is not None:
                dt = jnp.where(live[:, None, None], dt, 0.0)
        else:
            xbc, tail = causal_conv(
                xbc, tail, lp["ssm_conv"], lp["ssm_conv_bias"], lo, hi)
            if lo is not None:
                dt = jnp.where(span_mask(t, lo, hi)[..., None], dt, 0.0)
        xbc = jax.nn.silu(xbc)
        xs, bm, cm = jnp.split(xbc, [inner, inner + gn], axis=-1)
        xs = xs.reshape(b, t, h, p)
        bm, cm = bm.reshape(b, t, g, n), cm.reshape(b, t, g, n)
    if t == 1:
        with scope("ssm.step"):
            step = (xs[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], d)
            if layer is None:
                y, state = ssd_step(*step, state)
            else:  # imported here, as the attention kernels are: a second
                # of every process's start that a pool's thread can overlap
                from llm_consensus_tpu.ops.pallas.ssm_step import (
                    ssd_step_in_place)

                y, state = ssd_step_in_place(state, layer, *step)
            y = y[:, None]
    else:
        with scope("ssm.scan"):
            y, state = ssd_chunked(xs, dt, a, bm, cm, d, state, cfg.ssm_chunk)
    with scope("ssm.norm"):
        y = gated_group_norm(
            y.reshape(b, t, inner), z, lp["ssm_norm"], g, cfg.rms_eps)
    with scope("ssm.out_proj"):
        out = qeinsum("btk,kd->btd", y.astype(u.dtype), lp["ssm_out"])
        return out * cfg.ssm_out_multiplier, state, tail
