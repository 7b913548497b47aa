"""Kimi Delta Attention: a gated delta rule in plain XLA (no kernel yet).

The one part of a layer of kind ``K`` (``ModelConfig.has_kda``), on the
normed input ``u`` [T, D]; ``H`` heads, keys and values ``P`` wide::

    q, k, v = silu(conv(u Wq | u Wk | u Wv))   # depthwise, causal, no bias
    q = q / |q| * P^-1/2;  k = k / |k|         # a head (|x|^2 + 1e-6 under the root)
    g = -exp(A_log[h]) * softplus((u Wf_a) Wf_b + dt_bias)    # [H, P] log decay <= 0, a CHANNEL
    beta = sigmoid(u W_beta) [* 2 under kda_neg_eigval]        # [H]
    S' = diag(exp(g_t)) S_{t-1}                # [P, P] a head, float32, rows are key channels
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T   # the delta rule: correct what the state holds for k_t
    o_t = S_t^T q_t
    out = (rms_norm_head(o_t) * sigmoid((u Wg_a) Wg_b)) @ Wo

Two forms of the rule, the same numbers: ``kda_chunked`` for T > 1 and
``kda_step`` for T = 1. The chunked form over ``C`` positions with the
decay sums ``G_t = g_1 + ... + g_t`` inside a chunk and the carried-in state
``S_0``: every position writes ``k_t u_t^T`` with ``u_t = beta_t (v_t -
S'_t^T k_t)``, and what it reads of the state is what the chunk's earlier
positions wrote, decayed, so that::

    (I + A) U = beta (V - (K e^G) S_0),  A[t, i] = beta_t sum_c k_t[c] k_i[c] e^(G_t[c] - G_i[c]),  i < t
    O = (Q e^G) S_0 + B U,               B[t, i] = sum_c q_t[c] k_i[c] e^(G_t[c] - G_i[c]),          i <= t
    S_C = diag(e^(G_C)) S_0 + (K e^(G_C - G))^T U

``(I + A)^-1`` is a unit lower-triangular inverse a chunk a head, made by
forward substitution (the rule itself on 64 x 64 numbers, which no product of
powers of ``A`` is when keys repeat: row by row inside a sub-chunk's diagonal
block, block row by block row across them), for all chunks at once;
``U = (I + A)^-1 beta V - (I + A)^-1 (beta K e^G) S_0`` leaves one small
recurrence over chunk states.

**No factor here is ever the exponential of a positive number.** A decay of
``e^-1.6`` a position is ``e^-102`` a chunk, and ``e^(G_t - G_i)`` cannot be
split as ``e^(G_t) e^(-G_i)``. ``A`` and ``B`` are made in sub-chunks of 16:
inside one the difference ``G_t - G_i`` is taken a channel, masked to ``i <=
t`` BEFORE the exponential; between two the product splits at the later
sub-chunk's start ``r``, ``e^(G_t - G_r) e^(G_r - G_i)``, both exponents <= 0.
A factor may round to 0 where the true product is smaller still. State,
decay sums and every product are float32 at ``highest`` precision: the chunk
products are a few percent of a layer's operations and the state is carried
through thousands of steps.

A row's state has no sequence axis. Every function takes the span of real
positions ``[lo, hi)`` of each row inside the call's T (``None``: all): a
position outside it has ``beta`` = 0 and ``g`` = 0 (it neither writes nor
decays) and adds nothing to the convolution's tail, as ops/ssm.py treats its
own.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from llm_consensus_tpu.obs.scopes import scope
from llm_consensus_tpu.ops.quant import qeinsum
from llm_consensus_tpu.ops.ssm import causal_conv, conv_step, span_mask

_HI = jax.lax.Precision.HIGHEST
SUB = 16  # positions a sub-chunk: the farthest a decay's reference point lies


def _substitute(a: jax.Array) -> jax.Array:
    """``(I + a)^-1`` for ``a`` [..., n, n] strictly lower triangular, row by
    row: row t is ``e_t - a[t] @ (rows before it)``."""
    n = a.shape[-1]
    eye = jnp.eye(n, dtype=a.dtype)

    def row(t, inv):
        a_t = jax.lax.dynamic_index_in_dim(a, t, a.ndim - 2, keepdims=False)
        new = jax.lax.dynamic_index_in_dim(eye, t, 0, keepdims=False) - jnp.einsum(
            "...i,...ij->...j", a_t, inv, precision=_HI)
        return jax.lax.dynamic_update_index_in_dim(inv, new, t, a.ndim - 2)

    # Rows from t on are still the identity's and a[t, i >= t] is 0.
    return jax.lax.fori_loop(1, n, row, jnp.broadcast_to(eye, a.shape))


def unit_lower_inverse(a: jax.Array) -> jax.Array:
    """``(I + a)^-1`` for ``a`` [..., C, C] strictly lower triangular, by
    forward substitution in blocks of ``SUB``: the diagonal blocks row by row
    (15 steps over a sixteenth of the table each, where 63 over all of it
    moved the whole inverse through memory a step), then block row I is
    ``inv_II (E_I - a[I, before I] @ (block rows before it))``."""
    c = a.shape[-1]
    sub = min(SUB, c)
    ns = c // sub
    blocks = a.reshape(*a.shape[:-2], ns, sub, ns, sub)
    on_diagonal = _substitute(jnp.stack(
        [blocks[..., i, :, i, :] for i in range(ns)], axis=-3))
    rows = [jnp.pad(on_diagonal[..., 0, :, :],
                    [(0, 0)] * (a.ndim - 1) + [(0, c - sub)])]
    for i in range(1, ns):
        before = jnp.concatenate(rows, axis=-2)              # [..., i sub, C]
        own = jnp.pad(jnp.eye(sub, dtype=a.dtype), [(0, 0), (i * sub, c - (i + 1) * sub)])
        rows.append(jnp.matmul(
            on_diagonal[..., i, :, :],
            own - jnp.matmul(a[..., i * sub:(i + 1) * sub, :i * sub], before,
                             precision=_HI),
            precision=_HI))
    return jnp.concatenate(rows, axis=-2)


def decayed_products(x: jax.Array, k: jax.Array, cum: jax.Array,
                     strict: bool) -> jax.Array:
    """``M[t, i] = sum_c x_t[c] k_i[c] exp(cum_t[c] - cum_i[c])`` for ``i <=
    t`` (``i < t`` when ``strict``), 0 elsewhere. ``x``, ``k``, ``cum``
    [..., C, P], ``cum`` the inclusive decay sums (non-increasing in t)."""
    *lead, c, p = x.shape
    sub = min(SUB, c)
    ns = c // sub
    xb, kb, cb = (v.reshape(*lead, ns, sub, p) for v in (x, k, cum))
    # Inside a sub-chunk: the difference a channel, masked before the exp.
    keep = jnp.tril(jnp.ones((sub, sub), bool), -1 if strict else 0)
    decay = jnp.exp(jnp.where(
        keep[..., None], cb[..., :, None, :] - cb[..., None, :, :], -jnp.inf))
    diag = jnp.sum(xb[..., :, None, :] * kb[..., None, :, :] * decay, axis=-1)
    out = jnp.einsum(
        "...itj,iu->...ituj", diag, jnp.eye(ns, dtype=x.dtype)
    ).reshape(*lead, c, c)
    if ns == 1:
        return out
    # Between sub-chunks: split at the later one's start, the decay sum
    # after the last position before it; both exponents are <= 0 (a key at
    # or after the split is masked, its exponent clamped).
    ref = cb[..., :-1, -1, :]                                # [..., ns-1, P]
    x_off = xb[..., 1:, :, :] * jnp.exp(cb[..., 1:, :, :] - ref[..., None, :])
    k_off = k[..., None, :, :] * jnp.exp(jnp.minimum(
        ref[..., None, :] - cum[..., None, :, :], 0.0))      # [..., ns-1, C, P]
    off = jnp.einsum("...itc,...ijc->...itj", x_off, k_off, precision=_HI)
    before = (jnp.arange(c) // sub)[None, :] < jnp.arange(1, ns)[:, None]
    off = jnp.where(before[:, None, :], off, 0.0).reshape(*lead, c - sub, c)
    return out + jnp.pad(off, [(0, 0)] * len(lead) + [(sub, 0), (0, 0)])


def kda_step(q, k, v, g, beta, state):
    """One position of the rule. ``q``, ``k``, ``g`` [B, H, P]; ``v`` [B, H,
    P]; ``beta`` [B, H] (0, with ``g`` 0, on a row that does not advance);
    ``state`` [B, H, P, P] float32, rows the key channels. Returns ``(o [B,
    H, P] float32, new state)``."""
    state = state * jnp.exp(g)[..., None]
    held = jnp.sum(state * k[..., None], axis=-2)            # S'^T k
    state = state + (beta[..., None] * k)[..., None] * (v - held)[..., None, :]
    return jnp.sum(state * q[..., None], axis=-2), state


def kda_chunked(q, k, v, g, beta, state, chunk: int):
    """The rule over T positions in chunks of ``chunk``.

    ``q``, ``k``, ``v``, ``g`` [B, T, H, P] float32 (``q`` and ``k``
    normalised, ``g`` the log decay, <= 0); ``beta`` [B, T, H]; at a
    position that does not advance the state ``beta`` and ``g`` are 0;
    ``state`` [B, H, P, P] float32, carried in. Returns ``(o [B, T, H, P]
    float32, state after position T-1)``. T need not be a multiple of
    ``chunk``: the tail is padded with such positions.
    """
    bsz, t, h, p = q.shape
    if chunk > SUB and chunk % SUB:
        raise ValueError(f"a delta chunk is whole sub-chunks of {SUB}, got {chunk}")
    nc = -(-t // chunk)
    pad = nc * chunk - t

    def chunks(x):  # [B, T, H, ...] -> float32 [B, nc, H, chunk, ...]
        x = x.astype(jnp.float32)
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        return jnp.moveaxis(x.reshape(bsz, nc, chunk, *x.shape[2:]), 2, 3)

    qc, kc, vc, gc, bc = (chunks(x) for x in (q, k, v, g, beta[..., None]))
    cum = jnp.cumsum(gc, axis=-2)                            # decay sums, <= 0
    inv = unit_lower_inverse(decayed_products(kc, kc, cum, strict=True) * bc)
    read = decayed_products(qc, kc, cum, strict=False)       # B above
    mm = lambda a, b: jnp.matmul(a, b, precision=_HI)  # noqa: E731
    grow = jnp.exp(cum)
    u_own = mm(inv, bc * vc)                                 # [B, nc, H, C, P]
    w = mm(inv, bc * kc * grow)                              # what S_0 takes off it
    to_end = jnp.swapaxes(kc * jnp.exp(cum[..., -1:, :] - cum), -1, -2)
    end_decay = grow[..., -1, :, None]                       # [B, nc, H, P, 1]

    def carry(s, per_chunk):
        u_own, w, q_in, read, to_end, end_decay = per_chunk
        u = u_own - mm(w, s)
        return end_decay * s + mm(to_end, u), mm(q_in, s) + mm(read, u)

    state, o = jax.lax.scan(
        carry, state.astype(jnp.float32), tuple(
            jnp.moveaxis(x, 1, 0)
            for x in (u_own, w, qc * grow, read, to_end, end_decay)))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)            # [B, nc, C, H, P]
    return o.reshape(bsz, nc * chunk, h, p)[:, :t], state


def head_norm_gate(o, gate, weight, eps: float):
    """``rms_norm`` over each head of ``o`` [..., H, P] times ``weight`` [P],
    times ``sigmoid(gate)`` [..., H, P]: the norm first, then the gate."""
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return o * weight.astype(jnp.float32) * jax.nn.sigmoid(gate)


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda(cfg, u: jax.Array, lp: dict, state: jax.Array, tail: jax.Array,
        lo: Optional[jax.Array] = None, hi: Optional[jax.Array] = None):
    """The delta-rule part of one layer on the normed input ``u`` [B, T, D].

    ``lp`` holds the layer's ``wq, wk, wv, wo`` and ``kda_*`` leaves;
    ``state`` [B, H, P, P] float32 and ``tail`` [B, K-1, 3 H P] are the
    row's carried state; ``lo``, ``hi`` [B] bound each row's real positions
    inside T (``None``: all real). Returns ``(out [B, T, D], new state, new
    tail)``.
    """
    b, t, _ = u.shape
    h, p, inner = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_inner
    f32 = jnp.float32
    # From the projections' accumulators to the out-projection's input the
    # part stays float32, as ops/ssm.py keeps its mixer.
    with scope("kda.in_proj"):
        qkv = jnp.concatenate([
            qeinsum("btd,dk->btk", u, lp[name], preferred_element_type=f32)
            for name in ("wq", "wk", "wv")], axis=-1)
    with scope("kda.gate"):
        def low_rank(a, b_):
            return qeinsum("btr,rk->btk", qeinsum(
                "btd,dr->btr", u, lp[a], preferred_element_type=f32),
                lp[b_], preferred_element_type=f32)

        g = -jnp.exp(lp["kda_a_log"].astype(f32))[:, None] * jax.nn.softplus(
            low_rank("kda_f_a", "kda_f_b").reshape(b, t, h, p)
            + lp["kda_dt_bias"].astype(f32).reshape(h, p))
        beta = jax.nn.sigmoid(
            qeinsum("btd,dh->bth", u, lp["kda_beta"], preferred_element_type=f32))
        if cfg.kda_neg_eigval:
            beta = beta * 2.0
        out_gate = low_rank("kda_g_a", "kda_g_b").reshape(b, t, h, p)
    with scope("kda.conv"):
        no_bias = jnp.zeros((cfg.kda_conv_width,), f32)
        if t == 1:
            live = None if lo is None else jnp.logical_and(lo == 0, hi == 1)
            qkv, tail = conv_step(qkv, tail, lp["kda_conv"], no_bias, live)
            real = None if live is None else live[:, None]
        else:
            qkv, tail = causal_conv(qkv, tail, lp["kda_conv"], no_bias, lo, hi)
            real = None if lo is None else span_mask(t, lo, hi)
        if real is not None:
            g = jnp.where(real[..., None, None], g, 0.0)
            beta = jnp.where(real[..., None], beta, 0.0)
        q, k, v = (x.reshape(b, t, h, p) for x in jnp.split(
            jax.nn.silu(qkv), [inner, 2 * inner], axis=-1))
        q, k = _unit(q) * p ** -0.5, _unit(k)
    if t == 1:
        with scope("kda.step"):
            o, state = kda_step(
                q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state)
            o = o[:, None]
    else:
        with scope("kda.scan"):
            o, state = kda_chunked(q, k, v, g, beta, state, cfg.kda_chunk)
    with scope("kda.norm"):
        y = head_norm_gate(o, out_gate, lp["kda_norm"], cfg.rms_eps)
    with scope("kda.out_proj"):
        out = qeinsum(
            "btk,kd->btd", y.reshape(b, t, inner).astype(u.dtype), lp["wo"])
        return out, state, tail
