"""Fused GQA decode attention for TPU (Pallas/Mosaic), paged over layers.

The decode hot path (T = 1) on the XLA route costs far more than its
bytes: per layer per step it runs a chain of small ops — dynamic-slice
the cache, build the [B, S] mask, two batched matmuls with a contraction
of ``g`` (the GQA group, often 2), an fp32 softmax — each a separate
kernel with its own launch and VMEM round trip (profiled: ~24 µs/layer
on consensus-1b for ~2 MB of cache reads that should cost ~3 µs). This
kernel fuses the whole thing: one pass over the width-bounded cache
per batch row, online softmax in scratch, one output write.

Design notes, TPU-first:
  * The kernel consumes the **full stacked cache** [L, B, S, Hkv, dh]
    and selects its layer through the BlockSpec index map (the paged-
    attention pattern): the layer index rides the scalar-prefetch
    vector, and every K/V block is DMA'd straight from the stack in
    HBM. Round 2 instead sliced the layer entry out of the stack and
    reshaped it to a collapsed lane layout per layer per step — each a
    materialized copy of the whole width-bounded cache, which profiling
    showed cost ~4-6 ms/step at batch 32 against a ~0.4 ms kernel. A
    block's trailing dims are (Hkv, dh): dh % 128 == 0 keeps lanes
    tiled, and the Hkv sublane dim covers the full array dim, which
    Mosaic accepts for both bf16 and int8 operands.
  * The causal frontier ``pos`` is **data, not shape** (it advances
    every step inside the decode chunk's scan): it arrives via scalar
    prefetch together with ``layer_idx`` and per-row ``row_start``
    offsets, so one compiled kernel serves every layer, every step,
    every slot state, and both the single-stream and continuous-
    batching layouts.
  * Work is bounded by the caller's ``kv_width`` bucket at the *grid*
    level — fewer kv blocks, not a sliced operand — so attention cost
    scales with the causal frontier, never with cache capacity, and no
    bytes are ever copied to enforce the bound.
  * Grid (B/b_block, kv_blocks), kv innermost, with a statically
    unrolled per-head loop INSIDE each iteration whose matmuls are
    BATCHED over up to 8 batch rows: the per-head matmuls are tiny, so
    per-grid-point overhead and small DMAs — not FLOPs — bound the
    kernel. One [b_block, block_k, Hkv, dh] transfer per iteration
    amortizes both across heads AND rows. (b_block, block_k) are chosen
    to maximize bytes per iteration within a VMEM budget that counts
    code blocks, scale blocks, and dequant temporaries.
  * GQA without expansion: kv head h serves its ``g`` query heads as a
    static [g, dh] row slice; both matmuls run bf16 → fp32 accumulation.
  * int8 KV ({"q8": [L, B, S, Hkv, dh] int8, "s": [L, B, Hkv, S]}) is
    consumed directly: HBM streams codes + per-row scales (half the
    bytes) and no dequantized K/V is ever materialized — the per-column
    K scale is constant over the dh contraction so it applies to the
    scores, and the V scale is constant over the column contraction so
    it folds into the probabilities. Scales are stored seq-MINOR so
    their VMEM blocks tile exactly (columns on lanes, matching the
    score layout).

The reference has no analog (its "attention" is on the other side of an
HTTPS call — /root/reference/internal/provider/openai.go:97).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_consensus_tpu.utils import knobs
from llm_consensus_tpu.utils.backend import pallas_interpret

NEG_INF = -1e30
_LANES = 128
_BLOCK_K_CAP = 512
# Conservative share of the 16 MB scoped VMEM limit left to the K/V code
# blocks, their scale blocks and the dequant temporaries (see _fits).
_VMEM_BUDGET = 12 * 1024 * 1024


def _pow2_block(width: int, cap: int) -> int:
    """Largest power-of-two divisor of ``width``, capped at ``cap``."""
    bk = 1
    while bk * 2 <= cap and width % (bk * 2) == 0:
        bk *= 2
    return bk


def _legal_block_ks(width: int, quantized: bool) -> list[int]:
    """Legal kv block lengths for an attention span of ``width``, largest
    first. A block must divide the span exactly (the grid covers it with
    no padding — padding would mean copying the cache), so candidates
    are the power-of-two divisors. The collapsed (block_k, Hkv·dh) view
    the kernel matmuls over needs 8 sublanes; int8 KV additionally puts
    block_k on the LANES of its seq-minor scale block [1, bb, Hkv,
    block_k], which Mosaic tiles in 128s. One block spanning the whole
    width is always a legal shape ("equal to the array dim")."""
    top = _pow2_block(width, _BLOCK_K_CAP)
    floor = _LANES if quantized else 8
    out = []
    bk = top
    while bk >= floor:
        out.append(bk)
        bk //= 2
    if not out and top == width:
        out = [width]
    return out


def _fits(b_block: int, block_k: int, hkv: int, dh: int, kv_item: int,
          quantized: bool) -> bool:
    """VMEM budget check for one grid iteration's blocks.

    Factor 8 = K+V × up-to-quadruple buffering: the Mosaic pipeline was
    measured allocating ~2× the naive double-buffer estimate (a
    4×-factor budget chose blocks that exceeded the 16 MB scoped limit
    by 4% on v5e at batch 8 bf16). Quantized adds the seq-minor scale
    blocks (exact-tiling, tiny) and the per-head int8→bf16 code
    conversions feeding the matmuls."""
    codes = 8 * b_block * block_k * hkv * dh * kv_item
    scales = 8 * b_block * hkv * block_k * 2 if quantized else 0
    temps = 2 * b_block * block_k * dh * 2 if quantized else 0
    return codes + scales + temps <= _VMEM_BUDGET


def _choose_blocks(b: int, width: int, hkv: int, dh: int, kv_item: int,
                   quantized: bool) -> Optional[tuple[int, int]]:
    """(b_block, block_k) maximizing bytes per grid iteration —
    per-iteration overhead (semaphores, DMA issue) dwarfs the tiny
    per-head matmuls — among legal block shapes that fit VMEM; None when
    no legal shape fits (the caller's predicate routes to XLA)."""
    best = None
    for cand_b in (8, 4, 2, 1):
        if b % cand_b:
            continue
        for cand_k in _legal_block_ks(width, quantized):
            if _fits(cand_b, cand_k, hkv, dh, kv_item, quantized):
                if best is None or cand_b * cand_k > best[0] * best[1]:
                    best = (cand_b, cand_k)
                break
    return best


def decode_flash_supported(
    n_heads: int, n_kv_heads: int, dh: int, width: Optional[int] = None,
    quantized: bool = False,
) -> bool:
    """True when the kernel can be built for these shapes — the SAME
    block chooser ``decode_attention`` runs, so a ``True`` here never
    turns into a Mosaic rejection at dispatch.

    The K/V blocks are (1, b_block, block_k, Hkv, dh) over the stacked
    [L, B, S, Hkv, dh] cache: the lane dim needs dh % 128 == 0 and the
    Hkv sublane dim covers its full array dim (accepted for bf16 and
    int8). ``width`` (the attention span the grid will cover — cache
    capacity or the caller's bucket) must factor into a legal kv block
    that fits VMEM at one batch row per iteration (a one-row block
    divides every batch). bf16 or int8 storage is assumed.
    """
    if n_heads % n_kv_heads or dh % _LANES:
        return False
    if width is None:
        return True
    return _choose_blocks(
        1, width, n_kv_heads, dh, 1 if quantized else 2, quantized
    ) is not None


def _kernel(
    scalars_ref,  # [2 + B] i32 SMEM: [pos, layer, row_start_0, ...]
    q_ref,   # [bb, 1, Hq, dh]; qstruct: [bb, Hq, Hkv·dh] pre-structured
    k_ref,   # [1, bb, block_k, Hkv, dh] — this layer's block, bb rows
    v_ref,   # [1, bb, block_k, Hkv, dh]
    *refs,   # quantized: (ks_ref [1, bb, Hkv, block_k], vs_ref) then outputs
    scale: float,
    block_k: int,
    n_kv_blocks: int,
    n_kv_heads: int,
    group: int,
    dh: int,
    b_block: int,
    sliding_window: Optional[int],
    logit_softcap: Optional[float],
    quantized: bool,
    qstruct: bool,
    w8a8: bool,
    return_state: bool,
):
    qs_ref = None
    refs = list(refs)
    if quantized and w8a8:
        ks_ref, vs_ref, qs_ref = refs[:3]
        refs = refs[3:]
    elif quantized:
        ks_ref, vs_ref = refs[:2]
        refs = refs[2:]
    else:
        ks_ref = vs_ref = None
    if return_state:
        # Extra outputs: the online-softmax running max and denominator,
        # so a caller can MERGE this result with attention over another
        # KV source (the shared-prefix decode path) — the standard
        # two-source combine: o = Σ w_i·o_i / Σ w_i, w_i = l_i·exp(m_i−m).
        o_ref, ms_ref, ls_ref, m_ref, l_ref, acc_ref = refs
    else:
        ms_ref = ls_ref = None
        o_ref, m_ref, l_ref, acc_ref = refs
    bb = pl.program_id(0)  # batch-row block
    j = pl.program_id(1)   # kv block (innermost)
    pos = scalars_ref[0]
    # Per-row frontiers for this batch block (SMEM scalar reads,
    # statically unrolled). Mosaic cannot reshape a tiny vector of
    # scalars into a 3-D broadcastable form, so row-start TENSORS are
    # built where needed with unrolled scalar selects over an axis-0
    # iota (see _row_start_like) — b_block is at most 8, so that is a
    # handful of cheap vector selects.
    rs_rows = [
        scalars_ref[2 + bb * b_block + i] for i in range(b_block)
    ]
    rs_min = rs_rows[0]
    for r in rs_rows[1:]:
        rs_min = jnp.minimum(rs_min, r)

    def _row_start_like(shape):
        """row_start broadcast to ``shape`` (axis 0 = batch row)."""
        if b_block == 1:
            return jnp.full(shape, rs_rows[0], jnp.int32)
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        out = jnp.full(shape, rs_rows[0], jnp.int32)
        for i in range(1, b_block):
            out = jnp.where(row == i, rs_rows[i], out)
        return out

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    k_start = j * block_k
    live = k_start <= pos  # any valid column in this block?
    if sliding_window is not None:
        live = jnp.logical_and(live, k_start + block_k > pos - sliding_window + 1)
    # Live if ANY row in the block still needs these columns.
    live = jnp.logical_and(live, k_start + block_k > rs_min)

    def expand_scales(ref):
        """[1, bb, Hkv, bk] scale block → [bb, Hq, bk] f32: each kv
        head's row repeated over its group of query rows (shared by
        K and V so the head ordering cannot diverge)."""
        return jnp.concatenate(
            [
                ref[0][:, h : h + 1, :]
                for h in range(n_kv_heads)
                for _ in range(group)
            ],
            axis=1,
        ).astype(jnp.float32)

    def _qstruct_tail(s, vv, dtype):
        """Shared tail of both dense-GQA forms: softcap → column mask →
        online softmax → V-scale fold (quantized) → pv matmul →
        own-head extraction → scratch update. ONE copy of the
        numerically delicate logic, whatever produced the raw scaled
        scores ``s`` [bb, Hq, block_k]."""
        hq = n_kv_heads * group
        if logit_softcap is not None:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        sshape = (b_block, 1, block_k)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, sshape, 2)
        smask = jnp.logical_and(
            cols <= pos, cols >= _row_start_like(sshape)
        )
        if sliding_window is not None:
            smask = jnp.logical_and(cols > pos - sliding_window, smask)
        s = jnp.where(smask, s, NEG_INF)
        m_prev = m_ref[:, :, :1]                       # [bb, Hq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2)[..., None])
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_ref[:, :, :1] + jnp.sum(p, axis=2)[..., None]
        if quantized:
            vs_full = expand_scales(vs_ref)
            # Garbage slots past a frontier can hold NaN/Inf scales;
            # where() (a select, not a multiply) keeps them out.
            p = p * jnp.where(smask, vs_full, jnp.zeros_like(vs_full))
        t = jax.lax.dot_general(
            p.astype(dtype), vv.astype(dtype) if quantized else vv,
            (((2,), (1,)), ((0,), (0,))),  # [bb, Hq, Hkv·dh]
            preferred_element_type=jnp.float32,
        )
        # Own-head extraction: query head i reads its kv head's lane
        # slice (static slices, concatenated back to [bb, Hq, dh]).
        pv = jnp.concatenate(
            [
                t[:, i : i + 1, (i // group) * dh : (i // group + 1) * dh]
                for i in range(hq)
            ],
            axis=1,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, (b_block, hq, _LANES))
        l_ref[...] = jnp.broadcast_to(l_new, (b_block, hq, _LANES))

    def _qstruct_w8a8_block():
        """qstruct with int8×int8 MXU scores (opt-in, LLMC_DECODE_W8A8):
        q arrives pre-quantized (per-row symmetric int8, scale operand)
        and the int8 cache CODES feed the score matmul directly at the
        MXU's double int8 rate; the per-row q scale × per-column K scale
        fold into the f32 score scaling, so no K-code → bf16 convert
        exists at all. The pv matmul stays bf16 (quantizing
        probabilities would stack a second error term for little gain).
        Accuracy: adds q's int8 rounding (~0.5% relative on scores) on
        top of the int8-KV error every path already carries — the same
        class of tradeoff as int8 weights, and why this is opt-in
        rather than the default."""
        kk = k_ref[0].reshape(b_block, block_k, n_kv_heads * dh)
        vv = v_ref[0].reshape(b_block, block_k, n_kv_heads * dh)
        s = jax.lax.dot_general(
            q_ref[...], kk,
            (((2,), (2,)), ((0,), (0,))),  # int8 × int8 → [bb, Hq, bk] i32
            preferred_element_type=jnp.int32,
        ).astype(jnp.float32)
        s = s * qs_ref[:, :, :1]  # per-row q dequant scale
        s = s * expand_scales(ks_ref)
        _qstruct_tail(s * scale, vv, jnp.bfloat16)

    def _qstruct_block():
        """Dense-GQA form: ONE score matmul and ONE pv matmul per
        iteration over the head-collapsed [bb, block_k, Hkv·dh] blocks.

        The per-head form runs 2·Hkv tiny matmuls per iteration with
        M = group (2-4): MXU pipeline fill dominates and per-row cost
        stops scaling with bytes (~7.5 µs/row/layer at batch 128 against
        a ~2.6 µs bytes bound). Collapsing heads makes M = Hq and the
        contraction Hkv·dh: the zero-padded q rows spend ~Hkv× redundant
        FLOPs, which the otherwise-idle MXU absorbs, and the fill is
        paid twice per iteration instead of 2·Hkv times. Scales, masks,
        and the online softmax run over all heads at once (full sublane
        occupancy instead of group-of-2 rows).
        """
        kk = k_ref[0].reshape(b_block, block_k, n_kv_heads * dh)
        vv = v_ref[0].reshape(b_block, block_k, n_kv_heads * dh)
        dtype = q_ref.dtype
        if not quantized:
            # Zero invalid V rows: garbage (NaN/Inf) cache slots past a
            # frontier would otherwise ride 0·NaN = NaN through the pv
            # contraction. (int8 codes cannot be NaN; scale select in
            # the tail covers scales.)
            nshape = (b_block, block_k, 1)
            ncols = k_start + jax.lax.broadcasted_iota(jnp.int32, nshape, 1)
            nvalid = jnp.logical_and(
                ncols <= pos, ncols >= _row_start_like(nshape)
            )
            vv = jnp.where(nvalid, vv, jnp.zeros_like(vv))
        # q_ref here is the PRE-STRUCTURED [bb, Hq, Hkv·dh] operand (each
        # query head's dh values sit in its kv head's lane slice, zeros
        # elsewhere) built once per step outside the kernel.
        s = jax.lax.dot_general(
            q_ref[...], kk.astype(dtype) if quantized else kk,
            (((2,), (2,)), ((0,), (0,))),  # [bb, Hq, block_k]
            preferred_element_type=jnp.float32,
        )
        if quantized:
            # Per-column K scale (cheap VPU multiply on f32 scores;
            # columns ride lanes in both operands).
            s = s * expand_scales(ks_ref)
        _qstruct_tail(s * scale, vv, dtype)

    def _per_head_block():
        kk = k_ref[0]  # [bb, block_k, Hkv, dh] (int8 when quantized)
        vv = v_ref[0]
        dtype = q_ref.dtype
        # The score mask is head-independent — build it ONCE per kv
        # block (per-batch VPU mask work scales with B×bucket; rebuilding
        # it n_kv_heads times would multiply it). Column validity rides
        # the same [bb, ·, block_k] lane layout the scales use.
        sshape = (b_block, group, block_k)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, sshape, 2)
        smask = jnp.logical_and(
            cols <= pos, cols >= _row_start_like(sshape)
        )
        if sliding_window is not None:
            smask = jnp.logical_and(cols > pos - sliding_window, smask)
        if not quantized:
            # Masked columns score exp(NEG_INF - m) = 0, but 0 * NaN =
            # NaN in the p @ v contraction — zero invalid v rows so
            # garbage (stale or poisoned) cache slots past the frontier
            # can never leak through. (Quantized: int8 codes cannot be
            # NaN; the p·scale zeroing below covers scales.)
            nshape = (b_block, block_k, 1, 1)
            ncols = k_start + jax.lax.broadcasted_iota(jnp.int32, nshape, 1)
            nvalid = jnp.logical_and(
                ncols <= pos, ncols >= _row_start_like(nshape)
            )
            vv = jnp.where(nvalid, vv, jnp.zeros_like(vv))
        # Unrolled per-head loop over STATIC head slices of the shared
        # block (one big DMA serves every head); each head's matmuls are
        # BATCHED over the bb rows, so grid iterations — and their
        # per-iteration overhead — scale with B / b_block, not B.
        for h in range(n_kv_heads):
            q = q_ref[:, 0, h * group:(h + 1) * group, :]   # [bb, g, dh]
            k = kk[:, :, h, :]                               # [bb, block_k, dh]
            v = vv[:, :, h, :]
            s = jax.lax.dot_general(
                q, k.astype(dtype) if quantized else k,
                (((2,), (2,)), ((0,), (0,))),  # [bb, g, block_k]
                preferred_element_type=jnp.float32,
            )
            if quantized:
                # int8 KV without any in-VMEM dequantized K/V: the
                # per-column K scale is constant over the dh contraction,
                # so it applies to the SCORES; the V scale is constant
                # over the column contraction, so it folds into p below.
                # Seq-minor scale blocks put columns on lanes — exactly
                # the layout the score rows already have.
                s = s * ks_ref[0, :, h, :][:, None, :].astype(jnp.float32)
            s = s * scale
            if logit_softcap is not None:
                s = logit_softcap * jnp.tanh(s / logit_softcap)
            s = jnp.where(smask, s, NEG_INF)

            rows = slice(h * group, (h + 1) * group)
            m_prev = m_ref[:, rows, :1]                      # [bb, g, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=2)[..., None])
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_ref[:, rows, :1] + jnp.sum(p, axis=2)[..., None]
            if quantized:
                # Garbage slots past a frontier can hold NaN/Inf scales;
                # where() (a select, not a multiply) guarantees they
                # cannot leak through even as NaN·0.
                vsc = jnp.where(
                    smask[:, :1, :],
                    vs_ref[0, :, h, :][:, None, :].astype(jnp.float32),
                    jnp.zeros((b_block, 1, block_k), jnp.float32),
                )
                p = p * vsc
            pv = jax.lax.dot_general(
                p.astype(dtype), v.astype(dtype) if quantized else v,
                (((2,), (1,)), ((0,), (0,))),                # [bb, g, dh]
                preferred_element_type=jnp.float32,
            )
            acc_ref[:, rows, :] = acc_ref[:, rows, :] * alpha + pv
            m_ref[:, rows, :] = jnp.broadcast_to(
                m_new, (b_block, group, _LANES)
            )
            l_ref[:, rows, :] = jnp.broadcast_to(
                l_new, (b_block, group, _LANES)
            )

    @pl.when(live)
    def _block():
        if qstruct and w8a8:
            _qstruct_w8a8_block()
        elif qstruct:
            _qstruct_block()
        else:
            _per_head_block()

    @pl.when(j == n_kv_blocks - 1)
    def _finish():
        l = l_ref[:, :, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        out = (acc_ref[...] / l).astype(o_ref.dtype)
        if qstruct:
            o_ref[...] = out
        else:
            o_ref[:, 0, :, :] = out
        if return_state:
            ms_ref[...] = m_ref[...]
            ls_ref[...] = l_ref[...]


def decode_attention(
    q: jax.Array,   # [B, 1, Hq, dh]
    k,              # [L, B, S, Hkv, dh] stack, or int8 dict {"q8", "s"}
    v,              # same form as k — the FULL layer-stacked cache
    pos: jax.Array,  # scalar i32: last valid cache slot (the current write)
    layer_idx: jax.Array | int = 0,  # scalar i32: layer to attend within
    row_start: Optional[jax.Array] = None,  # [B] i32 first valid slot per row
    *,
    scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    kv_width: Optional[int] = None,  # static attention span bound (≥ pos+1)
    interpret: Optional[bool] = None,
    return_state: bool = False,
):
    """Single-step GQA attention over one layer of the cache → [B, 1, Hq, dh].

    Row ``b`` attends slots ``row_start[b] <= p <= pos`` of layer
    ``layer_idx`` (windowed when ``sliding_window``); semantics match the
    XLA mask path for T = 1. ``k``/``v`` are the full stacked cache (or
    its int8 dict form): the CODE stacks' layer is selected by the
    BlockSpec index map, so the multi-GB codes are never sliced,
    reshaped, or dequantized outside VMEM. The small int8 SCALE stacks
    are the one exception — they are sliced to the layer host-graph-side
    (see the comment at the slice) because passing the full stacks made
    XLA stage them into the custom call's operand space each call.
    ``kv_width`` bounds the kv grid — attention work scales with the
    caller's frontier bucket, not cache capacity.

    ``return_state=True`` additionally returns the online-softmax state
    ``(m, l)`` as fp32 [B, Hq] (running max of scaled scores; softmax
    denominator at that max), so the caller can merge this output with
    attention over a second KV source — the shared-prefix decode path
    (ops/attention.py merge_attention_states).
    """
    quantized = isinstance(k, dict)
    if quantized:
        kq, ks = k["q8"], k["s"]
        vq, vs = v["q8"], v["s"]
        # Slice THIS layer's scales down to [1, B, Hkv, S] before the
        # call. The full [L, B, Hkv, S] stacks are small enough that XLA
        # stages them into the custom call's operand memory space — at
        # 8B serving shapes (32×128×8×768 bf16 = 50 MB) that staging
        # copy ran once per layer-step and was the single largest
        # non-matmul term in the decode step (profiled: 3.96 ms/step of
        # pure copy at B=128, ~18% of the step). The layer slice is
        # 1.6 MB. The multi-GB CODE stacks are unaffected — they stream
        # from HBM block-by-block via the index map, never staged.
        ks = jax.lax.dynamic_index_in_dim(ks, layer_idx, 0, keepdims=True)
        vs = jax.lax.dynamic_index_in_dim(vs, layer_idx, 0, keepdims=True)
    else:
        kq, vq = k, v
    b, t, hq, dh = q.shape
    n_layers, _, s_dim, hkv, _ = kq.shape
    if t != 1:
        raise ValueError(f"decode kernel is T=1 only, got T={t}")
    if hq % hkv:
        raise ValueError(f"n_heads {hq} not a multiple of n_kv_heads {hkv}")
    group = hq // hkv
    scale = dh**-0.5 if scale is None else scale
    if interpret is None:
        interpret = pallas_interpret()

    w = s_dim if kv_width is None else min(kv_width, s_dim)
    kv_item = kq.dtype.itemsize
    # forward() only dispatches here when decode_flash_supported — the
    # same chooser — found a legal block. Direct callers at other spans
    # (the interpret-mode parity tests at ragged widths) get the
    # smallest dividing block, which only the interpreter accepts.
    b_block, block_k = _choose_blocks(
        b, w, hkv, dh, kv_item, quantized
    ) or (1, _pow2_block(w, 8))
    forced = knobs.get_str("LLMC_DECODE_BLOCKS")
    if forced:
        # Tuning override "bbxbk" (e.g. "2x512"): bypasses the chooser so
        # block-shape sweeps on real hardware need no code edits. Any
        # malformed, non-dividing or Mosaic-illegal value is ignored (a
        # tuning knob must never take down the decode hot path).
        try:
            fb, _, fk = forced.partition("x")
            fb, fk = int(fb), int(fk)
        except ValueError:
            fb = fk = 0
        if fb > 0 and b % fb == 0 and fk in _legal_block_ks(w, quantized):
            b_block, block_k = fb, fk
    n_kv_blocks = w // block_k
    n_b_blocks = b // b_block

    if row_start is None:
        row_start = jnp.zeros((b,), jnp.int32)
    scalars = jnp.concatenate([
        jnp.asarray(pos, jnp.int32).reshape(1),
        jnp.asarray(layer_idx, jnp.int32).reshape(1),
        row_start.astype(jnp.int32),
    ])

    # Dense-GQA ("qstruct") form for small GQA groups: the per-head form's
    # 2·Hkv tiny matmuls (M = group) are MXU-fill-bound at serving batch
    # sizes; collapsing heads into one matmul pair per iteration trades
    # ~Hkv× redundant FLOPs (zero-padded q rows) for ~Hkv× fewer pipeline
    # fills. LLMC_DECODE_QSTRUCT=0 forces the per-head form.
    qstruct = (
        2 <= group <= 4
        and knobs.get_bool("LLMC_DECODE_QSTRUCT")
    )
    # Opt-in int8×int8 MXU scores (see _qstruct_w8a8_block): q quantizes
    # once per step; the score matmul consumes the int8 cache CODES with
    # no bf16 conversion at double MXU rate. Off by default — it adds
    # q-rounding error on top of int8-KV's, the same accuracy class as
    # int8 weights but a new knob, so deployments choose it explicitly.
    w8a8 = (
        qstruct
        and quantized
        and knobs.get_bool("LLMC_DECODE_W8A8")
    )

    kernel = functools.partial(
        _kernel,
        scale=scale,
        block_k=block_k,
        n_kv_blocks=n_kv_blocks,
        n_kv_heads=hkv,
        group=group,
        dh=dh,
        b_block=b_block,
        sliding_window=sliding_window,
        logit_softcap=logit_softcap,
        quantized=quantized,
        qstruct=qstruct,
        w8a8=w8a8,
        return_state=return_state,
    )
    # K/V blocks select (layer from the prefetched scalars, batch block,
    # kv block, ALL heads): one [b_block, block_k, Hkv, dh] transfer per
    # iteration serves every head and up to 8 batch rows — straight from
    # the stacked cache, no per-layer materialization.
    kv_spec = pl.BlockSpec(
        (1, b_block, block_k, hkv, dh),
        lambda b_, j, s_: (s_[1], b_, j, 0, 0),
    )
    q_scale_op = None
    if qstruct:
        # Pre-structure q: head i's dh values land in kv head i//g's lane
        # slice of a [B, Hq, Hkv·dh] operand (zeros elsewhere), so the
        # in-kernel score matmul contracts the full collapsed lane dim.
        eye = jnp.eye(hkv, dtype=q.dtype)
        # [b, h, g, e, d] = q[b, h, g, d] · eye[h, e]; rows (h, g) → Hq,
        # lanes (e, d) → Hkv·dh, nonzero only where e == h.
        q_op = jnp.einsum(
            "bhgd,he->bhged", q[:, 0].reshape(b, hkv, group, dh), eye
        ).reshape(b, hq, hkv * dh)
        if w8a8:
            # Per-row symmetric int8: one quantization per step (q is
            # grid-invariant), amortized over every kv block. Shares the
            # one row-quantizer convention (ops/quant.quantize_rows_sym).
            from llm_consensus_tpu.ops.quant import quantize_rows_sym

            q_op, q_scale_op = quantize_rows_sym(q_op)
        q_spec = pl.BlockSpec(
            (b_block, hq, hkv * dh), lambda b_, j, s_: (b_, 0, 0)
        )
    else:
        q_op = q
        q_spec = pl.BlockSpec(
            (b_block, 1, hq, dh), lambda b_, j, s_: (b_, 0, 0, 0)
        )
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [scalars, q_op, kq, vq]
    if quantized:
        # Seq-minor scale stacks [L, B, Hkv, S]: the block's lane dim is
        # the kv span, so scale tiles are exact (a [..., Hkv, 1] layout
        # pads its lanes 128× in VMEM — measured blowing the scoped
        # limit), and in-kernel the per-column scales line up with the
        # score rows' lanes with no transpose.
        # Layer dim is pre-sliced above, so the scale index map pins it
        # to 0 (codes still page their layer via s_[1]).
        scale_spec = pl.BlockSpec(
            (1, b_block, hkv, block_k),
            lambda b_, j, s_: (0, b_, 0, j),
        )
        in_specs += [scale_spec, scale_spec]
        operands += [ks, vs]
        if w8a8:
            in_specs.append(
                pl.BlockSpec((b_block, hq, 1), lambda b_, j, s_: (b_, 0, 0))
            )
            operands.append(q_scale_op)
    # Bytes per call: one layer's width-bounded K/V stream (+ scales).
    kv_bytes = 2 * b * w * hkv * dh * kv_item
    if quantized:
        kv_bytes += 2 * b * w * hkv * ks.dtype.itemsize
    if qstruct:
        out_spec = pl.BlockSpec(
            (b_block, hq, dh), lambda b_, j, s_: (b_, 0, 0),
        )
        out_shape = jax.ShapeDtypeStruct((b, hq, dh), q.dtype)
    else:
        out_spec = pl.BlockSpec(
            (b_block, 1, hq, dh), lambda b_, j, s_: (b_, 0, 0, 0),
        )
        out_shape = jax.ShapeDtypeStruct((b, 1, hq, dh), q.dtype)
    out_specs, out_shapes = [out_spec], [out_shape]
    if return_state:
        # State rides out lane-tiled [B, Hq, 128] (the scratch layout);
        # column 0 carries the value — sliced to [B, Hq] after the call.
        state_spec = pl.BlockSpec(
            (b_block, hq, _LANES), lambda b_, j, s_: (b_, 0, 0),
        )
        state_shape = jax.ShapeDtypeStruct((b, hq, _LANES), jnp.float32)
        out_specs += [state_spec, state_spec]
        out_shapes += [state_shape, state_shape]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_b_blocks, n_kv_blocks),
            in_specs=in_specs,
            out_specs=out_specs if return_state else out_spec,
            scratch_shapes=[
                pltpu.VMEM((b_block, hq, _LANES), jnp.float32),
                pltpu.VMEM((b_block, hq, _LANES), jnp.float32),
                pltpu.VMEM((b_block, hq, dh), jnp.float32),
            ],
        ),
        out_shape=out_shapes if return_state else out_shape,
        cost_estimate=pl.CostEstimate(
            flops=4 * b * hq * w * dh,
            bytes_accessed=kv_bytes + 2 * q.size * q.dtype.itemsize,
            transcendentals=b * hq * w,
        ),
        # Batch-row blocks are independent (each writes its own output
        # block); declaring the grid's batch dim parallel lets Mosaic
        # overlap one iteration's K/V DMAs with its neighbor's compute
        # instead of serializing the whole sweep on DMA latency.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
    if return_state:
        out, m_out, l_out = out
        out = out[:, None] if qstruct else out
        return out, m_out[:, :, 0], l_out[:, :, 0]
    return out[:, None] if qstruct else out
