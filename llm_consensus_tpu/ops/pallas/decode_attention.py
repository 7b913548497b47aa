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
  * **The sweep follows live rows and valid slots, not pool rows ×
    bucket width** (``_sweep_plan``). A pool row without a stream
    carries a ``row_start`` past the frontier (engine/batcher.py
    DEAD_ROW): data like ``pos``, so one program serves every
    occupancy. Every K, V and scale index map clamps its kv block into
    the blocks that hold a row block's ``[row_start, pos]`` (and the
    sliding window), and a row block with no valid slot is given the
    block index the step beside it already holds; the pipeline does not
    fetch an index twice, so dead rows and the blocks before a row's
    start cost a grid step each (~0.35 us) and neither bytes nor
    compute. Measured on a v5e at the 3B's shape (6 rows, 16 / 2 heads,
    1,920 slots, one live row): 74.7 → 19.0 us a layer call.
  * Grid (B/b_block, kv_blocks), kv innermost, with a statically
    unrolled per-head loop INSIDE each iteration whose matmuls are
    BATCHED over the block's batch rows: the per-head matmuls are tiny,
    so per-grid-point overhead and small DMAs — not FLOPs — bound the
    kernel. One [b_block, block_k, Hkv, dh] transfer per iteration
    amortizes both across heads AND rows. ``_choose_blocks`` takes the
    longest block of ONE row that fits VMEM and ``_ITER_BYTES`` (any
    128-multiple divisor of the bucket up to ``_ITER_SLOTS``: 1,920
    slots are three blocks of a 2-KV-head model, not fifteen), and
    groups rows only while an iteration still moves less than that.
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
# Most cache slots (rows × block_k) one grid iteration covers. The body is
# unrolled over its block, and Mosaic's compile time grows with it (a
# 1,920-slot block of a 2-KV-head model compiles in 4.2 s where a
# 640-slot one takes 0.9 and runs as fast: every new bucket a pool's
# frontier reaches is one more such compile in somebody's warm-up); wider
# spans are cut at their largest 128-multiple divisor below this.
_ITER_SLOTS = 1024
# Conservative share of the 16 MB scoped VMEM limit left to the K/V code
# blocks, their scale blocks and the dequant temporaries (see _fits).
_VMEM_BUDGET = 12 * 1024 * 1024
# K + V bytes one grid iteration should move before rows are grouped into
# one block: a grid step costs a fixed ~0.35 us whatever it does, so a
# block of a few hundred KB (a short bucket of a few-head model) is
# grouped over rows until an iteration moves about this much, and a
# block that already does stays ONE row, so that a row without a stream
# is skipped alone (see _sweep_plan).
_ITER_BYTES = 2 * 1024 * 1024


def _pow2_block(width: int, cap: int) -> int:
    """Largest power-of-two divisor of ``width``, capped at ``cap``."""
    bk = 1
    while bk * 2 <= cap and width % (bk * 2) == 0:
        bk *= 2
    return bk


def _legal_block_ks(width: int, quantized: bool) -> list[int]:
    """Legal kv block lengths for an attention span of ``width``, largest
    first. A block must divide the span exactly (the grid covers it with
    no padding — padding would mean copying the cache). Candidates are
    every divisor that is a multiple of 128 slots — block_k rides the
    LANES of the score tiles, and of int8 KV's seq-minor scale block
    [1, bb, Hkv, block_k], which Mosaic tiles in 128s — so a bucket of
    the 128-slot ladder is not cut into its smallest pieces (1,920 →
    640 / 384 / 128, where the power-of-two rule alone gave 128);
    and, for spans that are no multiple of 128, the power-of-two divisors
    down to the 8 sublanes the collapsed (block_k, Hkv·dh) view needs
    (bf16 KV only). One block spanning the whole width is always a legal
    shape ("equal to the array dim")."""
    out = [
        width // n for n in range(1, width // _LANES + 1)
        if width % n == 0 and (width // n) % _LANES == 0
        and width // n <= _ITER_SLOTS
    ]
    top = _pow2_block(width, _ITER_SLOTS)
    if not quantized:
        bk = min(top, _LANES // 2)
        while bk >= 8:
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
    """(b_block, block_k): ONE batch row of the longest legal kv block
    that fits VMEM and moves no more than ``_ITER_BYTES``, then as many
    rows a block as stay within both and within ``_ITER_SLOTS``.
    Per-iteration overhead (semaphores, DMA issue) dwarfs the tiny
    per-head matmuls, so an iteration should move enough bytes; but a
    call's first block is fetched with nothing to hide behind, and rows
    are skipped block by block (_sweep_plan), so a block is no longer
    and holds no more rows than that takes. None when no legal shape
    fits (the caller's predicate routes to XLA)."""
    ks = [
        k for k in _legal_block_ks(width, quantized)
        if _fits(1, k, hkv, dh, kv_item, quantized)
    ]
    if not ks:
        return None
    row_bytes = 2 * hkv * dh * kv_item  # K + V, one slot of one row
    block_k = next((k for k in ks if k * row_bytes <= _ITER_BYTES), ks[-1])
    for cand_b in (8, 4, 2):
        if (
            b % cand_b == 0
            and cand_b * block_k <= _ITER_SLOTS
            and cand_b * block_k * row_bytes <= _ITER_BYTES
            and _fits(cand_b, block_k, hkv, dh, kv_item, quantized)
        ):
            return cand_b, block_k
    return 1, block_k


def _blocks(b: int, width: int, hkv: int, dh: int, kv_item: int,
            quantized: bool) -> tuple[int, int]:
    """The (b_block, block_k) a call at these shapes runs with: the
    chooser's, or the ``LLMC_DECODE_BLOCKS`` sweep override."""
    # forward() only dispatches here when decode_flash_supported — the
    # same chooser — found a legal block. Direct callers at other spans
    # (the interpret-mode parity tests at ragged widths) get the
    # smallest dividing block, which only the interpreter accepts.
    b_block, block_k = _choose_blocks(
        b, width, hkv, dh, kv_item, quantized
    ) or (1, _pow2_block(width, 8))
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
        if fb > 0 and b % fb == 0 and fk in _legal_block_ks(width, quantized):
            b_block, block_k = fb, fk
    return b_block, block_k


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


def _sweep_plan(pos, row_start, b_block: int, block_k: int,
                n_kv_blocks: int, sliding_window: Optional[int]):
    """What the grid sweeps, per batch-row block: ``(src, lo, hi)``, three
    [B / b_block] i32 vectors that ride the scalar-prefetch vector behind
    ``row_start``.

    A row's valid slots are ``[max(row_start, pos − window + 1), pos]``,
    and a row whose ``row_start`` lies past ``pos`` has none: that is how
    the scheduler marks a pool row without a stream (it is data, like
    ``pos``: no program per occupancy). A row block is LIVE when any of
    its rows has a valid slot; ``lo`` and ``hi`` are then the first and
    last kv block that hold one, its grid steps fetch block
    ``clip(j, lo, hi)`` of its own rows (``src`` is the block itself) and
    compute for ``lo <= j <= hi``. The pipeline does not fetch a block
    index it already holds, so the steps before ``lo`` and after ``hi``
    cost no bytes. A DEAD row block is given the block index its live
    neighbour's sweep ends on (the nearest live block before it: ``src``
    that block, ``lo = hi =`` its last kv block) or, before the first
    live block, the one that block's sweep starts on: the same index as
    the step beside it, so a dead row is neither fetched nor computed.
    With no live row at all every step names one block.
    """
    pos = jnp.asarray(pos, jnp.int32)
    n_b = row_start.shape[0] // b_block
    rs_min = jnp.min(row_start.reshape(n_b, b_block), axis=1)
    live = rs_min <= pos
    first = jnp.maximum(rs_min, 0)
    if sliding_window is not None:
        first = jnp.maximum(first, pos - sliding_window + 1)
    hi = jnp.clip(pos // block_k, 0, n_kv_blocks - 1)
    lo = jnp.minimum(first // block_k, hi)
    idx = jnp.arange(n_b, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(live, idx, -1))  # nearest live <= b
    ahead = jnp.argmax(live).astype(jnp.int32)         # first live (or 0)
    src = jnp.where(before >= 0, before, ahead)
    at = jnp.where(before >= 0, hi, lo[ahead])  # a dead block's one index
    return (
        src,
        jnp.where(live, lo, at),
        jnp.where(live, jnp.broadcast_to(hi, (n_b,)), at),
    )


def _plan_entry(scalars_ref, n_rows, n_b_blocks, b_):
    """Row block ``b_``'s ``(src, lo, hi)`` out of the prefetched scalars
    ``[pos, layer, row_start × B, src × n_b, lo × n_b, hi × n_b]``."""
    base = 2 + n_rows
    return (
        scalars_ref[base + b_],
        scalars_ref[base + n_b_blocks + b_],
        scalars_ref[base + 2 * n_b_blocks + b_],
    )


def decode_sweep_plan(pos, row_start, *, width: int, n_kv_heads: int,
                      dh: int, kv_item: int, quantized: bool,
                      sliding_window: Optional[int]) -> jax.Array:
    """The sweep plan (_sweep_plan) of one decode step as the i32 vector
    ``decode_attention(sweep=...)`` takes: the same for every layer of the
    step, so a model computes it once beside its layer loop. ``width``,
    ``n_kv_heads`` (a shard's, under tensor parallelism), ``kv_item`` (the
    cache's bytes an element) and ``quantized`` are those of the calls it
    is for: they pick the blocks the plan counts in."""
    row_start = row_start.astype(jnp.int32)
    b_block, block_k = _blocks(
        row_start.shape[0], width, n_kv_heads, dh, kv_item, quantized
    )
    return jnp.concatenate(_sweep_plan(
        pos, row_start, b_block, block_k, width // block_k, sliding_window
    ))


def _kernel(
    scalars_ref,  # i32 SMEM: [pos, layer, row_start × B, sweep plan]
    q_ref,   # [bb, 1, Hq, dh]; qstruct: [bb, Hq, Hkv·dh] pre-structured
    k_ref,   # [1, bb, block_k, Hkv, dh] — this layer's block, bb rows
    v_ref,   # [1, bb, block_k, Hkv, dh]
    *refs,   # quantized: (ks_ref [1, bb, Hkv, block_k], vs_ref) then outputs
    scale: float,
    block_k: int,
    n_kv_blocks: int,
    n_b_blocks: int,
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
    # The sweep plan (_sweep_plan), written once for every form below and
    # every operand's index map: the body runs only for the kv blocks
    # that hold a valid slot of some row of this block, and for no block
    # at all when none of its rows has a stream (its output stays the
    # zeros of _init, its state (NEG_INF, 0)).
    _, lo, hi = _plan_entry(scalars_ref, b_block * n_b_blocks, n_b_blocks, bb)
    live = jnp.logical_and(
        rs_min <= pos, jnp.logical_and(j >= lo, j <= hi)
    )

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
            # A row that met no valid slot (no stream, or its row_start
            # past the frontier) reports (NEG_INF, 0): absent to the
            # shared-prefix merge, whatever its block-mates summed.
            ms_ref[...] = m_ref[...]
            ls_ref[...] = jnp.where(
                m_ref[...] <= NEG_INF, 0.0, l_ref[...]
            )


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
    sweep: Optional[jax.Array] = None,  # decode_sweep_plan() of (pos, row_start)
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

    ``sweep`` is ``decode_sweep_plan`` of the same ``pos``, ``row_start`` and
    shapes: a caller that runs many layers at one ``pos`` computes it once
    outside its layer loop (left to this call it costs every layer three
    small programs beside the kernel, which XLA does not hoist).

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
    b_block, block_k = _blocks(b, w, hkv, dh, kv_item, quantized)
    n_kv_blocks = w // block_k
    n_b_blocks = b // b_block

    if row_start is None:
        row_start = jnp.zeros((b,), jnp.int32)
    row_start = row_start.astype(jnp.int32)
    if sweep is None:
        sweep = decode_sweep_plan(
            pos, row_start, width=w, n_kv_heads=hkv, dh=dh, kv_item=kv_item,
            quantized=quantized, sliding_window=sliding_window,
        )
    elif sweep.shape != (3 * n_b_blocks,):
        raise ValueError(
            f"sweep plan of {sweep.shape} does not fit {n_b_blocks} row "
            "blocks: plan it from this call's shapes (decode_sweep_plan)"
        )
    scalars = jnp.concatenate([
        jnp.asarray(pos, jnp.int32).reshape(1),
        jnp.asarray(layer_idx, jnp.int32).reshape(1),
        row_start,
        sweep,
    ])

    def kv_block(b_, j, s_):
        """(row block, kv block) that grid step (b_, j) reads — the one
        clamp every K, V and scale index map shares."""
        src, lo, hi = _plan_entry(s_, b, n_b_blocks, b_)
        return src, jnp.clip(j, lo, hi)

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
        n_b_blocks=n_b_blocks,
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
        lambda b_, j, s_: (s_[1], *kv_block(b_, j, s_), 0, 0),
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
        def scale_block(b_, j, s_):
            src, jk = kv_block(b_, j, s_)
            return 0, src, 0, jk

        scale_spec = pl.BlockSpec((1, b_block, hkv, block_k), scale_block)
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
        name="llmc_decode_attention",
    )(*operands)
    if return_state:
        out, m_out, l_out = out
        out = out[:, None] if qstruct else out
        return out, m_out[:, :, 0], l_out[:, :, 0]
    return out[:, None] if qstruct else out
