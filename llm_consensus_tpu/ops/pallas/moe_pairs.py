"""The routed experts' products over a SMALL buffer of sorted pairs.

A decode step of a routed model hands the expert layer a handful of rows
(six rows x 6-22 choices, an eighth of them on experts held here): a dozen
experts hit, one to three rows each. ``jax.lax.ragged_dot`` over the whole
run of ``L x E`` groups is the chip's compiler's grouped product, built for
thousands of rows an expert; at these sizes what it costs stands over the
bytes it reads (PERF.md section 6, PR 46). This kernel streams the experts
hit and nothing else:

  * **Bounded by the pairs.** One program instance. It compacts the layer's
    ``E`` group sizes into the list of experts that hold rows (a scalar loop,
    no copy and no product for an expert without rows) and loops over that
    list: a visit an expert hit, at most ``min(E, P)``.
  * **The stack in place.** The expert stacks stay in the device's memory
    (``pl.ANY``) as ``[L, E, K, N]``; the layer and each visit's expert
    index the copies, so no layer's slice of experts is ever made.
  * **One pass a matrix.** A visited expert's matrix comes in chunks of
    whole rows (``bk`` of its ``K`` rows: one contiguous run of the stack,
    about ``_CHUNK_BYTES``), two chunks in flight, the next visit's first
    chunk asked for while the last of this one is multiplied. The rows of
    the pairs are few and live in fast memory whole, as ``[K / bk, P, bk]``
    so that a chunk's columns are a leading index.
  * **The same numbers.** Operands as stored, products accumulated in
    float32 on the matrix unit over the chunks of ``K``, each product
    rounded to the rows' type as ``ragged_dot`` hands it on; the activation
    and a gated expert's product of the two are made on the rounded values
    in float32 and rounded once (the vector unit has no narrower form). A
    row past the last group reads 0.

``experts_over_pairs`` is the expert layer's three (two, ungated) products as
two calls: the first makes ``h = act(x W_gate) * (x W_up)`` (``act(x W_up)``)
and leaves it in the second's layout, the second ``h W_down``. Its gradient
is the grouped product's (ops/moe.py hands it the way back), so a training
step on a handful of tokens still differentiates.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_consensus_tpu.ops.mlp import _activate
from llm_consensus_tpu.utils.backend import pallas_interpret

ROW_TILE = 16            # rows a product: one sublane tile of bfloat16
_CHUNK_BYTES = 2 << 20   # a chunk of one matrix; two in flight a matrix
_LANES = 128
_FAST_MEMORY = 96 << 20  # what a call may keep, of a v5e's 128 MiB


def chunk_rows(k: int, n: int, itemsize: int) -> int:
    """Rows of a ``[k, n]`` matrix a chunk: the most that divide ``k`` in
    whole lane tiles and keep a chunk within ``_CHUNK_BYTES`` (one lane tile
    where even that is larger; all of ``k`` where it is no multiple of a
    lane tile: a CI-size expert)."""
    if k % _LANES:
        return k
    fits = [bk for bk in range(_LANES, k + 1, _LANES)
            if k % bk == 0 and bk * n * itemsize <= _CHUNK_BYTES]
    return max(fits) if fits else _LANES


def fast_memory_bytes(p: int, k: int, n: int, n_w: int, item: int) -> int:
    """What one call over ``p`` rows keeps in fast memory: two chunks a
    stack in flight, a float32 accumulator a stack, the rows in and out
    (counted twice over, as a pipelined block would be)."""
    bk = chunk_rows(k, n, item)
    return 2 * n_w * bk * n * item + n_w * p * n * 4 + 2 * p * (k + n) * item


def fits_fast_memory(p: int, k: int, f: int, gated: bool, item: int) -> bool:
    """Whether both calls of an expert layer ``k -> f -> k`` over ``p`` rows
    stay within ``_FAST_MEMORY``. Compiled for a described v5e: 400 rows at
    Mixtral's 4,096 -> 14,336 gated (90 MB by this count) is taken, at
    8,192 -> 28,672 (180 MB) refused for 146 MB used of 128."""
    return max(fast_memory_bytes(p, k, f, 1 + gated, item),
               fast_memory_bytes(p, f, k, 1, item)) <= _FAST_MEMORY


def _kernel(layer_ref, sizes_ref, x_ref, *rest, n_w: int, activation):
    """``x_ref`` [nk, P, bk]; ``n_w`` stacks [L, E, K, N] left where they
    lie; ``out_ref`` [N / bo, P, bo]; then the scratch: the visit list
    (expert, first row, end row), the chunk buffers [2, n_w, bk, N], their
    semaphores, the accumulators [n_w, P, N] float32."""
    stacks, out_ref = rest[:n_w], rest[n_w]
    v_expert, v_lo, v_hi, bufs, sems, acc = rest[n_w + 1:]
    nk, _, bk = x_ref.shape
    n_out, _, bo = out_ref.shape
    layer = layer_ref[0]

    def note(e, carry):  # the experts that hold rows, in order
        n, row = carry
        size = sizes_ref[e]

        @pl.when(size > 0)
        def _():
            v_expert[n] = e
            v_lo[n] = row
            v_hi[n] = row + size

        return n + (size > 0).astype(jnp.int32), row + size

    n_visits, _ = jax.lax.fori_loop(
        0, sizes_ref.shape[0], note, (jnp.int32(0), jnp.int32(0)))
    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    def copies(v, c, slot):
        rows = pl.ds(pl.multiple_of(c * bk, bk), bk)
        return [
            pltpu.make_async_copy(
                w.at[layer, v_expert[v], rows], bufs.at[slot, j],
                sems.at[slot, j])
            for j, w in enumerate(stacks)]

    @pl.when(n_visits > 0)
    def _():
        for copy in copies(0, 0, 0):
            copy.start()

    def visit(v, carry):
        lo, hi = v_lo[v], v_hi[v]
        first, end = lo // ROW_TILE, (hi + ROW_TILE - 1) // ROW_TILE

        def chunk(c, carry):
            slot = (v * nk + c) % 2
            last = c + 1 == nk
            nv, nc = jnp.where(last, v + 1, v), jnp.where(last, 0, c + 1)

            @pl.when(nv < n_visits)
            def _():
                for copy in copies(nv, nc, 1 - slot):
                    copy.start()

            for copy in copies(v, c, slot):
                copy.wait()

            def tile(t, carry):
                rows = pl.ds(pl.multiple_of(t * ROW_TILE, ROW_TILE), ROW_TILE)
                x = x_ref[c, rows, :]
                for j in range(n_w):
                    part = jnp.dot(
                        x, bufs[slot, j], preferred_element_type=jnp.float32)
                    acc[j, rows, :] = jnp.where(c > 0, acc[j, rows, :], 0) + part
                return carry

            return jax.lax.fori_loop(first, end, tile, carry)

        jax.lax.fori_loop(0, nk, chunk, 0)

        def finish(t, carry):
            r0 = pl.multiple_of(t * ROW_TILE, ROW_TILE)
            rows = pl.ds(r0, ROW_TILE)

            def rounded(j):  # a product as ``ragged_dot`` hands it on
                return acc[j, rows, :].astype(out_ref.dtype).astype(jnp.float32)

            y = rounded(0)
            if activation is not None:
                y = _activate(y, activation)
            if n_w == 2:
                y = y * rounded(1)
            y = y.astype(out_ref.dtype)
            row = r0 + jax.lax.broadcasted_iota(jnp.int32, (ROW_TILE, bo), 0)
            own = (row >= lo) & (row < hi)
            for o in range(n_out):
                out_ref[o, rows, :] = jnp.where(
                    own, y[:, o * bo:(o + 1) * bo], out_ref[o, rows, :])
            return carry

        return jax.lax.fori_loop(first, end, finish, carry)

    jax.lax.fori_loop(0, n_visits, visit, 0)


def _grouped(x, stacks, layer, sizes, activation, out_chunk, interpret):
    """``x`` [nk, P, bk] (``P`` in whole ``ROW_TILE``s) through expert
    ``e``'s matrix of each of ``stacks`` for the rows of group ``e``, as
    ``[N / out_chunk, P, out_chunk]``."""
    nk, p, bk = x.shape
    _, held, k, n = stacks[0].shape
    n_w = len(stacks)
    item = stacks[0].dtype.itemsize
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    visits = min(held, p)
    need = fast_memory_bytes(p, k, n, n_w, item)
    return pl.pallas_call(
        functools.partial(_kernel, n_w=n_w, activation=activation),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[vmem] + [pl.BlockSpec(memory_space=pl.ANY)] * n_w,
            out_specs=vmem,
            scratch_shapes=[
                pltpu.SMEM((visits,), jnp.int32),
                pltpu.SMEM((visits,), jnp.int32),
                pltpu.SMEM((visits,), jnp.int32),
                pltpu.VMEM((2, n_w, bk, n), stacks[0].dtype),
                pltpu.SemaphoreType.DMA((2, n_w)),
                pltpu.VMEM((n_w, p, n), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n // out_chunk, p, out_chunk), x.dtype),
        cost_estimate=pl.CostEstimate(
            flops=2 * n_w * p * k * n,
            bytes_accessed=n_w * visits * k * n * item,
            transcendentals=0,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(need * 1.25) + (4 << 20),
        ),
        interpret=interpret,
        name="llmc_moe_pairs",
    )(jnp.asarray(layer, jnp.int32).reshape(1), sizes, x, *stacks)


@functools.partial(jax.jit, static_argnames=("activation", "interpret"))
def experts_over_pairs(rows, w_gate, w_up, w_down, layer, sizes,
                       activation: str, interpret: Optional[bool] = None):
    """``rows`` [P, K], sorted by held expert, ``P`` in whole ``ROW_TILE``s;
    ``w_gate`` (or None), ``w_up`` [L, E, K, F] and ``w_down`` [L, E, F, K]
    whole; ``sizes`` [E] int32, the rows of each expert of layer ``layer``.
    Returns [P, K]: row ``r`` of expert ``e`` through ``e``'s MLP, 0 past the
    last group. A function of its own to the compiler (``jit``): a program
    that unrolls its layers lowers the kernel once."""
    if interpret is None:
        interpret = pallas_interpret()
    p, k = rows.shape
    f = w_up.shape[-1]
    item = w_up.dtype.itemsize
    bk, bf = chunk_rows(k, f, item), chunk_rows(f, k, item)
    x = rows.reshape(p, k // bk, bk).swapaxes(0, 1)
    first = (w_up,) if w_gate is None else (w_gate, w_up)
    h = _grouped(x, first, layer, sizes, activation, bf, interpret)
    return _grouped(h, (w_down,), layer, sizes, None, k, interpret)[0]
