"""One position of the Mamba-2 recurrence, in place in the cache's state stack.

The T = 1 form of ops/ssm.py ``ssd_step`` for a state that lives in a stack
``[L, B, H, P, N]`` float32: a decode step of a state-keeping layer reads its
rows' state ONCE, out of the stack, and writes it ONCE, into the stack, with
``y = S C`` made on the way. In plain XLA the slice, the recurrence and the
update compile to two fusions that each read the state (one reduces ``y``
out of the new state, one writes it), three passes over a layer's rows where
two are needed (PERF.md section 5).

Design notes:
  * The stack is aliased in and out (``input_output_aliases``) and paged by
    the block index map: the layer is a prefetched scalar, a block is
    ``block_h`` heads of one row, about 2 MiB, so that reads and writes of
    neighbouring blocks overlap. Nothing else of the stack is touched.
  * Every number is the one ``ssd_step`` makes, by the same operations in
    the same order: ``S' = S decay + (dt x) (outer) B`` and ``y = sum_n S'
    C``, float32 on the vector unit (no matrix unit: a product there would
    not be the float32 product). The per-head scalars and the small
    vectors (``exp(dt A)``, ``dt x``, ``D x``) are made outside, as
    ``ssd_step`` makes them; a row that does not advance (``dt`` = 0) gets
    ``S 1 + 0 B``.
  * Every operand is a row a head, as the model holds it: what varies with
    ``n`` (``B``, ``C``, the decay spread over N lanes) is spread over the
    sublanes of a head's ``[P, N]`` tile; ``dt x`` varies with ``p``, the
    sublanes, so a block's ``[block_h, P]`` rows are transposed once; and
    ``y`` is reduced over ``n`` after a transpose of the head's products,
    which lands it as a row (a reduction over the lanes cost a rotation
    tree a sublane tile, a fifth of the time at 64 x 128 a head). The
    transposes move numbers and make none: on the CPU the compiler folds
    them into the reduction ``ssd_step`` makes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_consensus_tpu.utils.backend import pallas_interpret

_BLOCK_BYTES = 2 << 20  # a block's state: in and out, each double-buffered


def _block_heads(h: int, p: int, n: int) -> int:
    """Heads a block: the most that divide ``h`` and keep a block's state
    within ``_BLOCK_BYTES``, in whole sublane tiles of the ``[H, N]`` rows
    (all of ``h`` where it is smaller than that)."""
    fits = [hb for hb in range(8, h + 1, 8)
            if h % hb == 0 and hb * p * n * 4 <= _BLOCK_BYTES]
    return max(fits) if fits else h


def _kernel(layer_ref, s_ref, dec_ref, b_ref, c_ref, dtx_ref, o_ref, y_ref):
    """``s_ref``, ``o_ref`` [1, 1, block_h, P, N]; ``dec_ref``, ``b_ref``,
    ``c_ref`` [1, block_h, N]; ``dtx_ref``, ``y_ref`` [1, block_h, P]."""
    del layer_ref  # the index maps' own
    heads = s_ref.shape[2]
    tile = 8 if heads % 8 == 0 else heads  # heads a sublane tile of the rows

    def a_tile(t, carry):
        # A loop over tiles with one tile's heads written out: a block's 64
        # heads all written out cost a third of a second a kernel in
        # lowering alone, for every program at every start of a server.
        h0 = pl.multiple_of(t * tile, tile)
        heads_of = pl.ds(h0, tile)
        dec, b, c = dec_ref[0, heads_of], b_ref[0, heads_of], c_ref[0, heads_of]
        dtx = dtx_ref[0, heads_of].T  # [P, tile]: a head's dt x down the sublanes
        ys = []
        for i in range(tile):
            row = slice(i, i + 1)
            new = s_ref[0, 0, h0 + i] * dec[row] + dtx[:, row] * b[row]
            o_ref[0, 0, h0 + i] = new.astype(o_ref.dtype)
            ys.append(jnp.sum((new * c[row]).T, axis=0, keepdims=True))
        y_ref[0, heads_of] = jnp.concatenate(ys, axis=0)
        return carry

    jax.lax.fori_loop(0, heads // tile, a_tile, 0)


@functools.partial(jax.jit, static_argnames="interpret")
def ssd_step_in_place(stack, layer, xs, dt, a, bm, cm, d,
                      interpret: Optional[bool] = None):
    """``ssd_step`` on layer ``layer`` of ``stack`` [L, B, H, P, N] float32,
    where it lies. ``xs`` [B, H, P]; ``dt`` [B, H] float32 (0 on a row that
    does not advance); ``a`` [H] float32, negative; ``bm``, ``cm`` [B, G,
    N]; ``d`` [H]. Returns ``(y [B, H, P] float32, the stack with this
    layer's rows advanced)``. A function of its own to the compiler
    (``jit``): a program that unrolls its layers lowers the kernel once."""
    if interpret is None:
        interpret = pallas_interpret()
    _, bsz, h, p, n = stack.shape
    g = bm.shape[1]
    hb = _block_heads(h, p, n)
    xf = xs.astype(jnp.float32)
    bh = jnp.repeat(bm.astype(jnp.float32), h // g, axis=1)  # [B, H, N]
    ch = jnp.repeat(cm.astype(jnp.float32), h // g, axis=1)
    decay = jnp.broadcast_to(jnp.exp(dt * a)[..., None], (bsz, h, n))

    def rows(width):  # [B, H, width]: a block's heads
        return pl.BlockSpec((1, hb, width), lambda b, j, li: (b, j, 0))

    state = pl.BlockSpec((1, 1, hb, p, n), lambda b, j, li: (li[0], b, j, 0, 0))
    stack, y = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, h // hb),
            in_specs=[state, rows(n), rows(n), rows(n), rows(p)],
            out_specs=[state, rows(p)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(stack.shape, stack.dtype),
            jax.ShapeDtypeStruct((bsz, h, p), jnp.float32),
        ],
        input_output_aliases={1: 0},  # operand 0 is the prefetched layer
        cost_estimate=pl.CostEstimate(
            flops=5 * bsz * h * p * n,
            bytes_accessed=2 * bsz * h * p * n * 4,
            transcendentals=0,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="llmc_ssd_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), stack, decay, bh, ch,
      dt[..., None] * xf)
    return y + d[:, None] * xf, stack
