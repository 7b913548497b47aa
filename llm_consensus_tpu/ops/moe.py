"""Routed expert layer: dropless, and told which experts it holds.

One layer serves three families: ``mixtral`` (one group, every expert held,
weights renormalised over the chosen), ``deepseek_v2`` (device-limited
routing over groups, a chip's share of the experts, shared experts, a routed
scale) and ``nemotron_h`` (LatentMoE: sigmoid scores chosen with a
correction bias, ungated experts that work in a latent width between two
projections, one shared expert of a width of its own on the full width).
Two scorings (``softmax``, ``sigmoid_bias``) and two expert forms (gated,
``W_down (act(x W_gate) * x W_up)``, three matrices; ungated, ``W_down act(x
W_up)``, two: ``w_gate=None``):

  * **Routing** runs over the router's WHOLE width in float32: scores
    ``s = softmax(h · W_g)``; with groups, a group's score is its largest
    ``s``, the best ``groups_per_token`` groups stay and the rest are
    masked; the ``top_k`` largest ``s`` among what stays are chosen. The
    choice is made on the logits, of which ``s`` is a monotone function.
    Weights are the chosen ``s``, renormalised to sum to 1 (``norm_topk``:
    a softmax over the chosen logits, as Mixtral publishes it) or not, times
    ``routed_scale``. Under ``sigmoid_bias`` the scores are ``s = sigmoid(h
    · W_g)``, the ``top_k`` largest ``s + b`` are chosen (``b`` a stored
    per-expert correction bias, one group), and the weights are the chosen
    ``s`` WITHOUT ``b``, divided by their sum under ``norm_topk``, times
    ``routed_scale``.
  * **The latent** (``latent=(w_in, w_out)``): the routed experts read ``u
    = h · w_in`` and their weighted sum goes through ``w_out`` back to the
    model's width; the router and the shared expert read ``h``. ``w_out`` is
    linear and has no bias, so the shares of an expert-parallel layer still
    add up after it.
  * **What is held.** The expert leaves carry ``E`` experts, those numbered
    ``[first_expert, first_expert + E)`` of the router's outputs: one chip's
    share of an expert-parallel layer. Of each token's chosen experts the
    layer keeps those it holds and computes their part of the result; what
    absent experts would have added is left out (their chips add it, in a
    deployment, through an exchange that one chip does not run).
  * **Dropless dispatch.** The ``N × top_k`` (token, expert) pairs are
    sorted by held expert, pairs on absent experts last; the held experts'
    three products (two, ungated) run over the sorted rows and each token
    sums its own pairs' results, weighted. Static shapes come from the
    buffer of ``N × top_k`` pairs, not from a capacity: no pair on a held
    expert is ever dropped.
  * **Which products** (``pairs_kernel_serves``, by the static size of the
    pairs buffer, the leaves' type and the mesh they lie on, nothing of a
    model's name). A buffer of at most ``PAIRS_KERNEL_MAX`` pairs over plain
    expert leaves on one device, a decode step's handful of rows, runs in
    ONE kernel over the sorted pairs (ops/pallas/moe_pairs.py): it visits
    only the experts that hold rows and streams each one's matrices once,
    out of the whole ``[L, E, ...]`` stacks where they lie. A larger buffer
    (a prompt's chunk, a panel wave: hundreds of rows an expert), int8
    stacks (dequantized whole) and stacks sharded over a mesh of more than
    one device (the compiler partitions no kernel; ``ragged_dot`` it does)
    run as grouped matrix products (``jax.lax.ragged_dot``) over every
    layer's experts as one run of ``L × E`` groups. The same numbers either
    way: operands as stored, float32 accumulation, each product rounded to
    ``x.dtype``.
  * **Shared experts** (one MLP of the experts' form, ``n_shared ×
    d_expert`` wide or of a width of its own) see every token and are added
    once.

``moe_block(..., with_stats=True)`` also returns three int32 sums for the
tracing (docs/observability.md): pairs in all, pairs on held experts, held
experts that took at least one row.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from llm_consensus_tpu.obs.scopes import scope
from llm_consensus_tpu.ops.mlp import _activate, gated_mlp, plain_mlp
from llm_consensus_tpu.ops.quant import dequantize, is_quantized, qeinsum
from llm_consensus_tpu.utils.backend import pallas_interpret

NEG_INF = -jnp.inf
# The largest buffer of pairs whose products run in the kernel over the
# sorted pairs (ops/pallas/moe_pairs.py). Read on the chip (PERF.md section
# 6, PR 46: bfloat16, layers x 16 steps in one program, ms a step,
# ``ragged_dot`` -> the kernel) at the three cells' expert shapes, 1,024 x
# 2,688 ungated / 4,096 x 1,280 gated / 5,120 x 1,536 gated: a pool of six
# rows (144 / 48 / 48 pairs) 2.43 -> 1.14 / 1.48 -> 1.03 / 1.39 -> 1.29; a
# pool of eighteen (400 / 144 / 112 pairs) 5.68 -> 2.59 / 3.75 -> 2.57 /
# 3.41 -> 3.18; pools of 48 and of 128 rows (up to 2,816 / 1,024 / 768
# pairs, every held expert hit) 9.93 -> 4.21 and 13.30 -> 4.79 / 8.20 ->
# 4.80 and 17.77 -> 6.63 / 5.90 -> 5.33 and 9.55 -> 6.34. The kernel won at
# every size read, the largest expert (15.7 MB a matrix) included. Why there
# is a largest buffer all the same: the kernel keeps every row of the buffer
# in fast memory, in and out, beside a float32 accumulator of [stacks, P, N]
# (P = 400 at N = 2,688: 4.3 MB; a prompt's chunk of 11,264 pairs: 121 MB,
# which the chip does not have), where ``ragged_dot`` tiles the rows; that
# bound is asked of the shapes too (``moe_pairs.fits_fast_memory``). The
# number stands at an eighteen-row pool's largest buffer: no decode step of
# a cell is larger, and a prompt's chunk (44-78 rows an expert, thousands of
# pairs) was not read against anything.
PAIRS_KERNEL_MAX = 400


def route(
    logits: jax.Array,        # [N, R] float32 router logits, whole width
    top_k: int,
    n_groups: int = 1,
    groups_per_token: int = 1,
    norm_topk: bool = True,
    routed_scale: float = 1.0,
    bias: Optional[jax.Array] = None,   # [R]: sigmoid scoring's correction bias
) -> tuple[jax.Array, jax.Array]:
    """Chosen experts [N, k] (indices into the router's width) and their
    weights [N, k] float32. With ``bias`` the scoring is ``sigmoid_bias``."""
    n, r = logits.shape
    if bias is not None:
        if n_groups > 1:
            raise ValueError(
                "router scoring 'sigmoid_bias' over expert groups is not "
                f"computed, got {n_groups} groups")
        scores = jax.nn.sigmoid(logits)
        _, top_idx = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        weights = jnp.take_along_axis(scores, top_idx, axis=-1)
        if norm_topk:
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
        return top_idx, weights * routed_scale
    if n_groups > 1:
        grouped = logits.reshape(n, n_groups, r // n_groups)
        _, top_groups = jax.lax.top_k(grouped.max(axis=-1), groups_per_token)
        keep = jnp.zeros((n, n_groups), bool).at[
            jnp.arange(n)[:, None], top_groups].set(True)
        logits_for_choice = jnp.where(
            keep[:, :, None], grouped, NEG_INF).reshape(n, r)
    else:
        logits_for_choice = logits
    top_logits, top_idx = jax.lax.top_k(logits_for_choice, top_k)
    if norm_topk:
        weights = jax.nn.softmax(top_logits, axis=-1)
    else:
        weights = jnp.exp(
            top_logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True))
    return top_idx, weights * routed_scale


def pairs_kernel_serves(pairs: int, w_up, mesh=None) -> bool:
    """Whether the held experts' products over a buffer of ``pairs`` (token,
    expert) pairs run in the kernel over the sorted pairs: a buffer that is
    small against the tile ``ragged_dot`` was built for, plain expert leaves
    (``w_up`` as stored: an int8 stack is dequantized whole and keeps the
    grouped product), on ONE device (``mesh``: the one the program's operands
    lie on, if any. Over more than one device the expert stacks are sharded,
    parallel/sharding.py, and the chip's compiler refuses to partition a
    kernel: the grouped product it partitions), rows and accumulators that
    fit the chip's fast memory (counted as for gated experts, the larger
    form), widths in whole lane tiles (the chip's compiler takes no other
    slice of a stack; the interpreter has no tiling)."""
    if is_quantized(w_up) or pairs > PAIRS_KERNEL_MAX:
        return False
    if mesh is not None and mesh.size > 1:
        return False
    from llm_consensus_tpu.ops.pallas.moe_pairs import ROW_TILE, fits_fast_memory

    k, f = w_up.shape[-2:]
    if not fits_fast_memory(-(-pairs // ROW_TILE) * ROW_TILE, k, f,
                            True, w_up.dtype.itemsize):
        return False  # pairs x widths beyond what the kernel may keep
    return not (k % 128 or f % 128) or pallas_interpret()


def _grouped(rows, stacks, group_sizes, activation: str):
    """The held experts' MLP over the sorted rows as grouped matrix
    products: ``stacks`` ``[G, ...]`` each, ``group_sizes`` [G]."""
    h = _activate(jax.lax.ragged_dot(rows, stacks[0], group_sizes), activation)
    if len(stacks) == 3:
        h = h * jax.lax.ragged_dot(rows, stacks[1], group_sizes)
    return jax.lax.ragged_dot(h, stacks[-1], group_sizes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _over_pairs(rows, stacks, layer, sizes, activation: str):
    """``_grouped`` for layer ``layer`` of ``stacks`` ``[L, E, ...]``, whose
    experts take ``sizes`` [E] rows, in the kernel over the sorted pairs
    (imported here, where it is first traced, as the attention kernels
    are)."""
    from llm_consensus_tpu.ops.pallas.moe_pairs import experts_over_pairs

    gate = stacks[0] if len(stacks) == 3 else None
    return experts_over_pairs(
        rows, gate, stacks[-2], stacks[-1], layer, sizes, activation)


def _over_pairs_fwd(rows, stacks, layer, sizes, activation):
    return (_over_pairs(rows, stacks, layer, sizes, activation),
            (rows, stacks, layer, sizes))


def _over_pairs_bwd(activation, saved, ct):
    """The way back is the grouped product's, over the whole run."""
    rows, stacks, layer, sizes = saved
    n_stacked, held = stacks[0].shape[:2]

    def flat(rows, stacks):
        run = jax.lax.dynamic_update_slice(
            jnp.zeros((n_stacked * held,), sizes.dtype), sizes, (layer * held,))
        return _grouped(
            rows, tuple(w.reshape(-1, *w.shape[2:]) for w in stacks), run,
            activation)

    return (*jax.vjp(flat, rows, stacks)[1](ct), None, None)


_over_pairs.defvjp(_over_pairs_fwd, _over_pairs_bwd)


def moe_block(
    x: jax.Array,          # [B, T, D]
    w_router: jax.Array,   # [D, R]: the router's whole width
    w_gate,                # [E, D, F]: the E experts held here
    w_up,                  # [E, D, F]
    w_down,                # [E, F, D]
    top_k: int,
    activation: str = "silu",
    *,
    first_expert: int = 0,
    n_groups: int = 1,
    groups_per_token: int = 1,
    norm_topk: bool = True,
    routed_scale: float = 1.0,
    scoring: str = "softmax",
    router_bias=None,      # [R]: the correction bias of "sigmoid_bias"
    latent: Optional[tuple] = None,   # (w_in [D, Z], w_out [Z, D]) or None
    shared: Optional[tuple] = None,   # (ws_gate, ws_up, ws_down) or None
    layer=None,            # expert leaves are whole stacks [L, E, ...]: which layer
    with_stats: bool = False,
    mesh=None,             # the mesh the operands lie on: a kernel wants ONE device
):
    """``w_gate=None`` (and ``shared[0] is None``): ungated experts."""
    if scoring not in ("softmax", "sigmoid_bias"):
        raise ValueError(
            f"router scoring {scoring!r} is not computed: only 'softmax' "
            "and 'sigmoid_bias'")
    if (scoring == "sigmoid_bias") != (router_bias is not None):
        raise ValueError(
            f"router scoring {scoring!r} "
            + ("needs" if router_bias is None else "takes no")
            + " correction bias")
    b, t, d = x.shape
    n = b * t
    with scope("moe.route"):
        tokens = x.reshape(n, d)
    gated = w_gate is not None
    stacks = (w_gate, w_up, w_down) if gated else (w_up, w_down)
    kernel = pairs_kernel_serves(n * top_k, w_up, mesh)
    with scope("moe.experts"):
        stacks = tuple(dequantize(w, x.dtype) for w in stacks)
    if kernel:
        from llm_consensus_tpu.ops.pallas.moe_pairs import ROW_TILE

        # The kernel takes the stacks as they are, [L, E, ...], and the layer
        # beside them: its groups are this layer's own.
        if layer is None:
            with scope("moe.experts"):
                stacks, layer = tuple(w[None] for w in stacks), jnp.int32(0)
        held, first_group = stacks[0].shape[1], 0
    elif layer is None:
        held, first_group = stacks[0].shape[0], 0
    else:
        # The stacks of every layer as ONE run of L*E groups, of which only
        # this layer's are given rows: the grouped product then fetches the
        # experts it needs out of the stacks where they lie. Handing it one
        # layer's slice instead makes XLA copy that layer's every expert
        # (0.9 GB a layer a step at 20 experts of 5,120 x 1,536) first.
        n_stacked, held = stacks[0].shape[:2]
        first_group = layer * held
        with scope("moe.experts"):
            stacks = tuple(
                w.reshape(n_stacked * held, *w.shape[2:]) for w in stacks)
    n_groups_all = held if kernel else stacks[0].shape[0]

    with scope("moe.route"):
        logits = jnp.einsum(
            "nd,dr->nr", tokens.astype(jnp.float32),
            w_router.astype(jnp.float32))
        top_idx, weights = route(
            logits, top_k, n_groups, groups_per_token, norm_topk, routed_scale,
            router_bias)
    wide = tokens  # what the router and the shared expert read
    if latent is not None:
        with scope("moe.latent_in"):
            tokens = qeinsum("nd,dz->nz", tokens, latent[0])
    z = tokens.shape[-1]  # the width the routed experts work in

    # Sort the N*k pairs by held expert; a pair on an absent expert takes
    # a key past every group and sorts last. The buffer is rounded up to
    # whole 8-row tiles (rows that are no pair sort last too): XLA's TPU
    # grouped-product kernel takes no other, and a buffer it refuses is
    # computed as one dense product an expert over every row. The kernel
    # over the sorted pairs takes whole tiles of its own.
    with scope("moe.experts"):
        pairs = n * top_k
        local = top_idx.reshape(-1) - first_expert
        is_held = (local >= 0) & (local < held)
        key = jnp.pad(
            jnp.where(is_held, first_group + local, n_groups_all),
            (0, -pairs % (ROW_TILE if kernel else 8)),
            constant_values=n_groups_all)
        order = jnp.argsort(key, stable=True)
        pair_token = jnp.minimum(order // top_k, n - 1)
        group_sizes = jnp.zeros((n_groups_all,), jnp.int32).at[key].add(1, mode="drop")

        rows = tokens[pair_token]                                   # [P, Z]
        if kernel:
            y = _over_pairs(rows, stacks, layer, group_sizes, activation)
        else:
            y = _grouped(rows, stacks, group_sizes, activation)     # [P, Z]
        # Back to (token, choice) order; a row past the last group holds
        # nothing of an expert and is masked, not multiplied by zero.
        back = jnp.zeros_like(order).at[order].set(jnp.arange(order.size))
        y = jnp.where(is_held[:, None], y[back[:pairs]], 0).reshape(n, top_k, z)
        out = jnp.einsum(
            "nk,nkd->nd", weights, y, preferred_element_type=jnp.float32
        ).astype(x.dtype)

    if latent is not None:
        with scope("moe.latent_out"):
            out = qeinsum("nz,zd->nd", out, latent[1])
    if shared is not None:
        with scope("moe.shared"):
            out = out + (
                gated_mlp(wide, *shared, activation) if shared[0] is not None
                else plain_mlp(wide, *shared[1:], activation))
    with scope("moe.experts"):
        out = out.reshape(b, t, d)
    if not with_stats:
        return out
    with scope("moe.stats"):
        stats = jnp.stack([
            jnp.asarray(n * top_k, jnp.int32),
            jnp.sum(group_sizes),
            jnp.sum(group_sizes > 0, dtype=jnp.int32),
        ])
    return out, stats
