"""Routed expert layer: dropless, and told which experts it holds.

One layer serves Mixtral (one group, every expert held, weights
renormalised over the chosen) and DeepSeek-V2 (device-limited routing over
groups, a chip's share of the experts, shared experts, a routed scale):

  * **Routing** runs over the router's WHOLE width in float32: scores
    ``s = softmax(h · W_g)``; with groups, a group's score is its largest
    ``s``, the best ``groups_per_token`` groups stay and the rest are
    masked; the ``top_k`` largest ``s`` among what stays are chosen. The
    choice is made on the logits, of which ``s`` is a monotone function.
    Weights are the chosen ``s``, renormalised to sum to 1 (``norm_topk``:
    a softmax over the chosen logits, as Mixtral publishes it) or not, times
    ``routed_scale``.
  * **What is held.** The expert leaves carry ``E`` experts, those numbered
    ``[first_expert, first_expert + E)`` of the router's outputs: one chip's
    share of an expert-parallel layer. Of each token's chosen experts the
    layer keeps those it holds and computes their part of the result; what
    absent experts would have added is left out (their chips add it, in a
    deployment, through an exchange that one chip does not run).
  * **Dropless dispatch.** The ``N × top_k`` (token, expert) pairs are
    sorted by held expert, pairs on absent experts last; the held experts
    run as three grouped matrix products (``jax.lax.ragged_dot``) over the
    sorted rows and each token sums its own pairs' results, weighted. Static shapes come
    from the buffer of ``N × top_k`` pairs, not from a capacity: no pair on
    a held expert is ever dropped.
  * **Shared experts** (one SwiGLU of ``n_shared × d_expert``) see every
    token and are added once.

``moe_block(..., with_stats=True)`` also returns three int32 sums for the
tracing (docs/observability.md): pairs in all, pairs on held experts, held
experts that took at least one row.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from llm_consensus_tpu.obs.scopes import scope
from llm_consensus_tpu.ops.mlp import _activate, gated_mlp
from llm_consensus_tpu.ops.quant import dequantize

NEG_INF = -jnp.inf


def route(
    logits: jax.Array,        # [N, R] float32 router logits, whole width
    top_k: int,
    n_groups: int = 1,
    groups_per_token: int = 1,
    norm_topk: bool = True,
    routed_scale: float = 1.0,
) -> tuple[jax.Array, jax.Array]:
    """Chosen experts [N, k] (indices into the router's width) and their
    weights [N, k] float32."""
    n, r = logits.shape
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
    shared: Optional[tuple] = None,   # (ws_gate, ws_up, ws_down) or None
    layer=None,            # expert leaves are whole stacks [L, E, ...]: which layer
    with_stats: bool = False,
):
    if scoring != "softmax":
        raise ValueError(
            f"router scoring {scoring!r} is not computed: only 'softmax'")
    b, t, d = x.shape
    n = b * t
    with scope("moe.route"):
        tokens = x.reshape(n, d)
    with scope("moe.experts"):
        w_gate, w_up, w_down = (
            dequantize(w, x.dtype) for w in (w_gate, w_up, w_down))
    if layer is None:
        held, first_group = w_gate.shape[0], 0
    else:
        # The stacks of every layer as ONE run of L*E groups, of which only
        # this layer's are given rows: the grouped product then fetches the
        # experts it needs out of the stacks where they lie. Handing it one
        # layer's slice instead makes XLA copy that layer's every expert
        # (0.9 GB a layer a step at 20 experts of 5,120 x 1,536) first.
        n_stacked, held = w_gate.shape[:2]
        first_group = layer * held
        with scope("moe.experts"):
            w_gate, w_up, w_down = (
                w.reshape(n_stacked * held, *w.shape[2:])
                for w in (w_gate, w_up, w_down))
    n_groups_all = w_gate.shape[0]

    with scope("moe.route"):
        logits = jnp.einsum(
            "nd,dr->nr", tokens.astype(jnp.float32),
            w_router.astype(jnp.float32))
        top_idx, weights = route(
            logits, top_k, n_groups, groups_per_token, norm_topk, routed_scale)

    # Sort the N*k pairs by held expert; a pair on an absent expert takes
    # a key past every group and sorts last. The buffer is rounded up to
    # whole 8-row tiles (rows that are no pair sort last too): XLA's TPU
    # grouped-product kernel takes no other, and a buffer it refuses is
    # computed as one dense product an expert over every row.
    with scope("moe.experts"):
        pairs = n * top_k
        local = top_idx.reshape(-1) - first_expert
        is_held = (local >= 0) & (local < held)
        key = jnp.pad(
            jnp.where(is_held, first_group + local, n_groups_all), (0, -pairs % 8),
            constant_values=n_groups_all)
        order = jnp.argsort(key, stable=True)
        pair_token = jnp.minimum(order // top_k, n - 1)
        group_sizes = jnp.zeros((n_groups_all,), jnp.int32).at[key].add(1, mode="drop")

        rows = tokens[pair_token]                                   # [P, D]
        h = _activate(jax.lax.ragged_dot(rows, w_gate, group_sizes), activation)
        h = h * jax.lax.ragged_dot(rows, w_up, group_sizes)
        y = jax.lax.ragged_dot(h, w_down, group_sizes)              # [P, D]
        # Back to (token, choice) order; a row past the last group holds
        # nothing of an expert and is masked, not multiplied by zero.
        back = jnp.zeros_like(order).at[order].set(jnp.arange(order.size))
        y = jnp.where(is_held[:, None], y[back[:pairs]], 0).reshape(n, top_k, d)
        out = jnp.einsum(
            "nk,nkd->nd", weights, y, preferred_element_type=jnp.float32
        ).astype(x.dtype)

    if shared is not None:
        with scope("moe.shared"):
            out = out + gated_mlp(tokens, *shared, activation)
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
