"""The names of the parts of a device program: one flat vocabulary.

A device trace names an operation by the program it ran in
(``decode_chunk__<model>__kv<width>__s<steps>``, engine/engine.py) and by
its HLO text, which says nothing of which PART of a layer or of a chunk it
belongs to. Every part of every hot program is therefore traced under
``jax.named_scope("llmc.<part>")``: metadata only (an operation's
``op_name`` gains a path component; no operand, no program and no number a
step computes changes), which the profiler's trace carries beside each
operation and ``benchmark/trace_scopes.py`` sums into a ``step_split``.

This module holds the NAMES and nothing else: the scope sites
(models/transformer.py, ops/*, engine/engine.py, engine/batcher.py) write
``with scope("attn.sweep"):``, and tests/test_scopes.py lowers every
family's programs and fails on a part of a family that has no scope and
on an ``llmc.`` name that is not here.

docs/observability.md "Scopes" says where each is and what it covers.
"""

from __future__ import annotations

import jax

PREFIX = "llmc."

# Every model, every hot program.
COMMON = (
    "embed",        # the token gather and its multipliers
    "layers",       # the layer scan's own: a layer's leaves out of their
                    # stacks, its counter (a part inside the body names itself)
    "norm",         # a block's two norms and the final norm
    "mlp",          # the dense gated MLP
    "head",         # the position pick, the head product, multiplier, softcap
    "sample",       # the sampler (decode chunks; the pool's admit finish)
    "sentinel",     # the finite-logit check and the fault lane's poison
    "chunk.tail",   # token, position and key updates; the sums a chunk returns
    "cache.splice",  # _splice, _splice_rows: the prefill's cache hand-over
)
# Grouped-query attention over keys and values.
ATTN = (
    "attn.proj",      # q / k / v products, biases, rotary (and its tables)
    "attn.kv_write",  # this call's keys and values into the full stacks
    "attn.sweep",     # mask or sweep plan, cache read, scores, softmax, values
    "attn.out",       # the output product
)
# An attention layer whose output passes a learned gate (cfg.attn_out_gate).
ATTN_GATE = (
    "attn.gate",      # the gate's product, its sigmoid, the multiply
)
# A stack that mixes window and full attention layers (cfg.attn_kinds), with
# an RMS norm over each query and key head (cfg.qk_norm).
ATTN_KINDS = (
    "attn.sweep_window",  # a "W" layer's sweep, and its kind's sweep plan:
                          # ``attn.sweep`` under the window, by its own name
                          # so that a trace splits device time by kind
    "attn.qk_norm",       # the two head norms, between the split and rotary
)
# Latent attention, in place of ATTN.
MLA = (
    "mla.q",       # wq_a, q_norm, wq_b, the rotary part (and its tables)
    "mla.kv_a",    # wkv_a, kv_norm, the shared rotary key, the latent's write
    "mla.absorb",  # the products with wkv_b's halves: onto the query and the
                   # output at T = 1, the latents' expansion in the prefill form
    "mla.sweep",   # mask, latent read, scores, softmax, weighted sum
    "mla.out",     # the output product
)
# The routed expert layer, beside ``mlp`` where a model has dense layers too.
MOE = (
    "moe.route",    # router logits, groups, the choice and its weights
    "moe.experts",  # the sort, the three grouped products, the way back
    "moe.latent_in",   # LatentMoE: the projection into the experts' width
    "moe.latent_out",  # LatentMoE: the routed sum's projection back out
    "moe.shared",   # the shared experts' MLP
    "moe.stats",    # the three sums the tracing fetches
)
# The state-space mixer, beside ATTN.
SSM = (
    "ssm.in_proj",      # the in-projection, its multipliers, dt, A, D
    "ssm.conv",         # the causal convolution and its activation
    "ssm.scan",         # the chunked recurrence (T > 1)
    "ssm.step",         # one position (T = 1): in a cache, its rows in place
    "ssm.norm",         # the gate and the grouped norm
    "ssm.out_proj",     # the out-projection and its multiplier
    "ssm.state_write",  # state (T > 1) and tail out of and back into the stacks
)
# The delta-rule layer (Kimi Delta Attention), a one-part layer of kind K.
KDA = (
    "kda.in_proj",      # the q / k / v products
    "kda.gate",         # the decay's and the output gate's low-rank pairs,
                        # dt_bias, A, beta
    "kda.conv",         # the causal convolution, its activation, the norms
                        # of q and k, the span's masks
    "kda.scan",         # the chunked rule (T > 1)
    "kda.step",         # one position of the rule (T = 1)
    "kda.norm",         # the per-head norm and the output gate
    "kda.out_proj",     # the out-projection
    "kda.state_write",  # state and tail out of and back into the full stacks
)

SCOPES = COMMON + ATTN + ATTN_GATE + ATTN_KINDS + MLA + MOE + SSM + KDA
_KNOWN = frozenset(SCOPES)


def scope(part: str):
    """``jax.named_scope("llmc.<part>")`` for a part of the vocabulary; a
    name that is not in it is a programming error, raised at trace time."""
    if part not in _KNOWN:
        raise ValueError(f"llmc.{part} is not in obs/scopes.py SCOPES")
    return jax.named_scope(PREFIX + part)


__all__ = [
    "ATTN", "ATTN_GATE", "ATTN_KINDS", "COMMON", "KDA", "MLA", "MOE", "PREFIX", "SCOPES", "SSM",
    "scope"]
