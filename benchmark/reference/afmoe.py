"""Plain float32 reference forward for the ``afmoe`` stack (Trinity-Mini).

Written from the catalog's row for ``Trinity-Mini`` (``model_type`` ``afmoe``:
its ``config`` and ``described_as``) and the ``afmoe`` modelling code as
remembered (there is no network here), independent of ``models/
transformer.py``, ``ops/attention.py`` and ``ops/moe.py``: it imports nothing
from ``llm_consensus_tpu``. Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching, no sorted dispatch, no sweep plan.

A published layer is two parts under a SANDWICH norm (four RMSNorms a layer,
eps 1e-5, plain weights, no bias anywhere)::

    x0 = embed[ids] * sqrt(hidden_size)                # mup_enabled
    x  = x + post_attention_layernorm(Attn(input_layernorm(x)))
    x  = x + post_mlp_layernorm(MLP(pre_mlp_layernorm(x)))
    logits = rms_norm(x; final_norm) @ W_head          # untied head

The served tree states it as two one-part layers, each ``x + post_norm(
part(norm(x)))``, and the pattern (``more_fields.layer_kinds``) says what each
is. With ``h`` [T, D] the part's normed input:

  * ``W`` (a ``sliding_attention`` layer) and ``*`` (a ``full_attention``
    layer: layer ``i`` of the published stack iff ``(i + 1) % 4 == 0``), ``Hq``
    query and ``Hkv`` key/value heads of ``dh``::

        q, k, v = h Wq, h Wk, h Wv;      g = h Wg      # g: D -> Hq dh
        q = rms_norm(q; w_q [dh]);  k = rms_norm(k; w_k [dh])   # over the HEAD's
            # width, one weight vector for all heads of a projection
        q, k = rotary(q), rotary(k)                    # ``W`` layers ONLY: theta
            # 10,000 over the whole head; a ``*`` layer turns nothing
        a_i = softmax_j(q_i . k_j / sqrt(dh)) v_j      # j <= i, and on a ``W``
            # layer i - j < sliding_window: query i sees key j iff it is one
            # of the last ``sliding_window`` positions, itself included
        out = (concat(heads) * sigmoid(g)) @ Wo

    Attention runs over blocks of 512 queries past 1,024 positions, each
    block against the keys it can see alone (``decoder.attention``).
  * ``D``, the dense MLP of a leading dense layer: ``W2 (silu(W1 h) * W3 h)``
    at ``intermediate_size``.
  * ``E``, the expert MLP of every other layer::

        s = sigmoid(h @ W_r)                           # float32, all 128 outputs
        chosen = the 8 largest of (s + b)              # b: the stored bias; one group
        w = s[chosen] / sum(s[chosen]) * route_scale   # the bias chooses and does not weigh
        out = sum over chosen e of w_e * W2_e (silu(W1_e h) * W3_e h)  +  shared(h)

Departures from the published model and sizes it does not state, each also
under ``assumed`` in the configuration's file:

  * **What the config's keys do not state is the modelling code's**: the
    sandwich norm, the two head norms, the output gate and its width
    (elementwise over all ``Hq dh`` outputs; the published "26B-A3B" does
    not tell 26.12 B with it from 25.86 B without), no rotary embedding on
    ``full_attention`` layers, the expert bias as a stored vector.
  * Rotary pairs are half-split ``(i, i + dh/2)`` where the published code
    may interleave; under random weights the pairing is immaterial as long
    as program and reference pair alike.
  * **The share.** The served tree holds ``n_experts`` routed experts, those
    numbered ``[first_expert, first_expert + n_experts)`` of the router's
    ``router_width`` outputs (the cell: all 128 of 128). The reference is
    given the same share: it routes over the whole width and loops over the
    HELD experts; an expert outside the share adds nothing, the shared expert
    counts once.
  * Weights are read in the program's layout (``[contract, out]``).

It reads the tree the engine serves: ``embed, final_norm, lm_head`` and a
stack a layer kind: ``layers_attn`` (``attn_norm, wq, wk, wv, wo, w_ogate,
q_head_norm [dh], k_head_norm [dh], post_norm``: BOTH attention kinds in the
pattern's order, a layer's index its place among the attention layers),
``layers_mlp`` (``mlp_norm, w_gate, w_up, w_down, post_norm``) and
``layers_moe`` (``mlp_norm, w_router [D, R], router_bias [R], w_gate, w_up [E,
D, F], w_down [E, F, D], ws_gate, ws_up, ws_down, post_norm``). Every size
comes from the model's entry in the configuration file (the core fields and
``more_fields``). On the chip it runs beside about 12.4 GB of served state:
every product is a small jitted piece, and **a layer's routed experts are
taken in groups** of ``EXPERT_GROUP`` out of the whole stacks where they lie
(16 experts are 0.2 GB as stored; a whole layer's slice is 1.6 GB and its
float32 cast 3.2 GB), each expert of a group upcast alone.

What is compared (``compared``), with the readings behind each limit, is at
the bottom.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.decoder import (
    _take_layer, attention, dense, one_at_a_time, rms_norm, rope)
from benchmark.reference.deepseek_v2 import _swiglu
from benchmark.reference.nemotron_h import _norm, logits, route  # noqa: F401

FAMILIES = ("afmoe",)
STORED_LEAVES = (
    ("layers_attn", "wq"), ("layers_attn", "w_ogate"), ("layers_attn", "wo"),
    ("layers_mlp", "w_up"),
    ("layers_moe", "w_gate"), ("layers_moe", "w_down"), ("layers_moe", "ws_up"),
)
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
EXPERT_GROUP = 16   # routed experts taken out of the stacks at a time


@partial(jax.jit, static_argnames=(
    "n_heads", "n_kv_heads", "head_dim", "theta", "eps", "window", "rotary"))
def attention_part(u, w, *, n_heads, n_kv_heads, head_dim, theta, eps, window,
                   rotary):
    """Output-gated attention with head norms on the normed input ``u`` [T,
    D]: under ``window`` and with rotary embedding, or neither."""
    t = u.shape[0]
    q = (u @ dense(w["wq"])).reshape(t, n_heads, head_dim)
    k = (u @ dense(w["wk"])).reshape(t, n_kv_heads, head_dim)
    v = (u @ dense(w["wv"])).reshape(t, n_kv_heads, head_dim)
    q = rms_norm(q, dense(w["q_head_norm"]), eps)
    k = rms_norm(k, dense(w["k_head_norm"]), eps)
    if rotary:
        pos = jnp.arange(t)
        q, k = rope(q, pos, theta), rope(k, pos, theta)
    a = attention(q, k, v, window).reshape(t, n_heads * head_dim)
    return (a * jax.nn.sigmoid(u @ dense(w["w_ogate"]))) @ dense(w["wo"])


@partial(jax.jit, static_argnames=("n",))
def _take_experts(stack, layer, first, n: int):
    """Experts ``[first, first + n)`` of ``layer`` out of a whole ``[L, E,
    ...]`` stack (a plain leaf or an int8 pair), as stored."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_slice(
            a, (layer, first) + (0,) * (a.ndim - 2), (1, n) + a.shape[2:])[0],
        stack)


@jax.jit
def _group_sum(h, gates, w_gate, w_up, w_down):
    """``sum_e gates[:, e] * W2_e (silu(W1_e h) * W3_e h)`` over one group's
    experts, one at a time (each upcast alone)."""
    def one(out, at):
        gate, *w_e = at
        return out + gate[:, None] * _swiglu(h, *w_e), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h), (gates.T, w_gate, w_up, w_down))
    return out


def experts(h, stacks: dict, w: dict, layer: int, more: dict):
    """The expert MLP on the normed input ``h`` [T, D], as this share
    computes it: ``stacks`` the whole expert stacks, ``w`` this layer's
    other leaves."""
    idx, weights = route(
        h, w["w_router"], w["router_bias"], float(more.get("routed_scale", 1.0)),
        top_k=more["experts_per_token"], norm_topk=more.get("norm_topk", True))
    first, held = more.get("first_expert", 0), more["n_experts"]
    out = jnp.zeros_like(h)
    for e0 in range(0, held, EXPERT_GROUP):     # the held experts, in groups
        n = min(EXPERT_GROUP, held - e0)
        numbers = first + e0 + jnp.arange(n)
        gates = jnp.sum(
            jnp.where(idx[:, :, None] == numbers, weights[:, :, None], 0.0), axis=1)
        group = [_take_experts(stacks[k], layer, e0, n) for k in EXPERT_LEAVES]
        out = one_at_a_time(out + _group_sum(h, gates, *group))
    return out + _swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"])


def hidden(params: dict, spec: dict, token_ids) -> jax.Array:
    """The final-normed hidden states [T, D] in float32 for one sequence of
    token ids; ``spec`` is the model's whole entry in the configuration
    file."""
    more = spec.get("more_fields") or {}
    kinds = more.get("layer_kinds") or ""
    if (spec["family"] not in FAMILIES or len(kinds) != spec["n_layers"]
            or set(kinds) - set("W*DE")):
        raise ValueError(
            f"no plain reference for family {spec['family']!r} with "
            f"layer_kinds {kinds!r} over {spec['n_layers']} layers; have {FAMILIES}")
    window = spec.get("sliding_window")
    if (more.get("router_scoring") != "sigmoid_bias" or not more.get("gated_experts", True)
            or more.get("rotary", True)
            or not (more.get("attn_out_gate") and more.get("qk_norm")
                    and more.get("post_norm") and more.get("embed_scale"))
            or more.get("n_expert_groups", 1) != 1 or more.get("moe_latent")
            or more.get("activation", "silu") != "silu"
            or more.get("n_shared_experts") != 1
            or ("W" in kinds and not window)):
        raise ValueError(
            "this reference computes the sandwich norm, head norms, an output "
            "gate, rotary embedding on window layers alone, a scaled "
            "embedding, sigmoid_bias routing in one group and gated silu "
            f"experts with one shared expert; the file states {more} under "
            f"sliding_window {window}")
    moe = params["layers_moe"]
    router = moe["w_router"].shape[-1]
    held = jax.tree.leaves(moe["w_up"])[0].shape[1]
    if router != (more.get("router_width") or more["n_experts"]) or held != more["n_experts"]:
        raise ValueError(
            f"the served router has {router} outputs over {held} held experts, "
            f"the file states {more.get('router_width')} over {more['n_experts']}")
    ids = jnp.asarray(token_ids, jnp.int32)
    eps = float(spec["rms_eps"])
    moe_own = {k: v for k, v in moe.items() if k not in EXPERT_LEAVES}
    seen = {"attn": 0, "D": 0, "E": 0}
    with jax.default_matmul_precision("highest"):
        x = params["embed"][ids].astype(jnp.float32) * math.sqrt(spec["d_model"])
        for kind in kinds:
            counted = "attn" if kind in "W*" else kind
            i, seen[counted] = seen[counted], seen[counted] + 1
            if kind in "W*":
                w = _take_layer(params["layers_attn"], i)
                part = attention_part(
                    _norm(x, w["attn_norm"], eps), w, n_heads=spec["n_heads"],
                    n_kv_heads=spec["n_kv_heads"], head_dim=spec["head_dim"],
                    theta=float(spec["rope_theta"]), eps=eps,
                    window=window if kind == "W" else None, rotary=kind == "W")
            elif kind == "D":
                w = _take_layer(params["layers_mlp"], i)
                part = _swiglu(
                    _norm(x, w["mlp_norm"], eps), w["w_gate"], w["w_up"], w["w_down"])
            else:
                w = _take_layer(moe_own, i)
                part = experts(_norm(x, w["mlp_norm"], eps), moe, w, i, more)
            x = one_at_a_time(x + _norm(part, w["post_norm"], eps))
        return _norm(x, params["final_norm"], eps)


def forward(params: dict, spec: dict, token_ids) -> jax.Array:
    """Logits [T, V] in float32 for one sequence of token ids: ``logits`` of
    every row of ``hidden``."""
    return logits(params, spec, hidden(params, spec, token_ids))


# What is compared, and at which limit. As for every routed model here, the
# worst position cannot be held to a rounding limit: the program computes in
# bfloat16, the rounding of the hidden state moves the router's scores a
# little, and where a position's 8th and 9th expert of 128 nearly tie it picks
# another than this float32 reference; a flip moves its position by one of 8
# normalised weights times 2.826. Here the flips show as a SECOND MODE and not
# as a tail: four fifths of the positions read about 0.01 and a fifth read
# over 0.1 (p90 0.154-0.166), because nothing carries a flip on (no recurrent
# state; a later position sees an earlier one's flip only through its keys and
# values, averaged over thousands), so both medians sit in the lower mode and
# are steady to a twentieth over the seeds.
#
# Readings on the chip at the cell's widths (one v5e, published layers 1-5 as
# `WDWE*EWEWE`, all 128 experts held, an eighth of the vocabulary), AT THE
# CELL'S LENGTHS: 3,136 positions of which the last 64 are decoded through the
# cache, in 4,096 slots, in blocks of 512 (six blocks of prefill through the
# cache at a traced start under each kind's mask, then the decode kernel under
# each kind's sweep plan); the window is 2,048, so 1,024 prefilled positions
# and every decoded one are past it. One scratch script on an engine of its
# own, which runs `parity.errors_blocked` itself (my chip run A, PR 48, 6.7
# chip-minutes; PERF.md section 6), each statistic as lowest-highest over the
# seeds, drawn at random below 2^31 + 2^28:
#
#   sound, bfloat16 weights and cache as the file states (12 seeds):
#     worst 0.332-0.393, median 0.01013-0.01083, decoded median
#     0.00951-0.01007, median of the prefilled positions past the window
#     (2,048-3,071) 0.00967-0.01016, before it 0.01055-0.01164; p90
#     0.154-0.166, share of positions over 0.1 0.179-0.210.
#   control, one precision lower: int8 weights AND int8 activations in every
#   product but the grouped ones (`LLMC_QUANT=int8` with `LLMC_W8A8=1`, the
#   nearest mode below bfloat16 the program runs; this reference reads the same
#   dequantized tree), 6 seeds: median 0.1052-0.1095, past the window
#   0.1030-0.1099, decoded median 0.0340-0.1208 (a median of 64 positions
#   between two modes swings), worst 0.372-0.403: fails the median, the group
#   past the window and the decoded median on every seed; its worst position is
#   NOT separated from the sound runs' (0.372 lies under 0.393).
#   control, the window layers computed WITHOUT their window (the program
#   under `sliding_window` 2^20 over the same tree: both masks and both sweep
#   plans see everything), 6 seeds: decoded median 0.653-0.667, the prefilled
#   positions past the window 0.509-0.536 (before it the sound runs' numbers
#   to the last digit), worst 0.718-0.757; the median over ALL positions only
#   0.017-0.033, a third of them being past the window: it is the two groups
#   that hold the window, the prefilled one the masks, the decoded one the
#   decode kernel's plan.
#   control, rotary embedding applied on the full layer too (`rotary` true
#   over the same tree), 6 seeds: median 0.147-0.161, decoded median
#   0.128-0.172, past the window 0.133-0.145, worst 0.359-0.409: fails all
#   three medians on every seed.
#
# The same readings through the harness's own comparison: `benchmark/
# controls.py` runs `parity.check_engine`, and so `compared()` below, on sound
# seeds and on each control (those three and a fourth, ANOTHER TOKEN at every
# decoded position), and exits 0 only if every sound seed reads `ok` and every
# control is refused by a limit of `compared()`. On the chip at the same
# lengths (my chip run E, PR 48; the first six of run A's seeds): sound `ok`
# on 6 of 6 with the numbers above; no window, rotary on the full layer and
# the lower precision each refused on 6 of 6 by the medians named above;
# another token refused on 6 of 6 by the worst position, 1.137-1.173, and the
# decoded median, 1.096-1.118 (`benchmark/tests/test_controls.py` asks the
# same of the CI-size model in float32 on the CPU).
#
# So the worst position is held only against what is not this model at all (a
# position computed from another token, the fourth control, reads 1.14-1.17;
# two unrelated rows would read the square root of 2): TOLERANCE 0.8 is twice
# the sound runs' largest and 0.7 of that control's smallest, and it fails
# none of the first three controls, whose worst positions (0.36-0.41,
# 0.72-0.76) lie among the sound runs' or under it. The three medians are held between
# their readings: MEDIAN_LIMIT and PAST_WINDOW_MEDIAN_LIMIT 0.03 are 2.8 and
# 3.0 times the sound runs' largest, and the lower precision's smallest is 3.5
# and 3.4 times them (the missing window reads 17 times the second);
# DECODED_MEDIAN_LIMIT 0.02 is twice the sound runs' largest and the lower
# precision's smallest of six is 1.7 times it (the missing window 33 times,
# rotary on the full layer 6 times).
TOLERANCE = 0.8
MEDIAN_LIMIT = 0.03
DECODED_MEDIAN_LIMIT = 0.02
PAST_WINDOW_MEDIAN_LIMIT = 0.03
PAST_WINDOW = 2048   # the published window: the prefilled positions from here
                     # on are a group of their own, where a run has them


def compared(err, n_prefill: int) -> dict:
    """The worst position against TOLERANCE, which is held against
    arithmetic alone (a position computed from another token reads 1.14 and
    more on the chip: the control ``another-token``) and NOT against a lower
    precision, a missing window or a rotary rule on the wrong kind, whose
    worst positions lie among the sound runs' or under the limit; the median
    position against MEDIAN_LIMIT (a lower precision, a rotary rule on the
    wrong kind of layer: an error in every position), the median of the
    decoded positions, each through the cache under each kind's sweep plan,
    against DECODED_MEDIAN_LIMIT (a broken cache, a window the decode sweep
    does not keep, a lower precision), and where the prefill reaches past
    the published window of 2,048 the median of its positions from there on
    against PAST_WINDOW_MEDIAN_LIMIT (a window the masks do not keep).

    Lengths the limits were read at: 3,136 positions, the last 64 decoded, in
    4,096 slots, in blocks of 512 (PR 48, on the chip). Both medians sit in
    the lower of two modes (a fifth of the positions carry a routing flip and
    read over 0.1); rounding accumulates along a sequence and the share of
    flipped positions with it, so a reading at another length is another
    number: read sound runs and the three controls there first."""
    out = {
        "rel_err_max": [float(err.max()), TOLERANCE],
        "rel_err_median": [float(np.median(err)), MEDIAN_LIMIT],
        "rel_err_decoded_median": [
            float(np.median(err[n_prefill:])), DECODED_MEDIAN_LIMIT],
    }
    if n_prefill > PAST_WINDOW:
        out["rel_err_past_window_median"] = [
            float(np.median(err[PAST_WINDOW:n_prefill])), PAST_WINDOW_MEDIAN_LIMIT]
    return out
