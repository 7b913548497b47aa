"""Plain float32 reference forward for the ``nemotron_h`` stack.

Written from the published ``config.json`` of
NVIDIA-Nemotron-3-Super-120B-A12B-BF16 (``model_type`` ``nemotron_h``), the
Mamba-2 recurrence (arXiv 2405.21060) and the LatentMoE layer as the
configuration's keys state it, independent of ``models/transformer.py``,
``ops/ssm.py`` and ``ops/moe.py``: it imports nothing from
``llm_consensus_tpu``. Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching, no chunked scan, no sorted dispatch.

The pattern (``layer_kinds``, the published ``hybrid_override_pattern``) says
what each layer is; every layer is ONE part behind one norm and one add, with
``x`` [T, D] the residual stream, eps 1e-5 and a plain norm weight::

    x0 = embed[ids]                                    # no embedding scale
    x  = x + part(rms_norm(x; the layer's norm))       # every layer
    logits = rms_norm(x; final_norm) @ W_head          # untied head

  * ``M``, a Mamba-2 mixer on ``u`` [T, D] (no multiplier, no bias on the
    projections)::

        p = u @ W_in                                   # z | xs | B | C | dt
        xBC = silu(conv(xs | B | C))    # depthwise, causal, K taps and a bias, zeros before position 0
        dt = softplus(dt + dt_bias);  A = -exp(A_log)
        S_t = exp(dt_t A_h) S_{t-1} + dt_t xs_t[h] (outer) B_t[g],  S_-1 = 0,  g = h // (H / G)
        y_t[h] = S_t C_t[g] + D_h xs_t[h]
        out = (rms_norm over each of G slices of (y * silu(z)); times ssm_norm) @ W_out

    **The recurrence is the plain one**: one ``lax.scan`` over positions with
    the state ``[H, P, N]`` in float32, a position a step (``falcon_h1.mixer``
    of this package, every multiplier 1: the same equations, written once).
  * ``E``, the LatentMoE layer on ``h`` [T, D]::

        s = sigmoid(h @ W_g)                           # float32, the router's whole width
        chosen = the experts_per_token largest of (s + b)      # b: the stored correction bias; one group
        w = s[chosen] / sum(s[chosen]) * routed_scale  # the bias chooses and does not weigh
        u = h @ W_latent_in                            # D -> Z, the experts' width
        r = sum over chosen e of w_e * (relu(u @ W1_e)^2 @ W2_e)     # ungated, no bias
        out = r @ W_latent_out + relu(h @ W1_s)^2 @ W2_s     # the shared expert reads h, on the full width

  * ``*``, grouped-query attention on ``u``: ``q, k, v = u Wq, u Wk, u Wv``,
    causal softmax at scale ``head_dim^-1/2``, ``out = concat(heads) @ Wo``.
    No bias and **no rotary embedding**.

Departures from the published model, each also under ``assumed`` in the
configuration's file:

  * **No rotary embedding in attention.** The published ``nemotron_h``
    forward applies none (the mixers carry position); the config's
    ``rope_theta`` and ``partial_rotary_factor`` are keys nothing reads.
    There is no network here to read the modelling code again.
  * **No multi-token-prediction module** (``num_nextn_predict_layers`` 1,
    pattern ``*E``): it drafts, and adds nothing to the next-token logits
    that are compared here.
  * **The share.** The served tree holds ``n_experts`` routed experts, those
    numbered ``[first_expert, first_expert + n_experts)`` of the router's
    ``router_width`` outputs: one chip's share of an expert-parallel layer
    (``deployment`` in the configuration's file). The reference is given the
    same share as the file gives it: it routes over the whole width and
    loops over the HELD experts, one at a time; an expert outside the share
    adds nothing, ``W_latent_out`` is linear and without bias (so the shares
    of a deployment add up after it), the shared expert counts once.
  * Weights are read in the program's layout (``[contract, out]``; the
    convolution ``ssm_conv [C, K]`` with tap K-1 on the current position).

It reads the tree the engine serves: ``embed, final_norm, lm_head`` and a
stack a layer kind, each indexed by a layer's place WITHIN its kind:
``layers_ssm`` (``attn_norm, ssm_in, ssm_conv, ssm_conv_bias, ssm_dt_bias,
ssm_a_log, ssm_d, ssm_norm, ssm_out``), ``layers_moe`` (``mlp_norm, w_router
[D, R], router_bias [R], w_latent_in [D, Z], w_latent_out [Z, D], w_up [E,
Z, F], w_down [E, F, Z], ws_up [D, Fs], ws_down [Fs, D]``) and
``layers_attn`` (``attn_norm, wq, wk, wv, wo``). Every size comes from the
model's entry in the configuration file (the core fields and
``more_fields``). On the chip it runs beside about 12 GB of served state:
every product is a small jitted piece, the widest upcast weight is the
mixer's in-projection (0.30 GB) and the experts are upcast one at a time.

What is compared (``compared``), with the readings behind each limit, is at
the bottom.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.decoder import (
    _take_layer, attention, dense, one_at_a_time, rms_norm)
from benchmark.reference.falcon_h1 import mixer

FAMILIES = ("nemotron_h",)
STORED_LEAVES = (
    ("layers_ssm", "ssm_in"), ("layers_ssm", "ssm_out"),
    ("layers_moe", "w_up"), ("layers_moe", "w_down"),
    ("layers_moe", "w_latent_in"), ("layers_moe", "ws_up"),
    ("layers_attn", "wq"), ("layers_attn", "wo"),
)
VOCAB_BLOCK = 16384  # columns of the head a block of logits


@jax.jit
def _mm(x, w):
    return x @ dense(w)


@jax.jit
def _norm(x, w, eps):
    return rms_norm(x, dense(w), eps)


@jax.jit
def _relu2_mlp(h, w_up, w_down):
    """The ungated expert: ``relu(h W1)^2 W2``."""
    return jnp.square(jax.nn.relu(h @ dense(w_up))) @ dense(w_down)


@partial(jax.jit, static_argnames=("n_heads", "n_kv_heads", "head_dim"))
def attention_part(u, w, *, n_heads, n_kv_heads, head_dim):
    """Attention on the normed input ``u`` [T, D]: no rotary embedding."""
    t = u.shape[0]
    q = (u @ dense(w["wq"])).reshape(t, n_heads, head_dim)
    k = (u @ dense(w["wk"])).reshape(t, n_kv_heads, head_dim)
    v = (u @ dense(w["wv"])).reshape(t, n_kv_heads, head_dim)
    a = attention(q, k, v, None).reshape(t, n_heads * head_dim)
    return a @ dense(w["wo"])


@partial(jax.jit, static_argnames=("top_k", "norm_topk"))
def route(h, w_router, bias, routed_scale, *, top_k, norm_topk):
    """Chosen experts [T, k] and their weights [T, k]: sigmoid scores over
    the router's whole width, chosen by score + bias, weighed by the score."""
    s = jax.nn.sigmoid(h @ dense(w_router))
    _, idx = jax.lax.top_k(s + dense(bias), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * routed_scale


def experts(h, w, more: dict):
    """The LatentMoE layer on the normed input ``h`` [T, D], as this share
    computes it."""
    idx, weights = route(
        h, w["w_router"], w["router_bias"], float(more.get("routed_scale", 1.0)),
        top_k=more["experts_per_token"], norm_topk=more.get("norm_topk", True))
    first = more.get("first_expert", 0)
    u = _mm(h, w["w_latent_in"])
    r = jnp.zeros_like(u)
    for e in range(more["n_experts"]):          # the held experts, one at a time
        w_e = [jax.tree.map(lambda a: a[e], w[k]) for k in ("w_up", "w_down")]
        gate = jnp.sum(jnp.where(idx == first + e, weights, 0.0), axis=-1)
        r = r + gate[:, None] * _relu2_mlp(u, *w_e)
    return _mm(r, w["w_latent_out"]) + _relu2_mlp(h, w["ws_up"], w["ws_down"])


def hidden(params: dict, spec: dict, token_ids) -> jax.Array:
    """The final-normed hidden states [T, D] in float32 for one sequence of
    token ids; ``spec`` is the model's whole entry in the configuration
    file."""
    more = spec.get("more_fields") or {}
    kinds = more.get("layer_kinds") or ""
    if spec["family"] not in FAMILIES or len(kinds) != spec["n_layers"]:
        raise ValueError(
            f"no plain reference for family {spec['family']!r} with "
            f"layer_kinds {kinds!r} over {spec['n_layers']} layers; have {FAMILIES}")
    if (more.get("router_scoring") != "sigmoid_bias" or more.get("gated_experts", True)
            or more.get("rotary", True) or more.get("n_expert_groups", 1) != 1
            or more.get("activation") != "relu2" or not more.get("moe_latent")
            or more.get("n_shared_experts") != 1):
        raise ValueError(
            "this reference computes sigmoid_bias routing in one group, "
            "ungated relu2 experts in a latent width, one shared expert and "
            f"attention without rotary embedding; the file states {more}")
    router = params["layers_moe"]["w_router"].shape[-1]
    held = jax.tree.leaves(params["layers_moe"]["w_up"])[0].shape[1]
    if router != (more.get("router_width") or more["n_experts"]) or held != more["n_experts"]:
        raise ValueError(
            f"the served router has {router} outputs over {held} held experts, "
            f"the file states {more.get('router_width')} over {more['n_experts']}")
    ids = jnp.asarray(token_ids, jnp.int32)
    eps = float(spec["rms_eps"])
    ones = (1.0, jnp.ones((5,), jnp.float32), 1.0)
    seen = {"M": 0, "E": 0, "*": 0}
    with jax.default_matmul_precision("highest"):
        x = params["embed"][ids].astype(jnp.float32)
        for kind in kinds:
            i, seen[kind] = seen[kind], seen[kind] + 1
            if kind == "M":
                w = _take_layer(params["layers_ssm"], i)
                part = mixer(
                    _norm(x, w["attn_norm"], eps), w, ones,
                    heads=more["ssm_heads"], head_dim=more["ssm_head_dim"],
                    state=more["ssm_state"], groups=more["ssm_groups"], eps=eps)
            elif kind == "*":
                w = _take_layer(params["layers_attn"], i)
                part = attention_part(
                    _norm(x, w["attn_norm"], eps), w, n_heads=spec["n_heads"],
                    n_kv_heads=spec["n_kv_heads"], head_dim=spec["head_dim"])
            else:
                w = _take_layer(params["layers_moe"], i)
                part = experts(_norm(x, w["mlp_norm"], eps), w, more)
            x = one_at_a_time(x + part)
        return _norm(x, params["final_norm"], eps)


def logits(params: dict, spec: dict, rows) -> jax.Array:
    """Logits [n, V] in float32 of ``rows`` [n, D], any rows of ``hidden``'s:
    the untied head, in blocks of its columns."""
    with jax.default_matmul_precision("highest"):
        head = params["embed"].T if spec["tie_embeddings"] else params["lm_head"]
        cols = jax.tree.leaves(head)[0].shape[-1]
        return jnp.concatenate([
            _mm(rows, jax.tree.map(lambda a: a[..., c:c + VOCAB_BLOCK], head))
            for c in range(0, cols, VOCAB_BLOCK)], axis=-1)


def forward(params: dict, spec: dict, token_ids) -> jax.Array:
    """Logits [T, V] in float32 for one sequence of token ids: ``logits`` of
    every row of ``hidden``."""
    return logits(params, spec, hidden(params, spec, token_ids))


# What is compared, and at which limit. As for every routed model here, the
# worst position cannot be held to a rounding limit: the program computes in
# bfloat16, the rounding of the hidden state moves the router's scores a
# little, and where a position's 22nd and 23rd expert nearly tie (of 512
# sigmoid scores plus a bias, near-ties are common: position 0, which has no
# context to inherit a flip from, reads 0.011 on most seeds and 0.08-0.28 on
# some) it picks another than this float32 reference. A flip on a held expert
# moves its position by one of 22 normalised weights times 5, and through the
# mixers' states and the attention layer every later position a little.
#
# Readings on the chip at the cell's widths (one v5e, layers 0-10, 64 of 512
# experts held, an eighth of the vocabulary, 1,024 positions of which the last
# 64 are decoded through both caches, taken whole; my chip runs A and B, PR 41;
# PERF.md section 6), each statistic as lowest-highest over the seeds:
#
#   sound, bfloat16 weights and a float32 state as the file states (20 seeds:
#   12 read by a scratch script on an engine of its own, 8 by the cell's own
#   runs after their windows, chip runs A and B):
#     worst 0.303-0.405, median 0.0170-0.0246, decoded median (64 positions
#     behind the same flips: it swings) 0.0112-0.0451; over the scratch
#     script's twelve also p99 0.251-0.287, p90 0.116-0.142, share of
#     positions over 0.1 0.135-0.194, mean 0.046-0.055.
#   control, one precision lower: int8 weights AND int8 activations in every
#   product but the grouped ones (`LLMC_QUANT=int8` with `LLMC_W8A8=1`, the
#   nearest mode below bfloat16 the program runs), 6 seeds: worst 0.409-0.464,
#   p90 0.290-0.296, share over 0.1 0.953-0.969, median 0.178-0.185, decoded
#   median 0.175-0.215: fails both medians' limits, seven times over.
#   control, int8 ACTIVATIONS IN THE ROUTED EXPERTS alone (each row of each
#   grouped product rounded to int8 against its own largest value; the issue's
#   control), 6 seeds: median 0.0220-0.0316, mean 0.050-0.064, p90
#   0.121-0.146, worst 0.319-0.385: NOT separated (its lowest median lies
#   under the sound runs' highest; a limit between 0.0246 and 0.0220 does not
#   exist). The routed sum is one of three terms of an expert layer's output
#   beside the shared expert's and the residual, a twelfth of a token's
#   operations, and a row's int8 rounding (2^-8 of its largest value) is of
#   the size of the bfloat16 rounding (2^-9 of each value) that every other
#   product's activations already carry.
#   control, the router's logits rounded to bfloat16 (the issue's other
#   control), 6 seeds: median 0.0179-0.0224, worst 0.336-0.423: NOT separated
#   by any statistic, as `deepseek_v2` read: the flips come from the hidden
#   state's rounding, which the router reads in either case, not from the
#   router's own product.
#   int8 WEIGHTS alone under the bfloat16 file, 6 seeds: median 0.0191-0.0223:
#   not separated (a per-channel int8 weight is about as exact as a bfloat16
#   activation, and this reference reads the same dequantized tree); what
#   fails it is `stored_as_stated`, the harness's check of how the tree is
#   stored.
#
# So the worst position is held only against what is not this model at all (a
# position computed from another token reads the square root of 2): TOLERANCE
# 0.8 is twice the sound runs' largest, and 1.41 is 1.8 times it. The two
# medians are held between their readings: MEDIAN_LIMIT 0.06 is 2.4 times the
# sound runs' largest and the lower precision's smallest is 3.0 times it;
# DECODED_MEDIAN_LIMIT 0.09 is 2.0 times the sound runs' largest (a median of
# 64 positions, the widest-swinging statistic here) and the lower precision's
# smallest is 1.9 times it.
TOLERANCE = 0.8
MEDIAN_LIMIT = 0.06
DECODED_MEDIAN_LIMIT = 0.09


def compared(err, n_prefill: int) -> dict:
    """The worst position against TOLERANCE (another token), the median
    position against MEDIAN_LIMIT (a lower precision, an error in every
    position), the median of the decoded positions, each through both caches,
    against DECODED_MEDIAN_LIMIT (a broken cache or state, a lower
    precision).

    Lengths the limits were read at: 1,024 positions, the last 64 decoded, in
    1,024 slots, taken whole (PR 41, on the chip). Flips accumulate along a
    sequence (each moves every later position a little, through five mixers'
    states), so the medians read at another length are other numbers: read
    them there first."""
    return {
        "rel_err_max": [float(err.max()), TOLERANCE],
        "rel_err_median": [float(np.median(err)), MEDIAN_LIMIT],
        "rel_err_decoded_median": [
            float(np.median(err[n_prefill:])), DECODED_MEDIAN_LIMIT],
    }
