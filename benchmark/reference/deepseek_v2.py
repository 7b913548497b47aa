"""Plain float32 reference forward for the ``deepseek_v2`` block.

Written from the published ``config.json`` and modelling code of
DeepSeek-V2 (arXiv 2405.04434), independent of ``models/transformer.py``: it
imports nothing from ``llm_consensus_tpu``. Straightforward ``jax.numpy`` in
float32 under ``jax.default_matmul_precision("highest")``: no kernels, no
cache, no absorption, no batching, no scan. With ``h`` the normed hidden
state of one token:

  * **Queries.** ``c_q = RMSNorm(h · W_qa)``; ``q = c_q · W_qb``, each head
    split into ``q_nope`` and ``q_rope``; ``q_rope`` is rotated.
  * **Latent.** ``[c_kv ; k_r] = h · W_kva``; ``c_kv ← RMSNorm(c_kv)``;
    ``k_rope`` = rotated ``k_r``, one head shared by every query head.
  * **Attention, always in the prefill form, for every position.**
    ``[k_nope ; v]_head = c_kv · W_kvb``; scores ``(q_nope · k_nope + q_rope
    · k_rope) · scale``, causal softmax, ``o_head = Σ p · v``, ``out =
    concat(o) · W_o``.
  * **Scale.** ``(qk_nope + qk_rope)^-0.5 · m²``, ``m = 0.1 · mscale_all_dim
    · ln(factor) + 1``. The rotary table is YaRN's: per frequency a blend of
    ``θ^(-2i/d)`` and the same ÷ ``factor``, by the linear ramp between the
    dimensions that ``beta_fast`` and ``beta_slow`` turns over the original
    context give; cos and sin times ``mscale(factor, mscale) /
    mscale(factor, mscale_all_dim)``.
  * **Experts** (every layer after the first ``n_dense_layers``). ``s =
    softmax(h · W_g)`` over the router's whole width; a group's score is its
    largest ``s``; the best ``groups_per_token`` groups stay, the rest are
    masked to 0; the ``experts_per_token`` largest ``s`` among those are
    chosen; weights are those ``s``, not renormalised (``norm_topk`` false),
    times ``routed_scale``. ``y = Σ_chosen w_e · SwiGLU_e(h) +
    SwiGLU_shared(h)``. The leading layers have a dense SwiGLU of ``d_ff``.
  * Pre-norm residual blocks, final norm, untied head.

**The share.** The served tree holds ``n_experts`` experts, those numbered
``[first_expert, first_expert + n_experts)`` of the router's
``router_width`` outputs: one chip's share of an expert-parallel layer
(``deployment`` in the configuration's file). The reference is given the
same share: it routes over the whole width and loops over the HELD experts,
one at a time, each expert's weights upcast alone; an expert outside the
share adds nothing, the shared experts count once.

Departure from the checkpoint: rotary pairs are half-split (i, i + d/2)
where the published code interleaves (2i, 2i + 1); with random weights the
pairing is immaterial as long as program and reference pair alike.

It reads the program's layout: stacks ``layers_dense`` (leading dense
layers) and ``layers``, leaves ``attn_norm, mlp_norm, wq_a, q_norm, wq_b,
wkv_a, kv_norm, wkv_b, wo`` and ``w_gate, w_up, w_down`` (dense) or
``w_router [D, R]``, ``w_gate, w_up [E, D, F]``, ``w_down [E, F, D]``,
``ws_gate, ws_up, ws_down`` (routed); every size comes from the model's
entry in the configuration file (``more_fields``). On the chip it runs
beside 13-14 GB of served state: every product is a small jitted piece, so
its own peak is one upcast weight (at most 0.34 GB, ``wo``) plus a block of
scores, and the sequence and the vocabulary are taken in blocks.

What is compared (``compared``), with the readings behind each limit, is at
the bottom. ``forward`` leaves the smallest routing margin of each position
of its last call in ``LAST_MARGINS``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.decoder import (
    _take_layer, dense, one_at_a_time, rms_norm)

FAMILIES = ("deepseek_v2",)
STORED_LEAVES = (
    ("layers", "wq_b"), ("layers", "wkv_b"), ("layers", "wo"),
    ("layers", "w_gate"), ("layers", "w_down"), ("layers", "ws_up"),
    ("layers_dense", "w_up"),
)
HEAD_BLOCK = 16      # query heads a block of scores
QUERY_BLOCK = 512    # query positions a block of scores
VOCAB_BLOCK = 16384  # columns of the head a block of logits

# Per position, the smallest gap of its last forward between what routing
# kept and what it passed over (min over expert layers): "expert" between
# the last chosen score and the best score left among the kept groups,
# "group" between the last kept group's score and the best group left. In
# units of the softmax score. None before any call.
LAST_MARGINS = None


@jax.jit
def _mm(x, w):
    """x · w with the stored weight upcast alone."""
    return x @ dense(w)


@jax.jit
def _norm(x, w, eps):
    return rms_norm(x, dense(w), eps)


@jax.jit
def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ dense(w_gate)) * (h @ dense(w_up))) @ dense(w_down)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim, theta, factor, beta_fast, beta_slow, original_max):
    """YaRN's inverse frequencies [dim/2], as the published code's
    ``DeepseekV2YarnRotaryEmbedding`` computes them (numpy, float32)."""
    def correction_dim(turns):
        return dim * math.log(original_max / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inter = extra / factor
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def rotary_tables(more: dict, theta: float, t: int):
    """cos, sin [T, qk_rope_dim / 2]."""
    dim = more["qk_rope_dim"]
    yarn = more.get("rope_yarn")
    ratio = 1.0
    if yarn:
        factor, beta_fast, beta_slow, mscale, mscale_all_dim, orig = yarn
        inv = yarn_inv_freq(dim, theta, factor, beta_fast, beta_slow, orig)
        ratio = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)
    else:
        inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    ang = np.arange(t, dtype=np.float32)[:, None] * inv[None, :]
    return jnp.asarray(np.cos(ang) * ratio), jnp.asarray(np.sin(ang) * ratio)


def softmax_scale(more: dict) -> float:
    scale = (more["qk_nope_dim"] + more["qk_rope_dim"]) ** -0.5
    yarn = more.get("rope_yarn")
    if yarn:
        scale *= yarn_mscale(yarn[0], yarn[4]) ** 2
    return scale


def rotate(x, cos, sin):
    """Half-split rotary embedding of x [T, H, d] by cos/sin [T, d/2]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


@partial(jax.jit, static_argnames=("scale",))
def _attend(q_nope, q_rope, k_nope, k_rope, v, q0, scale):
    """Causal attention of a block of query positions (first one ``q0``) and
    a block of heads, prefill form. q_* [Tq, Hb, ·], k_nope/v [T, Hb, ·],
    k_rope [T, rope] shared by the heads."""
    scores = (
        jnp.einsum("thd,shd->hts", q_nope, k_nope)
        + jnp.einsum("thr,sr->hts", q_rope, k_rope)
    ) * scale
    tq, t = q_nope.shape[0], k_nope.shape[0]
    causal = jnp.arange(t)[None, :] <= (q0 + jnp.arange(tq))[:, None]
    p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hts,shd->thd", p, v)


def attention_block(x, w, spec: dict, cos, sin):
    """The attention half of a block on x [T, D], residual included."""
    more = spec["more_fields"]
    t, n_heads, eps = x.shape[0], spec["n_heads"], float(spec["rms_eps"])
    nope, rope, vdim, rank = (
        more["qk_nope_dim"], more["qk_rope_dim"], more["v_head_dim"],
        more["kv_lora_rank"])
    h = _norm(x, w["attn_norm"], eps)
    c_q = _norm(_mm(h, w["wq_a"]), w["q_norm"], eps)
    q = _mm(c_q, w["wq_b"]).reshape(t, n_heads, nope + rope)
    q_nope, q_rope = q[..., :nope], rotate(q[..., nope:], cos, sin)
    ckr = _mm(h, w["wkv_a"])
    c_kv = _norm(ckr[:, :rank], w["kv_norm"], eps)
    k_rope = rotate(ckr[:, None, rank:], cos, sin)[:, 0]
    kv = _mm(c_kv, w["wkv_b"]).reshape(t, n_heads, nope + vdim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = softmax_scale(more)
    out = []
    for q0 in range(0, t, QUERY_BLOCK):
        rows = slice(q0, min(q0 + QUERY_BLOCK, t))
        heads = [
            _attend(q_nope[rows, hb:hb + HEAD_BLOCK], q_rope[rows, hb:hb + HEAD_BLOCK],
                    k_nope[:, hb:hb + HEAD_BLOCK], k_rope, v[:, hb:hb + HEAD_BLOCK],
                    q0, scale=scale)
            for hb in range(0, n_heads, HEAD_BLOCK)
        ]
        out.append(jnp.concatenate(heads, axis=1))
    a = jnp.concatenate(out, axis=0).reshape(t, n_heads * vdim)
    return x + _mm(a, w["wo"])


@partial(jax.jit, static_argnames=("top_k", "n_groups", "groups_per_token"))
def route(h, w_router, top_k, n_groups, groups_per_token):
    """Chosen experts [T, k], their scores [T, k], and the two margins [T]
    (expert choice, group choice), as the published gate computes them."""
    s = jax.nn.softmax(h @ dense(w_router), axis=-1)        # [T, R]
    t, r = s.shape
    group_scores = s.reshape(t, n_groups, r // n_groups).max(axis=-1)
    ranked = jnp.sort(group_scores, axis=-1)[:, ::-1]
    if groups_per_token < n_groups:
        group_margin = ranked[:, groups_per_token - 1] - ranked[:, groups_per_token]
    else:
        group_margin = jnp.full((t,), jnp.inf)
    _, top_groups = jax.lax.top_k(group_scores, groups_per_token)
    keep = jnp.any(
        jnp.arange(n_groups)[None, None, :] == top_groups[:, :, None], axis=1)
    masked = jnp.where(jnp.repeat(keep, r // n_groups, axis=1), s, 0.0)
    scores, idx = jax.lax.top_k(masked, top_k + 1)
    return idx[:, :top_k], scores[:, :top_k], scores[:, top_k - 1] - scores[:, top_k], group_margin


def experts(h, w, more: dict):
    """The routed expert layer on h [T, D], as this share computes it, and
    the two routing margins [T]."""
    idx, scores, m_expert, m_group = route(
        h, w["w_router"], more["experts_per_token"],
        more.get("n_expert_groups", 1), more.get("groups_per_token", 1))
    if more.get("norm_topk", True):
        scores = scores / jnp.sum(scores, axis=-1, keepdims=True)
    scores = scores * more.get("routed_scale", 1.0)
    first = more.get("first_expert", 0)
    out = jnp.zeros_like(h)
    for e in range(more["n_experts"]):          # the held experts, one at a time
        w_e = [jax.tree.map(lambda a: a[e], w[k]) for k in ("w_gate", "w_up", "w_down")]
        gate = jnp.sum(jnp.where(idx == first + e, scores, 0.0), axis=-1)
        out = out + gate[:, None] * _swiglu(h, *w_e)
    if more.get("n_shared_experts"):
        out = out + _swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"])
    return out, m_expert, m_group


def hidden(params: dict, spec: dict, token_ids) -> jax.Array:
    """The final-normed hidden states [T, D] in float32 for one sequence of
    token ids; ``spec`` is the model's whole entry in the configuration
    file. Leaves the routing margins of the call in ``LAST_MARGINS``."""
    global LAST_MARGINS
    more = spec.get("more_fields") or {}
    if spec["family"] not in FAMILIES or not more.get("kv_lora_rank"):
        raise ValueError(
            f"no latent-attention reference for family {spec['family']!r} "
            f"with more_fields {sorted(more)}; have {FAMILIES}")
    router = params["layers"]["w_router"].shape[-1]
    held = jax.tree.leaves(params["layers"]["w_gate"])[0].shape[1]
    if router != (more.get("router_width") or more["n_experts"]) or held != more["n_experts"]:
        raise ValueError(
            f"the served router has {router} outputs over {held} held experts, "
            f"the file states {more.get('router_width')} over {more['n_experts']}")
    n_dense = more.get("n_dense_layers", 0)
    if n_dense != (jax.tree.leaves(params["layers_dense"])[0].shape[0]
                   if "layers_dense" in params else 0):
        raise ValueError(f"the file states {n_dense} leading dense layers")
    ids = jnp.asarray(token_ids, jnp.int32)
    eps = float(spec["rms_eps"])
    with jax.default_matmul_precision("highest"):
        cos, sin = rotary_tables(more, float(spec["rope_theta"]), ids.shape[0])
        x = params["embed"][ids].astype(jnp.float32)
        m_expert = m_group = jnp.full((ids.shape[0],), jnp.inf)
        for i in range(spec["n_layers"]):
            routed = i >= n_dense
            w = _take_layer(
                params["layers"] if routed else params["layers_dense"],
                i - n_dense if routed else i)
            x = attention_block(x, w, spec, cos, sin)
            h = _norm(x, w["mlp_norm"], eps)
            if routed:
                y, me, mg = experts(h, w, more)
                m_expert, m_group = jnp.minimum(m_expert, me), jnp.minimum(m_group, mg)
            else:
                y = _swiglu(h, w["w_gate"], w["w_up"], w["w_down"])
            x = one_at_a_time(x + y)
        x = _norm(x, params["final_norm"], eps)
    LAST_MARGINS = {
        "expert": np.asarray(m_expert, np.float64),
        "group": np.asarray(m_group, np.float64),
    }
    return x


def logits(params: dict, spec: dict, rows) -> jax.Array:
    """Logits [n, V] in float32 of ``rows`` [n, D], any rows of ``hidden``'s:
    the head, in blocks of its columns."""
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


# What is compared, and at which limit. The worst position of this model
# cannot be held to a rounding limit: the program computes in bfloat16, the
# rounding of the hidden state moves router scores a little, and where a
# position's last kept and first passed-over expert (or group: a group
# near-tie moves three candidates at once) nearly tie it then picks another
# than this float32 reference; with `routed_scale` 16 on unnormalised weights
# one such pick on a HELD expert moves that position by 0.2-0.8, and through
# attention every later position by a little.
#
# Readings on the chip at the cell's widths (one v5e, 1 dense + 5 expert
# layers, 20 of 160 experts held, 1,024 positions of which the last 64 are
# decoded through the cache; my chip runs a1-a3, PR 31; PERF.md section 6),
# each statistic as lowest-highest over the seeds:
#
#   sound, bfloat16 as the file states (15 seeds: 8 read by a scratch script
#   on an engine of its own, 7 by the cell's own runs after the window):
#     worst 0.55-0.86, p99 0.42-0.52, p90 0.16-0.19, share of positions over
#     0.1 0.15-0.20, median 0.0328-0.0415, decoded median 0.0284-0.0382,
#     position 0 (no context, no flip) 0.0098-0.0109.
#   control, one precision lower: int8 weights AND int8 activations
#   (`LLMC_QUANT=int8` with `LLMC_W8A8=1`, the nearest mode below bfloat16
#   the program runs), 8 seeds: worst 0.70-0.90, p90 0.34-0.37, share over
#   0.1 0.96-1.00, median 0.135-0.151, decoded median 0.119-0.195.
#   int8 WEIGHTS alone under the bfloat16 file (the control the issue named),
#   8 seeds: median 0.034-0.043, decoded median 0.034-0.040, p90 0.15-0.21:
#   NOT separated by any statistic (a per-channel int8 weight is about as
#   exact as a bfloat16 activation: position 0 reads 0.0105-0.0121); what
#   fails it is `stored_as_stated`, the harness's check of how the tree is
#   stored. The router's logits rounded to bfloat16, 8 seeds: median
#   0.037-0.044: not separated either; the flips come from the hidden
#   state's rounding, not the router's own.
#   The routing margins (`LAST_MARGINS`) do not help: positions whose own
#   margins are the largest quarter read the same median (0.036-0.040) as all
#   of them, because what they carry is the earlier positions' flips.
#
# So the worst position is held only against what is not this model at all
# (a position computed from another token reads the square root of 2:
# TOLERANCE, 40% above the sound runs' largest, 15% under 1.41), and the two
# medians are held between their readings: MEDIAN_LIMIT 0.07 is 1.7 times the
# sound runs' largest and the control's smallest is 1.9 times it;
# DECODED_MEDIAN_LIMIT 0.065 is 1.7 times the sound runs' largest and the
# control's smallest 1.8 times it (at CI size decode steps whose cache
# writes are dropped read 0.47-0.60 there).
TOLERANCE = 1.2
MEDIAN_LIMIT = 0.07
DECODED_MEDIAN_LIMIT = 0.065


def compared(err, n_prefill: int) -> dict:
    """The worst position against TOLERANCE (another token), the median
    position against MEDIAN_LIMIT (a lower precision, an error in every
    position), the median of the decoded positions against
    DECODED_MEDIAN_LIMIT (a broken cache, a lower precision).

    Lengths the limits were read at: 1,024 positions, the last 64 decoded,
    in 1,024 slots, taken whole (PR 31, on the chip). Flips accumulate along
    a sequence (each moves every later position a little), so the medians
    read at another length are other numbers: read them there first."""
    return {
        "rel_err_max": [float(err.max()), TOLERANCE],
        "rel_err_median": [float(np.median(err)), MEDIAN_LIMIT],
        "rel_err_decoded_median": [
            float(np.median(err[n_prefill:])), DECODED_MEDIAN_LIMIT],
    }
