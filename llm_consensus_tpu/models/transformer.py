"""Generic decoder-only transformer in functional JAX.

One implementation serves every model family (llama/mistral/gemma/qwen2/
mixtral/deepseek_v2/falcon_h1/nemotron_h/solar_open2/afmoe) via static ``ModelConfig`` switches. This replaces the reference's
"compute layer" — three HTTP clients (/root/reference/internal/provider/
{openai,anthropic,google}.go) — with real on-device compute.

TPU-first design decisions:
  * Parameters are plain pytrees (nested dicts of arrays) with layers
    **stacked** on a leading axis; the layer loop is a ``lax.scan`` so XLA
    compiles one layer body regardless of depth (fast compiles, weight
    streaming during decode).
  * KV cache is a static-shaped [L, B, S_max, Hkv, dh] ring written with
    ``dynamic_update_slice`` — no shape changes between decode steps, so
    every step reuses the same compiled program.
  * All matmuls keep bf16 inputs with fp32 accumulation where it matters
    (softmax, norms, router, final logits).
  * Sharding is applied externally via ``parallel.sharding.param_axes``,
    which mirrors this module's pytree structure with logical axis names.

The DeepSeek-V2 block (``family="deepseek_v2"``; every size from the
published ``config.json``): pre-norm residual blocks, final norm, untied
head. Attention is latent (ops/latent_attention.py has the equations): the
cache holds ``c_kv ‖ k_rope`` (``kv_lora_rank + qk_rope_dim`` values a token
a layer); ``forward`` takes the prefill form for T > 1 and the absorbed form
for T = 1. The softmax scale is ``(qk_nope_dim + qk_rope_dim)^-0.5 · m²``
with ``m = 0.1 · mscale_all_dim · ln(factor) + 1``; the rotary table is
YaRN's (ops/rope.py ``yarn_inv_freq``), cos and sin multiplied by
``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``. The first
``n_dense_layers`` layers (stack ``layers_dense``) have a dense SwiGLU of
``d_ff``; the others (stack ``layers``) the routed expert layer of
ops/moe.py: ``s = softmax(h · W_g)`` over the router's whole width, the best
``groups_per_token`` of ``n_expert_groups`` groups by their largest ``s``,
the ``experts_per_token`` largest ``s`` among them, weights those ``s`` not
renormalised times ``routed_scale``, plus the shared experts. Departure
from the checkpoint's layout: rotary pairs are half-split (i, i + d/2) where
the published code interleaves (2i, 2i + 1); under random weights the
pairing is immaterial as long as program and reference pair alike.

The Falcon-H1 block (``family="falcon_h1"``, ``cfg.has_ssm``): every layer
runs a Mamba-2 mixer (ops/ssm.py has the equations) BESIDE grouped-query
attention on the same normed input, ``x + mixer + attention``, then a SwiGLU
MLP; the published fixed multipliers (muP) scale the embedding, the head, the
attention input, keys and output, the mixer's input, projection segments and
output, and the MLP's gate and output. The cache holds, beside keys and
values, a sub-tree ``"ssm"`` of per-ROW state: the recurrence's float32
state and the convolution's tail, which have no sequence axis. A recurrence
must know where a row's real tokens END inside a padded chunk as well as
where they start: ``forward(row_end=...)``.

The Nemotron-H stack (``family="nemotron_h"``, ``cfg.layer_kinds``): every
layer is ONE part behind ONE norm and ONE residual add, ``x + part(norm(x))``,
and the pattern says which: ``M`` a Mamba-2 mixer (ops/ssm.py, every
multiplier 1), ``E`` the LatentMoE layer (ops/moe.py: sigmoid scores chosen
with a correction bias, ungated relu2 experts in a latent width, one shared
expert on the full width), ``*`` grouped-query attention WITHOUT rotary
embedding (the mixers carry position). Each kind has a parameter stack of
its own (``layers_ssm``, ``layers_moe``, ``layers_attn``) and a cache of its
own length: keys and values ``[n_attn_layers, ...]``, state and tail
``[n_ssm_layers, ...]``, nothing for an expert layer. ``forward`` walks the
static pattern, unrolled, and gives each layer its index WITHIN its kind.

The Solar-Open2 stack (``family="solar_open2"``) is the same walk over two
more parts. A published layer there is two-part and pre-norm, ``x +=
mixer(norm(x)); x += experts(norm(x))``, which IS two one-part layers: its
period of four published layers is ``*EKEKEKE``. ``K`` is a delta-rule layer
(Kimi Delta Attention, ops/delta.py has the equations): stack ``layers_kda``,
and in the cache a matrix state ``[H, P, P]`` float32 and one convolution
tail over ``q | k | v`` a ROW a layer, under the same ``STATE_KEY`` sub-tree
and names as a mixer's (a stack has one kind of state). ``*`` there passes
its output through a learned gate before ``wo`` (``cfg.attn_out_gate``: ``o *
sigmoid(h W_gate)``, elementwise); ``E`` is the gated expert layer of
ops/moe.py under sigmoid scores with a correction bias.

The AFMoE stack (``family="afmoe"``, Trinity-Mini) walks two more kinds. ``W``
is attention under a window of its own beside ``*``: ``cfg.sliding_window``
is then the window of the ``W`` layers alone, which always apply the rotary
embedding (``cfg.rotary`` stays what ``*`` layers do: none there), and
``forward`` makes a mask and, on the decode route, a sweep plan ONCE A KIND
(``cfg.attn_kinds``), which ``_walk_kinds`` hands each attention layer by
its kind. Both kinds share the stack ``layers_attn`` and the cache's ``k`` /
``v`` leaves (same heads, same width: a layer's index is its place among the
attention layers), every layer holding the arena's whole width. ``D`` is a
dense gated MLP (stack ``layers_mlp``): a published leading dense layer's
second part. Queries and keys pass an RMS norm over the head's width before
rotary (``cfg.qk_norm``: one weight vector a projection for all heads), and a
one-part layer ends in a norm of its own (``cfg.post_norm``: ``x +
post_norm(part(norm(x)))``, the published sandwich).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from llm_consensus_tpu.analysis import sanitizer
from llm_consensus_tpu.models.config import ModelConfig
from llm_consensus_tpu.obs.scopes import scope
from llm_consensus_tpu.ops.attention import attention, make_attention_mask
from llm_consensus_tpu.ops.delta import kda
from llm_consensus_tpu.ops.mlp import gated_mlp
from llm_consensus_tpu.ops.moe import moe_block
from llm_consensus_tpu.ops.quant import (
    STATE_KEY, is_quantized, kv_layer, kv_read, kv_write_rows, qeinsum)
from llm_consensus_tpu.ops.norms import rms_norm
from llm_consensus_tpu.ops.latent_attention import latent_attention
from llm_consensus_tpu.ops.rope import (
    apply_rope, rope_angles, rope_inv_freq, yarn_inv_freq, yarn_mscale)
from llm_consensus_tpu.ops.ssm import mixer as ssm_mixer


class AttentionRoutes:
    """Trace-time record of the attention path ``forward`` chose.

    Whether a model's prefill and decode run the Pallas kernels or XLA
    attention is decided inside ``forward`` — from shapes, mesh and the
    requested impl — and shows in nothing the program returns (dh = 64
    models, for one, legitimately decode through XLA). Every cached
    ``forward`` books its choice here while it is traced, so a jitted
    step counts once per compiled program, not per call. Phase is
    ``"decode"`` for T = 1 and ``"prefill"`` otherwise; path is
    ``"pallas"``, ``"xla"`` or ``"ring"``.
    """

    def __init__(self) -> None:
        self._lock = sanitizer.make_lock("models.attention_routes")
        self._seen: dict = {}

    def note(self, model: str, phase: str, path: str) -> None:
        with self._lock:
            paths = self._seen.setdefault(model, {}).setdefault(phase, {})
            paths[path] = paths.get(path, 0) + 1

    def snapshot(self, model: str) -> dict:
        """``{phase: {path: programs traced}}`` for ``model``."""
        with self._lock:
            return {
                phase: dict(paths)
                for phase, paths in self._seen.get(model, {}).items()
            }

    def reset(self) -> None:
        with self._lock:
            self._seen.clear()


attention_routes = AttentionRoutes()


# -- parameter init ----------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16,
                leaf_hook=None, shardings: Optional[dict] = None) -> dict:
    """Random-init parameter pytree (layers stacked on axis 0; a family
    with leading dense layers has two stacks, ``layers_dense`` and then
    ``layers``; a family whose every layer is one part, ``cfg.layer_kinds``,
    has a stack for each kind it has: ``layers_ssm``, ``layers_moe``,
    ``layers_attn``, ``layers_kda``, ``layers_mlp``).

    ``shardings`` (a tree of ``jax.sharding.Sharding`` shaped like the
    result: ``parallel.sharding.param_shardings``) makes every leaf
    directly under its sharding, one jitted program a leaf with that
    ``out_shardings``: each device generates its own shard, so no device
    ever holds a leaf whole unless its spec says so, and nothing lands on
    a device outside the shardings' mesh. A 14.5 GB bf16 tree for a tp=2
    slice then costs each of its chips half, where init-then-shard built
    all of it on the default device first. The values do not depend on the
    sharding (the threefry generator is partitionable and nothing here
    reduces), nor on the hook below.

    ``leaf_hook(name, array) -> array`` transforms each leaf AS it is
    created — ops/quant.init_params_quantized uses it to quantize
    leaf-by-leaf so peak HBM is the quantized tree plus ONE bf16 leaf,
    not the full bf16 tree (the difference between an 8B random init
    fitting one 16 GB chip and OOMing before quantization starts). The
    key sequence is independent of the hook, so hooked and post-hoc
    quantization produce identical values.
    """
    keys = iter(jax.random.split(key, 32))
    # Leaf names are unique within the top level and within a stack;
    # ``stack`` below points this at the stack it is making.
    sharding_of = dict(shardings or {})

    def make(name, fn, *args):
        # Jitted so XLA fuses normal→scale→astype into one kernel that
        # writes ``dtype`` directly: the eager form materializes the
        # float32 intermediate, and on an 8B model that is a 7.5 GB
        # transient PER STACKED LEAF — the difference between the
        # streamed-quantized init fitting one 16 GB chip or not.
        # Values are identical (same op chain, same key).
        w = jax.jit(fn, out_shardings=sharding_of.get(name))(*args)
        return leaf_hook(name, w) if leaf_hook is not None else w

    def normal(k, shape, std, name):
        return make(
            name,
            lambda kk: (
                jax.random.normal(kk, shape, jnp.float32) * std
            ).astype(dtype),
            k,
        )

    def norm(shape, name):
        # offset parameterization: stored weights are (w - offset), init 0
        fill = jnp.zeros if cfg.norm_offset else jnp.ones
        return make(name, lambda: fill(shape, dtype))

    d, dh, hq, hkv, f = (
        cfg.d_model, cfg.head_dim, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
    )
    proj_std = d ** -0.5

    def attn_leaves(l: int) -> dict:
        out = {
            "wq": normal(next(keys), (l, d, hq * dh), proj_std, "wq"),
            "wk": normal(next(keys), (l, d, hkv * dh), proj_std, "wk"),
            "wv": normal(next(keys), (l, d, hkv * dh), proj_std, "wv"),
            "wo": normal(next(keys), (l, hq * dh, d), (hq * dh) ** -0.5, "wo"),
        }
        if cfg.attn_out_gate:
            out["w_ogate"] = normal(
                next(keys), (l, d, hq * dh), proj_std, "w_ogate")
        if cfg.qk_norm:
            out["q_head_norm"] = norm((l, dh), "q_head_norm")
            out["k_head_norm"] = norm((l, dh), "k_head_norm")
        return out

    def dense_leaves(l: int) -> dict:
        return {
            "w_gate": normal(next(keys), (l, d, f), proj_std, "w_gate"),
            "w_up": normal(next(keys), (l, d, f), proj_std, "w_up"),
            "w_down": normal(next(keys), (l, f, d), f ** -0.5, "w_down"),
        }

    def routed_leaves(l: int) -> dict:
        """The expert layer's leaves: the router over its whole width, the
        held experts (three matrices, or two when ungated) at the model's
        width or at the latent's, the shared expert."""
        e, fe = cfg.n_experts, cfg.expert_width
        z, fs = cfg.moe_latent or d, cfg.shared_width
        names = ("w_gate", "w_up") if cfg.gated_experts else ("w_up",)
        out = {"w_router": normal(
            next(keys), (l, d, cfg.n_router), proj_std, "w_router")}
        if cfg.router_scoring == "sigmoid_bias":
            # A stored leaf: zero in a fresh model, moved by the published
            # training's load balancing; here random and large enough to
            # change the choice (a sigmoid's scores lie in (0, 1)).
            out["router_bias"] = normal(
                next(keys), (l, cfg.n_router), 0.1, "router_bias")
        if cfg.moe_latent:
            out["w_latent_in"] = normal(
                next(keys), (l, d, z), proj_std, "w_latent_in")
            out["w_latent_out"] = normal(
                next(keys), (l, z, d), z ** -0.5, "w_latent_out")
        for name in names:
            out[name] = normal(next(keys), (l, e, z, fe), z ** -0.5, name)
        out["w_down"] = normal(next(keys), (l, e, fe, z), fe ** -0.5, "w_down")
        if cfg.n_shared_experts:
            for name in names:
                out["ws" + name[1:]] = normal(
                    next(keys), (l, d, fs), proj_std, "ws" + name[1:])
            out["ws_down"] = normal(next(keys), (l, fs, d), fs ** -0.5, "ws_down")
        return out

    def stack(name: str, l: int, routed: bool) -> dict:
        """``l`` layers stacked on axis 0, with a dense or a routed MLP."""
        sharding_of.update((shardings or {}).get(name, {}))
        layers: dict = {
            "attn_norm": norm((l, d), "attn_norm"),
            "mlp_norm": norm((l, d), "mlp_norm"),
        }
        if cfg.is_latent:
            qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
            qk, rope, v = cfg.qk_nope_dim + cfg.qk_rope_dim, cfg.qk_rope_dim, cfg.v_head_dim
            layers.update({
                "wq_a": normal(next(keys), (l, d, qr), proj_std, "wq_a"),
                "q_norm": norm((l, qr), "q_norm"),
                "wq_b": normal(next(keys), (l, qr, hq * qk), qr ** -0.5, "wq_b"),
                "wkv_a": normal(next(keys), (l, d, kr + rope), proj_std, "wkv_a"),
                "kv_norm": norm((l, kr), "kv_norm"),
                "wkv_b": normal(
                    next(keys), (l, kr, hq * (cfg.qk_nope_dim + v)), kr ** -0.5,
                    "wkv_b"),
                "wo": normal(next(keys), (l, hq * v, d), (hq * v) ** -0.5, "wo"),
            })
        else:
            layers.update(attn_leaves(l))
        if cfg.qkv_bias:
            for name, width in (("bq", hq * dh), ("bk", hkv * dh), ("bv", hkv * dh)):
                layers[name] = make(name, lambda w=width: jnp.zeros((l, w), dtype))
        if cfg.has_ssm:
            layers.update(_init_mixer(cfg, l, keys, make, normal, norm, dtype))
        if routed:
            layers.update(routed_leaves(l))
        else:
            layers.update(dense_leaves(l))
        return layers

    def kind_stack(name: str, l: int, norm_name: str, leaves) -> dict:
        """``l`` one-part layers of one kind: the part's norm, named as the
        half that reads it names it, the part's leaves, and the norm the
        part ends in where the model has one."""
        sharding_of.update((shardings or {}).get(name, {}))
        out = {norm_name: norm((l, d), norm_name), **leaves(l)}
        if cfg.post_norm:
            out["post_norm"] = norm((l, d), "post_norm")
        return out

    if cfg.layer_kinds:
        makers = {
            "M": ("layers_ssm", "attn_norm", lambda l: _init_mixer(
                cfg, l, keys, make, normal, norm, dtype)),
            "E": ("layers_moe", "mlp_norm", routed_leaves),
            # both attention kinds in one stack, in the pattern's order
            "*W": ("layers_attn", "attn_norm", attn_leaves),
            "K": ("layers_kda", "attn_norm", lambda l: _init_kda(
                cfg, l, keys, make, normal, norm, dtype)),
            "D": ("layers_mlp", "mlp_norm", dense_leaves),
        }
        stacks = {
            name: kind_stack(
                name, sum(cfg.layer_kinds.count(k) for k in kinds), norm_name,
                leaves)
            for kinds, (name, norm_name, leaves) in makers.items()
            if set(kinds) & set(cfg.layer_kinds)
        }
    else:
        n_dense = cfg.n_dense_layers if cfg.is_moe else 0
        stacks = {"layers": stack("layers", cfg.n_layers - n_dense, cfg.is_moe)}
        if n_dense:
            stacks["layers_dense"] = stack("layers_dense", n_dense, False)

    params = {
        "embed": normal(next(keys), (cfg.vocab_size, d), 0.02, "embed"),
        "final_norm": norm((d,), "final_norm"),
        **stacks,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = normal(
            next(keys), (d, cfg.vocab_size), proj_std, "lm_head"
        )
    return params


def _dt_bias(u):
    """Uniform ``u`` to a step bias: ``dt`` log-uniform in [1e-3, 1e-1]
    through the inverse softplus (both recurrences' published initialiser)."""
    dt = jnp.exp(u * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
    return dt + jnp.log(-jnp.expm1(-dt))


def _init_mixer(cfg: ModelConfig, l: int, keys, make, normal, norm, dtype) -> dict:
    """The state-space mixer's leaves of ``l`` stacked layers
    (``init_params``'s makers; ops/ssm.py ``mixer`` reads them). Random like
    the rest, in the ranges the published initialiser draws from: ``dt``
    log-uniform in [1e-3, 1e-1] through the inverse softplus, ``A`` in
    [-16, -1], ``D`` about 1."""
    d, inner, c = cfg.d_model, cfg.ssm_inner, cfg.ssm_conv_width
    h, k = cfg.ssm_heads, cfg.ssm_conv

    def uniform(name, shape, fn):
        return make(name, lambda kk: fn(
            jax.random.uniform(kk, shape, jnp.float32)).astype(dtype), next(keys))

    return {
        "ssm_in": normal(next(keys), (l, d, cfg.ssm_proj_width), d ** -0.5, "ssm_in"),
        "ssm_conv": normal(next(keys), (l, c, k), k ** -0.5, "ssm_conv"),
        "ssm_conv_bias": normal(next(keys), (l, c), 0.02, "ssm_conv_bias"),
        "ssm_dt_bias": uniform("ssm_dt_bias", (l, h), _dt_bias),
        "ssm_a_log": uniform("ssm_a_log", (l, h), lambda u: jnp.log(1 + 15 * u)),
        "ssm_d": uniform("ssm_d", (l, h), lambda u: 0.5 + u),
        "ssm_norm": norm((l, inner), "ssm_norm"),
        "ssm_out": normal(next(keys), (l, inner, d), inner ** -0.5, "ssm_out"),
    }


def _init_kda(cfg: ModelConfig, l: int, keys, make, normal, norm, dtype) -> dict:
    """The delta-rule layer's leaves of ``l`` stacked layers
    (``init_params``'s makers; ops/delta.py ``kda`` reads them). Random like
    the rest, the decay's two leaves in the ranges the published initialiser
    draws from: ``A`` in [1, 16] a head, ``dt_bias`` the inverse softplus of
    log-uniform [1e-3, 1e-1] a channel. No bias anywhere."""
    d, inner, r = cfg.d_model, cfg.kda_inner, cfg.kda_rank
    h, k = cfg.kda_heads, cfg.kda_conv

    def uniform(name, shape, fn):
        return make(name, lambda kk: fn(
            jax.random.uniform(kk, shape, jnp.float32)).astype(dtype), next(keys))

    out = {
        name: normal(next(keys), (l, d, inner), d ** -0.5, name)
        for name in ("wq", "wk", "wv")}
    out["wo"] = normal(next(keys), (l, inner, d), inner ** -0.5, "wo")
    out["kda_conv"] = normal(
        next(keys), (l, cfg.kda_conv_width, k), k ** -0.5, "kda_conv")
    for gate in ("kda_f", "kda_g"):  # the decay's and the output gate's
        out[gate + "_a"] = normal(next(keys), (l, d, r), d ** -0.5, gate + "_a")
        out[gate + "_b"] = normal(next(keys), (l, r, inner), r ** -0.5, gate + "_b")
    out["kda_beta"] = normal(next(keys), (l, d, h), d ** -0.5, "kda_beta")
    out["kda_dt_bias"] = uniform("kda_dt_bias", (l, inner), _dt_bias)
    out["kda_a_log"] = uniform(
        "kda_a_log", (l, h), lambda u: jnp.log(1 + 15 * u))
    out["kda_norm"] = norm((l, cfg.kda_head_dim), "kda_norm")
    return out


def init_kv_cache(
    cfg: ModelConfig, batch: int, max_seq: Optional[int] = None,
    dtype=jnp.bfloat16, quant: Optional[str] = None,
) -> dict:
    """A zeroed cache of ``batch`` rows and ``max_seq`` slots (nothing valid
    yet), in one of three shapes:

    * ``{"k", "v"}``, each ``[L, B, S, Hkv, dh]``; with ``quant="int8"`` each
      a ``{"q8": codes [L, B, S, Hkv, dh], "s": scales [L, B, Hkv, S]}``
      (ops/quant.py): half the HBM capacity and decode read bandwidth.
    * a latent-attention model's ONE leaf ``{"kv": [L, B, S, 1,
      kv_lora_rank + qk_rope_dim]}``, the sequence on axis 2 like every other
      stack, so whatever maps over the tree (splice, compact, resize) takes
      it unchanged. ``cfg.cache_width`` is the one place that says how many
      values a token a layer holds.
    * a state-space model's ``{"k", "v", "ssm": {"state": [L, B, H, P, N]
      float32, "conv": [L, B, K-1, C]}}``: beside keys and values the
      recurrent state and the convolution's tail of each ROW, which have no
      sequence axis. They are told apart by their KEY (ops/quant.py
      ``STATE_KEY``, ``kv_tree_map``), never by their rank. Where every
      layer is one part (``cfg.layer_kinds``) each leaf counts the layers of
      its own kind: keys and values ``cfg.n_attn_layers``, state and tail
      ``cfg.n_state_layers``; an expert layer holds nothing. A delta-rule
      model's are the same two leaves: ``state`` ``[L, B, H, P, P]`` and
      ``conv`` over ``q | k | v`` (``cfg.row_state_shapes``).
    """
    s = max_seq or cfg.max_seq_len
    if cfg.has_state:
        if quant is not None:
            raise ValueError(
                f"{cfg.name}: no quantized cache for a state-space model: "
                f"kv cache quant {quant!r} is not computed")
        shape = (cfg.n_attn_layers, batch, s, cfg.n_kv_heads, cfg.head_dim)
        state, tail = cfg.row_state_shapes(batch)
        return {
            "k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            STATE_KEY: {
                "state": jnp.zeros((cfg.n_state_layers, *state), jnp.float32),
                "conv": jnp.zeros((cfg.n_state_layers, *tail), dtype),
            },
        }
    if cfg.is_latent:
        if quant is not None:
            raise ValueError(
                f"{cfg.name}: no quantized cache for a latent (MLA) model: "
                f"kv cache quant {quant!r} is not computed")
        return {"kv": jnp.zeros(
            (cfg.n_layers, batch, s, 1, cfg.cache_width), dtype)}
    shape = (cfg.n_attn_layers, batch, s, cfg.n_kv_heads, cfg.head_dim)
    if quant == "int8":
        # Scales are stored seq-MINOR [L, B, Hkv, S]: with seq on lanes
        # the decode kernel's scale blocks tile exactly, where a
        # [..., Hkv, 1] layout pads its 1-wide lane dim to 128 in VMEM
        # (measured: the padded blocks alone blew the 16 MB scoped-VMEM
        # limit at batch 8).
        entry = lambda: {  # noqa: E731
            "q8": jnp.zeros(shape, jnp.int8),
            "s": jnp.zeros(
                (cfg.n_attn_layers, batch, cfg.n_kv_heads, s), dtype
            ),
        }
        return {"k": entry(), "v": entry()}
    if quant is not None:
        raise ValueError(f"unknown kv cache quant mode {quant!r}")
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# -- forward -----------------------------------------------------------------


def embed_tokens(params: dict, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    """Token embedding lookup (+ Gemma's sqrt(d) scale) → [B, T, D]."""
    with scope("embed"):
        x = params["embed"][tokens].astype(params["embed"].dtype)
        if cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
        if cfg.embedding_multiplier != 1.0:
            x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return x


def unembed(params: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Final norm + LM head (+ final logit softcap) → fp32 logits [B, T, V]."""
    with scope("norm"):
        x = rms_norm(x, params["final_norm"], cfg.rms_eps, cfg.norm_offset)
    with scope("head"):
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = qeinsum(
            "btd,dv->btv", x, head, preferred_element_type=jnp.float32)
        if cfg.lm_head_multiplier != 1.0:
            logits = logits * cfg.lm_head_multiplier
        if cfg.final_logit_softcap is not None:
            logits = cfg.final_logit_softcap * jnp.tanh(
                logits / cfg.final_logit_softcap)
    return logits


def _layer(
    cfg: ModelConfig,
    x: jax.Array,            # [B, T, D]
    lp: dict,                # this layer's params (leading L axis removed)
    cos: jax.Array,
    sin: jax.Array,
    mask: Optional[jax.Array],  # [B, T, S]; None on the flash paths
    cache_k: Optional[jax.Array],  # FULL K stack [L, B, S, Hkv, dh]
    cache_v: Optional[jax.Array],
    start_pos: Optional[jax.Array],
    layer_idx: Optional[jax.Array] = None,  # this layer's slot in the stack
    flash_offset: Optional[int] = None,  # static q_offset → use Pallas kernel
    flash_mesh=None,  # wrap the kernel in shard_map over this mesh's tp axis
    kv_width: Optional[int] = None,  # attend only cache[:, :kv_width]
    qkv_pin=None,  # mesh: pin q/k/v head shardings (non-dividing tp)
    ring_mesh=None,  # SP prefill: ring attention over this mesh's sp axis
    decode_flash: bool = False,  # T=1: fused Pallas decode-attention kernel
    row_start: Optional[jax.Array] = None,  # [B] (decode_flash path only)
    decode_sweep: Optional[jax.Array] = None,  # the step's sweep plan (ditto)
    prefix_k=None,        # shared-prefix K stack [L, 1, P, Hkv, dh] (or int8 dict)
    prefix_v=None, mesh=None,  # mesh: forward's own, for a routed layer
    prefix_len=None,      # scalar i32: valid prefix slots
    prefix_rows=None,     # [B] bool: rows that attend the shared prefix
    routed: Optional[bool] = None,  # this stack's MLP is the routed expert
                                    # layer (None: whatever the model has)
    moe_stats: bool = False,  # also return the expert layer's three sums
    expert_stacks=None,  # (w_gate, w_up, w_down) whole [L, E, ...] stacks and
                         # this layer's index in them, in place of lp's own
    ssm=None,            # state-space model: the cache's FULL per-row state
                         # stacks {"state", "conv"}, or None without a cache
    ssm_span=None,       # (lo, hi) [B] each: a row's real positions in T
    kind: str = "*",     # which of cfg.attn_kinds this layer is: its window,
                         # its rotary rule and its sweep's scope
):
    """One block. Returns ``(x, cache_k, cache_v)``; with ``moe_stats`` on a
    routed stack the expert layer's sums follow, and for a state-space model
    its updated ``ssm`` stacks come last. Where every layer is one part
    (``cfg.layer_kinds``) this is an attention layer, whole: no mixer beside
    it, no MLP after it, ``(x, cache_k, cache_v)`` and nothing else."""
    routed = cfg.is_moe if routed is None else routed
    b, t, d = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    beside = cfg.has_ssm and not cfg.layer_kinds  # a mixer beside attention
    window, rotary = {k: (w, r) for k, w, r in cfg.attn_kinds}[kind]

    with scope("norm"):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps, cfg.norm_offset)
    if beside:
        # The mixer reads the same normed input as attention, which takes
        # its own multiplier from here on.
        mixed, ssm = _mixer_half(cfg, h, lp, ssm, layer_idx, ssm_span)
        if cfg.attention_in_multiplier != 1.0:
            with scope("attn.proj"):
                h = h * cfg.attention_in_multiplier
    if cfg.is_latent:
        # The latent stack rides where the K stack does; there is no V.
        attn_out, cache_k = latent_attention(
            h, lp, cos, sin, mask, cache_k, start_pos, layer_idx,
            n_heads=hq, kv_lora_rank=cfg.kv_lora_rank,
            qk_nope_dim=cfg.qk_nope_dim, qk_rope_dim=cfg.qk_rope_dim,
            v_head_dim=cfg.v_head_dim, scale=_latent_scale(cfg),
            rms_eps=cfg.rms_eps, kv_width=kv_width, absorbed=t == 1,
        )
        with scope("mla.out"):
            x = x + qeinsum("btk,kd->btd", attn_out, lp["wo"])
        return _mlp_half(
            cfg, x, lp, routed, moe_stats, cache_k, cache_v, expert_stacks, mesh)
    with scope("attn.proj"):
        q = qeinsum("btd,dk->btk", h, lp["wq"])
        k = qeinsum("btd,dk->btk", h, lp["wk"])
        v = qeinsum("btd,dk->btk", h, lp["wv"])
        if cfg.qkv_bias:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        if cfg.key_multiplier != 1.0:
            k = k * cfg.key_multiplier
        # The barrier stands between the three products and their split
        # into heads: the chip's layout assignment otherwise carries the
        # split back onto the weights, relays the whole wq / wk / wv stacks
        # at a program's entry and passes each layer's three leaves into
        # that layout before the products read them (24 MB a layer a decode
        # step at Mistral-7B's widths). It changes no value.
        q, k, v = jax.lax.optimization_barrier((q, k, v))
        q = q.reshape(b, t, hq, dh)
        k = k.reshape(b, t, hkv, dh)
        v = v.reshape(b, t, hkv, dh)
        if qkv_pin is not None and ring_mesh is None:
            # Non-dividing tp: the projection output shards split WITHIN a
            # head (e.g. Hkv=2 over tp=4 → 16-wide shards of a 32-wide head),
            # and GSPMD carrying that layout through the rope/cache-write
            # scan miscompiles on jax 0.4.x (measured O(1) logit error, not
            # ulps — the seed test_sp_prefill non-dividing-tp failure). Pin
            # each tensor to its head-aligned sharding — replicated heads
            # when tp doesn't divide that head count — BEFORE rope and the
            # cache write, matching cache_specs' degraded layout. Dividing
            # meshes never reach here (qkv_pin stays None), so the working
            # sharded paths are untouched.
            from jax.sharding import NamedSharding, PartitionSpec as P

            tp_sz = dict(qkv_pin.shape)["tp"]

            def pin(t_, n_heads_):
                ax = "tp" if n_heads_ % tp_sz == 0 else None
                return jax.lax.with_sharding_constraint(
                    t_, NamedSharding(qkv_pin, P(None, None, ax, None))
                )

            q, k, v = pin(q, hq), pin(k, hkv), pin(v, hkv)
        if cfg.qk_norm:
            with scope("attn.qk_norm"):
                q = rms_norm(q, lp["q_head_norm"], cfg.rms_eps, cfg.norm_offset)
                k = rms_norm(k, lp["k_head_norm"], cfg.rms_eps, cfg.norm_offset)
        if rotary:
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)

    if cache_k is not None:
        # Write this step's keys/values at (layer_idx, start_pos) into the
        # FULL stacked cache (quantized on write for int8 caches), then
        # attend over this layer's entry — prefix-sliced to kv_width when
        # set, so attention cost scales with the caller's frontier bound,
        # not cache capacity. The full-stack in-place write (vs. threading
        # per-layer entries through the scan as xs/ys) is what lets XLA
        # alias the cache through both the layer scan and the decode-step
        # scan instead of copying it every step — see kv_write_rows.
        with scope("attn.kv_write"):
            cache_k = kv_write_rows(cache_k, k, layer_idx, start_pos)
            cache_v = kv_write_rows(cache_v, v, layer_idx, start_pos)
    with scope(_sweep_scope(kind)):
        if cache_k is None:
            k_att, v_att = k, v
        elif decode_flash:
            # The decode kernel consumes the FULL code stacks directly
            # and pages its layer via the BlockSpec index map — no
            # per-layer slice, no relayout, no materialized dequant
            # (profiled at ~4-6 ms/step of pure copies at batch 32 in
            # the sliced form). int8 SCALE stacks are the exception:
            # the kernel slices them to the layer itself (1.6 MB) — the
            # full stacks got staged into the custom call's operand
            # space per call (decode_attention.py, round-5 profile).
            k_att, v_att = cache_k, cache_v
        elif flash_offset == 0 and (kv_width is None or kv_width >= t):
            # One-shot prefill from position 0 (the batched-admission and
            # first-chunk case): the causal frontier IS this chunk, so
            # attention needs exactly the k/v just computed — reading
            # them back out of the cache costs a per-layer dynamic-slice
            # copy plus (for int8 caches) a full-width dequant pass, all
            # for values we are still holding. int8 caches round-trip the
            # fresh tensors through quantize→dequantize so the attended
            # values stay BIT-IDENTICAL to a cache read-back (attention
            # quality loss applies uniformly across impls — greedy parity
            # with the XLA path depends on it).
            if is_quantized(cache_k):
                from llm_consensus_tpu.ops.quant import quantize_kv

                def roundtrip(fresh):
                    q8, sc = quantize_kv(fresh)
                    return q8.astype(x.dtype) * sc.astype(x.dtype)

                k_att, v_att = roundtrip(k), roundtrip(v)
            else:
                k_att, v_att = k.astype(x.dtype), v.astype(x.dtype)
        else:
            width = kv_width
            if flash_offset is not None:
                # The Pallas prefill kernel re-slices to the causal
                # frontier anyway, but slicing BEFORE kv_read keeps an
                # int8 cache's dequant bounded by the frontier too — the
                # kernel is a custom call, so XLA can't fuse the dequant
                # into it the way it does for the XLA attention path.
                frontier = flash_offset + t
                width = frontier if width is None else min(width, frontier)
            entry_k = kv_layer(cache_k, layer_idx, width)
            entry_v = kv_layer(cache_v, layer_idx, width)
            k_att = kv_read(entry_k, x.dtype)
            v_att = kv_read(entry_v, x.dtype)
        if ring_mesh is not None:
            from llm_consensus_tpu.parallel.ring import ring_attention

            # Sequence-parallel prefill: q/k/v are sequence-sharded over sp
            # (the whole sequence never lands on one device); ring attention
            # circulates KV blocks over ICI. Heads stay tp-sharded when the
            # mesh has a tp axis — the ring and the head split compose
            # without communicating. This layer's k/v are returned (in place
            # of cache entries) so the caller can assemble the decode cache.
            # Heads ride the tp axis only when it divides both head counts —
            # the same gating as the flash path; otherwise heads replicate
            # over tp and only the ring shards work.
            tp_size = ring_mesh.shape.get("tp", 1)
            head_axis = (
                "tp" if tp_size > 1 and hq % tp_size == 0 and hkv % tp_size == 0
                else None
            )
            attn_out = ring_attention(
                q, k_att, v_att, ring_mesh,
                axis_name="sp",
                head_axis=head_axis,
                scale=dh ** -0.5,
                sliding_window=window,
                logit_softcap=cfg.attn_logit_softcap,
            )
        elif flash_offset is not None:
            from llm_consensus_tpu.ops.pallas import flash_attention

            fa = partial(
                flash_attention,
                q_offset=flash_offset,
                scale=dh ** -0.5,
                sliding_window=window,
                logit_softcap=cfg.attn_logit_softcap,
            )
            if flash_mesh is not None:
                # Per-head attention over TP-sharded heads: each shard runs the
                # kernel on its own q/kv head slice — no collectives inside.
                from jax.sharding import PartitionSpec as P

                spec = P(None, None, "tp", None)  # [B, S, H, dh], heads on tp
                fa = jax.shard_map(
                    fa, mesh=flash_mesh,
                    in_specs=(spec, spec, spec), out_specs=spec,
                    check_vma=False,
                )
            attn_out = fa(q, k_att, v_att)
        elif decode_flash:
            from llm_consensus_tpu.ops.pallas import decode_attention

            with_state = prefix_k is not None

            def da(q_, k_, v_, pos_, li_, rs_, sweep_):
                return decode_attention(
                    q_, k_, v_, pos_, li_, rs_,
                    scale=dh ** -0.5,
                    sliding_window=window,
                    logit_softcap=cfg.attn_logit_softcap,
                    kv_width=kv_width,
                    return_state=with_state,
                    sweep=sweep_,
                )

            rs = row_start
            if rs is None:
                rs = jnp.zeros((b,), jnp.int32)
            if flash_mesh is not None:
                from jax.sharding import PartitionSpec as P

                spec = P(None, None, "tp", None)  # [B, 1, H, dh], heads on tp
                # Codes keep heads on axis 3 ([L, B, S, Hkv, dh]); the
                # seq-minor scale leaves are 4-D [L, B, Hkv, S] with heads
                # on axis 2 — each leaf gets the spec matching its rank.
                from llm_consensus_tpu.ops.quant import kv_seq_axis

                spec5 = P(None, None, None, "tp", None)
                spec4s = P(None, None, "tp", None)
                kv_spec = (
                    jax.tree.map(
                        lambda leaf: spec5 if kv_seq_axis(leaf) == 2 else spec4s,
                        k_att,
                    )
                    if is_quantized(k_att) else spec5
                )
                # The scalars (pos, layer, row_start, the step's sweep plan)
                # are the same on every shard.
                da = jax.shard_map(
                    da, mesh=flash_mesh,
                    in_specs=(spec, kv_spec, kv_spec, P(), P(), P(None), P(None)),
                    out_specs=(spec, P(None, "tp"), P(None, "tp"))
                    if with_state else spec,
                    check_vma=False,
                )
            attn_out = da(
                q, k_att, v_att, jnp.asarray(start_pos, jnp.int32), layer_idx, rs,
                decode_sweep,
            )
            if with_state:
                attn_out, m2, l2 = attn_out
                m2, l2 = m2[:, None], l2[:, None]  # [B, Hq] → [B, T=1, Hq]
        else:
            attn_out = attention(
                q, k_att, v_att, mask,
                scale=dh ** -0.5,
                logit_softcap=cfg.attn_logit_softcap,
                return_state=prefix_k is not None,
            )
            if prefix_k is not None:
                attn_out, m2, l2 = attn_out

        if prefix_k is not None:
            # Shared-prefix merge (the pool's one-prompt fan-out pattern):
            # every participating row attends ONE replicated prefix KV —
            # read once per step as a dense MXU matmul — instead of carrying
            # its own copy of the prompt KV through the per-row cache sweep.
            # Exact: two-source online-softmax combine of (prefix, own-row)
            # attention. Rows not flagged in ``prefix_rows`` contribute
            # (m=−inf, l=0) and pass through unchanged.
            from llm_consensus_tpu.ops.attention import (
                merge_attention_states, prefix_attention)

            pk = kv_read(kv_layer(prefix_k, layer_idx), x.dtype)[0]  # [P, Hkv, dh]
            pv = kv_read(kv_layer(prefix_v, layer_idx), x.dtype)[0]
            o1, m1, l1 = prefix_attention(
                q, pk, pv, prefix_len, prefix_rows,
                scale=dh ** -0.5,
                logit_softcap=cfg.attn_logit_softcap,
            )
            attn_out = merge_attention_states(o1, m1, l1, attn_out, m2, l2)
    if cfg.attn_out_gate:
        with scope("attn.gate"):
            gate = qeinsum("btd,dk->btk", h, lp["w_ogate"])
            attn_out = attn_out.reshape(b, t, hq * dh) * jax.nn.sigmoid(
                gate.astype(jnp.float32)).astype(attn_out.dtype)
    with scope("attn.out"):
        attn_out = qeinsum(
            "btk,kd->btd", attn_out.reshape(b, t, hq * dh), lp["wo"])
        if beside:
            attn_out = attn_out * cfg.attention_out_multiplier + mixed
        if cfg.post_norm:
            attn_out = rms_norm(
                attn_out, lp["post_norm"], cfg.rms_eps, cfg.norm_offset)
        x = x + attn_out

    if cfg.layer_kinds:
        return x, cache_k, cache_v
    if ring_mesh is not None:
        cache_k, cache_v = k, v  # fresh k/v for the caller's cache build
    out = _mlp_half(
        cfg, x, lp, routed, moe_stats, cache_k, cache_v, expert_stacks, mesh)
    return (*out, ssm) if cfg.has_ssm else out


def _sweep_scope(kind: str) -> str:
    """The scope an attention kind's sweep (and its plan) is traced under:
    a name a kind, so that a device trace splits a step's time by kind."""
    return "attn.sweep" if kind == "*" else "attn.sweep_window"


def _mixer_layer(cfg: ModelConfig, x, lp, ssm, layer_idx, span):
    """A one-part layer that keeps a state, a state-space mixer or a
    delta-rule layer: ``x + mixer(norm(x))``. Returns ``(x, ssm)``."""
    with scope("norm"):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps, cfg.norm_offset)
    mixed, ssm = _mixer_half(cfg, h, lp, ssm, layer_idx, span)
    # the residual add rides the layer's last product
    with scope("kda.out_proj" if cfg.has_kda else "ssm.out_proj"):
        return x + mixed, ssm


def _mixer_half(cfg: ModelConfig, h, lp, ssm, layer_idx, span):
    """The part of one layer that keeps a state (a state-space mixer, or a
    delta-rule layer) on the normed input ``h``: this layer's state and tail
    come out of the cache's full stacks ``ssm`` and go back in place (zeros
    and nothing kept without a cache). At T = 1 a mixer is handed the state
    stack whole, and advances this layer's rows where they lie."""
    b, t = h.shape[:2]
    lo, hi = span if span is not None else (None, None)
    mixer, state_write = (
        (kda, "kda.state_write") if cfg.has_kda
        else (ssm_mixer, "ssm.state_write"))
    if ssm is None:
        state, tail = cfg.row_state_shapes(b)
        return mixer(cfg, h, lp, jnp.zeros(state, jnp.float32),
                     jnp.zeros(tail, h.dtype), lo, hi)[0], None
    whole = (layer_idx,) if t == 1 and mixer is ssm_mixer else ()
    with scope(state_write):
        state = ssm["state"] if whole else jax.lax.dynamic_index_in_dim(
            ssm["state"], layer_idx, 0, keepdims=False)
        tail = jax.lax.dynamic_index_in_dim(
            ssm["conv"], layer_idx, 0, keepdims=False)
    if t > STATE_SEGMENT and t % STATE_SEGMENT == 0:
        out, state, tail = _in_segments(
            partial(mixer, cfg), h, lp, state, tail, lo, hi, state_write)
    else:
        out, state, tail = mixer(cfg, h, lp, state, tail, lo, hi, *whole)
    with scope(state_write):
        return out, {
            "state": state if whole else jax.lax.dynamic_update_index_in_dim(
                ssm["state"], state.astype(ssm["state"].dtype), layer_idx, 0),
            "conv": jax.lax.dynamic_update_index_in_dim(
                ssm["conv"], tail.astype(ssm["conv"].dtype), layer_idx, 0)}


# Positions a state-keeping part runs over at once. From its in-projection
# to its out-projection such a part is float32 elementwise work and a chunked
# scan over [T, heads x head size]: at 512 positions those arrays (17 MB each
# at 64 heads of 128) stay in the chip's fast memory, at 2,048 (67 MB) they go
# through device memory. Read on the chip (PERF.md section 6, PR 49, ms a
# prompt of 1.7k tokens, four chunks of 512 -> one of 2,048): Solar-Open2's
# three delta layers ``kda.scan`` 17.0 -> 25.0, ``kda.conv`` 2.1 -> 8.5 and
# 6.6 more in fusions the compiler names itself; Nemotron-3-Super's five
# mixers ``ssm.scan`` + ``ssm.conv`` + ``ssm.norm`` 8.8 -> 14.5. So a chunk
# wider than this, which a routed stack's experts want (engine.py
# ``_chunk_width``), passes through a state-keeping part in segments, its
# state and tail carried from each to the next as they are from chunk to
# chunk: the same arithmetic as chunks this wide.
STATE_SEGMENT = 512


def _in_segments(part, h, lp, state, tail, lo, hi, state_write: str):
    """``part(h, lp, state, tail, lo, hi)`` over ``h`` [B, T, D] in segments
    of ``STATE_SEGMENT`` positions, one after the other; each row's real
    span ``[lo, hi)`` (``None``: every position) is cut to each segment."""
    b, t, d = h.shape
    n = t // STATE_SEGMENT
    with scope(state_write):
        segments = jnp.moveaxis(h.reshape(b, n, STATE_SEGMENT, d), 1, 0)
        starts = jnp.arange(n, dtype=jnp.int32) * STATE_SEGMENT

    def segment(carry, xs):
        seg, start = xs
        with scope(state_write):
            span = (None, None) if lo is None else tuple(
                jnp.clip(at - start, 0, STATE_SEGMENT) for at in (lo, hi))
        out, state, tail = part(seg, lp, *carry, *span)
        return (state, tail), out

    with scope(state_write):
        (state, tail), out = jax.lax.scan(
            segment, (state, tail), (segments, starts))
        return jnp.moveaxis(out, 0, 1).reshape(b, t, d), state, tail


def _latent_scale(cfg: ModelConfig) -> float:
    """Softmax scale of a latent-attention model: the whole query head's
    ``dh^-0.5`` times YaRN's ``m²`` (m from ``mscale_all_dim``)."""
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    if cfg.rope_yarn is not None:
        factor, _, _, _, mscale_all_dim, _ = cfg.rope_yarn
        scale *= yarn_mscale(factor, mscale_all_dim) ** 2
    return scale


# A routed stack's expert leaves (an ungated expert has no ``w_gate``), which
# ``forward`` hands to the grouped product whole, apart from the layer's own.
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _mlp_half(cfg: ModelConfig, x, lp, routed: bool, moe_stats: bool,
              cache_k, cache_v, expert_stacks=None, mesh=None):
    """The MLP half of a block on the post-attention residual ``x``: the
    dense gated MLP, or on a routed stack the expert layer (ops/moe.py),
    whose expert leaves are this layer's own (``lp``) or, from ``forward``'s
    scan, the whole stacks with this layer's index. Where a one-part layer
    ends in a norm of its own (``cfg.post_norm``) the half's output passes it
    before the residual add."""
    def ended(out):
        return rms_norm(
            out, lp["post_norm"], cfg.rms_eps, cfg.norm_offset
        ) if cfg.post_norm else out

    with scope("norm"):
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps, cfg.norm_offset)
    if not routed:
        with scope("mlp"):
            mlp_out = gated_mlp(
                h, lp["w_gate"], lp["w_up"], lp["w_down"], cfg.activation,
                cfg.mlp_multipliers)
            return x + ended(mlp_out), cache_k, cache_v
    *experts, layer = expert_stacks or (
        *(lp.get(k) for k in EXPERT_LEAVES), None)
    out = moe_block(
        h, lp["w_router"], *experts, layer=layer,
        top_k=cfg.experts_per_token, activation=cfg.activation,
        first_expert=cfg.first_expert, n_groups=cfg.n_expert_groups,
        groups_per_token=cfg.groups_per_token, norm_topk=cfg.norm_topk,
        routed_scale=cfg.routed_scale, scoring=cfg.router_scoring,
        router_bias=lp.get("router_bias"),
        latent=(lp["w_latent_in"], lp["w_latent_out"])
        if cfg.moe_latent else None,
        shared=(lp.get("ws_gate"), lp["ws_up"], lp["ws_down"])
        if cfg.n_shared_experts else None,
        with_stats=moe_stats, mesh=mesh,
    )
    with scope("moe.experts"):  # the residual add rides the layer's last sum
        if moe_stats:
            return x + ended(out[0]), cache_k, cache_v, out[1]
        return x + ended(out), cache_k, cache_v


def forward(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,                 # [B, T] int32
    cache: Optional[dict] = None,      # init_kv_cache(...) or None
    start_pos: jax.Array | int = 0,    # first absolute position of `tokens`
    remat: bool = False,               # rematerialize each layer (training)
    attn_impl: str = "xla",            # "xla" | "flash" (Pallas prefill kernel)
    mesh=None,                         # engine's mesh when params are TP-sharded
    kv_width: Optional[int] = None,    # attend only cache[:, :kv_width] (static)
    logits_index: Optional[jax.Array] = None,  # [B]: unembed only this position
    row_start: Optional[jax.Array] = None,  # [B]: first real slot per row
    prefix: Optional[dict] = None,     # shared-prefix KV cache [L, 1, P, Hkv, dh]
    prefix_len: Optional[jax.Array] = None,  # scalar i32 valid prefix slots
    prefix_rows: Optional[jax.Array] = None,  # [B] bool: rows attending prefix
    kv_mask: Optional[jax.Array] = None,  # [B, S] bool: written-slot bitmap
    moe_stats: bool = False,           # routed model: also return its sums
    row_end: Optional[jax.Array] = None,  # [B]: slot after a row's last real token
):
    """Run the model. Returns (logits [B, T, V] fp32, updated cache).

    ``row_end`` (with ``row_start``, in the same coordinates as
    ``start_pos``) bounds each row's REAL tokens inside this call's T for a
    state-space model, whose recurrent state one padded position would
    corrupt: positions before ``row_start`` or from ``row_end`` on neither
    advance the state nor enter the convolution's tail. ``None``: real to
    the end of the call. Attention never needed it (junk past a row's end is
    masked later or overwritten), and other models take no notice.

    ``moe_stats=True`` on a routed model returns a third value, int32[3]:
    over this call's expert layers the (token, chosen expert) pairs in all,
    the pairs on held experts, and the held experts that took at least one
    row (one count a layer). A model without a router returns two values
    whatever the flag says.

    Without a cache this is a plain training/eval forward over ``tokens``.
    With a cache it serves both prefill (T = prompt chunk) and decode (T = 1):
    keys/values are written at ``start_pos`` and attention spans the whole
    cache with invalid slots masked.

    ``remat=True`` checkpoints each scanned layer so the backward pass
    recomputes activations instead of keeping them live across all layers —
    the standard HBM-for-FLOPs trade on TPU (activations, not weights, are
    what blow past HBM at training sequence lengths).

    ``attn_impl="flash"`` routes cache prefill (T > 1, static ``start_pos``)
    through the fused Pallas kernel (ops/pallas/flash_attention.py), which
    never materializes the [B, Hq, T, S] score tensor and bounds work by
    the causal frontier instead of cache capacity, and T = 1 steps through
    the fused decode kernel. Shapes and meshes the kernels can't serve take
    the XLA path — a decision made here from ``flash_supported`` /
    ``decode_flash_supported`` and booked in ``attention_routes``, so
    "flash" is always safe to request and never a guess to read back.

    ``mesh``: when the params/cache carry TP NamedShardings, the Pallas
    kernel (a Mosaic custom call with no GSPMD partitioning rule) is wrapped
    in ``shard_map`` over the ``tp`` axis — per-head attention is
    embarrassingly parallel over the sharded head dim, so each shard runs
    the kernel on its own heads with no collectives. Gated to tp-only
    meshes whose degree divides both head counts; anything else falls back
    to the XLA path, which GSPMD partitions natively.
    """
    if cfg.is_latent:
        _refuse_latent(cfg, attn_impl, mesh, prefix, kv_mask)
    if cfg.has_state:
        _refuse_ssm(cfg, attn_impl, mesh, prefix, kv_mask)
    elif cfg.layer_kinds:
        refuse_one_part_mesh(cfg, mesh, attn_impl)
    if attn_impl == "ring":
        if cache is None or mesh is None or not (
            isinstance(start_pos, int) and start_pos == 0
        ):
            raise ValueError(
                "attn_impl='ring' is a one-shot sequence-parallel prefill: "
                "it needs a cache, a mesh with an sp axis, and start_pos=0"
            )
        attention_routes.note(cfg.name, "prefill", "ring")
        return _forward_ring_prefill(
            params, cfg, tokens, cache, mesh, logits_index
        )

    if row_start is not None and cache is None:
        raise ValueError(
            "row_start (left-padded batching) requires a cache: the "
            "no-cache mask path has no kv_valid to exclude pad slots"
        )
    if prefix is not None:
        if cache is None:
            raise ValueError("a shared prefix requires a cache")
        if cfg.sliding_window is not None:
            # Windowed attention would need the window to span the
            # prefix/suffix seam; the pool gates the feature off instead.
            raise ValueError(
                f"{cfg.name}: shared-prefix attention does not compose "
                "with sliding_window")
        if prefix_len is None:
            raise ValueError("prefix requires prefix_len")
    if kv_mask is not None:
        # Written-slot bitmap (batched speculative decode): per-row
        # acceptance leaves REJECTED slots behind the shared frontier
        # holding junk KV that is never rewritten, so slot validity is no
        # longer the contiguous [row_start, frontier) interval — the
        # bitmap is the complete per-(row, slot) validity source and the
        # row_start clamp is skipped below. Positions of old valid slots
        # computed from the CURRENT row_start underestimate their true
        # write-time positions (row_start only grows as holes accrue),
        # which keeps the causal compare correct for full attention —
        # every valid old slot is strictly in the past of every query —
        # but NOT for sliding windows, hence the gate.
        if cache is None or row_start is None:
            raise ValueError("kv_mask requires a cache and row_start")
        if cfg.sliding_window is not None:
            raise ValueError(
                f"{cfg.name}: kv_mask (speculative holes) does not "
                "compose with sliding_window")

    b, t = tokens.shape
    x = embed_tokens(params, cfg, tokens)

    from llm_consensus_tpu.ops.pallas.flash_attention import flash_supported

    # shard_tp: 1 = unsharded (run the kernel bare), >1 = tp-only mesh (run
    # it under shard_map), 0 = mesh has a non-trivial non-tp axis — the
    # kernel would see sharded operands it can't partition, so force XLA.
    shard_tp = 1
    if mesh is not None:
        sizes = dict(mesh.shape)
        tp = sizes.pop("tp", 1)
        shard_tp = tp if all(v == 1 for v in sizes.values()) else 0
    if shard_tp == 0:
        flash_heads_ok = False
    elif shard_tp == 1:
        flash_heads_ok = flash_supported(t, cfg.n_heads, cfg.n_kv_heads)
    else:
        flash_heads_ok = (
            cfg.n_heads % shard_tp == 0
            and cfg.n_kv_heads % shard_tp == 0
            and flash_supported(
                t, cfg.n_heads // shard_tp, cfg.n_kv_heads // shard_tp
            )
        )
    flash_offset = (
        int(start_pos)
        if (
            attn_impl == "flash"
            and not cfg.is_latent  # no kernel computes over a latent yet
            and cache is not None
            and isinstance(start_pos, int)
            and row_start is None  # kernel assumes one shared offset
            and prefix is None     # prefill kernel has no merge-state form
            and kv_mask is None    # kernels derive validity from pos alone
            and flash_heads_ok
        )
        else None
    )
    # T=1 decode steps (traced start_pos) take the fused decode kernel:
    # the XLA route's mask build + tiny batched matmuls + softmax cost a
    # chain of kernel launches per layer per step.
    from llm_consensus_tpu.ops.pallas.decode_attention import (
        decode_flash_supported)

    if cache is not None:
        k_store = _k_store(cache)
        decode_width = k_store.shape[2] if kv_width is None else min(
            kv_width, k_store.shape[2]
        )
        decode_quantized = is_quantized(cache.get("k"))
    else:
        decode_width, decode_quantized = None, False
    if shard_tp == 1:
        decode_heads_ok = decode_flash_supported(
            cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            width=decode_width, quantized=decode_quantized,
        )
    elif shard_tp > 1:
        decode_heads_ok = (
            cfg.n_heads % shard_tp == 0
            and cfg.n_kv_heads % shard_tp == 0
            and decode_flash_supported(
                cfg.n_heads // shard_tp, cfg.n_kv_heads // shard_tp,
                cfg.head_dim, width=decode_width,
                quantized=decode_quantized,
            )
        )
    else:
        decode_heads_ok = False
    decode_flash = (
        attn_impl == "flash"
        and not cfg.is_latent
        and cache is not None
        and t == 1
        and flash_offset is None
        and kv_mask is None  # the decode kernel has no bitmap form
        and decode_heads_ok
    )
    flash_mesh = mesh if (
        (flash_offset is not None or decode_flash) and shard_tp > 1
    ) else None
    if cache is not None:
        if cfg.is_latent:
            # A route name of its own for each form, so that a kernel for
            # either shows in what a configuration expects.
            path = "xla_latent_absorbed" if t == 1 else "xla_latent"
        else:
            path = "pallas" if flash_offset is not None or decode_flash else "xla"
        attention_routes.note(cfg.name, "decode" if t == 1 else "prefill", path)

    start = jnp.asarray(start_pos, jnp.int32)
    # Positions and rotary tables belong to the projections, the mask to
    # the sweep (a latent model: ``mla.q`` and ``mla.sweep``).
    with scope("mla.q" if cfg.is_latent else "attn.proj"):
        positions = start + jnp.arange(t, dtype=jnp.int32)[None, :]  # [1, T]
        if row_start is not None:
            # Right-aligned batch (left-padded rows): positions are
            # row-relative so every row's first real token is position 0 —
            # RoPE, causality, and sliding windows all follow.
            positions = positions - row_start[:, None]
        positions = jnp.broadcast_to(positions, (b, t))
        pos_offset = None
        if prefix is not None:
            # Suffix-resident rows: cache slot j holds ABSOLUTE position
            # prefix_len + (j − row_start) for participating rows, so RoPE
            # angles (and the mask's causal compare below) shift by the
            # prefix length. Non-participating rows carry their full prompt
            # in their own window — no shift.
            plen = jnp.asarray(prefix_len, jnp.int32)
            if prefix_rows is not None:
                pos_offset = plen * prefix_rows.astype(jnp.int32)  # [B]
            else:
                pos_offset = jnp.broadcast_to(plen, (b,))
            positions = positions + pos_offset[:, None]
        # one table for every kind that turns its heads (one theta a model)
        cos, sin = _rotary_tables(cfg, positions) if any(
            r for _, _, r in cfg.attn_kinds) else (None, None)

    # A mask and, on the decode route, a sweep plan ONCE A KIND of attention
    # layer (``cfg.attn_kinds``: one for every model but a stack that mixes
    # window and full layers), each under its kind's window.
    masks = {}
    with scope("mla.sweep" if cfg.is_latent else "attn.sweep"):
        if flash_offset is not None or decode_flash:
            pass  # the kernels derive causality from pos/q_offset: no mask
        elif cache is not None:
            s = _k_store(cache).shape[2]
            if kv_width is not None:
                s = min(s, kv_width)
            kv_slots = jnp.arange(s, dtype=jnp.int32)[None, :]
            kv_valid = jnp.broadcast_to(kv_slots < (start + t), (b, s))
            if kv_mask is not None:
                # Bitmap validity (speculative holes): slots the bitmap
                # clears are junk even below the frontier, and valid slots
                # may sit below row_start (which accrues hole counts, not
                # the row's first slot) — the bitmap replaces the interval
                # clamp entirely. Slots at/above the frontier inside this
                # call's write window are marked valid by the CALLER before
                # dispatch (intra-window causality comes from the position
                # compare below).
                kv_positions = jnp.broadcast_to(kv_slots, (b, s)) - row_start[:, None]
                kv_valid = jnp.logical_and(kv_valid, kv_mask[:, :s])
            elif row_start is not None:
                kv_positions = jnp.broadcast_to(kv_slots, (b, s)) - row_start[:, None]
                kv_valid = jnp.logical_and(kv_valid, kv_slots >= row_start[:, None])
            else:
                kv_positions = jnp.broadcast_to(kv_slots, (b, s))
            if pos_offset is not None:
                # Keep the causal compare in the same (absolute) basis the
                # query positions moved to.
                kv_positions = kv_positions + pos_offset[:, None]
            for kind, window, _ in cfg.attn_kinds:
                masks[kind] = make_attention_mask(
                    positions, kv_positions, kv_valid, window)
        else:
            for kind, window, _ in cfg.attn_kinds:
                masks[kind] = make_attention_mask(
                    positions, positions, None, window)
    first_kind = cfg.attn_kinds[0][0]  # a uniform model's only one
    mask = masks.get(first_kind)

    qkv_pin = None
    if mesh is not None and cache is not None:
        tp_sz = dict(mesh.shape).get("tp", 1)
        if tp_sz > 1 and (cfg.n_heads % tp_sz or cfg.n_kv_heads % tp_sz):
            qkv_pin = mesh
    sweeps = {}
    if decode_flash:
        # Which kv blocks of which rows this step's attention sweeps is
        # the same in every layer: planned once here, beside the layer
        # scan, from the frontier and the rows' starts (a row without a
        # stream starts past the frontier and is left out).
        from llm_consensus_tpu.ops.pallas.decode_attention import (
            decode_sweep_plan)

        for kind, window, _ in cfg.attn_kinds:
            with scope(_sweep_scope(kind)):
                sweeps[kind] = decode_sweep_plan(
                    start,
                    jnp.zeros((b,), jnp.int32) if row_start is None else row_start,
                    width=decode_width,
                    n_kv_heads=cfg.n_kv_heads // max(shard_tp, 1),  # a shard's
                    dh=cfg.head_dim, kv_item=k_store.dtype.itemsize,
                    quantized=decode_quantized,
                    sliding_window=window,
                )
    sweep = sweeps.get(first_kind)
    ssm_span = None
    if cfg.has_state and (row_start is not None or row_end is not None):
        # Each row's real positions [lo, hi) inside this call's T.
        with scope("kda.conv" if cfg.has_kda else "ssm.conv"):
            lo = jnp.zeros((b,), jnp.int32) if row_start is None else jnp.clip(
                row_start - start, 0, t)
            hi = jnp.full((b,), t, jnp.int32) if row_end is None else jnp.clip(
                row_end - start, 0, t)
            ssm_span = (lo, jnp.maximum(hi, lo))
    layer_fn = partial(
        _layer, cfg, flash_offset=flash_offset, flash_mesh=flash_mesh,
        kv_width=kv_width, qkv_pin=qkv_pin, ssm_span=ssm_span,
        decode_flash=decode_flash, row_start=row_start, decode_sweep=sweep,
        prefix_k=prefix["k"] if prefix is not None else None,
        prefix_v=prefix["v"] if prefix is not None else None,
        prefix_len=prefix_len, mesh=mesh,
        prefix_rows=prefix_rows,
    )

    # A family with leading dense layers has two stacks, scanned one after
    # the other; the cache's layer axis counts both. One whose every layer
    # is one part has a stack a kind and scans none (``_walk_kinds``).
    stacks = [] if cfg.layer_kinds else [(params["layers"], cfg.is_moe)]
    if "layers_dense" in params:
        stacks.insert(0, (params["layers_dense"], False))
    moe_stats = moe_stats and cfg.is_moe
    with scope("moe.stats"):
        stats = jnp.zeros((3,), jnp.int32) if moe_stats else None

    def block(x, lp, experts, at, stats, cs, *cache_args, **kw):
        routed = experts is not None
        out = layer_fn(
            x, lp, cos, sin, mask, *cache_args, routed=routed,
            moe_stats=moe_stats and routed,
            expert_stacks=(*experts, at) if routed else None, ssm=cs, **kw)
        if moe_stats and routed:
            with scope("moe.stats"):
                stats = stats + out[3]
        if cfg.has_ssm:
            cs = out[-1]
        return (*out[:3], stats, cs)

    # The cache rides the scan CARRY (full stacks, in-place row writes), not
    # xs/ys: the xs→ys form makes XLA materialize a fresh copy of both
    # stacks every outer decode step. A latent model's one stack rides
    # where K does; without a cache both places hold nothing.
    # A state-space model's per-row state stacks ride it beside them.
    if cache is None:
        ck = cv = cs = at = None
    else:
        ck, cv = (cache["kv"], None) if cfg.is_latent else (cache["k"], cache["v"])
        cs = cache.get(STATE_KEY)
        at = start
    if cfg.layer_kinds:
        by_kind = {
            kind: (masks.get(kind), sweeps.get(kind)) for kind, _, _ in cfg.attn_kinds}
        x, ck, cv, stats, cs = _walk_kinds(
            params, cfg, layer_fn, (x, ck, cv, stats, cs), (cos, sin, by_kind, at),
            ssm_span, moe_stats, remat and cache is None, mesh)
    li = jnp.asarray(0, jnp.int32)  # the layer, counted over both stacks
    for stack, routed in stacks:
        xs, experts = _scanned(stack, routed)
        first = li  # this stack's first layer

        def scan_body(carry, lp, experts=experts, first=first):
            x, ck, cv, li, stats, cs = carry
            x, ck, cv, stats, cs = block(
                x, lp, experts, li - first, stats, cs, ck, cv, at, layer_idx=li)
            return (x, ck, cv, li + 1, stats, cs), None

        if remat and cache is None:
            scan_body = jax.checkpoint(scan_body)
        # The scan's own work (a layer's leaves out of their stacks, its
        # counter) is ``layers``; inside the body each part's own scope is
        # the innermost and names it.
        with scope("layers"):
            (x, ck, cv, li, stats, cs), _ = jax.lax.scan(
                scan_body, (x, ck, cv, li, stats, cs), xs)
    if cache is None:
        new_cache = None
    else:
        new_cache = {"kv": ck} if cfg.is_latent else {"k": ck, "v": cv}
        if cs is not None:
            new_cache[STATE_KEY] = cs

    if logits_index is not None:
        # Prefill only samples one position; unembedding every position
        # would spend T×V×D FLOPs on logits nobody reads (~30% of an 8B
        # prefill at a 128k vocab).
        with scope("head"):
            x = jnp.take_along_axis(x, logits_index[:, None, None], axis=1)
    if moe_stats:
        return unembed(params, cfg, x), new_cache, stats
    return unembed(params, cfg, x), new_cache


def _scanned(stack: dict, routed: bool):
    """A stack's leaves as the layer scan (or the walk over one-part layers)
    slices them, and apart from them a routed stack's expert leaves, which
    stay whole: the grouped product fetches its experts out of the stacks
    (ops/moe.py)."""
    if not routed:
        return stack, None
    return ({k: v for k, v in stack.items() if k not in EXPERT_LEAVES},
            tuple(stack.get(k) for k in EXPERT_LEAVES))


def _walk_kinds(params, cfg: ModelConfig, layer_fn, carry, attn_args,
                ssm_span, moe_stats: bool, remat: bool, mesh=None):
    """``forward``'s layers where every layer is ONE part: the static
    pattern ``cfg.layer_kinds`` unrolled, each layer given its leaves out of
    its kind's stack and its index WITHIN its kind, which is its place in
    that kind's cache (keys and values for ``*``, state and tail for ``M``
    or ``K``) and in the stacked experts (``E``). An attention layer is handed
    the mask and the sweep plan of ITS kind (``by_kind``: ``*``, or ``W``
    under the window; both count through one stack and one cache). ``carry`` is ``(x, cache_k, cache_v,
    stats, ssm)`` as the layer scan carries it. Unrolled, not scanned: the
    pattern has no period a scan could run over (``MEMEMEM*EME`` is three
    ``ME`` pairs and five layers that repeat nothing), eleven small bodies
    compile in the time of a few, and a static index lets each layer read
    its leaves where they lie."""
    cos, sin, by_kind, at = attn_args
    moe_own, experts = _scanned(params["layers_moe"], True)
    stack_of = {"M": params.get("layers_ssm"), "E": moe_own,
                "*": params.get("layers_attn"), "K": params.get("layers_kda"),
                "W": params.get("layers_attn"), "D": params.get("layers_mlp")}

    def part(kind: str, i: int, carry):
        x, ck, cv, stats, cs = carry
        with scope("layers"):
            lp = jax.tree.map(lambda a: a[i], stack_of[kind])
            idx = jnp.asarray(i, jnp.int32)
        if kind in "MK":
            x, cs = _mixer_layer(cfg, x, lp, cs, idx, ssm_span)
        elif kind in "*W":
            mask, sweep = by_kind[kind]
            x, ck, cv = layer_fn(
                x, lp, cos, sin, mask, ck, cv, at, layer_idx=idx,
                decode_sweep=sweep, kind=kind)
        elif kind == "D":
            x, _, _ = _mlp_half(cfg, x, lp, False, False, None, None)
        else:
            x, _, _, *more = _mlp_half(
                cfg, x, lp, True, moe_stats, None, None, (*experts, idx), mesh)
            if moe_stats:
                with scope("moe.stats"):
                    stats = stats + more[0]
        return x, ck, cv, stats, cs

    seen = dict.fromkeys(stack_of, 0)
    for kind in cfg.layer_kinds:
        counted = "*" if kind == "W" else kind  # one stack, one cache
        fn = partial(part, kind, seen[counted])
        carry = (jax.checkpoint(fn) if remat else fn)(carry)
        seen[counted] += 1
    return carry


def _k_store(cache: dict) -> jax.Array:
    """The array whose axis 2 is the cache's slots: the latent stack, or
    the K stack (its codes when quantized)."""
    if "kv" in cache:
        return cache["kv"]
    return cache["k"]["q8"] if is_quantized(cache["k"]) else cache["k"]


def _rotary_tables(cfg: ModelConfig, positions: jax.Array):
    """cos and sin [B, T, rotary/2] for ``positions``: over the whole head,
    or for a latent model over its rotary part with YaRN's blend."""
    if not cfg.is_latent:
        inv_freq = rope_inv_freq(
            cfg.head_dim, cfg.rope_theta, cfg.rope_scaling_dict)
        return rope_angles(positions, inv_freq)
    if cfg.rope_yarn is None:
        return rope_angles(
            positions, rope_inv_freq(cfg.qk_rope_dim, cfg.rope_theta))
    factor, beta_fast, beta_slow, mscale, mscale_all_dim, orig = cfg.rope_yarn
    cos, sin = rope_angles(positions, yarn_inv_freq(
        cfg.qk_rope_dim, cfg.rope_theta, factor, beta_fast, beta_slow, orig))
    ratio = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)
    return cos * ratio, sin * ratio


def _refuse_latent(cfg: ModelConfig, attn_impl: str, mesh, prefix,
                   kv_mask) -> None:
    """What a latent-attention model does not get yet is refused by name,
    not computed wrongly."""
    if attn_impl == "ring":
        raise ValueError(
            f"{cfg.name}: no sequence-parallel (ring) prefill over a latent "
            "(MLA) cache")
    if prefix is not None:
        raise ValueError(
            f"{cfg.name}: no shared-prefix attention over a latent (MLA) cache")
    if kv_mask is not None:
        raise ValueError(
            f"{cfg.name}: no speculative decoding (written-slot bitmap) over "
            "a latent (MLA) cache")
    refuse_latent_mesh(cfg, mesh)


def _refuse_ssm(cfg: ModelConfig, attn_impl: str, mesh, prefix,
                kv_mask) -> None:
    """What a state-space model does not get yet is refused by name, not
    computed wrongly: its state exists only at the length it was saved at."""
    if attn_impl == "ring":
        raise ValueError(
            f"{cfg.name}: no sequence-parallel (ring) prefill of a "
            "state-space model: the scan is not split over chips")
    if prefix is not None:
        raise ValueError(
            f"{cfg.name}: no shared-prefix attention for a state-space model: "
            "a shared prefix has keys and values and no state to start from")
    if kv_mask is not None:
        raise ValueError(
            f"{cfg.name}: no speculative decoding (written-slot bitmap) of a "
            "state-space model: a rejected position cannot be taken back out "
            "of the state")
    refuse_ssm_mesh(cfg, mesh)


def refuse_ssm_mesh(cfg: ModelConfig, mesh) -> None:
    """A state-space model is not sharded yet: ``forward`` and the engine's
    constructor both refuse a mesh that would split it."""
    if mesh is not None and any(
            dict(mesh.shape).get(ax, 1) > 1 for ax in ("tp", "ep", "sp")):
        raise ValueError(
            f"{cfg.name}: a state-space model runs on one chip: a mesh with "
            f"tp, ep or sp > 1 is not computed, got {dict(mesh.shape)}")


def refuse_one_part_mesh(cfg: ModelConfig, mesh, attn_impl: str = "") -> None:
    """A stack of one-part layers without a state (window and full attention
    layers beside expert layers) runs on one chip, every leaf whole
    (parallel/sharding.py): ``forward`` and the engine's constructor both
    refuse a mesh that would split it, and the ring prefill, which scans the
    uniform stack."""
    if attn_impl == "ring":
        raise ValueError(
            f"{cfg.name}: no sequence-parallel (ring) prefill of a stack of "
            f"one-part layers (layer_kinds {cfg.layer_kinds!r})")
    if mesh is not None and any(
            dict(mesh.shape).get(ax, 1) > 1 for ax in ("tp", "ep", "sp")):
        raise ValueError(
            f"{cfg.name}: a stack of one-part layers runs on one chip: a mesh "
            f"with tp, ep or sp > 1 is not computed, got {dict(mesh.shape)}")


def refuse_latent_mesh(cfg: ModelConfig, mesh) -> None:
    """A latent-attention model is not sharded yet: ``forward`` and the
    engine's constructor both refuse a mesh that would split it."""
    if mesh is not None and any(
            dict(mesh.shape).get(ax, 1) > 1 for ax in ("tp", "ep")):
        raise ValueError(
            f"{cfg.name}: a latent (MLA) model runs on one chip: a mesh with "
            f"tp or ep > 1 is not computed, got {dict(mesh.shape)}")


def _forward_ring_prefill(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,     # [B, T], T divisible by the mesh's sp size
    cache: dict,           # init_kv_cache(...); T ≤ its capacity
    mesh,                  # Mesh with an "sp" axis (tp optional)
    logits_index: Optional[jax.Array],
) -> tuple[jax.Array, dict]:
    """Sequence-parallel one-shot prefill (SURVEY §5 long-context path).

    Activations are sharded over ``sp`` on the sequence dim, so no device
    ever materializes the whole prompt's activations; attention is ring
    attention (parallel/ring.py) with KV blocks circulating over ICI, and
    heads stay tp-sharded when the mesh has both axes. Per-layer K/V come
    back from the scan and are written into the decode cache in one
    update — GSPMD inserts the sp all-gather there, the single point
    where the full sequence assembles (the cache itself is the decode
    requirement). The judge's concatenated panel prompt is the consumer:
    its prefill footprint per chip drops by the sp factor.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from llm_consensus_tpu.ops.quant import quantize_kv

    b, t = tokens.shape
    x = embed_tokens(params, cfg, tokens)
    x = jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(None, "sp", None))
    )
    with scope("attn.proj"):
        positions = jnp.broadcast_to(
            jnp.arange(t, dtype=jnp.int32)[None, :], (b, t))
        cos, sin = _rotary_tables(cfg, positions)
    layer_fn = partial(_layer, cfg, ring_mesh=mesh, mesh=mesh)

    def scan_body(x, lp):
        x, k, v = layer_fn(x, lp, cos, sin, None, None, None, None)
        return x, (k, v)

    with scope("layers"):
        x, (ks, vs) = jax.lax.scan(scan_body, x, params["layers"])

    def write(entry, stack):  # [L, B, T, Hkv, dh] → cache positions [0, T)
        if is_quantized(entry):
            q8, s = quantize_kv(stack)
            s_rows = jnp.swapaxes(s[..., 0], 2, 3)  # [L, B, Hkv, T]
            return {
                "q8": jax.lax.dynamic_update_slice(
                    entry["q8"], q8, (0, 0, 0, 0, 0)
                ),
                "s": jax.lax.dynamic_update_slice(
                    entry["s"], s_rows.astype(entry["s"].dtype), (0, 0, 0, 0)
                ),
            }
        return jax.lax.dynamic_update_slice(
            entry, stack.astype(entry.dtype), (0, 0, 0, 0, 0)
        )

    with scope("attn.kv_write"):
        new_cache = {"k": write(cache["k"], ks), "v": write(cache["v"], vs)}
    if logits_index is not None:
        with scope("head"):
            x = jnp.take_along_axis(x, logits_index[:, None, None], axis=1)
    return unembed(params, cfg, x), new_cache
