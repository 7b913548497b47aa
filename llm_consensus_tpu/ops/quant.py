"""Weight-only int8 / int4 quantization for decode throughput.

Single-stream decode is HBM-bandwidth-bound: every step streams the full
weight set from HBM through the MXU. Storing matmul weights as int8 with
per-output-channel scales halves the bytes streamed vs bfloat16 — the
dominant term in decode latency — while prefill (compute-bound) loses
nothing. The reference has no analog (its compute is remote HTTP APIs);
this is a TPU-build extension, opt-in via ``LLMC_QUANT=int8|int4`` or
``Engine(quant=...)``.

int8 scheme: for a weight laid out ``[..., contract, out]`` (every matmul
weight in models/transformer.py init_params — attention projections, MLP,
MoE experts, lm_head), ``scale = max|w| / 127`` per output channel
(reduced over the contraction axis), ``q8 = round(w / scale)``. The
consuming einsum runs on ``q8`` converted to the activation dtype — XLA
fuses the convert into the dot's operand stream, so HBM reads stay int8 —
and the scale multiplies the *output* (exact: per-output-channel scales
are constant along the contraction), so no dequantized weight is ever
materialized.

int4 scheme: two codes packed per uint8 byte (``jnp.int4`` itself cannot
cross ``device_put`` on every platform we run on, so we pack by hand),
quartering the bytes streamed vs bfloat16. Scales are **group-wise**
along the contraction axis (default group 128, the AWQ/GPTQ convention —
per-channel scales are too coarse at 4 bits for real checkpoints): weight
``[..., C, O]`` is viewed as ``[..., G, g, O]`` with one scale per
``(group, out-channel)``. Codes are **offset-binary**: ``u = round(w /
s) + 8 ∈ [1, 15]``, so unpacking a nibble is a single mask-or-shift on
the unsigned byte — no sign-extension double-shift. Packing pairs the
first and second half of each group (``lo`` nibble ↔ ``q[..., :g/2,
:]``), so the two nibble planes are contiguous halves of each group, not
an interleave.

At 4 bits the binding cost is not HBM but the **VPU dequant ops** per
weight element (measured: a shift+shift+convert+mul chain makes int4
decode *slower* than int8 on v5e). The decode lowering therefore does
the dot on the raw unsigned nibbles (extract + convert only — 2 VPU ops
per element) and repairs offset and scale on the *output*:

    y = Σ_G s[G,o] · (x_lo·lo_u + x_hi·hi_u − 8·Σ(x_G))

exact because both the zero point (8) and the scale are constant within
a group. The grouped output ``[..., G, O]`` makes this a decode-only
lowering (rows ≤ a small bound); prefill takes the plain
dequantize-into-the-dot form, where the MXU — not the VPU — is the
bottleneck anyway.

Not quantized: embeddings (gather, shared with tied lm_heads), norm gains,
biases, and MoE router weights (tiny, and routing argmaxes are the one
place low-bit error visibly changes behavior).
"""

from __future__ import annotations

import contextlib
import threading
import warnings

import jax
import jax.numpy as jnp

from llm_consensus_tpu.utils import knobs

# Weight names eligible for quantization (init_params layout, all
# [..., contract, out]).
QUANT_KEYS = frozenset(
    {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head",
     # latent attention's projections and the shared experts
     "wq_a", "wq_b", "wkv_a", "wkv_b", "ws_gate", "ws_up", "ws_down",
     # a state-space mixer's two projections
     "ssm_in", "ssm_out",
     # LatentMoE's two projections around the routed sum
     "w_latent_in", "w_latent_out",
     # attention's output gate (a delta-rule layer's four projections go
     # by attention's names; its low-rank gates stay as made)
     "w_ogate"}
)


INT4_GROUP = 128  # contraction-axis group size for int4 scales


def is_quantized(w) -> bool:
    return isinstance(w, dict) and ("q8" in w or "q4" in w)


def _quantize(w: jax.Array) -> dict:
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
    scale = jnp.maximum(scale, jnp.finfo(jnp.float32).tiny).astype(jnp.float32)
    q8 = jnp.round(w.astype(jnp.float32) / scale)
    return {
        "q8": jnp.clip(q8, -127, 127).astype(jnp.int8),
        "s": scale.astype(w.dtype),
    }


def _quantize4(w: jax.Array, group: int = INT4_GROUP) -> dict:
    """Pack ``w`` [..., C, O] → {"q4": [..., G, g/2, O] uint8, "s": [..., G, 1, O]}.

    Offset-binary codes: byte = (q_lo + 8) | ((q_hi + 8) << 4), q ∈ [-7, 7].
    Falls back to one group (per-channel scale) when C doesn't divide by
    ``group``; g is always even because C is (model dims here are all
    multiples of 64).
    """
    *lead, c, o = w.shape
    if c % 2:
        raise ValueError(
            f"int4 packing needs an even contraction dim, got {c}"
        )
    g = group if (group and group % 2 == 0 and c % group == 0) else c
    wg = w.astype(jnp.float32).reshape(*lead, c // g, g, o)
    scale = jnp.max(jnp.abs(wg), axis=-2, keepdims=True) / 7.0
    scale = jnp.maximum(scale, jnp.finfo(jnp.float32).tiny)
    u = (jnp.clip(jnp.round(wg / scale), -7, 7) + 8).astype(jnp.uint8)
    lo, hi = u[..., : g // 2, :], u[..., g // 2 :, :]
    return {
        "q4": lo | (hi << 4),
        "s": scale.astype(w.dtype),
    }


def _unpack4(w: dict, dtype) -> jax.Array:
    """Unpacked, scaled weight [..., C, O] from an int4 dict.

    Mask/shift recover the unsigned nibbles, the concat restores
    contraction order (pack paired first/second half of each group
    precisely so this is a contiguous concat, not an interleave), and the
    zero point and group-wise scale apply to the weight. All of it is
    elementwise, so XLA streams the packed bytes from HBM and dequantizes
    on the way into the consuming dot.
    """
    p = w["q4"]
    lo = (p & 0xF).astype(dtype)
    hi = (p >> 4).astype(dtype)
    q = (jnp.concatenate([lo, hi], axis=-2) - 8.0) * w["s"].astype(dtype)
    *lead, groups, g, o = q.shape
    return q.reshape(*lead, groups * g, o)


def dequantize(w, dtype) -> jax.Array:
    """A stored weight as a plain ``dtype`` array (a plain leaf passes
    through): for the consumers ``qeinsum`` cannot serve, which reshape a
    weight (the latent attention's absorbed halves) or hand it to a grouped
    product (ops/moe.py)."""
    if not is_quantized(w):
        return w
    if "q4" in w:
        return _unpack4(w, dtype)
    return w["q8"].astype(dtype) * w["s"].astype(dtype)


# Donating variant frees each bfloat16 original as it converts (peak HBM
# overhead = one weight, not the whole tree) — but deletes the input, so
# it is only safe on arrays the caller owns.
_quantize_leaf_donate = jax.jit(_quantize, donate_argnames=("w",))
_quantize_leaf = jax.jit(_quantize)
_quantize4_leaf_donate = jax.jit(_quantize4, static_argnames=("group",),
                                 donate_argnames=("w",))
_quantize4_leaf = jax.jit(_quantize4, static_argnames=("group",))


def init_params_quantized(cfg, key, dtype=jnp.bfloat16,
                          mode: str = "int8", shardings=None) -> dict:
    """Random-init a parameter tree with every matmul weight quantized
    AS it is created (models/transformer.py init_params leaf_hook).

    Peak HBM ≈ quantized tree + one bf16 leaf, instead of the full bf16
    tree followed by quantization — on one 16 GB v5e that is the
    difference between an 8B-class random init fitting (≈8 GB int8 +
    3.8 GB largest leaf) and OOMing at init (16 GB bf16). Values are
    IDENTICAL to quantize_params(init_params(...), donate=True): the
    key sequence doesn't depend on the hook and the same per-leaf
    quantizer runs either way. With ``shardings`` (init_params) each
    leaf is made under its sharding and quantized there, as
    quantize_params does to a tree that was sharded whole.
    """
    from llm_consensus_tpu.models.transformer import init_params

    leaf = _quantize4_leaf_donate if mode == "int4" else _quantize_leaf_donate

    def hook(name: str, w):
        if name not in QUANT_KEYS:
            return w
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message=".*donated buffers were not usable.*"
            )
            return leaf(w)

    return init_params(
        cfg, key, dtype=dtype, leaf_hook=hook, shardings=shardings
    )


def quantize_params(params: dict, donate: bool = False,
                    mode: str = "int8") -> dict:
    """Quantize every eligible matmul weight in an init_params tree.

    ``donate=True`` frees each source array as it quantizes — pass it only
    for a tree you own (freshly initialized / checkpoint-loaded / your own
    device_put copies), never for caller-supplied params something else
    still references. ``mode`` is "int8" or "int4".
    """
    if mode == "int4":
        leaf = _quantize4_leaf_donate if donate else _quantize4_leaf
    else:
        leaf = _quantize_leaf_donate if donate else _quantize_leaf

    def maybe(w):
        if is_quantized(w):
            return w  # idempotent
        # Donated fp inputs can't alias the (differently-typed, packed)
        # outputs; the donation still frees each source eagerly, which is
        # its whole point here — silence jax's benign aliasing warning.
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message=".*donated buffers were not usable.*"
            )
            return leaf(w)

    out = dict(params)
    if "lm_head" in out:
        out["lm_head"] = maybe(out["lm_head"])
    for stack in ("layers_dense", "layers", "layers_ssm", "layers_moe",
                  "layers_attn", "layers_kda", "layers_mlp"):
        if stack in out:
            out[stack] = {
                name: maybe(w) if name in QUANT_KEYS else w
                for name, w in out[stack].items()
            }
    return out


# -- KV-cache quantization ---------------------------------------------------
#
# Long-context decode reads the whole cache every step and capacity caps
# max_seq (a 131k bf16 cache alone is ~9 GB on an 8-KV-head 1B model);
# int8 storage halves both. Scales are per (batch, position, head) over
# the head_dim axis — each written K/V row quantizes against its own max,
# so quality is insensitive to outlier positions elsewhere in the cache.


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """[..., dh] → (int8 codes, per-row scale [..., 1]) over the last axis."""
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, jnp.finfo(jnp.float32).tiny).astype(jnp.float32)
    q8 = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q8.astype(jnp.int8), scale.astype(x.dtype)


# The key of a cache's sub-tree of per-ROW state (a state-space model's
# recurrent state [L, B, H, P, N] and convolution tail [L, B, K-1, C]):
# leaves with layers on axis 0 and rows on axis 1 like every other, and NO
# sequence axis. They are told from the slot leaves by this key, never by
# their rank (a state is 5-D like a K stack, a tail 4-D like a scale stack).
STATE_KEY = "ssm"


def kv_seq_axis(leaf) -> int:
    """Seq axis of a stacked-cache SLOT leaf: 2 for the 5-D [L, B, S, H, dh]
    code/bf16 stacks, 3 (minor) for the 4-D seq-minor [L, B, H, S] int8
    scale stacks. This module owns the cache layout — every consumer that
    slices/rolls/masks along seq (batcher splice/compact, engine prefix
    restore) must route through this rule rather than re-encode it, and
    reaches the leaves through ``kv_tree_map``, which keeps the per-row
    state leaves (``STATE_KEY``: no sequence axis at all) away from it."""
    return 2 if leaf.ndim == 5 else 3


def kv_tree_map(slots, cache, *rest, state=None):
    """``jax.tree.map`` over a cache tree, by kind of leaf: ``slots`` over
    the leaves that have a sequence axis (``kv_seq_axis`` says which), and
    ``state`` over the per-row state leaves under ``STATE_KEY``, which have
    none. ``state=None`` keeps the first tree's state leaves as they are
    (what a slide or a mask along the sequence means for them). ``rest``
    are further trees of the same structure."""
    out = jax.tree.map(
        slots, *({k: v for k, v in t.items() if k != STATE_KEY}
                 for t in (cache, *rest)))
    if STATE_KEY in cache:
        out[STATE_KEY] = cache[STATE_KEY] if state is None else jax.tree.map(
            state, *(t[STATE_KEY] for t in (cache, *rest)))
    return out


def kv_write_rows(full, x: jax.Array, layer_idx, start_pos):
    """Write this step's K or V rows into the FULL stacked cache in place.

    ``full`` is [L, B, S, H, dh] with seq-minor scales [L, B, H, S] (or a
    plain bf16 stack); ``x`` is [B, T, H, dh]. Writing only the new rows
    at (layer_idx, 0, start_pos, 0, 0) — instead of threading per-layer
    entries through the layer scan as xs/ys — is what lets XLA alias the
    cache buffer through both the layer scan and the decode-step scan:
    profiling showed the xs/ys form copies the entire K and V stacks
    every decode step (~0.8 ms/step on a 4096-slot consensus-1b cache, a
    quarter of the step).
    """
    idx = (layer_idx, 0, start_pos, 0, 0)
    if not is_quantized(full):
        return jax.lax.dynamic_update_slice(full, x[None].astype(full.dtype), idx)
    q8, s = quantize_kv(x)
    s_rows = jnp.swapaxes(s[..., 0], 1, 2)  # [B, H, T], seq minor
    return {
        "q8": jax.lax.dynamic_update_slice(full["q8"], q8[None], idx),
        "s": jax.lax.dynamic_update_slice(
            full["s"], s_rows[None].astype(full["s"].dtype),
            (layer_idx, 0, 0, start_pos),
        ),
    }


def kv_layer(full, layer_idx, width=None):
    """One layer's cache entry [B, S(≤width), H, dh] from the full stack
    (scales come out [B, H, S≤width], their storage layout).

    Layer extraction and the width bound are ONE dynamic-slice: slicing
    the full layer first and narrowing afterwards invites XLA to relayout
    the whole [B, S_max, H, dh] entry for the attention consumer before
    the narrow (measured: a 67 MB copy per layer per decode step on a
    batch-8 consensus-1b cache); slicing to the width up front caps any
    such copy at the bytes attention actually reads.
    """
    def take(a, seq_axis=2):
        b, s = a.shape[1], a.shape[seq_axis]
        w = s if width is None else min(width, s)
        sizes = list(a.shape)
        sizes[0], sizes[seq_axis] = 1, w
        return jax.lax.dynamic_slice(
            a, (layer_idx,) + (0,) * (a.ndim - 1), sizes,
        )[0]

    if not is_quantized(full):
        return take(full)
    return {"q8": take(full["q8"]), "s": take(full["s"], seq_axis=3)}


def kv_read(entry, dtype) -> jax.Array:
    """Materialize a cache entry in ``dtype`` (width-narrowing happens in
    kv_layer, fused into the layer extract).

    For int8 entries the convert+scale fuses into the consuming attention
    matmul's operand stream, so HBM reads stay int8 — the same fusion the
    weight path relies on. The seq-minor scale [B, H, S] broadcasts back
    over the codes' [B, S, H, dh] layout via a transpose that fuses into
    the same elementwise pass.
    """
    if not is_quantized(entry):
        return entry
    s = jnp.swapaxes(entry["s"], 1, 2)[..., None]  # [B, S, H, 1]
    return entry["q8"].astype(dtype) * s.astype(dtype)


# Row bound for the nibble-dot decode lowering: beneath it the grouped
# [..., G, O] intermediate is trivially small and the lowering is a pure
# VPU win; above it (prefill) the MXU is the bottleneck and the plain
# dequantize-into-the-dot form avoids the G-sized intermediate.
_NIBBLE_DOT_MAX_ROWS = 16


def _int4_nibble_einsum(spec: str, x: jax.Array, w: dict, **kwargs) -> jax.Array:
    """Decode lowering: dot on raw unsigned nibbles, fix offset+scale on output.

    ``y = Σ_G s[G,o]·(x_first·lo_u + x_second·hi_u − 8·Σ x_G)`` — exact
    because the zero point (8) and scale are constant within a group.
    Dequant work per weight element drops to extract + convert (2 VPU
    ops); everything else is output-sized. Packing paired the first and
    second half of each group, so ``x`` splits into contiguous halves.
    """
    out_dtype = kwargs.pop("preferred_element_type", None) or x.dtype
    ins, out = spec.split("->")
    xsub, wsub = ins.split(",")
    c = wsub[-2]  # contraction letter: every weight here is [..., C, O]
    assert xsub.endswith(c), spec
    gl, hl = [l for l in "GHJKLMNPQRSTUVWXYZ" if l not in spec][:2]
    ol = wsub[-1]
    grouped = f"{xsub[:-1]}{gl}{hl},{wsub[:-2]}{gl}{hl}{ol}->{xsub[:-1]}{gl}{ol}"
    p, s = w["q4"], w["s"]
    *_, groups, half, o = p.shape
    lo = (p & 0xF).astype(x.dtype)
    hi = (p >> 4).astype(x.dtype)
    xg = x.reshape(x.shape[:-1] + (groups, 2 * half))
    yg = (
        jnp.einsum(grouped, xg[..., :half], lo, preferred_element_type=jnp.float32)
        + jnp.einsum(grouped, xg[..., half:], hi, preferred_element_type=jnp.float32)
        - 8.0 * jnp.sum(xg, axis=-1, dtype=jnp.float32)[..., None]
    )
    # Scale + reduce the group axis: einsum '...Go,(lead)Go->...o'. The
    # scale's lead axes (MoE experts) alias the x side's lead letters.
    s_sub = f"{wsub[:-2]}{gl}{ol}"
    final = f"{xsub[:-1]}{gl}{ol},{s_sub}->{out}"
    y = jnp.einsum(final, yg, s[..., 0, :].astype(jnp.float32))
    return y.astype(out_dtype)


def qeinsum(spec: str, x: jax.Array, w, **kwargs) -> jax.Array:
    """``jnp.einsum`` that accepts a quantized weight as the second operand.

    The convert to the activation dtype fuses into the dot (int8 HBM
    reads); the per-output-channel scale applies to the einsum output,
    whose trailing dims line up with the scale's ``[..., 1, out]`` shape
    by construction for every weight layout in this codebase.
    """
    if not is_quantized(w):
        return jnp.einsum(spec, x, w, **kwargs)
    if "q4" in w:
        impl = knobs.get_str("LLMC_INT4_IMPL")
        rows = 1
        for d in x.shape[:-1]:
            rows *= d
        if impl == "nibble" or (impl == "auto" and rows <= _NIBBLE_DOT_MAX_ROWS):
            return _int4_nibble_einsum(spec, x, w, **kwargs)
        # Prefill / wide-batch path: dequantize into the dot's operand
        # stream (group-wise scales vary along the contraction, so they
        # cannot move to the output like int8's).
        return jnp.einsum(spec, x, _unpack4(w, x.dtype), **kwargs)
    if w8a8_enabled():
        y = _w8a8_einsum(spec, x, w, **kwargs)
        if y is not None:
            return y
    y = jnp.einsum(spec, x, w["q8"].astype(x.dtype), **kwargs)
    # The kept contraction axis makes the scale [..., 1, out], which
    # right-aligns against every consumer's output shape here: [b,t,out]
    # for attention/MLP/lm_head ([1,out] broadcasts), [e,c,f] for MoE
    # experts ([e,1,f] broadcasts).
    return y * w["s"].astype(y.dtype)


_w8a8_ctx = threading.local()


@contextlib.contextmanager
def w8a8_scope(enabled):
    """Pin the W8A8 decision for everything traced inside.

    ``qeinsum`` decides at TRACE time; a bare environment read would let
    a cached executable compiled under the other setting serve a program
    whose caller wants this one (jit keys don't include the env). The
    engine's jitted wrappers thread their engine-level flag (a static
    arg, hence part of program identity) through this scope; direct
    callers outside any scope fall back to LLMC_W8A8."""
    prev = getattr(_w8a8_ctx, "value", None)
    _w8a8_ctx.value = enabled
    try:
        yield
    finally:
        _w8a8_ctx.value = prev


def w8a8_enabled() -> bool:
    v = getattr(_w8a8_ctx, "value", None)
    if v is not None:
        return bool(v)
    return knobs.get_bool("LLMC_W8A8")


def quantize_rows_sym(x: jax.Array):
    """Per-row symmetric int8 over the LAST axis → (codes int8,
    scale fp32 [..., 1]). The one copy of the max-abs/127, epsilon-floor,
    clip-round convention shared by the W8A8 matmul path and the decode
    kernel's q-quantization (ops/pallas/decode_attention.py)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    s = jnp.maximum(amax / 127.0, 1e-30)
    q = jnp.clip(jnp.round(xf / s), -127, 127).astype(jnp.int8)
    return q, s


def _w8a8_einsum(spec: str, x: jax.Array, w: dict, **kwargs):
    """Opt-in int8×int8 matmuls (LLMC_W8A8=1): activations quantize
    per row (symmetric int8 over the contraction axis) and the dot runs
    on the MXU's double int8 rate with int32 accumulation; the per-row
    activation scale and per-channel weight scale apply to the output —
    both are constant over the contraction, so the factorization is
    exact given the int8 rounding.

    Accuracy: adds the activation rounding error (~0.5% relative per
    dot) on top of the int8-weight error the quantized path already
    carries — the same class of tradeoff, but a NEW error source, so it
    ships opt-in rather than as the serving default; greedy outputs
    differ from the bf16-activation path (each config is internally
    token-exact: single-stream, generate_batch, and the pool all share
    the flag). The win is compute-bound decode at serving batch sizes,
    where the B-scaled bf16 matmul FLOPs are a leading step-time term.

    Returns None for specs whose output's leading dims are not the
    activation's (nothing in this codebase today) — caller falls back
    to the bf16-activation form.
    """
    ins, out = spec.split("->")
    xsub, wsub = ins.split(",")
    if not (xsub.endswith(wsub[-2]) and out.startswith(xsub[:-1])):
        return None
    xq, xs = quantize_rows_sym(x)
    kw = dict(kwargs)
    out_dtype = kw.pop("preferred_element_type", None) or x.dtype
    y = jnp.einsum(spec, xq, w["q8"], preferred_element_type=jnp.int32, **kw)
    y = y.astype(jnp.float32) * xs
    y = y * w["s"].astype(jnp.float32)
    return y.astype(out_dtype)
