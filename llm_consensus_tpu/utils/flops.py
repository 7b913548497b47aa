"""FLOPs accounting: parameter counts, per-token FLOPs, device peaks, MFU.

The reference's only throughput signal is a chars/4 display estimate
(/root/reference/internal/ui/ui.go:142); the BASELINE.json metric ladder
instead targets real decode MFU, which needs the model's analytic FLOPs
per token and the chip's peak. Counts follow the standard 2·N matmul
FLOPs-per-token rule (Kaplan et al.) with the attention quadratic term
added explicitly; MoE counts only the experts a token is routed through.
"""

from __future__ import annotations

import math
from typing import Optional

from llm_consensus_tpu.models.config import ModelConfig


def param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    """Analytic parameter count for ``cfg``.

    ``active_only`` counts MoE expert params only for the
    ``experts_per_token`` experts a token actually visits — the number that
    drives per-token compute (and therefore MFU), not checkpoint size.
    """
    d, dh = cfg.d_model, cfg.head_dim
    if cfg.is_latent:
        # low-rank q and kv paths with their norms, and the output
        qk = cfg.qk_nope_dim + cfg.qk_rope_dim
        attn = (
            d * cfg.q_lora_rank + cfg.q_lora_rank
            + cfg.q_lora_rank * cfg.n_heads * qk
            + d * (cfg.kv_lora_rank + cfg.qk_rope_dim) + cfg.kv_lora_rank
            + cfg.kv_lora_rank * cfg.n_heads * (cfg.qk_nope_dim + cfg.v_head_dim)
            + cfg.n_heads * cfg.v_head_dim * d
        )
    else:
        q = d * cfg.n_heads * dh
        kv = 2 * d * cfg.n_kv_heads * dh
        o = cfg.n_heads * dh * d
        attn = q + kv + o + (q if cfg.attn_out_gate else 0)
        attn += 2 * dh if cfg.qk_norm else 0  # one weight a projection
    if cfg.qkv_bias:
        attn += (cfg.n_heads + 2 * cfg.n_kv_heads) * dh
    # a mixer: in-projection, depthwise convolution with its bias, dt_bias /
    # A_log / D a head, gated norm, out-projection
    mixer = (
        d * cfg.ssm_proj_width + cfg.ssm_conv_width * (cfg.ssm_conv + 1)
        + 3 * cfg.ssm_heads + cfg.ssm_inner + cfg.ssm_inner * d
    ) if cfg.has_ssm else 0
    # a delta-rule layer: four projections, the convolution over q | k | v,
    # the decay's and the output gate's low-rank pairs, beta, dt_bias a
    # channel, A_log a head, the per-head norm
    kda = (
        4 * d * cfg.kda_inner + cfg.kda_conv_width * cfg.kda_conv
        + 2 * cfg.kda_rank * (d + cfg.kda_inner) + d * cfg.kda_heads
        + cfg.kda_inner + cfg.kda_heads + cfg.kda_head_dim
    ) if cfg.has_kda else 0
    # The experts HELD here (a chip's share counts what it holds); a token
    # visits at most experts_per_token of them. The router keeps its whole
    # width (and its correction bias), the shared experts see every token;
    # an expert has three matrices or, ungated, two, at the model's width
    # or at the latent's, whose two projections count once a layer.
    n_mlp = min(cfg.experts_per_token, cfg.n_experts) if active_only else cfg.n_experts
    mats = 3 if cfg.gated_experts else 2
    routed = (
        n_mlp * mats * (cfg.moe_latent or d) * cfg.expert_width
        + (mats * d * cfg.shared_width if cfg.n_shared_experts else 0)
        + d * cfg.n_router
        + (cfg.n_router if cfg.router_scoring == "sigmoid_bias" else 0)
        + 2 * d * cfg.moe_latent
    ) if cfg.is_moe else 0
    embed = cfg.vocab_size * d
    head = 0 if cfg.tie_embeddings else cfg.vocab_size * d
    if cfg.layer_kinds:
        # every layer ONE part behind one norm, and where the model has one
        # (never beside a state) before a norm of its own; "D" a dense MLP
        ends = d + (d if cfg.post_norm else 0)
        return (
            cfg.n_ssm_layers * (mixer + d) + cfg.n_expert_layers * (routed + ends)
            + cfg.n_attn_layers * (attn + ends) + cfg.n_kda_layers * (kda + d)
            + cfg.n_mlp_layers * (3 * d * cfg.d_ff + ends)
            + embed + head + d)
    attn += mixer  # the mixer beside attention
    dense_mlp = 3 * d * cfg.d_ff  # gate + up + down
    norms = 2 * d
    n_dense = cfg.n_layers - cfg.n_expert_layers
    total = n_dense * (attn + dense_mlp + norms)
    if cfg.is_moe:
        total += cfg.n_expert_layers * (attn + routed + norms)
    head = 0 if cfg.tie_embeddings else cfg.vocab_size * d
    return total + embed + head + d  # + final norm


def cache_bytes_per_token(cfg: ModelConfig, itemsize: int = 2) -> int:
    """Bytes one token holds in the cache over every layer that holds keys
    and values (``cfg.n_attn_layers``: all of them, or where every layer is
    one part the attention layers alone): the ONE count
    the pools, the prefix budget and the bandwidth models read
    (``cfg.cache_width`` values a layer: K and V heads, or a latent)."""
    return cfg.n_attn_layers * cfg.cache_width * itemsize


def live_cache_bytes(cfg: ModelConfig, context_len: int, itemsize: int = 2) -> int:
    """Bytes of keys and values one row's decode step reads at
    ``context_len``, by attention KIND: a layer that sees every position
    reads the whole context, a layer under ``cfg.sliding_window`` the last
    ``min(context, window)`` slots of it (``cfg.n_window_layers``: the "W"
    layers of a mixed stack, or every layer of a model that states a
    window). What the cache HOLDS a token is ``cache_bytes_per_token``: the
    arena is uniform, every layer the pool's whole width."""
    context = max(0, context_len)
    windowed = cfg.n_window_layers
    swept = (cfg.n_attn_layers - windowed) * context
    if windowed:
        swept += windowed * min(context, cfg.sliding_window)
    return swept * cfg.cache_width * itemsize


def state_bytes_per_row(cfg: ModelConfig, itemsize: int = 2) -> int:
    """Bytes one ROW of a state-space model holds beside its keys and
    values, over every layer that keeps a state and whatever its context:
    the float32 state (a mixer's, or a delta rule's matrix a head) and the
    convolution tail (``itemsize`` bytes a value). 0 without one. What
    ``cache_bytes_per_token`` is to a slot, this is to a row."""
    if not cfg.has_state:
        return 0
    state, tail = cfg.row_state_shapes(1)
    return cfg.n_state_layers * (
        math.prod(state) * 4 + math.prod(tail) * itemsize)


def flops_per_token(cfg: ModelConfig, context_len: int = 0) -> float:
    """Forward-pass FLOPs for one token at the given KV-cache depth.

    2 FLOPs per param-weight MAC (embedding lookup excluded, unembed
    included), plus the attention scores/values term 2·2·L·H·dh·S which the
    2N rule omits — negligible at short context, dominant for the judge's
    long concatenated prompt.
    """
    weights = param_count(cfg, active_only=True)
    if not cfg.tie_embeddings:
        # The embedding table is a lookup, not a matmul; subtract it. With
        # tied embeddings the same table IS the unembed matmul, so it stays.
        weights -= cfg.vocab_size * cfg.d_model
    # scores and values over the context: per head q·k and p·v, which for a
    # latent model (absorbed decode form) both sweep the latent
    swept = (
        2 * cfg.kv_lora_rank + cfg.qk_rope_dim if cfg.is_latent
        else 2 * cfg.head_dim
    )
    attn_quad = 2 * cfg.n_attn_layers * cfg.n_heads * swept * max(0, context_len)
    # a mixer's recurrence a token: decay and update the state (3 a value),
    # read it out (2 a value); constant in the context
    scan = 5 * cfg.n_ssm_layers * cfg.ssm_inner * cfg.ssm_state
    # a delta rule's: decay, read what is held, write, read out (7 a value)
    scan += 7 * cfg.n_kda_layers * cfg.kda_inner * cfg.kda_head_dim
    return 2.0 * weights + float(attn_quad + scan)


class UnknownDeviceError(LookupError):
    """A TPU whose ``device_kind`` the peaks tables below do not list."""


def _peak(table, device_kind: str):
    """The table entry matching jax's ``device_kind`` (substring match).

    A device that is not a TPU (the CPU the tests ask for) has no entry
    and gets None — consumers then report no utilization. A TPU that is
    not in the table is an ERROR, not a default: a missing row would
    otherwise drop every MFU/MBU gauge, on exactly the machine the
    numbers are for."""
    kind = device_kind.lower()
    for key, value in table:
        if key in kind:
            return value
    if "tpu" in kind:
        raise UnknownDeviceError(
            f"no published peaks for device_kind {device_kind!r}: add it "
            "to the tables in llm_consensus_tpu/utils/flops.py"
        )
    return None


# Peak dense bf16 TFLOP/s per chip, from published TPU specs (v5e: Google
# Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM).
_PEAK_TFLOPS = (
    ("v6e", 918.0),
    ("v6", 918.0),
    ("v5p", 459.0),
    ("v5 lite", 197.0),  # v5e reports "TPU v5 lite"
    ("v5e", 197.0),
    ("v4 lite", 138.0),  # v4i
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
)


def device_peak_flops(device_kind: str) -> Optional[float]:
    """Peak bf16 FLOP/s for a chip; None off-TPU (see :func:`_peak`)."""
    tflops = _peak(_PEAK_TFLOPS, device_kind)
    return None if tflops is None else tflops * 1e12


# int8 peak multiplier vs the dense bf16 rate, per generation: v5e/v5p/
# v6e execute int8×int8 at double rate; v4's published int8 TOPS equal
# its bf16 TFLOPS (1×); v2/v3 have no int8 MXU acceleration (None).
_INT8_MULT = (
    ("v6e", 2.0), ("v6", 2.0), ("v5p", 2.0), ("v5 lite", 2.0),
    ("v5e", 2.0), ("v4 lite", 1.0), ("v4", 1.0), ("v3", None), ("v2", None),
)


def device_peak_int8_ops(device_kind: str) -> Optional[float]:
    """Peak int8 OP/s for a chip, or None when the generation has no
    int8 MXU rate (v2/v3) or the device is not a TPU.

    Normalization convention (VERDICT r3 weak #4): every ``*_mfu`` field
    this framework reports is normalized against the DENSE BF16 peak,
    including W8A8 lanes — so W8A8 points can be compared directly
    against bf16-activation points on one scale. The int8-peak variant
    (bf16-normalized MFU × bf16_peak / int8_peak) is reported alongside
    W8A8 numbers as the honest utilization of the rate the silicon
    actually offers that lane; climbing toward an MFU target via W8A8
    without saying so would be a units game.
    """
    peak = device_peak_flops(device_kind)
    if peak is None:
        return None
    mult = _peak(_INT8_MULT, device_kind)
    return None if mult is None else mult * peak


def decode_mfu(
    cfg: ModelConfig,
    tokens_per_sec: float,
    device_kind: str,
    n_devices: int = 1,
    context_len: int = 0,
) -> Optional[float]:
    """Model FLOPs utilization of a decode stream, or None off-accelerator."""
    peak = device_peak_flops(device_kind)
    if peak is None or tokens_per_sec <= 0:
        return None
    return tokens_per_sec * flops_per_token(cfg, context_len) / (peak * n_devices)


# Peak HBM bandwidth GB/s per chip (published specs), matched like
# _PEAK_TFLOPS. Decode at batch 1 is bandwidth-bound — every step streams
# the weights (+KV) from HBM — so MBU, not MFU, is the utilization number
# that says how close decode runs to the hardware limit.
_PEAK_HBM_GBPS = (
    ("v6e", 1640.0),
    ("v6", 1640.0),
    ("v5p", 2765.0),
    ("v5 lite", 819.0),
    ("v5e", 819.0),
    ("v4 lite", 614.0),
    ("v4", 1228.0),
    ("v3", 900.0),
    ("v2", 700.0),
)


def device_peak_hbm_bw(device_kind: str) -> Optional[float]:
    """Peak HBM bytes/s for a chip; None off-TPU (see :func:`_peak`)."""
    gbps = _peak(_PEAK_HBM_GBPS, device_kind)
    return None if gbps is None else gbps * 1e9


def ridge_rows(device_kind: str, itemsize: float = 2) -> Optional[float]:
    """Rows at which a product with a STORED matrix of ``itemsize`` bytes a
    value stops being bound by the matrix's bytes: a value is read once and
    does two operations a row, so ``peak operations / peak bytes a second x
    itemsize / 2`` (a v5e: 240 rows of a bf16 matrix, 120 of an int8 one,
    which is dequantized for a bf16 product). None off a TPU."""
    flops, bw = device_peak_flops(device_kind), device_peak_hbm_bw(device_kind)
    if flops is None or bw is None:
        return None
    return flops / bw * itemsize / 2


def prefill_ridge_width(cfg: ModelConfig, device_kind: str,
                        itemsize: float = 2, floor: int = 512) -> int:
    """The width of a one-row prefill's chunks at which the LEAST-FED stored
    matrix of ``cfg``'s stack reaches the device's ridge (``ridge_rows``):
    ``floor`` doubled until it does. In a chunk of ``w`` tokens a dense
    matrix sees ``w`` rows; a held expert's sees the pairs a token sends to
    held experts over the experts held, ``w x experts_per_token / router
    width`` where routing is even (the share held cancels). Under that width
    every chunk streams every such matrix whole for fewer rows than pay for
    it; a prompt narrower than the width can at most stream them once.
    ``floor`` off a TPU, and for every model without a router."""
    ridge = ridge_rows(device_kind, itemsize)
    if ridge is None:
        return floor
    fed = cfg.experts_per_token / cfg.n_router if cfg.is_moe else 1.0
    width = floor
    while width * fed < ridge:
        width *= 2
    return width


# The largest float32 score transient ``[heads, chunk, bucket]`` a chunk of a
# one-row prefill may ask a layer for: 1 GiB, what 64 heads ask at 2,048 x
# 2,048 and 128 heads at 1,024 x 2,048. Read on the chip (PERF.md section 6,
# PR 49; ``memory_peak_bytes`` of a serving window of the chip's 16.9 GB, 512
# -> under this number): Trinity-Mini's and Nemotron-3-Super's shares (32
# heads) 2,048 wide 12.73 -> 12.74 and 11.23 -> 11.23 GB, Solar-Open2's (64)
# 2,048 wide 12.35 -> 12.35, DeepSeek-V2's (128) 1,024 wide 13.17 -> 13.17:
# a wave of six panel prompts sets those peaks, and the 0.57-0.95 GB the
# wider chunk's transients add (the compiler's count for a described chip)
# fit under them; at 2,048 DeepSeek-V2's chunk would ask 2.68 GB, in a
# process whose check after the window ends 0.47 GB under the limit.
PREFILL_SCORE_BYTES = 1 << 30


def prefill_chunk_width(ridge_width: int, n_heads: int, bucket: int,
                        floor: int = 512) -> int:
    """The width of the chunks of ONE prompt's one-row prefill: ``floor``
    doubled while it stays within the model's ``ridge_width``
    (``prefill_ridge_width``), within the prompt's ``bucket`` of cache slots
    (a wider chunk is padding) and within ``PREFILL_SCORE_BYTES`` of
    attention scores a layer."""
    width = floor
    while (2 * width <= min(ridge_width, bucket)
           and n_heads * 2 * width * bucket * 4 <= PREFILL_SCORE_BYTES):
        width *= 2
    return width


def decode_bytes_per_token(
    cfg: ModelConfig,
    context_len: int = 0,
    weight_bytes: int = 2,
    kv_bytes: int = 2,
    rows: int = 1,
) -> float:
    """HBM bytes streamed per decode step: active weights + the KV read.

    ``weight_bytes``/``kv_bytes`` are the storage widths (2 = bf16,
    1 = int8 quantized). Each of the step's ``rows`` reads its own live
    keys and values at ``context_len`` (``live_cache_bytes``: a live window
    a kind of attention layer), and a state-space model's recurrent state
    and convolution tail are read and written once a step by each row,
    whatever the context.
    """
    weights = param_count(cfg, active_only=True)
    kv = rows * live_cache_bytes(cfg, context_len, kv_bytes)
    state = 2 * rows * state_bytes_per_row(cfg, kv_bytes)
    return float(weights * weight_bytes + kv + state)


def decode_mbu(
    cfg: ModelConfig,
    tokens_per_sec: float,
    device_kind: str,
    n_devices: int = 1,
    context_len: int = 0,
    weight_bytes: int = 2,
    kv_bytes: int = 2,
) -> Optional[float]:
    """Memory-bandwidth utilization of a decode stream, or None off-chip."""
    peak = device_peak_hbm_bw(device_kind)
    if peak is None or tokens_per_sec <= 0:
        return None
    per_tok = decode_bytes_per_token(cfg, context_len, weight_bytes, kv_bytes)
    return tokens_per_sec * per_tok / (peak * n_devices)


def batched_decode_mbu(
    cfg: ModelConfig,
    tokens_per_sec: float,
    batch: int,
    device_kind: str,
    n_devices: int = 1,
    context_len: int = 0,
    weight_bytes: int = 2,
    kv_bytes: int = 2,
) -> Optional[float]:
    """Bandwidth utilization of a ``batch``-stream shared-frontier decode.

    The single-stream formula overcounts at batch N: co-resident streams
    share one weight read per STEP (that sharing is the whole point of
    continuous batching), while each stream reads its own KV. So
    bytes/step = weights + N·kv, and the step rate is the aggregate token
    rate / N.
    """
    peak = device_peak_hbm_bw(device_kind)
    if peak is None or tokens_per_sec <= 0 or batch <= 0:
        return None
    # weights + batch·kv per step: one bytes model serves both the
    # single-stream and the batched MBU (each row reads its own live slots).
    per_step = decode_bytes_per_token(
        cfg, context_len, weight_bytes, kv_bytes, rows=batch
    )
    return (tokens_per_sec / batch) * per_step / (peak * n_devices)
