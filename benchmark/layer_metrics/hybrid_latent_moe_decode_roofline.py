"""Kernels: roofline share of one decode step of a judge model whose every
layer is ONE part (a Mamba-2 mixer, a LatentMoE layer, or attention): the
least time the chip could take to stream what the step must read and write
(bytes / the device kind's peak bytes per second; a decode step at a handful
of rows is bound by bandwidth, not by operations) over the step's measured
device time (the judge's ``decode_chunk__<judge>__kv*__s*`` programs by name,
as ``judge_model_decode_step_dev_ms`` reads them: summed duration over summed
runs x steps, so a chunk the window's edge cut counts all its steps with the
time inside, and a window of thirteen chunks reads up to a thirteenth high).

The count of bytes lives here, counts BY LAYER KIND (``more_fields.
layer_kinds``: ``M`` mixer, ``E`` experts, ``*`` attention), and counts only
what every sound implementation must move in one step:

  * every held leaf outside the routed experts once, as stored: a mixer
    layer's norm, ``ssm_in``, the convolution and its bias, ``dt_bias, A_log,
    D``, the gated norm, ``ssm_out``; an expert layer's norm, router and
    correction bias, the two latent projections and the shared expert; an
    attention layer's norm and ``wq, wk, wv, wo``; the final norm and the head
    (the slice held). The embedding is a gather of a row a stream: not
    counted;
  * of the held routed experts, ONLY THE DISTINCT ONES HIT: ``d
    moe_expert_reads / d moe_layer_steps`` experts an expert layer a step
    (/statsz batchers, the decode chunks of the whole window), each ``2 x
    moe_latent x d_expert`` (ungated: two matrices in the latent width);
  * the live key and value slots: ``d decode_kv_slots_live / d decode_steps``
    slots a step (that counter sums slots over steps, not over layers) x ``2
    x n_kv_heads x head_dim`` values x the ATTENTION layers;
  * the recurrent state and the convolution tail, read AND written once a row
    a MIXER layer: ``d ssm_state_row_steps / d decode_steps`` rows a step x
    (``ssm_heads x ssm_head_dim x ssm_state`` float32 + ``(ssm_conv - 1) x``
    the convolution's channels as stored) x the mixer layers x 2.

A reading over 100% means this count is wrong. Nothing to read from a
program without the counters, from a trace without the named programs, or
for a judge that states no ``layer_kinds``."""

from benchmark import arith
from benchmark.layer_metrics import judge_model_decode_step_dev_ms
from benchmark.layer_metrics.hybrid_ssm_decode_roofline import (
    ITEMSIZE, conv_channels)


def kinds(spec: dict) -> dict:
    """Layers by kind, ``{"M": mixers, "E": expert layers, "*": attention}``."""
    pattern = spec["more_fields"]["layer_kinds"]
    return {kind: pattern.count(kind) for kind in "ME*"}


def mixer_matmul_params(spec: dict) -> int:
    """A mixer layer's two projections."""
    m, d = spec["more_fields"], spec["d_model"]
    inner = m["ssm_heads"] * m["ssm_head_dim"]
    return d * (inner + conv_channels(spec) + m["ssm_heads"]) + inner * d


def mixer_params(spec: dict) -> int:
    """Every leaf of a mixer layer, its norm included."""
    m = spec["more_fields"]
    inner = m["ssm_heads"] * m["ssm_head_dim"]
    return (
        mixer_matmul_params(spec)
        + conv_channels(spec) * (m["ssm_conv"] + 1)   # convolution and bias
        + 3 * m["ssm_heads"] + inner                  # dt_bias, A_log, D; gated norm
        + spec["d_model"]                             # the layer's norm
    )


def expert_fixed_matmul_params(spec: dict) -> int:
    """An expert layer's products outside its routed experts: the router, the
    two latent projections, the shared expert's two matrices."""
    m, d = spec["more_fields"], spec["d_model"]
    return (
        d * (m.get("router_width") or m["n_experts"])
        + 2 * d * m["moe_latent"] + 2 * d * m["d_shared"])


def expert_fixed_params(spec: dict) -> int:
    """Every leaf of an expert layer outside its routed experts."""
    m = spec["more_fields"]
    return (
        expert_fixed_matmul_params(spec)
        + (m.get("router_width") or m["n_experts"])   # the correction bias
        + spec["d_model"])                            # the layer's norm


def expert_params(spec: dict) -> int:
    """One routed expert: two matrices in the latent width."""
    m = spec["more_fields"]
    return 2 * m["moe_latent"] * m["d_expert"]


def attention_matmul_params(spec: dict) -> int:
    return spec["d_model"] * spec["head_dim"] * (
        2 * spec["n_heads"] + 2 * spec["n_kv_heads"])


def fixed_params(spec: dict) -> int:
    """Parameters a decode step streams whatever its rows chose."""
    n, d = kinds(spec), spec["d_model"]
    head = 0 if spec["tie_embeddings"] else d * spec["vocab_size"]
    return (
        n["M"] * mixer_params(spec) + n["E"] * expert_fixed_params(spec)
        + n["*"] * (attention_matmul_params(spec) + d) + d + head)


def state_bytes_per_row(spec: dict, stored: str) -> int:
    """What one row holds beside its slots, over the MIXER layers."""
    m = spec["more_fields"]
    state = m["ssm_heads"] * m["ssm_head_dim"] * m["ssm_state"] * 4
    tail = (m["ssm_conv"] - 1) * conv_channels(spec) * ITEMSIZE[stored]
    return kinds(spec)["M"] * (state + tail)


def step_bytes(spec: dict, stored: str, experts_hit: float, slots_live: float,
               state_rows: float) -> float:
    """Bytes one decode step must move, given the distinct held experts hit
    an expert layer, the step's live key/value slots and the rows whose
    state it carries."""
    n = kinds(spec)
    cache = slots_live * 2 * spec["n_kv_heads"] * spec["head_dim"] * n["*"]
    weights = fixed_params(spec) + n["E"] * experts_hit * expert_params(spec)
    return ITEMSIZE[stored] * (weights + cache) \
        + 2 * state_rows * state_bytes_per_row(spec, stored)


def counters(ctx):
    """(distinct held experts hit an expert layer a step, live key/value
    slots a step, rows of state a step) over the window's decode chunks; None
    without the counters."""
    judge = ctx["config"]["judge"]
    after = (ctx["stats_after"].get("batchers") or {}).get(judge) or {}
    if "moe_layer_steps" not in after or "ssm_state_row_steps" not in after:
        return None
    d = lambda key: arith.delta(  # noqa: E731
        ctx["stats_after"], ctx["stats_before"], "batchers", judge, key)
    layer_steps, steps = d("moe_layer_steps"), d("decode_steps")
    if layer_steps <= 0 or steps <= 0:
        return None
    return (d("moe_expert_reads") / layer_steps,
            d("decode_kv_slots_live") / steps, d("ssm_state_row_steps") / steps)


def stated(ctx):
    """The judge's entry if it states a pattern of one-part layers with
    latent experts and a mixer; else None."""
    cfg = ctx["config"]
    spec = cfg["models"][cfg["judge"]]
    more = spec.get("more_fields") or {}
    if not (more.get("layer_kinds") and more.get("moe_latent") and more.get("ssm_heads")):
        return None
    return spec


def read(ctx):
    spec = stated(ctx)
    if spec is None or ctx.get("peaks") is None:
        return None
    step_ms = judge_model_decode_step_dev_ms.read(ctx)
    counted = counters(ctx)
    if not step_ms or counted is None:
        return None
    least_ms = (
        step_bytes(spec, ctx["config"]["weights"], *counted)
        / ctx["peaks"]["hbm_bytes_per_s"] * 1e3
    )
    return least_ms / step_ms * 100.0
