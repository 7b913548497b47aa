"""Kernels: roofline share of one decode step of a judge model of delta-rule
layers beside output-gated attention, every layer followed by routed experts
(one-part layers ``*``, ``K``, ``E``): the least time the chip could take to
stream what the step must read and write (bytes / the device kind's peak
bytes per second; a decode step at a handful of rows is bound by bandwidth,
not by operations) over the step's measured device time (the judge's
``decode_chunk__<judge>__kv*__s*`` programs by name, as
``judge_model_decode_step_dev_ms`` reads them: summed duration over summed
runs x steps, so a chunk the window's edge cut counts all its steps with the
time inside, and a window of thirteen chunks reads up to a thirteenth high).

The count of bytes lives here, counts BY LAYER KIND (``more_fields.
layer_kinds``), and counts only what every sound implementation must move in
one step:

  * every held leaf outside the routed experts once, as stored: a delta
    layer's norm, ``wq, wk, wv, wo``, the convolution over ``q | k | v``, the
    decay's and the output gate's low-rank pairs, ``beta``, ``dt_bias``,
    ``A_log`` and the per-head norm; an attention layer's norm, ``wq, wk, wv,
    wo`` and its output gate; an expert half's norm, router, correction bias
    and shared expert; the final norm and the head (the slice held). The
    embedding is a gather of a row a stream: not counted;
  * of the held routed experts, ONLY THE DISTINCT ONES HIT: ``d
    moe_expert_reads / d moe_layer_steps`` experts an expert layer a step
    (/statsz batchers, the decode chunks of the whole window), each ``3 x
    d_model x d_expert`` (gated: three matrices at the model's width);
  * the live key and value slots: ``d decode_kv_slots_live / d decode_steps``
    slots a step (that counter sums slots over steps, not over layers) x ``2
    x n_kv_heads x head_dim`` values x the ATTENTION layers;
  * the delta rule's matrix state and the convolution tail, read AND written
    once a row a DELTA layer: ``d ssm_state_row_steps / d decode_steps`` rows
    a step (the counter a state layer already books) x (``kda_heads x
    kda_head_dim^2`` float32 + ``(kda_conv - 1) x 3 x kda_heads x
    kda_head_dim`` as stored) x the delta layers x 2. The one-position rule
    uses the state twice (what it holds for the key, then the query's
    read-out); a sound implementation reads it once.

A reading over 100% means this count is wrong. Nothing to read from a
program without the counters, from a trace without the named programs, or
for a judge that states no delta-rule layer."""

from benchmark.layer_metrics import judge_model_decode_step_dev_ms
from benchmark.layer_metrics.hybrid_latent_moe_decode_roofline import (
    attention_matmul_params, counters)
from benchmark.layer_metrics.hybrid_ssm_decode_roofline import ITEMSIZE


def kinds(spec: dict) -> dict:
    """Layers by kind, ``{"K": delta layers, "E": expert halves, "*":
    attention layers}``."""
    pattern = spec["more_fields"]["layer_kinds"]
    return {kind: pattern.count(kind) for kind in "KE*"}


def delta_inner(spec: dict) -> int:
    m = spec["more_fields"]
    return m["kda_heads"] * m["kda_head_dim"]


def delta_matmul_params(spec: dict) -> int:
    """A delta layer's products: four projections, the two low-rank pairs,
    beta."""
    m, d, inner = spec["more_fields"], spec["d_model"], delta_inner(spec)
    return 4 * d * inner + 2 * m["kda_rank"] * (d + inner) + d * m["kda_heads"]


def delta_params(spec: dict) -> int:
    """Every leaf of a delta layer, its norm included."""
    m, inner = spec["more_fields"], delta_inner(spec)
    return (
        delta_matmul_params(spec)
        + 3 * inner * m.get("kda_conv", 4)            # the convolution, no bias
        + inner + m["kda_heads"] + m["kda_head_dim"]  # dt_bias, A_log, the head norm
        + spec["d_model"])                            # the layer's norm


def gated_attention_matmul_params(spec: dict) -> int:
    """``wq, wk, wv, wo`` and the output gate, as wide as ``wq``."""
    return attention_matmul_params(spec) + (
        spec["d_model"] * spec["n_heads"] * spec["head_dim"])


def expert_fixed_matmul_params(spec: dict) -> int:
    """An expert half's products outside its routed experts: the router and
    the shared expert's three matrices."""
    m, d = spec["more_fields"], spec["d_model"]
    return d * (m.get("router_width") or m["n_experts"]) + (
        3 * d * m["n_shared_experts"] * m["d_expert"])


def expert_fixed_params(spec: dict) -> int:
    """Every leaf of an expert half outside its routed experts."""
    m = spec["more_fields"]
    return (
        expert_fixed_matmul_params(spec)
        + (m.get("router_width") or m["n_experts"])   # the correction bias
        + spec["d_model"])                            # the half's norm


def expert_params(spec: dict) -> int:
    """One routed expert: three matrices at the model's width."""
    return 3 * spec["d_model"] * spec["more_fields"]["d_expert"]


def fixed_params(spec: dict) -> int:
    """Parameters a decode step streams whatever its rows chose."""
    n, d = kinds(spec), spec["d_model"]
    head = 0 if spec["tie_embeddings"] else d * spec["vocab_size"]
    return (
        n["K"] * delta_params(spec) + n["E"] * expert_fixed_params(spec)
        + n["*"] * (gated_attention_matmul_params(spec) + d) + d + head)


def state_bytes_per_row(spec: dict, stored: str) -> int:
    """What one row holds beside its slots, over the DELTA layers."""
    m, inner = spec["more_fields"], delta_inner(spec)
    state = inner * m["kda_head_dim"] * 4
    tail = (m.get("kda_conv", 4) - 1) * 3 * inner * ITEMSIZE[stored]
    return kinds(spec)["K"] * (state + tail)


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


def stated(ctx):
    """The judge's entry if it states a pattern of one-part layers with a
    delta-rule layer and routed experts; else None."""
    cfg = ctx["config"]
    spec = cfg["models"][cfg["judge"]]
    more = spec.get("more_fields") or {}
    if not (more.get("layer_kinds") and more.get("kda_heads") and more.get("n_experts")):
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
