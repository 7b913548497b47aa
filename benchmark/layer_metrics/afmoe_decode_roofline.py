"""Kernels: roofline share of one decode step of a judge model whose stack
mixes window and full attention layers beside a dense MLP and routed expert
layers (one-part layers ``W``, ``*``, ``D``, ``E``): the least time the chip
could take to stream what the step must read (bytes / the device kind's peak
bytes per second; a decode step at a handful of rows is bound by bandwidth,
not by operations) over the step's measured device time (the judge's
``decode_chunk__<judge>__kv*__s*`` programs by name, as
``judge_model_decode_step_dev_ms`` reads them: summed duration over summed
runs x steps, so a chunk the window's edge cut counts all its steps with the
time inside, and a window of thirteen chunks reads up to a thirteenth high).

The count of bytes lives here, counts BY LAYER KIND (``more_fields.
layer_kinds``), and counts only what every sound implementation must move in
one step:

  * every held leaf outside the routed experts once, as stored: an attention
    layer's two norms, ``wq, wk, wv, wo``, its output gate and its two head
    norms (window or full: the same leaves); the dense MLP's two norms and
    three matrices; an expert layer's two norms, router, correction bias and
    shared expert; the final norm and the head (the slice held). The
    embedding is a gather of a row a stream: not counted;
  * of the held routed experts, ONLY THE DISTINCT ONES HIT: ``d
    moe_expert_reads / d moe_layer_steps`` experts an expert layer a step
    (/statsz batchers, the decode chunks of the whole window), each ``3 x
    d_model x d_expert`` (gated: three matrices at the model's width);
  * the live key and value slots BY KIND: a full layer reads each live row's
    whole context, ``d decode_kv_slots_live / d decode_steps`` slots a step
    (what that counter means for a pool of mixed windows), a window layer
    the last ``min(context, sliding_window)`` of it, ``d
    decode_kv_slots_window_layer / d decode_steps`` (the counter such a pool
    books beside it, engine/batcher.py ``_window_decode``; each sums slots
    over steps for ONE layer of its kind), x ``2 x n_kv_heads x head_dim``
    values x that kind's layers.

A reading over 100% means this count is wrong. Nothing to read from a
program without the counters, from a trace without the named programs, or
for a judge that states no window layer."""

from benchmark import arith
from benchmark.layer_metrics import judge_model_decode_step_dev_ms
from benchmark.layer_metrics.delta_moe_decode_roofline import (  # noqa: F401
    expert_fixed_matmul_params, expert_params, gated_attention_matmul_params)
from benchmark.layer_metrics.hybrid_ssm_decode_roofline import ITEMSIZE


def kinds(spec: dict) -> dict:
    """Layers by kind, ``{"W": window attention, "*": full attention, "D":
    dense MLPs, "E": expert layers}``."""
    pattern = spec["more_fields"]["layer_kinds"]
    return {kind: pattern.count(kind) for kind in "W*DE"}


def attention_params(spec: dict) -> int:
    """Every leaf of an attention layer of either kind: its products, the
    two head norms, the layer's two norms."""
    return (gated_attention_matmul_params(spec) + 2 * spec["head_dim"]
            + 2 * spec["d_model"])


def dense_matmul_params(spec: dict) -> int:
    return 3 * spec["d_model"] * spec["d_ff"]


def expert_fixed_params(spec: dict) -> int:
    """Every leaf of an expert layer outside its routed experts."""
    m = spec["more_fields"]
    return (
        expert_fixed_matmul_params(spec)
        + (m.get("router_width") or m["n_experts"])   # the correction bias
        + 2 * spec["d_model"])                        # the layer's two norms


def fixed_params(spec: dict) -> int:
    """Parameters a decode step streams whatever its rows chose."""
    n, d = kinds(spec), spec["d_model"]
    head = 0 if spec["tie_embeddings"] else d * spec["vocab_size"]
    return (
        (n["W"] + n["*"]) * attention_params(spec)
        + n["D"] * (dense_matmul_params(spec) + 2 * d)
        + n["E"] * expert_fixed_params(spec) + d + head)


def step_bytes(spec: dict, stored: str, experts_hit: float,
               slots_full: float, slots_window: float) -> float:
    """Bytes one decode step must read, given the distinct held experts hit
    an expert layer and the live key/value slots ONE layer of each kind
    sweeps."""
    n = kinds(spec)
    slot = 2 * spec["n_kv_heads"] * spec["head_dim"]
    cache = slot * (n["*"] * slots_full + n["W"] * slots_window)
    weights = fixed_params(spec) + n["E"] * experts_hit * expert_params(spec)
    return ITEMSIZE[stored] * (weights + cache)


def counters(ctx):
    """(distinct held experts hit an expert layer a step, live slots a step a
    full layer sweeps, live slots a step a window layer sweeps) over the
    window's decode chunks; None without the counters."""
    judge = ctx["config"]["judge"]
    after = (ctx["stats_after"].get("batchers") or {}).get(judge) or {}
    if "moe_layer_steps" not in after or "decode_kv_slots_window_layer" not in after:
        return None
    d = lambda key: arith.delta(  # noqa: E731
        ctx["stats_after"], ctx["stats_before"], "batchers", judge, key)
    layer_steps, steps = d("moe_layer_steps"), d("decode_steps")
    if layer_steps <= 0 or steps <= 0:
        return None
    return (d("moe_expert_reads") / layer_steps,
            d("decode_kv_slots_live") / steps,
            d("decode_kv_slots_window_layer") / steps)


def stated(ctx):
    """The judge's entry if it states a pattern of one-part layers with a
    window attention layer and routed experts; else None."""
    cfg = ctx["config"]
    spec = cfg["models"][cfg["judge"]]
    more = spec.get("more_fields") or {}
    if not ("W" in (more.get("layer_kinds") or "") and more.get("n_experts")
            and spec.get("sliding_window")):
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
