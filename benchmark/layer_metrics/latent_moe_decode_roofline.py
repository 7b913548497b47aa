"""Kernels: roofline share of one decode step of a latent-attention, routed
judge model: the least time the chip could take to stream what the step
must read (bytes / the device kind's peak bytes per second; a decode step
at a handful of rows is bound by bandwidth, not by operations) over the
step's measured device time (the judge's ``decode_chunk__<judge>__kv*__s*``
programs by name, as ``judge_model_decode_step_dev_ms`` reads them).

The count of bytes lives here, and counts only what every sound
implementation must stream in one step:

  * every leaf outside the routed experts once, as stored: each layer's
    attention (``wq_a, wq_b, wkv_a, wkv_b, wo`` and the two latent norms),
    the leading dense layers' SwiGLU, each expert layer's router and shared
    experts, the norms, the final norm and the head (the slice held). The
    embedding is a gather of a row a stream: not counted;
  * of the held routed experts, those that took at least one row:
    ``d moe_expert_reads / d moe_layer_steps`` experts an expert layer a
    step (/statsz batchers, the decode chunks of the whole window), each
    ``3 x d_model x d_expert``;
  * the live latent slots: ``d decode_kv_slots_live / d decode_steps`` slots
    a step (that counter sums slots over steps, not over layers) x
    ``(kv_lora_rank + qk_rope_dim)`` values x layers.

A reading over 100% means this count is wrong. Nothing to read from a
program without the counters, or from a trace without the named programs."""

from benchmark import arith
from benchmark.layer_metrics import judge_model_decode_step_dev_ms

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def attention_params(spec: dict) -> int:
    m, d, h = spec["more_fields"], spec["d_model"], spec["n_heads"]
    return (
        d * m["q_lora_rank"] + m["q_lora_rank"]
        + m["q_lora_rank"] * h * (m["qk_nope_dim"] + m["qk_rope_dim"])
        + d * (m["kv_lora_rank"] + m["qk_rope_dim"]) + m["kv_lora_rank"]
        + m["kv_lora_rank"] * h * (m["qk_nope_dim"] + m["v_head_dim"])
        + h * m["v_head_dim"] * d
    )


def expert_params(spec: dict) -> int:
    """One routed expert."""
    return 3 * spec["d_model"] * spec["more_fields"]["d_expert"]


def fixed_params(spec: dict) -> int:
    """Parameters a decode step streams whatever its rows chose."""
    m, d = spec["more_fields"], spec["d_model"]
    n_dense = m.get("n_dense_layers", 0)
    n_routed = spec["n_layers"] - n_dense
    per_layer = attention_params(spec) + 2 * d
    dense_mlp = 3 * d * spec["d_ff"]
    routed_fixed = d * (m.get("router_width") or m["n_experts"]) \
        + m.get("n_shared_experts", 0) * expert_params(spec)
    head = 0 if spec["tie_embeddings"] else d * spec["vocab_size"]
    return (
        spec["n_layers"] * per_layer + n_dense * dense_mlp
        + n_routed * routed_fixed + d + head
    )


def step_bytes(spec: dict, stored: str, experts_hit: float, slots_live: float) -> float:
    """Bytes one decode step must stream, given the held experts hit an
    expert layer and the live latent slots of the step."""
    m = spec["more_fields"]
    item = ITEMSIZE[stored]
    n_routed = spec["n_layers"] - m.get("n_dense_layers", 0)
    cache = slots_live * (m["kv_lora_rank"] + m["qk_rope_dim"]) * spec["n_layers"]
    return item * (
        fixed_params(spec) + n_routed * experts_hit * expert_params(spec) + cache)


def counters(ctx):
    """(held experts hit an expert layer a step, live latent slots a step)
    over the window's decode chunks; None without the counters."""
    judge = ctx["config"]["judge"]
    after = (ctx["stats_after"].get("batchers") or {}).get(judge) or {}
    if "moe_layer_steps" not in after:
        return None
    d = lambda key: arith.delta(  # noqa: E731
        ctx["stats_after"], ctx["stats_before"], "batchers", judge, key)
    layer_steps, steps = d("moe_layer_steps"), d("decode_steps")
    if layer_steps <= 0 or steps <= 0:
        return None
    return d("moe_expert_reads") / layer_steps, d("decode_kv_slots_live") / steps


def read(ctx):
    cfg = ctx["config"]
    spec = cfg["models"][cfg["judge"]]
    if not (spec.get("more_fields") or {}).get("kv_lora_rank") or ctx.get("peaks") is None:
        return None
    step_ms = judge_model_decode_step_dev_ms.read(ctx)
    counted = counters(ctx)
    if not step_ms or counted is None:
        return None
    least_ms = (
        step_bytes(spec, cfg["weights"], *counted)
        / ctx["peaks"]["hbm_bytes_per_s"] * 1e3
    )
    return least_ms / step_ms * 100.0
