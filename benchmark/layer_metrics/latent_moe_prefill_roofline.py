"""Kernels: roofline share of the judge prompt's prefill on a
latent-attention, routed judge model: the operations one such prefill must
do / the device kind's peak bf16 operations per second, over its measured
device time (a prefill of a thousand tokens and more is bound by
operations). The time is the trace's: the judge model's
``prefill_chunks_loop__<judge>__kv*`` and ``prefill_chunk__<judge>__kv*``
programs by name, summed duration over runs; one run of the loop prefills
one judge prompt. (The judge model's panel prompts, six short rows in one
``jit__prefill_step``, carry no model's name and are not in it.) A run cut
by the window's edge counts whole with the part of its time inside, so a
window of few runs reads high.

The count of operations lives here. For a prompt of ``n`` tokens really
admitted (the window's mean, as ``judge_prompt_tok_p50`` takes it from
``admit_tokens``), two operations a multiply-add, counting only what every
sound implementation must compute:

  * the matrix products of each token: every layer's attention projections
    (the latent expanded to keys and values ONCE a token), the leading dense
    layers' SwiGLU, each expert layer's router, shared experts, and one
    expert's SwiGLU for each pair on a held expert (``d
    moe_prefill_pairs_held`` over the tokens and expert layers prefilled:
    an eighth of six a token if routing is even);
  * the prefill form's attention over the causal half: ``n (n + 1) / 2``
    (query, key) pairs a head a layer, ``qk_nope + qk_rope`` for the score
    and ``v_head_dim`` for the value;
  * the head for the one position that is sampled.

A reading over 100% means this count is wrong. Nothing to read from a
program without the counter, or from a trace without the named programs."""

from benchmark import arith, trace_spans
from benchmark.layer_metrics import judge_prompt_tok_p50
from benchmark.layer_metrics.latent_moe_decode_roofline import (
    attention_params, expert_params)


def prefill_ops(spec: dict, n: float, pairs_held_per_token_layer: float) -> float:
    """Operations of one prefill of ``n`` tokens."""
    m, d, h = spec["more_fields"], spec["d_model"], spec["n_heads"]
    n_dense = m.get("n_dense_layers", 0)
    n_routed = spec["n_layers"] - n_dense
    norms = m["q_lora_rank"] + m["kv_lora_rank"]  # no products
    per_token = (
        spec["n_layers"] * (attention_params(spec) - norms)
        + n_dense * 3 * d * spec["d_ff"]
        + n_routed * (
            d * (m.get("router_width") or m["n_experts"])
            + (m.get("n_shared_experts", 0) + pairs_held_per_token_layer)
            * expert_params(spec))
    )
    causal = n * (n + 1) / 2 * h * (
        m["qk_nope_dim"] + m["qk_rope_dim"] + m["v_head_dim"]) * spec["n_layers"]
    head = d * spec["vocab_size"]
    return 2.0 * (n * per_token + causal + head)


def judge_prefill_programs(ctx):
    """(runs, seconds) of the judge model's named prefill programs on its
    first chip; None without them."""
    trace = ctx.get("trace")
    if not trace or not trace.get("chips"):
        return None
    judge = ctx["config"]["judge"]
    engines = (ctx["stats_after"].get("device") or {}).get("engines") or {}
    devices = (engines.get(judge) or {}).get("devices") or [0]
    chip = trace["chips"].get(f"/device:TPU:{devices[0]}")
    if chip is None:
        return None
    runs = {"prefill_chunks_loop": 0, "prefill_chunk": 0}
    total_s = 0.0
    for name, p in chip["programs"].items():
        prog = trace_spans.program_of(name)
        if prog and prog[0] in runs and prog[1] == trace_spans.name_safe(judge):
            runs[prog[0]] += p["runs"]
            total_s += p["total_s"]
    # one run of the loop is one prompt; bare chunks are parts of prompts
    # this count cannot tell apart, so a window that saw any is not read
    if not runs["prefill_chunks_loop"] or runs["prefill_chunk"] or total_s <= 0:
        return None
    return runs["prefill_chunks_loop"], total_s


def read(ctx):
    cfg = ctx["config"]
    judge = cfg["judge"]
    spec = cfg["models"][judge]
    more = spec.get("more_fields") or {}
    if not more.get("kv_lora_rank") or ctx.get("peaks") is None:
        return None
    after = (ctx["stats_after"].get("batchers") or {}).get(judge) or {}
    programs = judge_prefill_programs(ctx)
    n = judge_prompt_tok_p50.read(ctx)
    if "moe_prefill_pairs_held" not in after or programs is None or not n or n <= 0:
        return None
    d = lambda key: arith.delta(  # noqa: E731
        ctx["stats_after"], ctx["stats_before"], "batchers", judge, key)
    n_routed = spec["n_layers"] - more.get("n_dense_layers", 0)
    slot_tokens = d("prefill_slot_tokens")
    if slot_tokens <= 0:
        return None
    # the programs route every token slot they cover, padding included
    per_token_layer = d("moe_prefill_pairs_held") / (slot_tokens * n_routed)
    runs, total_s = programs
    least_s = prefill_ops(spec, n, per_token_layer) / ctx["peaks"]["bf16_flops_per_s"]
    return least_s / (total_s / runs) * 100.0
