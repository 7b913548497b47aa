"""Kernels: roofline share of the judge prompt's prefill on a judge model of
delta-rule layers beside output-gated attention, every layer followed by
routed experts: the operations one such prefill must do / the device kind's
peak bf16 operations per second, over its measured device time (a prefill of
a thousand tokens and more is bound by operations). The time is the trace's:
the judge model's ``prefill_chunks_loop__<judge>__kv*`` programs by name,
summed duration over runs; one run of the loop prefills one judge prompt
(``latent_moe_prefill_roofline.judge_prefill_programs``, which also says why
the judge model's panel prompts are not in it, and why a window of few runs
reads high).

The count of operations lives here and counts BY LAYER KIND. For a prompt of
``n`` REAL tokens (the mean, over the window's runs, of
``timings.judge_prompt_tokens``), two operations a multiply-add, counting
only what every sound implementation must compute:

  * a delta layer, a token: the four projections, the two low-rank pairs and
    beta; the convolution's taps; and the chunked rule at its live positions
    (``rule_macs_per_token``: a head's two decayed tables over the causal
    half of a chunk, the unit lower-triangular inverse by forward
    substitution and its two products, and the chunk's four products with
    the carried state);
  * an expert half, a token: the router, the shared expert's three matrices,
    and for each pair on a HELD expert (``d moe_prefill_pairs_held`` over the
    token slots and expert halves the window's prefill programs covered: an
    eighth of 8 a token if routing is even) the expert's three matrices, ``3
    x d_model x d_expert`` multiply-adds;
  * the attention layers: the four projections and the output gate's a
    token, and over the causal half ``n (n + 1) / 2`` (query, key) pairs a
    head, ``head_dim`` for the score and ``head_dim`` for the value: the one
    layer at its live width;
  * the head for the one position that is sampled.

A reading over 100% means this count is wrong. Nothing to read from a
program without the counters, from a trace without the named programs, or
for a judge that states no delta-rule layer."""

from benchmark import arith
from benchmark.layer_metrics.delta_moe_decode_roofline import (
    delta_inner, delta_matmul_params, expert_fixed_matmul_params,
    expert_params, gated_attention_matmul_params, kinds, stated)
from benchmark.layer_metrics.hybrid_ssm_prefill_roofline import judge_prompt_tokens
from benchmark.layer_metrics.latent_moe_prefill_roofline import (
    judge_prefill_programs)


def rule_macs_per_token(spec: dict) -> float:
    """Multiply-adds a token of a delta layer's chunked rule and its
    convolution, over all heads, keys and values ``P`` wide, ``C`` positions
    a chunk: the two tables' causal halves (``C P``), the inverse's forward
    substitution (``C^2 / 6``), its products with ``beta V`` and ``beta K``
    over the lower triangle (``C P``), the table's with ``U`` (``C P / 2``),
    and the three with the carried state (``3 P^2``: what it takes off
    ``U``, the query's read-out, the chunk's end state)."""
    m = spec["more_fields"]
    p, c = m["kda_head_dim"], m.get("kda_chunk", 64)
    rule = 3 * p * p + 2.5 * c * p + c * c / 6
    return m["kda_heads"] * rule + 3 * delta_inner(spec) * m.get("kda_conv", 4)


def prefill_ops(spec: dict, n: float, pairs_held_per_token_layer: float) -> float:
    """Operations of one prefill of ``n`` real tokens."""
    layers = kinds(spec)
    per_token = (
        layers["K"] * (delta_matmul_params(spec) + rule_macs_per_token(spec))
        + layers["E"] * (
            expert_fixed_matmul_params(spec)
            + pairs_held_per_token_layer * expert_params(spec))
        + layers["*"] * gated_attention_matmul_params(spec)
    )
    causal = (
        n * (n + 1) / 2 * spec["n_heads"] * 2 * spec["head_dim"] * layers["*"])
    head = spec["d_model"] * spec["vocab_size"]
    return 2.0 * (n * per_token + causal + head)


def read(ctx):
    spec = stated(ctx)
    if spec is None or ctx.get("peaks") is None:
        return None
    judge = ctx["config"]["judge"]
    after = (ctx["stats_after"].get("batchers") or {}).get(judge) or {}
    programs = judge_prefill_programs(ctx)
    n = judge_prompt_tokens(ctx)
    if ("moe_prefill_pairs_held" not in after or "ssm_positions_swept" not in after
            or programs is None or not n):
        return None
    d = lambda key: arith.delta(  # noqa: E731
        ctx["stats_after"], ctx["stats_before"], "batchers", judge, key)
    slot_tokens = d("prefill_slot_tokens")
    if slot_tokens <= 0:
        return None
    # the programs route every token slot they cover, padding included
    per_token_layer = d("moe_prefill_pairs_held") / (slot_tokens * kinds(spec)["E"])
    runs, total_s = programs
    least_s = prefill_ops(spec, n, per_token_layer) / ctx["peaks"]["bf16_flops_per_s"]
    return least_s / (total_s / runs) * 100.0
