"""Kernels: roofline share of the judge prompt's prefill on a judge model
whose stack mixes window and full attention layers beside a dense MLP and
routed expert layers that hold every expert: the least time the chip could
take, THE LARGER of the operations one such prefill must do / the device
kind's peak bf16 operations per second and the bytes it must stream / its
peak bytes per second, over its measured device time. The time is the
trace's: the judge model's ``prefill_chunks_loop__<judge>__kv*`` programs by
name, summed duration over runs; one run of the loop prefills one judge
prompt (``latent_moe_prefill_roofline.judge_prefill_programs``, which also
says why the judge model's panel prompts are not in it, and why a window of
few runs reads high).

Why both bounds: with all 128 experts held, a judge prompt of under two
thousand tokens sends about a hundred rows to each expert, and reading every
expert's three matrices once (6.4 GB of the 6.95 GB held outside the
embedding) takes about as long as all the prompt's operations: the two
bounds lie within a tenth of each other here, where the other routed cells
(5-40 experts held, hundreds of rows an expert) are bound by operations.

Both counts live here and go BY LAYER KIND. For a prompt of ``n`` REAL tokens
(the mean, over the window's runs, of ``timings.judge_prompt_tokens``),
counting only what every sound implementation of a prefill must do:

  * operations, two a multiply-add: a token's products (an attention layer's
    four projections and output gate, the dense MLP's three matrices, an
    expert layer's router and shared expert, and for each pair on a HELD
    expert, ``d moe_prefill_pairs_held`` over the token slots and expert
    layers the window's prefill programs covered, the expert's three
    matrices); the (query, key) pairs a head, ``head_dim`` for the score and
    ``head_dim`` for the value: a full layer the causal half ``n (n + 1) /
    2``, a window layer ``sum_i min(i + 1, sliding_window)``; the head for
    the one position that is sampled;
  * bytes, as stored: ONCE A PROMPT every held leaf outside the embedding,
    the routed experts all of them (a prompt's thousands of pairs leave no
    expert of 128 unvisited) and the head. How the program cuts a prompt
    into chunks is not in the count: a program that streams the experts
    again for every chunk reads that many times lower, and that gap is what
    the metric shows.

A reading over 100% means a count is wrong. Nothing to read from a program
without the counters, from a trace without the named programs, or for a
judge that states no window layer."""

from benchmark import arith
from benchmark.layer_metrics.afmoe_decode_roofline import (
    dense_matmul_params, expert_fixed_matmul_params, expert_params,
    fixed_params, gated_attention_matmul_params, kinds, stated)
from benchmark.layer_metrics.hybrid_ssm_decode_roofline import ITEMSIZE
from benchmark.layer_metrics.hybrid_ssm_prefill_roofline import judge_prompt_tokens
from benchmark.layer_metrics.latent_moe_prefill_roofline import (
    judge_prefill_programs)


def window_pairs(n: float, window: int) -> float:
    """(Query, key) pairs of ``n`` positions under ``window``: ``sum_i min(i
    + 1, window)``."""
    if n <= window:
        return n * (n + 1) / 2
    return window * (window + 1) / 2 + (n - window) * window


def prefill_ops(spec: dict, n: float, pairs_held_per_token_layer: float) -> float:
    """Operations of one prefill of ``n`` real tokens."""
    layers = kinds(spec)
    per_token = (
        (layers["W"] + layers["*"]) * gated_attention_matmul_params(spec)
        + layers["D"] * dense_matmul_params(spec)
        + layers["E"] * (
            expert_fixed_matmul_params(spec)
            + pairs_held_per_token_layer * expert_params(spec)))
    pairs = (layers["*"] * n * (n + 1) / 2
             + layers["W"] * window_pairs(n, spec["sliding_window"]))
    scored = pairs * spec["n_heads"] * 2 * spec["head_dim"]
    head = spec["d_model"] * spec["vocab_size"]
    return 2.0 * (n * per_token + scored + head)


def prefill_bytes(spec: dict, stored: str) -> float:
    """Bytes one prefill must stream, whatever its length: every held leaf
    outside the embedding once."""
    held = (fixed_params(spec) + kinds(spec)["E"]
            * spec["more_fields"]["n_experts"] * expert_params(spec))
    return ITEMSIZE[stored] * held


def read(ctx):
    spec = stated(ctx)
    if spec is None or ctx.get("peaks") is None:
        return None
    cfg = ctx["config"]
    judge = cfg["judge"]
    after = (ctx["stats_after"].get("batchers") or {}).get(judge) or {}
    programs = judge_prefill_programs(ctx)
    n = judge_prompt_tokens(ctx)
    if "moe_prefill_pairs_held" not in after or programs is None or not n:
        return None
    d = lambda key: arith.delta(  # noqa: E731
        ctx["stats_after"], ctx["stats_before"], "batchers", judge, key)
    slot_tokens = d("prefill_slot_tokens")
    if slot_tokens <= 0:
        return None
    # the programs route every token slot they cover, padding included
    per_token_layer = d("moe_prefill_pairs_held") / (slot_tokens * kinds(spec)["E"])
    runs, total_s = programs
    least_s = max(
        prefill_ops(spec, n, per_token_layer) / ctx["peaks"]["bf16_flops_per_s"],
        prefill_bytes(spec, cfg["weights"]) / ctx["peaks"]["hbm_bytes_per_s"])
    return least_s / (total_s / runs) * 100.0
