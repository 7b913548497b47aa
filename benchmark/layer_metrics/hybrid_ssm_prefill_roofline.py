"""Kernels: roofline share of the judge prompt's prefill on a hybrid
state-space judge model: the operations one such prefill must do / the device
kind's peak bf16 operations per second, over its measured device time (a
prefill of a thousand tokens and more is bound by operations). The time is
the trace's: the judge model's ``prefill_chunks_loop__<judge>__kv*`` programs
by name, summed duration over runs; one run of the loop prefills one judge
prompt (``latent_moe_prefill_roofline.judge_prefill_programs``, which also
says why the judge model's panel prompts are not in it, and why a window of
few runs reads high).

The count of operations lives here. For a prompt of ``n`` REAL tokens (the
mean, over the window's runs, of ``timings.judge_prompt_tokens``: what the
judge's pool admitted for that run by its own counter; not
``judge_prompt_tok_p50``, which divides a window's tokens by its completed
runs), two operations a multiply-add, counting only what every sound
implementation must compute:

  * the matrix products of each token, every layer: attention's four
    projections, the mixer's in- and out-projection, the SwiGLU's three;
  * attention over the causal half: ``n (n + 1) / 2`` (query, key) pairs a
    head a layer, ``head_dim`` for the score and ``head_dim`` for the value;
  * the scan's four products a chunk of ``Q = ssm_chunk`` positions, a
    token: ``C B^T`` (``Q x N`` a group), the scores times the inputs
    (``Q x P`` a head), the chunk's end state and the carried state's
    contribution (``P x N`` a head each);
  * the depthwise convolution, ``ssm_conv`` taps a channel;
  * the head for the one position that is sampled.

A reading over 100% means this count is wrong. Nothing to read from a
program without the mixer's counters, or from a trace without the named
programs."""

from benchmark.layer_metrics.hybrid_ssm_decode_roofline import (
    conv_channels, matmul_params)
from benchmark.layer_metrics.latent_moe_prefill_roofline import (
    judge_prefill_programs)


def scan_macs_per_token(spec: dict) -> int:
    """Multiply-adds of one layer's chunked scan and convolution, a token."""
    m = spec["more_fields"]
    q, n, p = m["ssm_chunk"], m["ssm_state"], m["ssm_head_dim"]
    return (
        m["ssm_groups"] * q * n + m["ssm_heads"] * (q * p + 2 * p * n)
        + m["ssm_conv"] * conv_channels(spec)
    )


def prefill_ops(spec: dict, n: float) -> float:
    """Operations of one prefill of ``n`` real tokens."""
    per_token = spec["n_layers"] * (matmul_params(spec) + scan_macs_per_token(spec))
    causal = (
        n * (n + 1) / 2 * spec["n_heads"] * 2 * spec["head_dim"] * spec["n_layers"])
    head = spec["d_model"] * spec["vocab_size"]
    return 2.0 * (n * per_token + causal + head)


def judge_prompt_tokens(ctx):
    """Mean real tokens of a judge prompt over the window's runs, from each
    run's ``timings.judge_prompt_tokens``; None where no run carries it."""
    counts = [
        ((r.get("doc") or {}).get("timings") or {}).get("judge_prompt_tokens")
        for r in ctx["ok"] + ctx["failed"]
    ]
    counts = [c for c in counts if c]
    return sum(counts) / len(counts) if counts else None


def read(ctx):
    cfg = ctx["config"]
    judge = cfg["judge"]
    spec = cfg["models"][judge]
    if not (spec.get("more_fields") or {}).get("ssm_heads") or ctx.get("peaks") is None:
        return None
    after = (ctx["stats_after"].get("batchers") or {}).get(judge) or {}
    programs = judge_prefill_programs(ctx)
    n = judge_prompt_tokens(ctx)
    if "ssm_positions_swept" not in after or programs is None or not n:
        return None
    runs, total_s = programs
    least_s = prefill_ops(spec, n) / ctx["peaks"]["bf16_flops_per_s"]
    return least_s / (total_s / runs) * 100.0
