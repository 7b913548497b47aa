"""Kernels: roofline share of one decode step of a hybrid state-space judge
model (a Mamba-2 mixer beside attention in every layer): the least time the
chip could take to stream what the step must read and write (bytes / the
device kind's peak bytes per second; a decode step at a handful of rows is
bound by bandwidth, not by operations) over the step's measured device time
(the judge's ``decode_chunk__<judge>__kv*__s*`` programs by name, as
``judge_model_decode_step_dev_ms`` reads them: summed duration over summed
runs x steps, so a chunk the window's edge cut counts all its steps with the
time inside, and a window of thirteen chunks reads up to a thirteenth high).

The count of bytes lives here, and counts only what every sound
implementation must move in one step:

  * every held leaf but the embedding once, as stored: each layer's attention
    (``wq, wk, wv, wo``), mixer (``ssm_in``, the convolution and its bias,
    ``dt_bias, A_log, D``, the gated norm, ``ssm_out``), SwiGLU and two norms,
    the final norm and the head (the slice held). The embedding is a gather
    of a row a stream: not counted;
  * the live key and value slots: ``d decode_kv_slots_live / d decode_steps``
    slots a step (that counter sums slots over steps, not over layers) x
    ``2 x n_kv_heads x head_dim`` values x layers;
  * the recurrent state and the convolution tail, read AND written once a row
    a layer: ``d ssm_state_row_steps / d decode_steps`` rows a step (/statsz
    batchers) x (``ssm_heads x ssm_head_dim x ssm_state`` float32 +
    ``(ssm_conv - 1) x`` the convolution's channels as stored) x layers x 2.

A reading over 100% means this count is wrong. Nothing to read from a
program without the counters, or from a trace without the named programs."""

from benchmark import arith
from benchmark.layer_metrics import judge_model_decode_step_dev_ms

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def conv_channels(spec: dict) -> int:
    m = spec["more_fields"]
    return m["ssm_heads"] * m["ssm_head_dim"] + 2 * m["ssm_groups"] * m["ssm_state"]


def matmul_params(spec: dict) -> int:
    """Weights of one layer's matrix products: attention, mixer, SwiGLU."""
    m, d = spec["more_fields"], spec["d_model"]
    inner = m["ssm_heads"] * m["ssm_head_dim"]
    attention = d * spec["head_dim"] * (2 * spec["n_heads"] + 2 * spec["n_kv_heads"])
    mixer = d * (inner + conv_channels(spec) + m["ssm_heads"]) + inner * d
    return attention + mixer + 3 * d * spec["d_ff"]


def layer_params(spec: dict) -> int:
    """Every leaf of one layer."""
    m = spec["more_fields"]
    inner = m["ssm_heads"] * m["ssm_head_dim"]
    small = (
        conv_channels(spec) * (m["ssm_conv"] + 1)   # convolution and bias
        + 3 * m["ssm_heads"] + inner                # dt_bias, A_log, D; gated norm
        + 2 * spec["d_model"]                       # the two norms
    )
    return matmul_params(spec) + small


def state_bytes_per_row(spec: dict, stored: str) -> int:
    """What one row holds beside its slots, over every layer."""
    m = spec["more_fields"]
    state = m["ssm_heads"] * m["ssm_head_dim"] * m["ssm_state"] * 4
    tail = (m["ssm_conv"] - 1) * conv_channels(spec) * ITEMSIZE[stored]
    return spec["n_layers"] * (state + tail)


def step_bytes(spec: dict, stored: str, slots_live: float, state_rows: float) -> float:
    """Bytes one decode step must move, given the step's live key/value
    slots and the rows whose state it carries."""
    item = ITEMSIZE[stored]
    d = spec["d_model"]
    head = 0 if spec["tie_embeddings"] else d * spec["vocab_size"]
    weights = spec["n_layers"] * layer_params(spec) + d + head
    cache = slots_live * 2 * spec["n_kv_heads"] * spec["head_dim"] * spec["n_layers"]
    return item * (weights + cache) + 2 * state_rows * state_bytes_per_row(spec, stored)


def counters(ctx):
    """(live key/value slots a step, rows of state a step) over the window's
    decode chunks; None without the counters."""
    judge = ctx["config"]["judge"]
    after = (ctx["stats_after"].get("batchers") or {}).get(judge) or {}
    if "ssm_state_row_steps" not in after:
        return None
    d = lambda key: arith.delta(  # noqa: E731
        ctx["stats_after"], ctx["stats_before"], "batchers", judge, key)
    steps = d("decode_steps")
    if steps <= 0:
        return None
    return d("decode_kv_slots_live") / steps, d("ssm_state_row_steps") / steps


def read(ctx):
    cfg = ctx["config"]
    spec = cfg["models"][cfg["judge"]]
    if not (spec.get("more_fields") or {}).get("ssm_heads") or ctx.get("peaks") is None:
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
