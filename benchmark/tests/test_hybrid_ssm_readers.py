"""The readers and the reference that came with the Falcon-H1 cell (PR 34):
each reader on hand-made contexts (a reading, nothing without the counters,
a count that cannot top 100% on a made-up step at the roofline and reads over
it when the counters claim more than the time allows), and the reference
against a literal per-position loop in numpy at the tiny size."""

import json
import os

import numpy as np
import pytest

from benchmark import peaks
from benchmark.layer_metrics import (
    hybrid_ssm_decode_roofline as decode_roof,
    hybrid_ssm_prefill_roofline as prefill_roof,
    ssm_scan_live_share)
from benchmark.reference import falcon_h1

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
JUDGE = "falcon-h1-34b"
V5E = peaks.peaks_of("TPU v5 lite")


def config(name: str) -> dict:
    with open(os.path.join(REPO, f"benchmark/configs/{name}.json")) as f:
        return json.load(f)


CONFIG = config("falcon-h1-34b-pp8-trio-bf16")
SPEC = CONFIG["models"][JUDGE]
LAYERS = SPEC["n_layers"]


def batcher(**counters) -> dict:
    return {"batchers": {JUDGE: counters}, "device": {"engines": {JUDGE: {"devices": [0]}}}}


def ctx(after: dict, programs: dict, runs=(), before=None) -> dict:
    return {
        "config": CONFIG, "peaks": V5E, "ok": list(runs), "failed": [],
        "stats_before": before or batcher(), "stats_after": after,
        "trace": {"chips": {"/device:TPU:0": {"programs": programs}}},
    }


def program(runs: int, total_s: float) -> dict:
    return {"runs": runs, "total_s": total_s, "mean_ms": total_s / runs * 1e3}


def run_with(judge_prompt_tokens: int) -> dict:
    return {"prompt_tokens": 100,
            "doc": {"timings": {"judge_prompt_tokens": judge_prompt_tokens}}}


COUNTERS = dict(
    decode_steps=1600, decode_kv_slots_live=1600 * 6 * 300,
    ssm_state_row_steps=1600 * 6, ssm_positions_swept=40_000,
    ssm_positions_live=30_000, admit_tokens=30_000, prefill_slot_tokens=38_000,
)
DECODE_NAME = f"decode_chunk__{JUDGE.replace('-', '_').replace('.', '_')}__kv384__s16"
LOOP_NAME = f"prefill_chunks_loop__{JUDGE.replace('-', '_').replace('.', '_')}__kv2048"


def test_the_count_of_bytes_is_the_table_of_the_issue():
    # attention 31.46 M, mixer 68.35 M, SwiGLU 330.30 M, a layer 430.12 M
    assert decode_roof.matmul_params(SPEC) == 31_457_280 + 68_321_280 + 330_301_440
    assert decode_roof.layer_params(SPEC) == 430_120_032
    assert decode_roof.conv_channels(SPEC) == 5120
    row = decode_roof.state_bytes_per_row(SPEC, "bfloat16")
    assert row == LAYERS * (32 * 128 * 256 * 4 + 3 * 5120 * 2)
    step = decode_roof.step_bytes(SPEC, "bfloat16", 6 * 300.0, 6.0)
    weights = 2 * (LAYERS * 430_120_032 + 5120 + 5120 * 32640)  # no embedding
    cache = 6 * 300 * 2 * 4 * 128 * 2 * LAYERS
    assert step == pytest.approx(weights + cache + 2 * 6 * row)
    # the state, in and out, is a twentieth of the step at six rows
    assert 0.04 < 2 * 6 * row / step < 0.07


def test_decode_roofline_reads_and_cannot_top_100_at_the_roofline():
    step = decode_roof.step_bytes(SPEC, "bfloat16", 6 * 300.0, 6.0)
    at_roofline_s = step / V5E["hbm_bytes_per_s"]
    c = ctx(batcher(**COUNTERS), {DECODE_NAME: program(10, 10 * 16 * at_roofline_s)})
    assert decode_roof.read(c) == pytest.approx(100.0)
    slower = ctx(batcher(**COUNTERS),
                 {DECODE_NAME: program(10, 10 * 16 * at_roofline_s * 1.25)})
    assert decode_roof.read(slower) == pytest.approx(80.0)
    # counters that claim more rows of state than the step's time allows for
    # read over 100: that is how a wrong count shows
    wrong = dict(COUNTERS, ssm_state_row_steps=1600 * 60)
    assert decode_roof.read(
        ctx(batcher(**wrong), {DECODE_NAME: program(10, 10 * 16 * at_roofline_s)})) > 105


def test_prefill_roofline_reads_and_cannot_top_100_at_the_roofline():
    runs = [run_with(1700), run_with(1900)]  # mean 1,800 real tokens
    ops = prefill_roof.prefill_ops(SPEC, 1800.0)
    per_token = LAYERS * decode_roof.matmul_params(SPEC)
    assert ops > 2 * 1800 * per_token                      # the products
    assert ops < 2 * 1800 * per_token * 1.04               # the rest is small
    assert prefill_roof.scan_macs_per_token(SPEC) == (
        2 * 128 * 256 + 32 * (128 * 128 + 2 * 128 * 256) + 4 * 5120)
    at_roofline_s = ops / V5E["bf16_flops_per_s"]
    c = ctx(batcher(**COUNTERS), {LOOP_NAME: program(3, 3 * at_roofline_s)}, runs)
    assert prefill_roof.read(c) == pytest.approx(100.0)
    c = ctx(batcher(**COUNTERS), {LOOP_NAME: program(3, 3 * at_roofline_s * 4)}, runs)
    assert prefill_roof.read(c) == pytest.approx(25.0)
    # runs that claim longer prompts than the time allows for read over 100
    c = ctx(batcher(**COUNTERS), {LOOP_NAME: program(3, 3 * at_roofline_s)},
            [run_with(3600)])
    assert prefill_roof.read(c) > 105
    # bare chunks in the window are parts of prompts: not read
    bare = LOOP_NAME.replace("prefill_chunks_loop", "prefill_chunk")
    c = ctx(batcher(**COUNTERS), {LOOP_NAME: program(3, 1.0), bare: program(2, 0.1)}, runs)
    assert prefill_roof.read(c) is None
    # no run says how long its judge prompt was: not read
    c = ctx(batcher(**COUNTERS), {LOOP_NAME: program(3, 1.0)}, [{"prompt_tokens": 9}])
    assert prefill_roof.read(c) is None


def test_scan_live_share_is_live_over_swept_of_the_window():
    before = batcher(ssm_positions_swept=10_000, ssm_positions_live=9_000)
    c = ctx(batcher(**COUNTERS), {}, before=before)
    assert ssm_scan_live_share.read(c) == pytest.approx(21_000 / 30_000 * 100)
    nothing_swept = ctx(before, {}, before=before)
    assert ssm_scan_live_share.read(nothing_swept) is None


NOTHING = {
    "no-counters": (batcher(decode_steps=5, decode_kv_slots_live=9), True),
    "no-trace-programs": (batcher(**COUNTERS), False),
}


@pytest.mark.parametrize("case", NOTHING)
def test_readers_find_nothing_and_do_not_raise(case):
    """The parent has named programs and none of the mixer's counters; a
    window can hold no judge program."""
    after, with_programs = NOTHING[case]
    programs = {DECODE_NAME: program(4, 0.4), LOOP_NAME: program(2, 0.4)}
    c = ctx(after, programs if with_programs else {}, [run_with(1800)])
    assert decode_roof.read(c) is None and prefill_roof.read(c) is None
    if with_programs:
        assert ssm_scan_live_share.read(c) is None
    no_trace = dict(c, trace=None)
    assert decode_roof.read(no_trace) is None and prefill_roof.read(no_trace) is None
    # a judge without a mixer (another cell's configuration) reads nothing
    dense = dict(c, config={**CONFIG, "models": {JUDGE: {"family": "mistral"}}})
    assert decode_roof.read(dense) is None and prefill_roof.read(dense) is None


def test_the_names_are_the_programs_names():
    from benchmark import trace_spans

    assert trace_spans.program_of(DECODE_NAME)[1] == trace_spans.name_safe(JUDGE)
    assert trace_spans.program_of(LOOP_NAME)[1] == trace_spans.name_safe(JUDGE)


# -- the reference against a literal loop --------------------------------------


def numpy_forward(params, spec, ids):
    """The block's equations as loops over positions, heads and taps, in
    float64 numpy: nothing shared with the reference but the equations."""
    m = spec["more_fields"]
    f = lambda a: np.asarray(a, np.float64)  # noqa: E731
    heads, p, n, groups = m["ssm_heads"], m["ssm_head_dim"], m["ssm_state"], m["ssm_groups"]
    inner, gn, k = heads * p, groups * n, m["ssm_conv"]
    eps, t = spec["rms_eps"], len(ids)
    hq, hkv, dh = spec["n_heads"], spec["n_kv_heads"], spec["head_dim"]

    def norm(x, w):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * f(w)

    def silu(x):
        return x / (1.0 + np.exp(-x))

    def rope(x, pos):
        half = dh // 2
        inv = 1.0 / (spec["rope_theta"] ** (np.arange(0, dh, 2) / dh))
        c, s = np.cos(pos * inv), np.sin(pos * inv)
        return np.concatenate(
            [x[:half] * c - x[half:] * s, x[half:] * c + x[:half] * s])

    x = f(params["embed"])[ids] * m["embedding_multiplier"]
    for layer in range(spec["n_layers"]):
        w = {key: f(leaf[layer]) for key, leaf in params["layers"].items()}
        u = norm(x, w["attn_norm"])
        # the mixer, a position at a time
        proj = (u * m["ssm_in_multiplier"]) @ w["ssm_in"]
        mz, mx, mb, mc, mdt = m["ssm_multipliers"]
        z = proj[:, :inner] * mz
        xbc = proj[:, inner:2 * inner + 2 * gn] * np.concatenate(
            [np.full(inner, mx), np.full(gn, mb), np.full(gn, mc)])
        dt = proj[:, 2 * inner + 2 * gn:] * mdt
        conv = np.zeros_like(xbc)
        for pos in range(t):
            for tap in range(k):
                src = pos - (k - 1) + tap
                if src >= 0:
                    conv[pos] += xbc[src] * w["ssm_conv"][:, tap]
            conv[pos] += w["ssm_conv_bias"]
        conv = silu(conv)
        dt = np.log1p(np.exp(dt + w["ssm_dt_bias"]))
        a = -np.exp(w["ssm_a_log"])
        state = np.zeros((heads, p, n))
        y = np.zeros((t, inner))
        for pos in range(t):
            for h in range(heads):
                g = h // (heads // groups)
                xs = conv[pos, h * p:(h + 1) * p]
                b = conv[pos, inner + g * n:inner + (g + 1) * n]
                c = conv[pos, inner + gn + g * n:inner + gn + (g + 1) * n]
                state[h] = np.exp(dt[pos, h] * a[h]) * state[h] + dt[pos, h] * np.outer(xs, b)
                y[pos, h * p:(h + 1) * p] = state[h] @ c + w["ssm_d"][h] * xs
        y = y * silu(z)
        size = inner // groups
        for g in range(groups):
            part = y[:, g * size:(g + 1) * size]
            y[:, g * size:(g + 1) * size] = part / np.sqrt(
                (part * part).mean(-1, keepdims=True) + eps)
        mixed = ((y * w["ssm_norm"]) @ w["ssm_out"]) * m["ssm_out_multiplier"]
        # attention, a query at a time
        ua = u * m["attention_in_multiplier"]
        q = (ua @ w["wq"]).reshape(t, hq, dh)
        kk = ((ua @ w["wk"]) * m["key_multiplier"]).reshape(t, hkv, dh)
        v = (ua @ w["wv"]).reshape(t, hkv, dh)
        out = np.zeros((t, hq, dh))
        for pos in range(t):
            for h in range(hq):
                g = h // (hq // hkv)
                qr = rope(q[pos, h], pos)
                scores = np.asarray(
                    [qr @ rope(kk[s, g], s) for s in range(pos + 1)]) / np.sqrt(dh)
                weights = np.exp(scores - scores.max())
                out[pos, h] = (weights / weights.sum()) @ v[:pos + 1, g]
        attended = (out.reshape(t, hq * dh) @ w["wo"]) * m["attention_out_multiplier"]
        x = x + mixed + attended
        hidden = norm(x, w["mlp_norm"])
        gate = silu((hidden @ w["w_gate"]) * m["mlp_multipliers"][0])
        x = x + ((gate * (hidden @ w["w_up"])) @ w["w_down"]) * m["mlp_multipliers"][1]
    return (norm(x, params["final_norm"]) @ f(params["lm_head"])) * m["lm_head_multiplier"]


def test_the_reference_is_the_literal_loop():
    import jax
    import jax.numpy as jnp

    spec = config("tiny-falcon-h1-rehearsal")["models"]["tiny-falcon-h1-mup"]
    m = spec["more_fields"]
    layers, d, f_, c = spec["n_layers"], spec["d_model"], spec["d_ff"], (
        m["ssm_heads"] * m["ssm_head_dim"] + 2 * m["ssm_groups"] * m["ssm_state"])
    inner = m["ssm_heads"] * m["ssm_head_dim"]
    shapes = {
        "attn_norm": (d,), "mlp_norm": (d,),
        "wq": (d, spec["n_heads"] * spec["head_dim"]),
        "wk": (d, spec["n_kv_heads"] * spec["head_dim"]),
        "wv": (d, spec["n_kv_heads"] * spec["head_dim"]),
        "wo": (spec["n_heads"] * spec["head_dim"], d),
        "w_gate": (d, f_), "w_up": (d, f_), "w_down": (f_, d),
        "ssm_in": (d, inner + c + m["ssm_heads"]), "ssm_conv": (c, m["ssm_conv"]),
        "ssm_conv_bias": (c,), "ssm_dt_bias": (m["ssm_heads"],),
        "ssm_a_log": (m["ssm_heads"],), "ssm_d": (m["ssm_heads"],),
        "ssm_norm": (inner,), "ssm_out": (inner, d),
    }
    rng = np.random.default_rng(5)
    draw = lambda shape, scale: jnp.asarray(  # noqa: E731
        rng.normal(size=shape) * scale, jnp.float32)
    params = {
        "embed": draw((spec["vocab_size"], d), 0.05),
        "final_norm": 1.0 + draw((d,), 0.1),
        "lm_head": draw((d, spec["vocab_size"]), d ** -0.5),
        "layers": {
            name: (1.0 + draw((layers, *shape), 0.1) if name.endswith("norm")
                   else draw((layers, *shape), 0.3 if len(shape) == 1 else shape[0] ** -0.5))
            for name, shape in shapes.items()
        },
    }
    ids = rng.integers(0, spec["vocab_size"], 21)
    got = np.asarray(falcon_h1.forward(params, spec, ids), np.float64)
    want = numpy_forward(jax.tree.map(np.asarray, params), spec, ids)
    err = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert err.max() < 2e-5


def test_the_reference_refuses_another_family():
    with pytest.raises(ValueError, match="no plain reference for family"):
        falcon_h1.forward({}, {"family": "mistral"}, [1, 2])


ERRORS = {
    # name: (per-position errors, decoded from, ok)
    "sound": (np.full(64, 0.004), 48, True),
    "every-position-off": (np.full(64, 0.5), 48, False),
    "decoded-positions-off": (np.r_[np.full(48, 0.004), np.full(16, 0.5)], 48, False),
    "another-token": (np.r_[np.full(63, 0.004), 1.41], 48, False),
}


@pytest.mark.parametrize("case", ERRORS)
def test_compared_holds_made_up_error_vectors_to_its_limits(case):
    err, n_prefill, ok = ERRORS[case]
    compared = falcon_h1.compared(np.asarray(err, np.float64), n_prefill)
    assert compared["rel_err_max"][1] == falcon_h1.TOLERANCE
    assert all(len(pair) == 2 for pair in compared.values())
    assert all(v <= limit for v, limit in compared.values()) == ok
