"""The AFMoE stack (PR 48, Trinity-Mini): window and full attention layers in
ONE stack, each kind with its own window, rotary rule, mask and decode sweep,
head norms and an output gate on both, every part under a sandwich norm,
beside a dense MLP and an expert layer that holds every expert; and what the
family is refused.

The yardstick is ``benchmark/reference/afmoe.py``, which imports nothing of
the program. The model is ``tiny-afmoe``: published layers 1-5 at CI size as
ten one-part layers ``WDWE*EWEWE`` (window 20, so that an 80-token sequence,
a 16-token chunk's seams and every decoded position cross the window's edge;
4/2 heads of 24; a dense MLP of 160; 16 gated experts of 40, 3 a token by
sigmoid score + bias times 2.826, one shared expert of 40).

Last: this family's programs lower to their pinned text (``tests/data/
lowered_text_pins.json``, keys ``afmoe.*``; the older families' pins are
tests/test_nemotron_h.py's and tests/test_solar_open2.py's).
"""

import copy
import dataclasses
from functools import partial
import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":  # the checkout in the working directory, not this file's
    sys.path.insert(0, os.getcwd())

from benchmark import parity, server  # noqa: E402
from benchmark.reference import afmoe as reference  # noqa: E402
from llm_consensus_tpu.engine import engine as E  # noqa: E402
from llm_consensus_tpu.models import (  # noqa: E402
    forward, get_config, init_kv_cache, init_params)
from llm_consensus_tpu.models.config import MODEL_PRESETS  # noqa: E402
from llm_consensus_tpu.ops.quant import quantize_params  # noqa: E402

NAME = "tiny-afmoe"
WINDOW = 20
PINS = os.path.join(REPO, "tests", "data", "lowered_text_pins.json")


def config(name: str) -> dict:
    with open(os.path.join(REPO, f"benchmark/configs/{name}.json")) as f:
        return json.load(f)


def tiny_spec() -> dict:
    """The preset as the harness states a model: the rehearsal's entry (its
    first six layers with 8 experts a token) with the preset's ten layers
    and 3 a token."""
    spec = copy.deepcopy(config("tiny-afmoe-rehearsal")["models"]["tiny-afmoe-top8"])
    spec.update(preset=True, n_layers=10)
    spec["more_fields"].update(layer_kinds="WDWE*EWEWE", experts_per_token=3)
    return spec


def rel_err(got, want) -> np.ndarray:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


IDS = np.random.default_rng(0).integers(0, 512, 80)


@pytest.fixture(scope="module")
def model():
    cfg = get_config(NAME)
    return cfg, init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)


@pytest.fixture(scope="module")
def want(model):
    """The reference's logits of the whole sequence."""
    return reference.forward(model[1], tiny_spec(), IDS)


@partial(jax.jit, static_argnames=("cfg", "remat", "attn_impl"))
def run(params, cfg, tokens, cache=None, start=0, row_start=None, remat=False,
        attn_impl="xla"):
    with jax.default_matmul_precision("highest"):
        return forward(params, cfg, tokens, cache, start, remat=remat,
                       row_start=row_start, attn_impl=attn_impl)


def through_the_cache(cfg, params, ids, n_pre, dtype, slots=96, **how):
    """Prefill ``n_pre`` positions at once, the rest a token a step."""
    cache = init_kv_cache(cfg, 1, slots, dtype)
    logits, cache = run(
        params, cfg, jnp.asarray(ids[None, :n_pre], jnp.int32), cache)
    rows = [logits[0]]
    for p in range(n_pre, len(ids)):
        step, cache = run(
            params, cfg, jnp.asarray(ids[None, p:p + 1], jnp.int32), cache,
            jnp.asarray(p, jnp.int32), **how)
        rows.append(step[0])
    return jnp.concatenate(rows, axis=0), cache


# -- the preset, its stacks and its cache ----------------------------------------


def test_the_rehearsals_entry_is_the_preset():
    cfg = get_config(NAME)
    assert server.model_config(NAME, tiny_spec()) == cfg
    assert cfg.layer_kinds == "WDWE*EWEWE" and cfg.sliding_window == WINDOW
    assert (cfg.n_attn_layers, cfg.n_window_layers, cfg.n_mlp_layers,
            cfg.n_expert_layers) == (5, 4, 1, 4)
    assert cfg.kind_layers("W") == (0, 2, 6, 8) and cfg.kind_layers("*") == (4,)
    # a window, a rotary rule a KIND: "*" sees everything and turns nothing
    assert cfg.attn_kinds == (("*", None, False), ("W", WINDOW, True))
    assert not cfg.has_state and cfg.is_moe and not cfg.is_latent
    assert cfg.qk_norm and cfg.post_norm and cfg.attn_out_gate and cfg.embed_scale
    # every older preset: the new fields off, ONE kind under the model's window
    for name in ("tiny-llama", "tiny-mistral", "tiny-nemotron-h",
                 "tiny-solar-open2", "tiny-falcon-h1", "tiny-deepseek-v2"):
        old = get_config(name)
        assert not (old.qk_norm or old.post_norm)
        assert old.attn_kinds == (("*", old.sliding_window, old.rotary),)
        assert old.n_mlp_layers == 0
        assert old.n_window_layers == (2 if name == "tiny-mistral" else 0)
    assert get_config("tiny-mistral").attn_kinds == (("*", 32, True),)


REFUSED_SHAPES = {
    # how the preset is changed, the words the refusal must hold
    "a-kind-unknown": (dict(layer_kinds="WDWE*EWEWX"), "layer_kinds"),
    "a-pattern-too-short": (dict(layer_kinds="WDWE*EWEW"), "layer_kinds"),
    "a-window-layer-without-a-window": (dict(sliding_window=None), "sliding_window"),
    "a-dense-layer-without-a-width": (dict(d_ff=0), "d_ff"),
    "a-window-layer-beside-a-state": (
        dict(layer_kinds="WDWE*EWEWM", ssm_heads=2, ssm_head_dim=8, ssm_state=8,
             post_norm=False), "not computed"),
    "a-post-norm-beside-a-state": (
        dict(layer_kinds="*E*E*E*E*M", ssm_heads=2, ssm_head_dim=8, ssm_state=8),
        "not computed"),
}


@pytest.mark.parametrize("case", REFUSED_SHAPES)
def test_post_init_refuses_what_does_not_fit_together(case):
    how, words = REFUSED_SHAPES[case]
    with pytest.raises(ValueError, match=words) as stop:
        get_config(NAME, **how)
    assert NAME in str(stop.value)


def test_post_init_refuses_the_new_fields_on_a_uniform_stack():
    with pytest.raises(ValueError, match="post_norm needs layer_kinds"):
        get_config("tiny-llama", post_norm=True)
    with pytest.raises(ValueError, match="qk_norm over heads is not computed"):
        get_config("tiny-deepseek-v2", qk_norm=True)
    # a head norm alone is any attention layer's to have
    assert get_config("tiny-llama", qk_norm=True).qk_norm


def test_a_stack_a_kind_and_one_cache_for_both_attention_kinds():
    cfg = get_config(NAME)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert set(shapes) == {
        "embed", "final_norm", "lm_head", "layers_attn", "layers_mlp", "layers_moe"}
    attn = shapes["layers_attn"]  # four window layers and the full one
    assert attn["wq"].shape == attn["w_ogate"].shape == (5, 96, 96)
    assert attn["wk"].shape == (5, 96, 48) and attn["wo"].shape == (5, 96, 96)
    assert attn["q_head_norm"].shape == attn["k_head_norm"].shape == (5, 24)
    assert attn["post_norm"].shape == attn["attn_norm"].shape == (5, 96)
    mlp = shapes["layers_mlp"]
    assert set(mlp) == {"mlp_norm", "w_gate", "w_up", "w_down", "post_norm"}
    assert mlp["w_gate"].shape == (1, 96, 160) and mlp["w_down"].shape == (1, 160, 96)
    moe = shapes["layers_moe"]
    assert (moe["w_gate"].shape, moe["ws_gate"].shape, moe["router_bias"].shape,
            moe["post_norm"].shape) == ((4, 16, 96, 40), (4, 96, 40), (4, 16), (4, 96))
    # the uniform arena: every attention layer the whole width, no state
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 3, 64, jnp.bfloat16))
    assert set(cache) == {"k", "v"}
    assert cache["k"].shape == cache["v"].shape == (5, 3, 64, 2, 24)
    q8 = jax.eval_shape(lambda: init_kv_cache(cfg, 3, 64, quant="int8"))
    assert q8["k"]["q8"].shape == (5, 3, 64, 2, 24) and q8["k"]["s"].shape == (5, 3, 2, 64)


# -- the model against the reference ----------------------------------------------

# float32 is tight. In bfloat16 a routed model's worst position is a routing
# flip (another expert than the float32 reference picks, on scores that nearly
# tie; at 3 of 16 one flip is a third of a token's routed weights): what is
# held is the median, about twice what this size reads.
PRECISIONS = {"float32": (jnp.float32, 3e-5, 3e-5), "bfloat16": (jnp.bfloat16, 1.2, 0.07)}


@pytest.mark.parametrize("precision", PRECISIONS)
def test_forward_matches_the_reference_whole_sequence(precision):
    dtype, worst, median = PRECISIONS[precision]
    cfg = get_config(NAME)
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=dtype)
    want = reference.forward(params, tiny_spec(), IDS)
    got, _ = run(params, cfg, jnp.asarray(IDS[None], jnp.int32))
    err = rel_err(got[0], want)
    assert err.max() < worst and np.median(err) < median


@pytest.mark.parametrize("n_pre", [1, 13, 37, 72])
def test_prefill_then_decode_through_the_cache_across_the_windows_edge(
        model, want, n_pre):
    """A window SHORTER than the sequence: a prefill that ends before the
    window fills (13), one past it (37, 72), and every position a step;
    each decoded position past 20 sees its window's slots of the cache and
    no older one, in four layers of five."""
    cfg, params = model
    got, _ = through_the_cache(cfg, params, IDS, n_pre, jnp.float32)
    assert rel_err(got, want).max() < 3e-5


@pytest.mark.parametrize("precision", PRECISIONS)
def test_prefill_then_decode_through_the_engine_matches_the_reference(precision):
    """The harness's own check at lengths past the window (96 positions, 32
    of them decoded, window 20), logits and not tokens."""
    dtype, worst, median = PRECISIONS[precision]
    spec = tiny_spec()
    spec["preset"] = False
    cfg = server.model_config(f"tiny-afmoe-{precision}", spec)
    eng = E.Engine(cfg, max_seq=256, seed=0, dtype=dtype)
    sizes = {"seq_len": 96, "decoded": 32, "cache_slots": 128}
    out = parity.check_engine(eng, spec, precision, 5, sizes)
    assert out["reference"] == "afmoe" and out["stored_as_stated"]
    assert out["rel_err_max"] < worst and out["rel_err_median"] < median
    assert out["compared"]["rel_err_decoded_median"][0] < median
    assert out["attention"] == {"prefill": ["xla"], "decode": ["xla"]}


def test_the_comparison_in_blocks_crosses_the_window_at_every_seam(model):
    """Past ``WHOLE_UP_TO`` positions the harness feeds the prefill through
    the cache a block at a time: blocks of 24 under a window of 20, so each
    block's first queries read the last block's keys and nothing older."""
    cfg, params = model
    eng = E.Engine(cfg, params=params, dtype=jnp.float32, max_seq=256)
    sizes = {"seq_len": 80, "decoded": 10, "cache_slots": 96}
    ids = parity.draw_ids(3, cfg.name, cfg.vocab_size, 80)
    with jax.default_matmul_precision("highest"):
        err, _, _ = parity.errors_blocked(eng, reference, tiny_spec(), ids, sizes, 24)
    assert err.shape == (80,) and err.max() < 3e-5


def test_chunked_prefill_whose_seams_and_padded_tail_cross_the_window(model, want):
    """The judge prompt's path: every chunk in one ``_prefill_chunks_loop``
    program (five chunks of 16 under a window of 20: each seam inside a
    window, five pads at the end), then decode steps."""
    cfg, params = model
    eng = E.Engine(cfg, params=params, dtype=jnp.float32, max_seq=256,
                   prefill_chunk=16)
    assert not eng.prefix_cache_enabled
    n_pre = 75
    with jax.default_matmul_precision("highest"):
        last, cache = eng._prefill_ids([int(i) for i in IDS[:n_pre]])
        assert eng.last_prefill.chunks == 5 and eng.last_prefill.reused == 0
        rows = [last]
        for p in range(n_pre, len(IDS)):
            logits, cache = run(
                params, cfg, jnp.asarray(IDS[None, p:p + 1], jnp.int32), cache,
                jnp.asarray(p, jnp.int32))
            rows.append(logits[0])
    assert rel_err(jnp.concatenate(rows), want[n_pre - 1:]).max() < 3e-5


def test_left_padded_rows_of_different_lengths_and_a_dead_row(model):
    """A left-padded wave: each row's first real token is its position 0 for
    the rotary rule of the window layers and for both masks; rows of 60, 41
    and 25 real tokens (all past the window) and a row without a stream."""
    from llm_consensus_tpu.engine.batcher import DEAD_ROW

    cfg, params = model
    starts, t = [0, 19, 35, DEAD_ROW], 60
    tokens = np.stack([IDS[i * 3:i * 3 + t] for i in range(4)])
    cache = init_kv_cache(cfg, 4, 96, jnp.float32)
    logits, cache = run(
        params, cfg, jnp.asarray(tokens, jnp.int32), cache, 0,
        jnp.asarray(starts, jnp.int32))
    step, cache = run(
        params, cfg, jnp.asarray(tokens[:, :1], jnp.int32), cache,
        jnp.asarray(t, jnp.int32), jnp.asarray(starts, jnp.int32))
    spec = tiny_spec()
    for row, start in enumerate(starts[:3]):
        own = np.concatenate([tokens[row, start:], tokens[row, :1]])
        want = reference.forward(params, spec, own)
        got = jnp.concatenate([logits[row, start:], step[row]])
        assert rel_err(got, want).max() < 3e-5, row
    assert bool(jnp.isfinite(step).all())


# -- what the comparison must SEE ---------------------------------------------------

MUST_FAIL = {
    # the program under a changed configuration, over the SAME tree
    "a-window-one-too-long": dict(sliding_window=WINDOW + 1),
    "a-window-one-too-short": dict(sliding_window=WINDOW - 1),
    "no-window": dict(sliding_window=4096),
    "rotary-on-the-full-layer": dict(rotary=True),
    "no-head-norms": dict(qk_norm=False),
    "no-post-norms": dict(post_norm=False),
    "no-embedding-scale": dict(embed_scale=False),
    "no-output-gate": dict(attn_out_gate=False),
}


@pytest.mark.parametrize("case", MUST_FAIL)
def test_the_comparison_fails_when_a_part_is_left_out(model, want, case):
    """An off-by-one at the window's edge, rotary on the full layer, a head
    norm or a post-norm left out: each reads far above what a sound float32
    run does (3e-5), whole and through the cache."""
    cfg, params = model
    wrong = dataclasses.replace(cfg, **MUST_FAIL[case])
    got, _ = run(params, wrong, jnp.asarray(IDS[None], jnp.int32))
    assert rel_err(got[0], want).max() > 1e-3
    stepped, _ = through_the_cache(wrong, params, IDS, 37, jnp.float32)
    assert rel_err(stepped, want).max() > 1e-3
    if case in ("a-window-one-too-long", "a-window-one-too-short", "no-window"):
        # the first positions see no edge: the fault is the window's alone
        assert rel_err(got[0, :WINDOW - 1], want[:WINDOW - 1]).max() < 3e-5


# -- the decode kernel, given each kind's plan -------------------------------------


def test_the_decode_kernel_sweeps_each_kinds_plan():
    """``decode_attention`` (interpret mode) under the plan of each kind: a
    step at slot 1,900 of four rows (one dead, one that started at 1,890)
    with no window, and under windows of 700 (blocks back) and 40 (inside
    the last block), against plain attention over the same cache."""
    from llm_consensus_tpu.engine.batcher import DEAD_ROW
    from llm_consensus_tpu.ops.attention import attention, make_attention_mask
    from llm_consensus_tpu.ops.pallas.decode_attention import (
        decode_attention, decode_sweep_plan)

    b, s, hq, hkv, dh, pos = 4, 2048, 8, 2, 128, 1900
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (b, 1, hq, dh), jnp.float32)
    k = jax.random.normal(keys[1], (2, b, s, hkv, dh), jnp.float32)
    v = jax.random.normal(keys[2], (2, b, s, hkv, dh), jnp.float32)
    row_start = jnp.asarray([0, 120, 1890, DEAD_ROW], jnp.int32)
    slots = jnp.arange(s, dtype=jnp.int32)[None, :]
    plans = {}
    for window in (None, 700, 40):
        plan = decode_sweep_plan(
            jnp.asarray(pos, jnp.int32), row_start, width=s, n_kv_heads=hkv,
            dh=dh, kv_item=4, quantized=False, sliding_window=window)
        plans[window] = np.asarray(plan)
        got = decode_attention(
            q, k, v, jnp.asarray(pos, jnp.int32), jnp.asarray(1, jnp.int32),
            row_start, scale=dh ** -0.5, sliding_window=window, sweep=plan)
        valid = (slots <= pos) & (slots >= row_start[:, None])
        mask = make_attention_mask(
            jnp.full((b, 1), pos, jnp.int32) - row_start[:, None],
            slots - row_start[:, None], valid, window)
        want = attention(q, k[1], v[1], mask, scale=dh ** -0.5)
        np.testing.assert_allclose(got[:3], want[:3], rtol=2e-5, atol=2e-5)
    # the plans differ where the window cuts blocks off the sweep's start
    assert not np.array_equal(plans[None], plans[40])


def test_a_decode_step_through_the_kernel_is_the_xla_step():
    """The preset with heads of 128 (the kernel's lane width): steps through
    the cache on the kernel route, each layer under ITS kind's plan, against
    the XLA route's masks; the route is booked as the kernel's."""
    from llm_consensus_tpu.models.transformer import attention_routes

    cfg = dataclasses.replace(
        get_config(NAME), name="tiny-afmoe-dh128", head_dim=128)
    params = init_params(cfg, jax.random.PRNGKey(4), dtype=jnp.float32)
    before = attention_routes.snapshot(cfg.name)
    xla, _ = through_the_cache(cfg, params, IDS[:48], 30, jnp.float32, slots=128)
    flash, _ = through_the_cache(
        cfg, params, IDS[:48], 30, jnp.float32, slots=128, attn_impl="flash")
    assert rel_err(flash, xla).max() < 3e-5
    assert parity.routes_since(cfg.name, before)["decode"] == ["pallas", "xla"]
    # and a wrong window on the kernel route is seen there too
    wrong = dataclasses.replace(cfg, sliding_window=WINDOW - 1)
    off, _ = through_the_cache(
        wrong, params, IDS[:48], 30, jnp.float32, slots=128, attn_impl="flash")
    assert rel_err(off[1:], xla[1:]).max() > 1e-3


def test_a_prefill_through_the_kernel_is_the_xla_prefill():
    """A one-shot prefill of 256 positions from position 0 on the prefill
    kernel's route (heads of 128): each layer's call under ITS kind's window
    (the kernel skips key blocks before the window's edge), against the XLA
    route's two masks."""
    from llm_consensus_tpu.models.transformer import attention_routes

    cfg = dataclasses.replace(get_config(NAME), name="tiny-afmoe-dh128p", head_dim=128)
    params = init_params(cfg, jax.random.PRNGKey(4), dtype=jnp.float32)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 512, (1, 256)), jnp.int32)
    before = attention_routes.snapshot(cfg.name)
    got = {
        impl: run(params, cfg, ids, init_kv_cache(cfg, 1, 384, jnp.float32),
                  attn_impl=impl)[0][0]
        for impl in ("xla", "flash")}
    assert rel_err(got["flash"], got["xla"]).max() < 3e-5
    assert parity.routes_since(cfg.name, before)["prefill"] == ["pallas", "xla"]
    wrong = dataclasses.replace(cfg, sliding_window=WINDOW + 1)
    off = run(params, wrong, ids, init_kv_cache(cfg, 1, 384, jnp.float32),
              attn_impl="flash")[0][0]
    assert rel_err(off, got["xla"]).max() > 1e-3


# -- training, int8, the pool --------------------------------------------------------


def test_remat_walks_the_same_layers(model):
    cfg, params = model
    tokens = jnp.asarray(IDS[None, :32], jnp.int32)

    def loss(p, remat):
        return jnp.mean(run(p, cfg, tokens, remat=remat)[0] ** 2)

    np.testing.assert_allclose(loss(params, True), loss(params, False), rtol=1e-6)
    grads = jax.jit(jax.grad(partial(loss, remat=True)))(params)
    assert not np.asarray(grads["layers_moe"].pop("router_bias")).any()
    for stack in ("layers_attn", "layers_mlp", "layers_moe"):
        for name, g in grads[stack].items():
            assert float(jnp.abs(g).max()) > 0, (stack, name)


def test_int8_weights_for_every_stored_leaf_are_computed(model):
    cfg, params = model
    q = quantize_params(params)
    for stack, leaf in reference.STORED_LEAVES:
        assert set(q[stack][leaf]) == {"q8", "s"}, (stack, leaf)
    for name in ("q_head_norm", "k_head_norm", "post_norm"):
        assert not isinstance(q["layers_attn"][name], dict)
    want = reference.forward(q, tiny_spec(), IDS[:48])
    got, _ = through_the_cache(cfg, q, IDS[:48], 40, jnp.float32)
    assert rel_err(got, want).max() < 3e-5


GREEDY = dict(temperature=0.0, ignore_eos=True)


def test_a_pool_books_what_each_kind_of_layer_swept(model, monkeypatch):
    """A wave of unequal rows in a pool of four: each stream token for token
    what the engine generates alone; the counter a windowed pool adds stands
    beside ``decode_kv_slots_live``, which keeps counting what a FULL layer
    sweeps."""
    from llm_consensus_tpu.engine import ContinuousBatcher, SamplingParams

    monkeypatch.setenv("LLMC_KV_POOL", "0")
    cfg, params = model
    eng = E.Engine(cfg, params=params, dtype=jnp.float32, max_seq=256,
                   stream_interval=8, prefill_chunk=16)
    assert {k: eng.build_stats[k] for k in (
        "ssm_layers", "kda_layers", "attn_layers", "expert_layers",
        "experts_held", "router_width", "cache_bytes_per_token",
        "state_bytes_per_row")} == {
            "ssm_layers": 0, "kda_layers": 0, "attn_layers": 5,
            "expert_layers": 4, "experts_held": 16, "router_width": 16,
            "cache_bytes_per_token": 5 * 2 * 2 * 24 * 4, "state_bytes_per_row": 0}
    s = SamplingParams(max_new_tokens=24, **GREEDY)
    prompts = ["short", "a prompt of middling length for the wave",
               "the longest of the three rows of this wave by some margin, "
               "long enough to take more than one prefill chunk"]
    pool = ContinuousBatcher(eng, max_batch=4)
    try:
        assert not pool._prefix_enabled
        futs = [pool.submit(p, s) for p in prompts]
        for p, f in zip(prompts, futs):
            assert f.result(timeout=300).token_ids == eng.generate(p, s).token_ids, p
        st = pool.snapshot()
    finally:
        pool.close()
    assert st["moe_layer_steps"] == st["decode_steps"] * 4
    assert 0 < st["moe_pairs_held"] == st["moe_pairs_total"]  # every expert held
    # the prompts pass 20 tokens: a window layer sweeps less than the full one
    assert 0 < st["decode_kv_slots_window_layer"] < st["decode_kv_slots_live"]
    # a uniform model's pool has no such counter
    other = ContinuousBatcher(E.Engine(get_config("tiny-mistral"), max_seq=64), max_batch=2)
    try:
        assert not [k for k in other.snapshot() if "window" in k]
    finally:
        other.close()


def test_the_window_counter_with_integers():
    from llm_consensus_tpu.engine.batcher import _window_decode, kv_slots_live

    cfg = get_config(NAME)
    # three steps from frontier 30: a row from slot 0 (contexts 31, 32, 33:
    # all past 20), one from slot 15 (16, 17, 18: none)
    assert _window_decode(cfg, 30, 3, [0, 15]) == {
        "decode_kv_slots_window_layer": 3 * 20 + 16 + 17 + 18}
    # what ``decode_kv_slots_live`` books for the same dispatch: a full layer's
    assert kv_slots_live(30, 3, 1, [0, 15], cfg.attn_kinds[0][1]) == (
        31 + 32 + 33 + 16 + 17 + 18)
    assert _window_decode(get_config("tiny-mistral"), 30, 3, [0]) == {}


def test_a_splice_moves_both_kinds_layers_together():
    from llm_consensus_tpu.engine.batcher import (
        _compact_cache, _shrink_rows, _splice)

    cfg = get_config(NAME)

    def marked(rows, slots, base):
        cache = init_kv_cache(cfg, rows, slots, jnp.float32)
        return jax.tree.map(
            lambda a: a + (base + jnp.arange(rows, dtype=a.dtype)).reshape(
                1, rows, *(1,) * (a.ndim - 2)), cache)

    pool, one = marked(4, 32, 10.0), marked(1, 16, 50.0)
    out = _splice(jax.tree.map(jnp.copy, pool), one, 2, 5, 16)
    k = np.asarray(out["k"])
    assert k.shape[0] == 5 and k[:, 2, 5:21].min() == 50.0 and k[:, 2, :5].max() == 12.0
    assert k[:, 1].max() == 11.0
    out = _compact_cache(jax.tree.map(jnp.copy, pool), jnp.asarray(6))
    assert out["k"].shape == pool["k"].shape
    out = _shrink_rows(jax.tree.map(jnp.copy, pool), 2)
    assert out["k"].shape[:2] == out["v"].shape[:2] == (5, 2)


# -- what the family is refused, by its message --------------------------------------


def _engine(**how):
    return E.Engine(get_config(NAME), max_seq=128, **how)


def _refuse_radix_arena(monkeypatch):
    monkeypatch.setenv("LLMC_KV_POOL", "1")
    _engine()


def _refuse_mesh():
    from llm_consensus_tpu.parallel import make_mesh

    _engine(mesh=make_mesh({"dp": 1, "tp": 2}, jax.devices()[:2]))


def _refuse_checkpoint():
    from llm_consensus_tpu.engine.checkpoint import load_hf_safetensors

    load_hf_safetensors(get_config(NAME), "/nonexistent")


def _forward_with(**kw):
    cfg = get_config(NAME)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    cache = init_kv_cache(cfg, 1, 32)
    forward(params, cfg, jnp.zeros((1, 4), jnp.int32), cache, 0, **kw)


REFUSALS = {
    # name: (what is tried, words the message must hold): what a model with
    # ANY windowed layer is refused, as a uniform windowed model is
    "speculative-bitmap": (
        lambda: _forward_with(kv_mask=jnp.ones((1, 32), bool),
                              row_start=jnp.zeros((1,), jnp.int32)),
        "kv_mask (speculative holes) does not compose with sliding_window"),
    "shared-prefix": (
        lambda: _forward_with(prefix={"k": None}, prefix_len=jnp.asarray(2)),
        "shared-prefix attention does not compose with sliding_window"),
    "radix-arena": (_refuse_radix_arena, "radix KV arena"),
    "mesh-tp": (_refuse_mesh, "runs on one chip"),
    "ring-prefill": (lambda: _forward_with(attn_impl="ring"), "no sequence-parallel"),
    "checkpoint-import": (_refuse_checkpoint, "no checkpoint importer"),
}


@pytest.mark.parametrize("case", REFUSALS)
def test_what_the_family_does_not_get_is_refused_by_name(case, monkeypatch):
    attempt, words = REFUSALS[case]
    with pytest.raises(ValueError) as stop:
        attempt(monkeypatch) if attempt is _refuse_radix_arena else attempt()
    assert words in str(stop.value) and NAME in str(stop.value)


def test_speculation_prefix_sharing_and_the_retained_prefix_are_off_by_name():
    """A pool asked to speculate warns by the model's name and decodes
    plainly; pooled prefix sharing and the retained prefix snapshot are
    off, as a uniform windowed model's are."""
    from llm_consensus_tpu.engine import ContinuousBatcher
    from llm_consensus_tpu.engine.speculative import SpecConfig
    from llm_consensus_tpu.parallel.mesh import best_tp

    eng = _engine()
    assert not eng.prefix_cache_enabled
    with pytest.warns(RuntimeWarning, match=f"disabled for '{NAME}'.*sliding_window"):
        pool = ContinuousBatcher(eng, max_batch=2, spec=SpecConfig(kind="lookup"))
    try:
        assert pool._spec is None and not pool._prefix_enabled
    finally:
        pool.close()
    assert best_tp(get_config(NAME), 4) == 1


def test_one_streams_speculation_is_plain_decoding_token_for_token():
    """What a uniform windowed model keeps, this one keeps: an engine's own
    speculation verifies k + 1 positions at a traced start under each kind's
    mask and leaves no hole behind (it needs no ``kv_mask``)."""
    from llm_consensus_tpu.engine import SamplingParams
    from llm_consensus_tpu.engine.speculative import (
        PromptLookupDrafter, SpeculativeEngine)

    eng = E.Engine(get_config(NAME), max_seq=256, dtype=jnp.float32)
    s = SamplingParams(max_new_tokens=40, **GREEDY)
    prompt = "a prompt that repeats, a prompt that repeats, a prompt that repeats, a"
    got = SpeculativeEngine(eng, PromptLookupDrafter()).generate(prompt, s)
    assert got.token_ids == eng.generate(prompt, s).token_ids


def test_the_leaves_are_whole_on_a_mesh_of_one():
    from llm_consensus_tpu.parallel.sharding import cache_specs, param_specs

    cfg = get_config(NAME)
    specs = param_specs(cfg)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert jax.tree.structure(specs, is_leaf=lambda x: not isinstance(x, dict)) \
        == jax.tree.structure(shapes)
    assert all(ax is None for spec in jax.tree.leaves(
        specs, is_leaf=lambda x: not isinstance(x, dict)) for ax in spec)
    assert set(cache_specs(cfg)) == {"k", "v"}


# -- counts ----------------------------------------------------------------------------


def published():
    """The model at its published sizes, and the cell's cut of it."""
    spec = config("trinity-mini-pp8-trio-bf16")["models"]["trinity-mini"]
    cut = server.model_config("cut", spec)
    whole = server.model_config("whole", {
        **spec, **{k: spec["published"][k] for k in ("n_layers", "vocab_size")},
        "more_fields": {**spec["more_fields"],
                        "layer_kinds": spec["published"]["layer_kinds"]}})
    return whole, cut


def test_param_count_is_the_tree_at_the_tiny_size():
    from llm_consensus_tpu.utils.flops import param_count

    cfg = get_config(NAME)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert param_count(cfg) == sum(x.size for x in jax.tree.leaves(shapes))
    plain = get_config("tiny-llama", qk_norm=True)
    shapes = jax.eval_shape(lambda: init_params(plain, jax.random.PRNGKey(0)))
    assert param_count(plain) == sum(x.size for x in jax.tree.leaves(shapes))


def test_param_count_at_the_published_sizes():
    from llm_consensus_tpu.utils.flops import cache_bytes_per_token, param_count

    whole, cut = published()
    assert whole.n_layers == 64 and whole.layer_kinds.startswith("WDWDWE*EWEWEWE*E")
    assert (whole.n_attn_layers, whole.n_window_layers, whole.n_mlp_layers,
            whole.n_expert_layers) == (32, 24, 2, 30)
    # layer i of the published 32 is full_attention iff (i + 1) % 4 == 0
    assert [i // 2 for i in whole.kind_layers("*")] == list(range(3, 32, 4))
    # the published 26B-A3B: the check that the layers are read right
    embed = 200_192 * 2048
    assert round(param_count(whole) / 1e9, 2) == 26.12
    # active without the embedding 3.065 B (the issue rounds it to 3.07)
    assert round((param_count(whole, active_only=True) - embed) / 1e9, 3) == 3.065
    # by part, the issue's hand numbers (each with its two norms)
    d = 2048
    attn = 3 * d * 4096 + 2 * d * 512 + 2 * 128 + 2 * d
    dense = 3 * d * 6144 + 2 * d
    outside = d * 128 + 128 + 3 * d * 1024 + 2 * d
    expert = 3 * d * 1024
    assert (attn, dense, outside + 128 * expert, 128 * expert) == (
        27_267_328, 37_752_832, 811_864_192, 805_306_368)
    assert param_count(whole) == (
        32 * attn + 2 * dense + 30 * (outside + 128 * expert) + 2 * embed + d)
    # without the output gate 25.86 B: the published name does not tell them apart
    ungated = dataclasses.replace(whole, attn_out_gate=False)
    assert round(param_count(ungated) / 1e9, 2) == 25.86
    # the cell's cut: published layers 1-5, every expert, an eighth of the vocabulary
    shapes = jax.eval_shape(lambda: init_params(cut, jax.random.PRNGKey(0)))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert param_count(cut) == n == (
        5 * attn + dense + 4 * (outside + 128 * expert) + 2 * 25_024 * d + d)
    assert round(2 * n / 1e9, 2) == 7.05
    assert cache_bytes_per_token(cut) == 5 * 2 * 4 * 128 * 2 == 10_240
    cache = jax.eval_shape(lambda: init_kv_cache(cut, 6, 4096))
    assert cache["k"].shape == (5, 6, 4096, 4, 128)


def test_decode_bytes_count_a_live_window_a_kind():
    from llm_consensus_tpu.utils.flops import (
        cache_bytes_per_token, decode_bytes_per_token, live_cache_bytes,
        param_count)

    _, cut = published()
    slot = 2 * 4 * 128 * 2                       # one layer's keys and values
    assert live_cache_bytes(cut, 1000) == 5 * 1000 * slot
    assert live_cache_bytes(cut, 3000) == (3000 + 4 * 2048) * slot
    weights = 2 * param_count(cut, active_only=True)
    assert decode_bytes_per_token(cut, 3000, rows=6) == weights + 6 * (3000 + 4 * 2048) * slot
    # a uniform windowed model: every layer under its one window
    mistral = get_config("mistral-7b")
    assert live_cache_bytes(mistral, 6000) == 4096 * cache_bytes_per_token(mistral)
    assert live_cache_bytes(mistral, 100) == 100 * cache_bytes_per_token(mistral)
    llama = get_config("tiny-llama")
    assert live_cache_bytes(llama, 6000) == 6000 * cache_bytes_per_token(llama)


def test_the_cells_file_states_the_catalogs_numbers():
    """Every number of the published config.json stands in the cell's file
    under its key, but the two the file lists as reduced."""
    doc = config("trinity-mini-pp8-trio-bf16")
    assert doc["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert doc["published"] == {"num_hidden_layers": 32, "vocab_size": 200192}
    assert (doc["num_hidden_layers"], doc["vocab_size"]) == (5, 25024)
    for key, value in {
            "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 4,
            "head_dim": 128, "intermediate_size": 6144, "moe_intermediate_size": 1024,
            "num_experts": 128, "num_experts_per_tok": 8, "num_shared_experts": 1,
            "num_dense_layers": 2, "sliding_window": 2048, "global_attn_every_n_layers": 4,
            "route_scale": 2.826, "route_norm": True, "score_func": "sigmoid",
            "n_group": 1, "topk_group": 1, "num_expert_groups": 1, "num_limited_groups": 1,
            "rms_norm_eps": 1e-05, "rope_theta": 10000, "rope_scaling": None,
            "mup_enabled": True, "tie_word_embeddings": False, "model_type": "afmoe",
            "max_position_embeddings": 131072, "load_balance_coeff": 0.001,
            "hidden_act": "silu", "use_grouped_mm": True}.items():
        assert doc[key] == value, key
    assert doc["layer_types"] == [
        "full_attention" if (i + 1) % 4 == 0 else "sliding_attention" for i in range(32)]
    spec = doc["models"]["trinity-mini"]
    more = spec["more_fields"]
    assert (more["n_experts"], more["router_width"], more["first_expert"],
            more["experts_per_token"], more["d_expert"], more["routed_scale"]) == (
                128, 128, 0, 8, 1024, 2.826)
    # the chip's layers are entries 1-5 of layer_types, each with its MLP
    held = "".join(
        ("*" if t == "full_attention" else "W") + ("D" if i < 2 else "E")
        for i, t in enumerate(doc["layer_types"]))[2:12]
    assert more["layer_kinds"] == held == "WDWE*EWEWE"
    assert spec["sliding_window"] == 2048 and spec["parity"]["seq_len"] > 2048
    assert parity.lengths(doc, "trinity-mini") == {
        "seq_len": 3136, "decoded": 64, "cache_slots": 4096}
    assert parity.lengths(doc, "qwen2.5-0.5b")["seq_len"] == 1024
    for word in ("modelling_code", "sandwich_norm", "head_norms", "attention_gate",
                 "rotary", "window", "router", "experts", "embedding", "layers",
                 "serving_peak", "weights", "tokenizer"):
        assert word in doc["assumed"]
    for item in ("sandwich_norm", "head_norms", "attention_gate", "rotary", "router"):
        assert "modelling code's" in doc["assumed"][item]


# -- this family's programs: pinned as this PR lowers them ---------------------------

PROGRAMS = ("decode_chunk", "six_row_wave", "judge_prompt_loop")
ROWS, SLOTS, CHUNK = 6, 256, 64


def lowered_text(program: str) -> str:
    """The text a program lowers to on the CI-size preset, on abstract
    operands of a pool of six (as tests/test_solar_open2.py ``lowered_text``)."""
    cfg = MODEL_PRESETS[NAME]
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    rows = 1 if program == "judge_prompt_loop" else ROWS
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, rows, SLOTS))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    if program == "decode_chunk":
        lowered = E._decode_chunk.lower(
            params, cfg, i32(ROWS), i32(), cache,
            jax.ShapeDtypeStruct((2,), jnp.uint32), 16, 0.0, None, None,
            row_start=i32(ROWS), kv_width=128, attn_impl="flash",
            sentinel=True, moe_stats=True)
    elif program == "six_row_wave":
        lowered = E._prefill_step.lower(
            params, cfg, i32(ROWS, CHUNK), i32(ROWS), cache,
            attn_impl="flash", row_start=i32(ROWS), kv_width=CHUNK,
            moe_stats=True)
    else:
        lowered = E._prefill_chunks_loop.lower(
            params, cfg, i32(4, 1, CHUNK), i32(), i32(), i32(1), cache, 4,
            SLOTS, moe_stats=True)
    return lowered.as_text()


def digest(program: str) -> str:
    return hashlib.sha256(lowered_text(program).encode()).hexdigest()


@pytest.mark.parametrize("program", PROGRAMS)
def test_the_familys_program_lowers_to_its_pinned_text(program):
    """Pinned in the PR that brought the family (PR 48), so that a later PR
    that means to leave these programs alone can see that it did."""
    with open(PINS) as f:
        pins = json.load(f)
    assert digest(program) == pins[f"afmoe.{program}"]


def test_the_older_pins_are_the_parents():
    """The 21 family pins and the 14 cell pins that were there stand as they
    stood (their values are what tests/test_nemotron_h.py, test_solar_open2.py
    and test_tpu_compile.py compare); this PR added three keys."""
    with open(PINS) as f:
        pins = json.load(f)
    mine = {k for k in pins if k.startswith("afmoe.")}
    assert mine == {f"afmoe.{p}" for p in PROGRAMS}
    assert len(pins) - len(mine) == 35
    assert hashlib.sha256(json.dumps(
        {k: v for k, v in pins.items() if k not in mine},
        sort_keys=True).encode()).hexdigest() == OLDER_PINS


OLDER_PINS = "8591fb8cd69dd26a3cb8487d409b7f66191c1285ea746e4c69af54445c234137"

if __name__ == "__main__":
    # python tests/test_afmoe.py <out.json>, from a checkout's root: the
    # digests of that checkout's lowered text, the pins above.
    with open(sys.argv[1], "w") as out:
        json.dump({f"afmoe.{p}": digest(p) for p in PROGRAMS}, out, indent=1)
        out.write("\n")
