"""The DeepSeek-V2 block (PR 31): latent (MLA) attention with its two forms,
YaRN, the dropless expert layer that is told which experts it holds, the
two stacks, the cache's one shape function, and what the family is refused.

The yardstick is ``benchmark/reference/deepseek_v2.py``, which imports
nothing of the program; the model is ``tiny-deepseek-v2`` (1 dense + 2 expert
layers, 8 experts in 4 groups of 2, 2 groups and 3 experts a token, 1 shared
expert), whole and with a strict share of its experts held.
"""

import copy
import glob
import io
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import parity, server
from benchmark.reference import deepseek_v2 as reference
from llm_consensus_tpu.engine import ContinuousBatcher, Engine, SamplingParams
from llm_consensus_tpu.models import (
    forward, get_config, init_kv_cache, init_params)
from llm_consensus_tpu.models import transformer
from llm_consensus_tpu.models.config import MODEL_PRESETS
from llm_consensus_tpu.obs import blackbox as bb_mod
from llm_consensus_tpu.obs.blackbox import FlightRecorder
from llm_consensus_tpu.ops.attention import NEG_INF
from llm_consensus_tpu.ops.latent_attention import (
    prefill_sweep, prefill_sweep_width)
from llm_consensus_tpu.ops.mlp import gated_mlp
from llm_consensus_tpu.ops.moe import moe_block, route
from llm_consensus_tpu.ops.norms import rms_norm
from llm_consensus_tpu.ops.quant import dequantize, kv_layer, kv_write_rows, qeinsum
from llm_consensus_tpu.ops.rope import apply_rope, yarn_inv_freq, yarn_mscale
from llm_consensus_tpu.utils.flops import cache_bytes_per_token, param_count

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = sorted(glob.glob(os.path.join(REPO, "benchmark/configs/*.json")))


def config(name: str) -> dict:
    with open(os.path.join(REPO, f"benchmark/configs/{name}.json")) as f:
        return json.load(f)


def tiny_spec(share: bool) -> dict:
    """The tiny model's entry as a configuration file states it: the
    rehearsal's strict share (experts 2-3, the second routing group), or
    the whole preset."""
    spec = copy.deepcopy(
        config("tiny-dsv2-rehearsal")["models"]["tiny-deepseek-v2-share"])
    if not share:
        spec["more_fields"].update(n_experts=8, router_width=0, first_expert=0)
    return spec


SHARES = {"whole": False, "strict-share": True}


def rel_err(got, want) -> np.ndarray:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def test_the_whole_entry_is_the_preset():
    assert server.model_config(
        "tiny-deepseek-v2", tiny_spec(False)) == get_config("tiny-deepseek-v2")
    cfg = get_config("tiny-deepseek-v2")
    assert (cfg.is_latent, cfg.is_moe, cfg.n_router, cfg.n_expert_layers) == (
        True, True, 8, 2)


@pytest.mark.parametrize("share", SHARES)
def test_forward_matches_the_reference_whole_sequence(share):
    spec = tiny_spec(SHARES[share])
    cfg = server.model_config("m", spec)
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, 96)
    want = reference.forward(params, spec, ids)
    with jax.default_matmul_precision("highest"):
        got, _ = forward(params, cfg, jnp.asarray(ids[None], jnp.int32))
    assert rel_err(got[0], want).max() < 1e-4
    margins = reference.LAST_MARGINS
    assert margins["expert"].shape == margins["group"].shape == (96,)
    assert (margins["expert"] >= 0).all() and (margins["group"] >= 0).all()


@pytest.mark.parametrize("share", SHARES)
def test_prefill_then_absorbed_decode_matches_the_reference(share):
    """The timed path's shape: a prefill (the prefill form), then cached
    decode steps (the absorbed form), against the reference's prefill form
    for every position; through the harness's own check."""
    spec = tiny_spec(SHARES[share])
    cfg = server.model_config("tiny-dsv2-under-test", spec)
    eng = Engine(cfg, max_seq=512, seed=0, dtype=jnp.float32)
    sizes = {"seq_len": 96, "decoded": 32, "cache_slots": 128}
    out = parity.check_engine(eng, spec, "float32", 5, sizes)
    assert out["reference"] == "deepseek_v2" and out["stored_as_stated"]
    assert out["rel_err_max"] < 1e-4 and out["rel_err_decoded_max"] < 1e-4
    paths = eng.attention_stats()["paths"]  # counted per model name, process-wide
    assert (set(paths["prefill"]), set(paths["decode"])) == (
        {"xla_latent"}, {"xla_latent_absorbed"})


# -- the prefill form's sweep (PR 32) ------------------------------------------


SWEEP_WIDTHS = {
    # name: (T, bucket, the chunk's end, the width it sweeps)
    "first-chunk": (512, 2048, 512, 512),
    "second-chunk": (512, 2048, 1024, 1024),
    "third-chunk": (512, 2048, 1536, 1536),
    "last-chunk": (512, 2048, 2048, 2048),
    "one-slot-past-a-width": (512, 2048, 513, 1024),
    "restored-base-of-64": (512, 2048, 64 + 512, 1024),
    "past-the-bucket-clamps": (512, 2048, 2560, 2048),
    "t-is-the-bucket": (256, 256, 256, 256),
    "bucket-no-multiple-of-t": (960, 1024, 960, 960),
    "coarser-past-eight-branches": (256, 4096, 256, 512),
    "coarser-last": (256, 4096, 4096, 4096),
}


@pytest.mark.parametrize("case", SWEEP_WIDTHS)
def test_the_width_rule(case):
    t, bucket, end, width = SWEEP_WIDTHS[case]
    assert prefill_sweep_width(t, bucket, end) == width
    widths, at = prefill_sweep(t, bucket, end)
    assert len(widths) <= 8 and widths[-1] == bucket
    assert list(widths) == sorted(set(widths)) and widths[0] >= min(t, bucket)
    # the traced index is the host's (jax.lax.switch clamps it as the host does)
    assert int(prefill_sweep(t, bucket, jnp.asarray(end, jnp.int32))[1]) == at


def whole_bucket_latent_attention(
        h, lp, cos, sin, mask, cache, start_pos, layer_idx, *, n_heads,
        kv_lora_rank, qk_nope_dim, qk_rope_dim, v_head_dim, scale, rms_eps,
        kv_width=None, absorbed=False):
    """The arithmetic of ``ops/latent_attention.py`` as PR 31 had it, kept
    here as the yardstick: every form sweeps the whole ``kv_width`` bucket
    and leaves what lies past the frontier to the mask."""
    b, t, _ = h.shape
    c_q = rms_norm(qeinsum("btd,dr->btr", h, lp["wq_a"]), lp["q_norm"], rms_eps)
    q = qeinsum("btr,rk->btk", c_q, lp["wq_b"]).reshape(
        b, t, n_heads, qk_nope_dim + qk_rope_dim)
    q_nope = q[..., :qk_nope_dim]
    q_rope = apply_rope(q[..., qk_nope_dim:], cos, sin)
    ckr = qeinsum("btd,dr->btr", h, lp["wkv_a"])
    c_kv = rms_norm(ckr[..., :kv_lora_rank], lp["kv_norm"], rms_eps)
    k_rope = apply_rope(ckr[..., None, kv_lora_rank:], cos, sin)[:, :, 0]
    latent = jnp.concatenate([c_kv, k_rope], axis=-1)
    if cache is not None:
        cache = kv_write_rows(cache, latent[:, :, None, :], layer_idx, start_pos)
        latent = kv_layer(cache, layer_idx, kv_width)[:, :, 0, :].astype(h.dtype)
    c_all, r_all = latent[..., :kv_lora_rank], latent[..., kv_lora_rank:]
    w_kvb = dequantize(lp["wkv_b"], h.dtype).reshape(
        kv_lora_rank, n_heads, qk_nope_dim + v_head_dim)
    w_uk, w_uv = w_kvb[..., :qk_nope_dim], w_kvb[..., qk_nope_dim:]
    f32 = dict(preferred_element_type=jnp.float32)
    rope_scores = jnp.einsum("bthr,bsr->bhts", q_rope, r_all, **f32)
    if absorbed:
        q_lat = jnp.einsum("bthd,chd->bthc", q_nope, w_uk)
        scores = jnp.einsum("bthc,bsc->bhts", q_lat, c_all, **f32)
    else:
        k_nope = jnp.einsum("bsc,chd->bshd", c_all, w_uk)
        scores = jnp.einsum("bthd,bshd->bhts", q_nope, k_nope, **f32)
    scores = jnp.where(mask[:, None], (scores + rope_scores) * scale, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(h.dtype)
    if absorbed:
        o_lat = jnp.einsum("bhts,bsc->bthc", probs, c_all)
        out = jnp.einsum("bthc,chd->bthd", o_lat, w_uv)
    else:
        v = jnp.einsum("bsc,chd->bshd", c_all, w_uv)
        out = jnp.einsum("bhts,bshd->bthd", probs, v)
    return out.reshape(b, t, n_heads * v_head_dim), cache


PREFILL_CHUNKS = {
    # name: (T, bucket, base, chunk index, the width it must sweep)
    "chunk-0-of-4": (32, 128, 0, 0, 32),
    "chunk-1-of-4": (32, 128, 0, 1, 64),
    "chunk-2-of-4": (32, 128, 0, 2, 96),
    "chunk-3-of-4": (32, 128, 0, 3, 128),
    "base-40-frontier-no-multiple": (32, 128, 40, 0, 96),
    "base-40-last": (32, 128, 40, 1, 128),
    "base-64-frontier-a-multiple": (32, 128, 64, 0, 96),
    "t-is-the-bucket": (128, 128, 0, 0, 128),
    "sixteen-chunks-eight-branches": (8, 128, 0, 5, 48),
}


@pytest.mark.parametrize("case", PREFILL_CHUNKS)
def test_the_prefill_form_sweeps_to_the_frontier(case, monkeypatch):
    """A chunk at a TRACED start equals the whole-bucket masked form to a
    reduction's order and the float32 reference inside the file's limit,
    and reads no slot past the width its frontier picks: those are NaN here,
    which the whole-bucket form multiplies into every sum."""
    t, bucket, base, index, width = PREFILL_CHUNKS[case]
    spec = tiny_spec(True)
    cfg = server.model_config("m", spec)
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    start = base + index * t
    assert prefill_sweep_width(t, bucket, start + t) == width
    ids = np.random.default_rng(7).integers(0, cfg.vocab_size, start + t)
    cache = init_kv_cache(cfg, 1, 256, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        if start:  # what came before, in one call at a static start
            _, cache = forward(
                params, cfg, jnp.asarray(ids[None, :start], jnp.int32), cache, 0)
        poisoned = {"kv": cache["kv"].at[:, :, width:].set(jnp.nan)}

        def chunk():  # a function of its own a form: jit caches by function
            return jax.jit(lambda cache, start_pos: forward(
                params, cfg, jnp.asarray(ids[None, start:], jnp.int32),
                cache, start_pos, kv_width=bucket)[0][0])

        at = jnp.asarray(start, jnp.int32)
        got = chunk()(poisoned, at)
        monkeypatch.setattr(transformer, "latent_attention", whole_bucket_latent_attention)
        whole_bucket = chunk()
        whole, reads_it_all = whole_bucket(cache, at), whole_bucket(poisoned, at)
    assert np.isfinite(np.asarray(got)).all()
    assert bool(np.isnan(np.asarray(reads_it_all)).any()) == (width < bucket)
    assert rel_err(got, whole).max() < 1e-5
    want = reference.forward(params, spec, ids)[start:]
    assert rel_err(got, want).max() < 1e-4


@pytest.mark.parametrize("share", SHARES)
def test_chunked_prefill_loop_then_absorbed_decode_matches_the_reference(share):
    """The judge prompt's path: every chunk in one ``_prefill_chunks_loop``
    program (four 32-token chunks of a 128-slot bucket, each at its own
    width), then cached decode steps in the absorbed form."""
    spec = tiny_spec(SHARES[share])
    cfg = server.model_config("tiny-dsv2-chunked", spec)
    eng = Engine(cfg, max_seq=256, seed=0, dtype=jnp.float32, prefill_chunk=32)
    n_pre, decoded = 100, 12
    ids = np.random.default_rng(11).integers(0, cfg.vocab_size, n_pre + decoded)
    with jax.default_matmul_precision("highest"):
        last, cache = eng._prefill_ids([int(i) for i in ids[:n_pre]])
        assert eng.last_prefill == (4, 128, 32 * (32 + 64 + 96 + 128), 0)
        rows = [last]
        for p in range(n_pre, n_pre + decoded):
            logits, cache = forward(
                eng.params, cfg, jnp.asarray(ids[None, p:p + 1], jnp.int32),
                cache, jnp.asarray(p, jnp.int32))
            rows.append(logits[0])
    want = reference.forward(eng.params, spec, ids)[n_pre - 1:]
    assert rel_err(jnp.concatenate(rows), want).max() < 1e-4
    paths = eng.attention_stats()["paths"]
    assert (set(paths["prefill"]), set(paths["decode"])) == (
        {"xla_latent"}, {"xla_latent_absorbed"})


def test_the_shares_add_up_to_the_uncut_layer():
    """The test that ties the share to the model: the routed part of the
    expert layer over the four shares of two experts, plus the shared expert
    once, is the uncut reference's layer output."""
    spec = tiny_spec(False)
    cfg = get_config("tiny-deepseek-v2")
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    lp = jax.tree.map(lambda a: a[1], params["layers"])
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 24, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want, _, _ = reference.experts(
            h.reshape(-1, cfg.d_model), lp, spec["more_fields"])
        routed = sum(
            moe_block(
                h, lp["w_router"],
                *(lp[k][first:first + 2] for k in ("w_gate", "w_up", "w_down")),
                top_k=cfg.experts_per_token, first_expert=first,
                n_groups=cfg.n_expert_groups,
                groups_per_token=cfg.groups_per_token,
                norm_topk=cfg.norm_topk, routed_scale=cfg.routed_scale)
            for first in range(0, 8, 2))
        shared = gated_mlp(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    got = (routed + shared).reshape(-1, cfg.d_model)
    assert rel_err(got, want).max() < 1e-5
    # and no single share is the whole layer
    assert rel_err(shared.reshape(-1, cfg.d_model), want).max() > 1e-2


def test_group_limited_routing_keeps_three_of_four_groups():
    """A token whose six best experts lie in four groups keeps only the
    three groups with the best single expert; the sixth choice comes from
    inside them."""
    logits = np.full((1, 16), -9.0, np.float32)   # 4 groups of 4
    logits[0, [0, 1]] = [5.0, 4.9]      # group 0
    logits[0, [4, 5]] = [4.8, 4.7]      # group 1
    logits[0, 8] = 4.6                  # group 2
    logits[0, 12] = 4.5                 # group 3: sixth best, its group fourth
    logits[0, 9] = 1.0                  # group 2's runner-up
    idx, weights = route(jnp.asarray(logits), 6, n_groups=4, groups_per_token=3,
                         norm_topk=False, routed_scale=2.0)
    assert sorted(idx[0].tolist()) == [0, 1, 4, 5, 8, 9]
    s = np.exp(logits[0]) / np.exp(logits[0]).sum()
    np.testing.assert_allclose(
        sorted(weights[0].tolist()), sorted(2.0 * s[[0, 1, 4, 5, 8, 9]]), rtol=1e-5)
    free, _ = route(jnp.asarray(logits), 6)
    assert sorted(free[0].tolist()) == [0, 1, 4, 5, 8, 12]


def test_dropless_every_token_on_one_expert_loses_none():
    e, d, f, n = 4, 16, 32, 40
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (1, n, d))
    router = jnp.zeros((d, e)).at[:, 2].set(jnp.sign(x[0].sum(0)))  # all pick 2
    x = jnp.abs(x) * jnp.sign(x[0].sum(0))
    wg, wu = (jax.random.normal(k, (e, d, f)) * 0.1 for k in ks[1:3])
    wd = jax.random.normal(ks[3], (e, f, d)) * 0.1
    out, stats = moe_block(x, router, wg, wu, wd, top_k=1, with_stats=True)
    assert stats.tolist() == [n, n, 1]
    want = gated_mlp(x, wg[2], wu[2], wd[2])
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-6)
    assert float(jnp.abs(out).min(axis=-1).max()) > 0  # no row dropped to zero


def test_yarn_frequencies_and_scale_are_the_published_ones():
    m = yarn_mscale(40.0, 0.707)
    assert m == pytest.approx(1.2608, abs=5e-5)
    assert yarn_mscale(1.0, 0.707) == 1.0
    inv = np.asarray(yarn_inv_freq(64, 10000.0, 40.0, 32.0, 1.0, 4096))
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # 32 turns in 4,096 positions falls at dimension 10.47, one at 22.5:
    # kept up to 10, divided by 40 from 23 on, a linear ramp between
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40.0, rtol=1e-6)
    ramp = (np.arange(11, 23) - 10) / 13.0
    np.testing.assert_allclose(
        inv[11:23], plain[11:23] * (1 - ramp) + plain[11:23] / 40.0 * ramp, rtol=1e-5)
    np.testing.assert_allclose(
        inv, reference.yarn_inv_freq(64, 10000.0, 40.0, 32.0, 1.0, 4096), rtol=1e-6)
    spec = config("deepseek-v2-ep8-trio-bf16")["models"]["deepseek-v2"]
    from llm_consensus_tpu.models.transformer import _latent_scale

    scale = _latent_scale(server.model_config("m", spec))
    assert scale == pytest.approx(192 ** -0.5 * m * m)
    assert scale == pytest.approx(reference.softmax_scale(spec["more_fields"]))


@pytest.mark.parametrize("share", SHARES)
def test_param_count_is_the_init_params_tree(share):
    cfg = server.model_config("m", tiny_spec(SHARES[share]))
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert set(shapes) == {"embed", "final_norm", "lm_head", "layers", "layers_dense"}
    assert shapes["layers_dense"]["w_gate"].shape == (1, cfg.d_model, cfg.d_ff)
    assert shapes["layers"]["w_gate"].shape == (
        2, cfg.n_experts, cfg.d_model, cfg.d_expert)
    assert shapes["layers"]["w_router"].shape == (2, cfg.d_model, 8)
    assert param_count(cfg) == sum(x.size for x in jax.tree.leaves(shapes))
    assert param_count(cfg, active_only=True) <= param_count(cfg)


def test_param_count_at_the_cells_widths():
    cfg = server.model_config(
        "m", config("deepseek-v2-ep8-trio-bf16")["models"]["deepseek-v2"])
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert param_count(cfg) == n
    assert n * 2 / 1e9 == pytest.approx(7.63, abs=0.005)  # bf16 GB as held


CACHE_CASES = {
    # name: (model or None for the cell's, bytes a token in bf16)
    "the-cells-latent": (None, 1152 * 6),
    "tiny-deepseek-v2": ("tiny-deepseek-v2", (40 + 16) * 2 * 3),
    "tiny-llama": ("tiny-llama", 2 * 2 * 2 * 32 * 2),
    "mistral-7b": ("mistral-7b", 131072),
    "qwen2.5-0.5b": ("qwen2.5-0.5b", 2 * 24 * 2 * 64 * 2),
}


@pytest.mark.parametrize("case", CACHE_CASES)
def test_cache_bytes_a_token(case):
    model, want = CACHE_CASES[case]
    cfg = get_config(model) if model else server.model_config(
        "m", config("deepseek-v2-ep8-trio-bf16")["models"]["deepseek-v2"])
    assert cache_bytes_per_token(cfg, 2) == want
    leaves = jax.tree.leaves(
        jax.eval_shape(lambda: init_kv_cache(cfg, 3, 64, jnp.bfloat16)))
    assert sum(x.size * 2 for x in leaves) == want * 3 * 64
    assert all(x.ndim == 5 and x.shape[2] == 64 for x in leaves)
    if cfg.is_latent:
        assert len(leaves) == 1 and leaves[0].shape[3:] == (1, cfg.cache_width)


def _engine(**how):
    return Engine(get_config("tiny-deepseek-v2"), max_seq=128, **how)


def _refuse_int8_cache():
    _engine(kv_quant="int8")


def _refuse_int8_cache_shape():
    init_kv_cache(get_config("tiny-deepseek-v2"), 1, 32, quant="int8")


def _refuse_radix_arena(monkeypatch):
    monkeypatch.setenv("LLMC_KV_POOL", "1")
    _engine()


def _refuse_mesh():
    from llm_consensus_tpu.parallel import make_mesh

    _engine(mesh=make_mesh({"dp": 1, "tp": 2}, jax.devices()[:2]))


def _refuse_speculation():
    from llm_consensus_tpu.engine.speculative import SpecConfig

    ContinuousBatcher(_engine(), max_batch=2, spec=SpecConfig(kind="lookup"))


def _forward_with(**kw):
    cfg = get_config("tiny-deepseek-v2")
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    cache = init_kv_cache(cfg, 1, 32)
    forward(params, cfg, jnp.zeros((1, 4), jnp.int32), cache, 0, **kw)


REFUSALS = {
    # name: (what is tried, words the message must hold)
    "int8-latent-cache": (_refuse_int8_cache, "no int8 cache for a latent"),
    "int8-latent-cache-shape": (_refuse_int8_cache_shape, "no quantized cache for a latent"),
    "radix-arena": (_refuse_radix_arena, "radix KV arena"),
    "mesh-tp": (_refuse_mesh, "runs on one chip"),
    "speculation": (_refuse_speculation, "no speculative pool decode"),
    "speculative-bitmap": (
        lambda: _forward_with(kv_mask=jnp.ones((1, 32), bool),
                              row_start=jnp.zeros((1,), jnp.int32)),
        "no speculative decoding"),
    "shared-prefix": (
        lambda: _forward_with(prefix={"kv": None}, prefix_len=jnp.asarray(2)),
        "no shared-prefix attention"),
    "ring-prefill": (lambda: _forward_with(attn_impl="ring"), "no sequence-parallel"),
}


@pytest.mark.parametrize("case", REFUSALS)
def test_what_the_family_does_not_get_is_refused_by_name(case, monkeypatch):
    attempt, words = REFUSALS[case]
    with pytest.raises(ValueError) as stop:
        attempt(monkeypatch) if attempt is _refuse_radix_arena else attempt()
    assert words in str(stop.value) and "tiny-deepseek-v2" in str(stop.value)


def test_unknown_router_scoring_is_refused():
    cfg = get_config("tiny-deepseek-v2", router_scoring="sigmoid")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    with pytest.raises(ValueError, match="sigmoid"):
        forward(params, cfg, jnp.zeros((1, 4), jnp.int32))


def test_a_pool_books_what_its_programs_routed():
    """Prefix sharing and the retained snapshot are off for the family; the
    pool's decode chunks and prefill programs return their routing sums,
    which the fetch worker books and the pool.fetch span carries."""
    spec = tiny_spec(True)
    cfg = server.model_config("tiny-dsv2-pool", spec)
    ring = FlightRecorder(capacity=512)
    bb_mod.install(ring)
    eng = Engine(cfg, max_seq=256, stream_interval=4)
    assert not eng.prefix_cache_enabled
    assert {k: eng.build_stats[k] for k in (
        "experts_held", "router_width", "cache_bytes_per_token")} == {
            "experts_held": 2, "router_width": 8, "cache_bytes_per_token": 336}
    pool = ContinuousBatcher(eng, max_batch=4)
    try:
        assert not pool._prefix_enabled
        sampling = SamplingParams(max_new_tokens=8, ignore_eos=True)
        outs = [pool.submit(p, sampling) for p in ("one prompt", "another, longer prompt")]
        assert [len(o.result(timeout=300).token_ids) for o in outs] == [8, 8]
        st = pool.snapshot()
    finally:
        pool.close()
    k, layers = cfg.experts_per_token, cfg.n_expert_layers
    assert st["moe_layer_steps"] == st["decode_steps"] * layers
    assert 0 < st["moe_pairs_held"] < st["moe_pairs_total"]
    assert 0 < st["moe_prefill_pairs_held"] <= st["moe_pairs_held"]
    assert st["moe_pairs_total"] % (k * layers) == 0
    assert 0 < st["moe_expert_reads"] <= 2 * st["moe_layer_steps"]
    fetches = [e.args for e in ring.snapshot()
               if e.name == "pool.fetch" and e.tid == "pool:tiny-dsv2-pool"]
    assert sum(a["moe_pairs_held"] + a["moe_prefill_pairs_held"] for a in fetches) \
        == st["moe_pairs_held"]
    assert sum(a["moe_expert_reads"] for a in fetches) == st["moe_expert_reads"]
    # a model without a router has none of it
    dense = ContinuousBatcher(Engine(get_config("tiny-llama"), max_seq=128), max_batch=2)
    try:
        assert not any(key.startswith("moe_") for key in dense.snapshot())
    finally:
        dense.close()


POOLS = {
    # name: (the model, pairs its four-chunk prompt's prefill sweeps)
    "latent": ("tiny-deepseek-v2", 512 * (512 + 1024 + 1536 + 2048)),
    "dense": ("tiny-llama", 4 * 512 * 2048),
}


@pytest.mark.parametrize("kind", POOLS)
def test_a_pool_books_the_pairs_its_prefill_programs_swept(kind):
    """A judge-sized prompt of 1,8xx tokens goes through one row in four
    512-token chunks of a 2,048-slot bucket: a latent pool books each chunk
    at the width its frontier picked (5,120 x 512 pairs), a dense pool its
    whole bucket a chunk; both book the causal pairs of the real tokens, and
    the ``pool.admit`` span carries the wave's own."""
    model, swept = POOLS[kind]
    ring = FlightRecorder(capacity=512)
    bb_mod.install(ring)
    pool = ContinuousBatcher(
        Engine(get_config(model), max_seq=4096, stream_interval=4), max_batch=2)
    try:
        out = pool.submit("judge this: " + "word " * 370,
                          SamplingParams(max_new_tokens=4, ignore_eos=True))
        n = out.result(timeout=600).prompt_tokens
        st = pool.snapshot()
    finally:
        pool.close()
    assert 1536 < n <= 2048
    assert st["prefill_slot_tokens"] == 4 * 512
    assert st["prefill_kv_pairs_swept"] == swept
    assert st["prefill_kv_pairs_live"] == n * (n + 1) // 2
    admit, = [e.args for e in ring.snapshot()
              if e.name == "pool.admit" and e.tid == f"pool:{model}"]
    assert (admit["route"], admit["chunks"]) == ("single", 4)
    assert (admit["pairs_swept"], admit["pairs_live"]) == (swept, n * (n + 1) // 2)


def test_a_wave_of_short_prompts_books_its_bucket_squared_a_row():
    """The panel prompts' waves: T is the bucket, one branch, rows x bucket x
    bucket pairs, padding rows and all; live counts the real rows alone."""
    ring = FlightRecorder(capacity=512)
    bb_mod.install(ring)
    pool = ContinuousBatcher(
        Engine(get_config("tiny-deepseek-v2"), max_seq=256, stream_interval=4),
        max_batch=4)
    try:
        outs = [pool.submit(p, SamplingParams(max_new_tokens=4, ignore_eos=True))
                for p in ("one prompt", "another, longer prompt", "a third")]
        ns = [o.result(timeout=300).prompt_tokens for o in outs]
        st = pool.snapshot()
    finally:
        pool.close()
    admits = [e.args for e in ring.snapshot()
              if e.name == "pool.admit" and e.tid == "pool:tiny-deepseek-v2"]
    assert any(a["route"] == "rows" for a in admits)
    for a in (a for a in admits if a["route"] == "rows"):  # however they fell
        bucket = a["slot_tokens"] // a["rows_padded"]
        assert (a["chunks"], a["pairs_swept"]) == (1, a["rows_padded"] * bucket * bucket)
    assert st["prefill_kv_pairs_swept"] == sum(a["pairs_swept"] for a in admits)
    assert st["prefill_kv_pairs_live"] == sum(n * (n + 1) // 2 for n in ns)


def test_a_consensus_run_with_it_as_panelist_and_judge():
    from llm_consensus_tpu.cli import main

    stdout, stderr = io.StringIO(), io.StringIO()
    code = main(
        ["--models", "tpu:tiny-deepseek-v2,tpu:tiny-qwen2",
         "--judge", "tpu:tiny-deepseek-v2", "--json", "--max-tokens", "24",
         "what is the answer?"],
        stdin=io.StringIO(""), stdout=stdout, stderr=stderr,
        install_signal_handlers=False,
    )
    assert code == 0, stderr.getvalue()
    doc = json.loads(stdout.getvalue())
    assert {r["model"] for r in doc["responses"]} == {
        "tpu:tiny-deepseek-v2", "tpu:tiny-qwen2"}
    assert doc["judge"] == "tpu:tiny-deepseek-v2" and isinstance(doc["consensus"], str)


@pytest.fixture
def presets():
    """The program's table, put back as it was after the case."""
    before = dict(MODEL_PRESETS)
    yield MODEL_PRESETS
    MODEL_PRESETS.clear()
    MODEL_PRESETS.update(before)


@pytest.mark.parametrize("path", CONFIGS, ids=[os.path.basename(p) for p in CONFIGS])
def test_every_benchmark_configuration_installs(path, presets):
    """The program's ModelConfig and the benchmark's files cannot drift
    apart unseen by the tier-1 tests: every file under benchmark/configs
    goes through the harness's install_models, and each model's stated
    count of parameters is the tree's."""
    with open(path) as f:
        cfg = json.load(f)
    server.install_models(cfg["models"])
    for name, spec in cfg["models"].items():
        assert presets[name] == server.model_config(name, spec)
        assert parity.reference_for(name, spec).FAMILIES
        assert param_count(presets[name]) > 0
    assert set(cfg["panel"]) | {cfg["judge"]} == set(cfg["models"])
