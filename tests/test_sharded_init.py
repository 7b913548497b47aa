"""A sharded engine makes each leaf of its random tree under that leaf's
sharding (ISSUE 25): same values as the unsharded initialiser, nothing
whole on any chip unless its spec says so, nothing outside the mesh; the
normal path (``TPUProvider.prepare`` → ``_engine_for``) then serves a tp=2
judge whose prefill and decode through the sharded cache agree with the
benchmark's plain reference; ``/statsz`` and the ``engine.build`` span say
what the build cost.

On the suite's eight virtual CPU devices; the four-chip placement uses the
first four, as one v5e 2x2 host gives.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding

from benchmark.reference import decoder
from llm_consensus_tpu import obs
from llm_consensus_tpu.models import forward, get_config, init_kv_cache, init_params
from llm_consensus_tpu.obs import blackbox as bb_mod
from llm_consensus_tpu.obs.blackbox import FlightRecorder
from llm_consensus_tpu.ops.quant import init_params_quantized, quantize_params
from llm_consensus_tpu.parallel.mesh import make_mesh
from llm_consensus_tpu.parallel.sharding import (
    param_shardings, param_specs, shard_pytree)
from llm_consensus_tpu.providers.tpu import TPUProvider

# Mistral-shaped: grouped-query attention (4 Q / 2 KV heads), a sliding
# window, an untied head. Qwen2-shaped: q/k/v biases.
SHAPES = ("tiny-mistral", "tiny-qwen2")
# The benchmark reference's view of a model: its published sizes by the
# names the configuration files use.
REFERENCE_KEYS = (
    "family", "n_layers", "n_heads", "n_kv_heads", "head_dim", "rope_theta",
    "rms_eps", "sliding_window", "tie_embeddings",
)


def _tp2_mesh():
    # Not the default device's pair: a leaf made on devices[0] first and
    # moved afterwards would show in the hook below.
    return make_mesh({"dp": 1, "tp": 2}, jax.devices()[2:4])


def _equal(a, b) -> None:
    np.testing.assert_array_equal(
        np.asarray(a.astype(jnp.float32)), np.asarray(b.astype(jnp.float32))
    )


@pytest.mark.parametrize("preset", SHAPES)
def test_sharded_initialiser_gives_the_unsharded_values_leaf_for_leaf(preset):
    cfg = get_config(preset)
    assert cfg.n_kv_heads < cfg.n_heads and not cfg.tie_embeddings
    mesh = _tp2_mesh()
    plain = init_params(cfg, jax.random.PRNGKey(11))
    sharded = init_params(
        cfg, jax.random.PRNGKey(11), shardings=param_shardings(cfg, mesh)
    )
    assert jax.tree.structure(plain) == jax.tree.structure(sharded)
    jax.tree.map(_equal, plain, sharded)
    # and the unsharded path still lands where it did: the default device
    assert {
        d.id for leaf in jax.tree.leaves(plain) for d in leaf.sharding.device_set
    } == {jax.devices()[0].id}


@pytest.mark.parametrize("preset", SHAPES)
def test_every_leaf_is_made_under_its_spec_and_never_whole_elsewhere(preset):
    cfg = get_config(preset)
    mesh = _tp2_mesh()
    specs = param_specs(cfg, mesh)
    spec_of = {**specs, **specs["layers"]}
    on_mesh = {d.id for d in mesh.devices.flat}
    seen: dict = {}

    def as_made(name, w):
        # Called with the leaf as the initialiser made it, before anything
        # could move it.
        spec = spec_of[name]
        assert w.sharding == NamedSharding(mesh, spec), (name, w.sharding)
        assert {s.device.id for s in w.addressable_shards} == on_mesh, name
        cut = [ax for ax in spec if ax is not None]
        for shard in w.addressable_shards:
            # a sharded leaf is on no device whole
            assert (shard.data.size == w.size) == (not cut), (name, spec)
        seen[name] = spec
        return w

    tree = init_params(
        cfg, jax.random.PRNGKey(5), shardings=param_shardings(cfg, mesh),
        leaf_hook=as_made,
    )
    flat_names = set(tree["layers"]) | (set(tree) - {"layers"})
    assert set(seen) == flat_names  # norms and biases too, not only matmuls
    assert any(ax == "tp" for ax in seen["w_gate"])
    assert any(ax == "tp" for ax in seen["lm_head"])


@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_sharded_quantized_init_is_shard_then_quantize(mode):
    """The int8/int4 multi-device case through the same hook: each leaf
    made under its sharding and quantized there equals the old order
    (whole tree, shard it, quantize it)."""
    cfg = get_config("tiny-mistral")
    mesh = _tp2_mesh()
    streamed = init_params_quantized(
        cfg, jax.random.PRNGKey(9), mode=mode,
        shardings=param_shardings(cfg, mesh),
    )
    whole = quantize_params(
        shard_pytree(
            init_params(cfg, jax.random.PRNGKey(9)), param_specs(cfg, mesh), mesh
        ),
        mode=mode,
    )
    assert jax.tree.structure(streamed) == jax.tree.structure(whole)
    jax.tree.map(_equal, streamed, whole)
    assert {
        d.id for leaf in jax.tree.leaves(streamed)
        for d in leaf.sharding.device_set
    } == {d.id for d in mesh.devices.flat}


# -- the normal path: prepare on four devices, judge tp = 2 ---------------------


@pytest.fixture(scope="module")
def four_chip_provider():
    """The placement `serve` plans for the benchmark's four-chip cell, at
    CI size: two panelists on a device each, the third panelist — also the
    judge — tp = 2 on the last two. The recorder and the ring are installed
    before any engine is built (spans bind at construction)."""
    for mod in (obs, bb_mod):
        mod.reset()
    ring = FlightRecorder(capacity=256)
    bb_mod.install(ring)
    provider = TPUProvider(ignore_eos=True, stream_interval=4, max_seq=128)
    panel = ["tpu:tiny-qwen2", "tpu:tiny-llama", "tpu:tiny-mistral"]
    provider.prepare(panel, "tpu:tiny-mistral", devices=jax.devices()[:4])
    engines = {m: provider._engine_for(m) for m in panel}
    yield provider, engines, ring
    provider.release()
    for mod in (obs, bb_mod):
        mod.reset()


def test_four_devices_hold_three_engines_on_disjoint_slices(four_chip_provider):
    _, engines, _ = four_chip_provider
    held = {
        m: {d.id for leaf in jax.tree.leaves(e.params)
            for d in leaf.sharding.device_set}
        for m, e in engines.items()
    }
    assert held["tpu:tiny-mistral"] == {2, 3}
    assert sorted(map(sorted, held.values())) == [[0], [1], [2, 3]]


def test_tp2_judge_agrees_with_the_reference_through_the_sharded_cache(
        four_chip_provider):
    """Prefill of the first positions, then the last ones decoded one at a
    time through the sharded key/value cache, against the plain reference
    over the whole sequence — both reading the tree the provider built.

    In float32 (the served bf16 numbers upcast, cache and activations
    float32) the two computations differ by summation order alone:
    measured 1.8e-6 here. The same comparison with the tree, the cache and
    the activations in bfloat16 measured 2.5e-2 (median 1.1e-2). The limit
    is 1e-4: fifty times the first, a hundredth of the second, so
    computing in a lower precision, or a wrong split of heads, window or
    collective, fails it."""
    _, engines, _ = four_chip_provider
    engine = engines["tpu:tiny-mistral"]
    cfg, mesh = engine.cfg, engine.mesh
    assert dict(mesh.shape)["tp"] == 2
    params = jax.tree.map(lambda w: w.astype(jnp.float32), engine.params)
    assert params["layers"]["wq"].sharding == engine.params["layers"]["wq"].sharding
    seq, decoded = 64, 16  # past the 32-slot window
    ids = np.random.default_rng(25).integers(0, cfg.vocab_size, seq)
    cache = engine._shard_fn(
        init_kv_cache(cfg, batch=1, max_seq=128, dtype=jnp.float32)
    )
    assert cache["k"].sharding.spec[3] == "tp"
    n_pre = seq - decoded
    place = engine._place
    with jax.default_matmul_precision("highest"):
        logits, cache = forward(
            params, cfg, place(np.asarray(ids[None, :n_pre], np.int32)),
            cache, 0, mesh=mesh,
        )
        rows = [logits[0]]
        for p in range(n_pre, seq):
            step, cache = forward(
                params, cfg, place(np.asarray(ids[None, p:p + 1], np.int32)),
                cache, place(np.asarray(p, np.int32)), mesh=mesh,
            )
            rows.append(step[0])
    got = np.asarray(jnp.concatenate(rows, axis=0), np.float64)
    shape = {k: getattr(cfg, k) for k in REFERENCE_KEYS}
    want = np.asarray(decoder.forward(engine.params, shape, ids), np.float64)
    err = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert err.shape == (seq,)
    assert err.max() <= 1e-4, (err.max(), err[n_pre:].max())


def test_statsz_and_the_build_span_say_what_each_engine_cost(four_chip_provider):
    provider, engines, ring = four_chip_provider
    stats = provider.device_stats()["engines"]
    spans = {
        e.args["model"]: e for e in ring.snapshot()
        if e.name == "engine.build" and e.tid == "engine"
    }
    assert set(stats) == set(spans) == {"tiny-qwen2", "tiny-llama", "tiny-mistral"}
    for model, entry in stats.items():
        tp = 2 if model == "tiny-mistral" else 1
        assert entry["tp"] == tp and len(entry["devices"]) == tp
        tree_bytes = sum(
            leaf.nbytes for leaf in jax.tree.leaves(engines[f"tpu:{model}"].params)
        )
        # a tp=2 engine's chip holds its half of the sharded leaves and
        # the (small) replicated ones whole
        assert tree_bytes / tp <= entry["param_bytes_per_chip"] < tree_bytes / tp * 1.1
        assert entry["build_s"] > 0
        args = spans[model].args
        assert args["devices"] == entry["devices"] and args["tp"] == tp
        assert args["param_bytes_per_chip"] == entry["param_bytes_per_chip"]
        assert 0 < args["init_s"] <= entry["build_s"]
        assert spans[model].dur_ns > 0


@pytest.mark.parametrize("kv_quant", [None, "int8"])
def test_a_cache_is_made_on_the_engines_chips(kv_quant):
    """``Engine.new_cache``: every pool, wave and prefix cache of an engine
    with a mesh is written shard by shard where it lives, under the
    shardings ``shard_fn`` would have moved it to, and is all zeros."""
    from llm_consensus_tpu.engine import Engine

    cfg = get_config("tiny-mistral")
    mesh = _tp2_mesh()
    engine = Engine(cfg, mesh=mesh, max_seq=64, kv_quant=kv_quant)
    cache = engine.new_cache(3, 32)
    moved = engine._shard_fn(init_kv_cache(
        cfg, batch=3, max_seq=32, dtype=engine._dtype, quant=kv_quant
    ))
    assert jax.tree.structure(cache) == jax.tree.structure(moved)
    for made, want in zip(jax.tree.leaves(cache), jax.tree.leaves(moved)):
        assert made.sharding == want.sharding and made.shape == want.shape
        assert made.dtype == want.dtype and not np.asarray(made).any()
        assert {s.device.id for s in made.addressable_shards} == {2, 3}
    assert "tp" in jax.tree.leaves(cache)[0].sharding.spec
    # capacity defaults to the engine's; the program is kept per shape
    assert jax.tree.leaves(engine.new_cache(2))[0].shape[2] == 64
    assert set(engine._cache_makers) == {(3, 32), (2, 64)}
