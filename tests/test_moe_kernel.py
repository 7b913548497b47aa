"""The routed experts' products over a small buffer of sorted pairs (PR 46):
``ops/pallas/moe_pairs.py`` against a plain float32 product an expert, and
``ops/moe.py moe_block`` through the kernel against itself through the
grouped product (``jax.lax.ragged_dot``), in interpret mode on the CPU.

Bounds, and why each:

  * float32 operands: 1e-5 of the result's largest value. The kernel sums a
    row's products over chunks of ``K`` and in tiles of sixteen rows, the
    reference in one product an expert: the same float32 terms in another
    order.
  * bfloat16 operands: the kernel is no further from the float32 reference
    than ``ragged_dot``'s own path is, plus one bfloat16 place of the
    result's largest value: both round each product to bfloat16; the kernel
    rounds a gated expert's activation once where the CPU's grouped path
    rounds it an operation.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_consensus_tpu.engine.batcher import ContinuousBatcher
from llm_consensus_tpu.engine.engine import Engine, SamplingParams
from llm_consensus_tpu.models.config import get_config
from llm_consensus_tpu.ops import moe
from llm_consensus_tpu.ops.mlp import _activate
from llm_consensus_tpu.ops.pallas import moe_pairs
from llm_consensus_tpu.ops import quant

F32_BOUND = 1e-5
BF16_PLACE = 2.0 ** -8

# name: (L, E, K, F, gated, activation, P, layer, sizes or a rule, chunk bytes)
# The three cells' shapes scaled down (nem3 1,024 -> 2,688 ungated in a
# latent width, 64 held; solar2 4,096 -> 1,280 gated, 40 held; dsv2 5,120 ->
# 1,536 gated, 20 held), then the odd ones.
KERNEL_CASES = {
    "nem3-scaled": (5, 64, 128, 336, False, "relu2", 144, 3, "eighth", None),
    "solar2-scaled": (4, 40, 512, 160, True, "silu", 48, 1, "eighth", None),
    "dsv2-scaled": (5, 20, 640, 192, True, "silu", 48, 2, "eighth", None),
    "no-pair-held": (2, 8, 64, 48, True, "silu", 32, 1, "none", None),
    "every-pair-on-one-expert": (2, 8, 64, 48, True, "silu", 48, 0, "one", None),
    "every-held-expert-hit-once": (2, 16, 64, 48, False, "relu2", 16, 1, "each", None),
    "rows-end-inside-a-tile": (2, 8, 64, 48, True, "gelu_tanh", 32, 1, (0, 3, 0, 7, 1, 0, 0, 2), None),
    "first-layer": (3, 8, 56, 40, False, "relu2", 32, 0, "eighth", None),
    "last-layer": (3, 8, 56, 40, False, "relu2", 32, 2, "eighth", None),
    # K in four chunks, F in three: an expert's matrix comes in pieces, the
    # next expert's first piece is asked for under this one's last, and an
    # expert of forty rows spans three tiles.
    "chunks-and-tiles": (2, 6, 512, 384, True, "silu", 64, 1, (0, 40, 0, 1, 9, 3), 128 * 384 * 4),
}


def sizes_of(rule, e: int, p: int, rng) -> np.ndarray:
    if not isinstance(rule, str):
        return np.asarray(rule, np.int32)
    sizes = np.zeros((e,), np.int32)
    if rule == "one":
        sizes[e // 2] = p
    elif rule == "each":
        sizes[:] = 1
    elif rule == "eighth":  # an eighth of the pairs, on whichever experts
        for expert in rng.integers(0, e, max(p // 8, 1)):
            sizes[expert] += 1
    return sizes


def plain(rows, w_gate, w_up, w_down, layer, sizes, activation):
    """One float32 product an expert over its own rows; 0 past the last."""
    f32 = functools.partial(np.asarray, dtype=np.float32)
    out, r = np.zeros(rows.shape, np.float32), 0
    for e, size in enumerate(sizes):
        x = f32(rows[r:r + size])
        h = x @ f32((w_up if w_gate is None else w_gate)[layer, e])
        h = np.asarray(_activate(jnp.asarray(h), activation))
        if w_gate is not None:
            h = h * (x @ f32(w_up[layer, e]))
        out[r:r + size] = h @ f32(w_down[layer, e])
        r += size
    return out


def operands(case: str, dtype):
    n_l, e, k, f, gated, activation, p, layer, rule, chunk = KERNEL_CASES[case]
    rng = np.random.default_rng(46)
    keys = jax.random.split(jax.random.PRNGKey(46), 4)
    stack = lambda key, *shape: (  # noqa: E731
        jax.random.normal(key, shape) / np.sqrt(shape[-2])).astype(dtype)
    w_gate = stack(keys[0], n_l, e, k, f) if gated else None
    w_up, w_down = stack(keys[1], n_l, e, k, f), stack(keys[2], n_l, e, f, k)
    rows = jax.random.normal(keys[3], (p, k)).astype(dtype)
    return (rows, w_gate, w_up, w_down, layer, sizes_of(rule, e, p, rng),
            activation), chunk


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", KERNEL_CASES)
def test_the_kernel_is_a_plain_product_an_expert(case, dtype, monkeypatch):
    (rows, w_gate, w_up, w_down, layer, sizes, activation), chunk = operands(
        case, jnp.dtype(dtype))
    if chunk:
        monkeypatch.setattr(moe_pairs, "_CHUNK_BYTES", chunk)
        jax.clear_caches()
        assert moe_pairs.chunk_rows(*w_up.shape[-2:], 4) * 4 == w_up.shape[-2]
    got = np.asarray(moe_pairs.experts_over_pairs(
        rows, w_gate, w_up, w_down, jnp.int32(layer), jnp.asarray(sizes),
        activation), np.float32)
    if chunk:
        jax.clear_caches()
    want = plain(rows, w_gate, w_up, w_down, layer, sizes, activation)
    held = int(sizes.sum())
    assert not got[held:].any()  # a row past the last group reads 0
    scale = max(float(np.abs(want).max()), 1.0)
    err = float(np.abs(got - want).max())
    if dtype == "float32":
        assert err <= F32_BOUND * scale, err
        return
    n_l, e = w_up.shape[:2]
    run = jnp.zeros((n_l * e,), jnp.int32).at[layer * e:(layer + 1) * e].set(sizes)
    grouped = np.asarray(moe._grouped(
        rows, tuple(w.reshape(n_l * e, *w.shape[2:])
                    for w in (w_gate, w_up, w_down) if w is not None),
        run, activation), np.float32)
    theirs = float(np.abs(grouped[:held] - want[:held]).max()) if held else 0.0
    assert err <= theirs + BF16_PLACE * scale, (err, theirs)


def test_a_chunk_is_whole_lane_tiles_within_its_bytes():
    rows = moe_pairs.chunk_rows
    assert rows(1024, 2688, 2) == 256    # nem3: 1.4 MB of a 5.5 MB matrix
    assert rows(2688, 1024, 2) == 896    # 21 lane tiles: thirds
    assert rows(4096, 1280, 2) == 512 and rows(1280, 4096, 2) == 256
    assert rows(5120, 1536, 2) == 640 and rows(1536, 5120, 2) == 128
    assert rows(56, 40, 4) == 56         # a CI-size expert: whole
    assert rows(128, 1 << 20, 2) == 128  # never less than a lane tile


# -- moe_block at T = 1: the kernel against the grouped product ----------------

def layer_operands(form: str, dtype, rows: int = 6):
    """A decode step's operands of one expert layer in a family's form."""
    keys = jax.random.split(jax.random.PRNGKey(7), 12)
    d, r, e, f, n_l = 64, 16, 8, 48, 3
    normal = lambda i, *shape: (  # noqa: E731
        jax.random.normal(keys[i], shape) / np.sqrt(shape[-2])).astype(dtype)
    x = jax.random.normal(keys[0], (rows, 1, d)).astype(dtype)
    if form == "mixtral":  # every expert held, one layer's own leaves
        args = (x, normal(1, d, e), normal(2, e, d, f), normal(3, e, d, f),
                normal(4, e, f, d))
        return args, dict(top_k=2, activation="silu")
    if form == "deepseek_v2":  # a share of the experts, groups, shared experts
        args = (x, normal(1, d, r), normal(2, n_l, e, d, f),
                normal(3, n_l, e, d, f), normal(4, n_l, e, f, d))
        return args, dict(
            top_k=3, activation="silu", first_expert=4, n_groups=4,
            groups_per_token=2, norm_topk=False, routed_scale=2.5,
            shared=(normal(5, d, 2 * f), normal(6, d, 2 * f),
                    normal(7, 2 * f, d)), layer=jnp.int32(2))
    z = 40  # nemotron_h: ungated experts in a latent width, a bias that chooses
    args = (x, normal(1, d, r), None, normal(3, n_l, e, z, f),
            normal(4, n_l, e, f, z))
    return args, dict(
        top_k=5, activation="relu2", first_expert=8, scoring="sigmoid_bias",
        router_bias=jax.random.normal(keys[8], (r,)), routed_scale=2.0,
        latent=(normal(9, d, z), normal(10, z, d)),
        shared=(None, normal(6, d, 72), normal(7, 72, d)), layer=jnp.int32(0))


def two_devices():
    """A mesh of two (virtual CPU) devices: under it the switch keeps the
    grouped product, as it does for stacks sharded over two chips."""
    return jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("tp",))


def both_paths(form, dtype, rows):
    """``moe_block`` through the kernel, and through the grouped product
    (handed a mesh of two devices: the switch's own rule, no knob)."""
    args, kw = layer_operands(form, dtype, rows)
    assert moe.pairs_kernel_serves(rows * kw["top_k"], args[3])
    assert not moe.pairs_kernel_serves(rows * kw["top_k"], args[3], two_devices())
    block = lambda **where: jax.jit(functools.partial(  # noqa: E731
        moe.moe_block, with_stats=True, **kw, **where))(*args)
    return block(), block(mesh=two_devices())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows", [6, 5])  # 5 x 3 and 5 x 5 pairs: no whole tile
@pytest.mark.parametrize("form", ["mixtral", "deepseek_v2", "nemotron_h"])
def test_a_decode_step_through_the_kernel_is_the_grouped_products(
        form, rows, dtype):
    (got, got_stats), (want, want_stats) = both_paths(
        form, jnp.dtype(dtype), rows)
    assert np.array_equal(got_stats, want_stats)  # the three sums: exactly
    assert int(want_stats[1]) > 0
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = max(float(np.abs(want).max()), 1.0)
    bound = F32_BOUND if dtype == "float32" else 2 * BF16_PLACE
    assert float(np.abs(got - want).max()) <= bound * scale


def test_the_way_back_is_the_grouped_products():
    """A training step on a handful of tokens goes forward through the
    kernel and back through ``ragged_dot``: the same gradients."""
    args, kw = layer_operands("deepseek_v2", jnp.float32)

    def grads(**where):
        def loss(x, w_gate, w_up, w_down):
            return jnp.sum(moe.moe_block(
                x, args[1], w_gate, w_up, w_down, **kw, **where) ** 2)

        return jax.grad(loss, argnums=(0, 1, 2, 3))(args[0], *args[2:])

    for got, want in zip(grads(), grads(mesh=two_devices())):
        assert float(jnp.abs(want).max()) > 0
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


def test_what_the_switch_sees():
    """The size of the pairs buffer, a plain leaf, the mesh it lies on, and
    nothing of a model's name: a prompt's chunk, an int8 stack and stacks
    over more than one device keep the grouped product."""
    leaf = jnp.zeros((2, 4, 128, 256), jnp.bfloat16)
    for rows in (6, 18):  # the cells' pools, and the deployment batch
        for choices in (22, 8, 6):
            assert moe.pairs_kernel_serves(rows * choices, leaf)
    assert moe.pairs_kernel_serves(moe.PAIRS_KERNEL_MAX, leaf)
    assert not moe.pairs_kernel_serves(moe.PAIRS_KERNEL_MAX + 1, leaf)
    assert not moe.pairs_kernel_serves(512 * 22, leaf)
    quantized = quant._quantize(leaf)
    assert quant.is_quantized(quantized)
    assert not moe.pairs_kernel_serves(6 * 22, quantized)
    # What the kernel keeps in fast memory is asked of the shapes: a buffer of
    # the largest number of pairs at Mixtral's widths fits, at twice those not.
    wide = lambda k, f: jax.ShapeDtypeStruct((2, 8, k, f), jnp.bfloat16)  # noqa: E731
    assert moe.pairs_kernel_serves(moe.PAIRS_KERNEL_MAX, wide(4096, 14336))
    assert not moe.pairs_kernel_serves(moe.PAIRS_KERNEL_MAX, wide(8192, 28672))
    assert moe.pairs_kernel_serves(16, wide(8192, 28672))
    one = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))
    assert moe.pairs_kernel_serves(6 * 22, leaf, one)  # a one-chip mesh is one chip
    assert not moe.pairs_kernel_serves(6 * 22, leaf, two_devices())


@pytest.mark.parametrize("tp", [1, 2])
def test_a_pool_books_the_steps_its_experts_ran_in_the_kernel(tp):
    """``moe_kernel_layer_steps`` beside ``moe_layer_steps``, in the counters
    and in the ``pool.fetch`` span; all of them while the pool's pairs fit
    the kernel on one device, none of an engine whose expert stacks are
    sharded over two; absent without a router."""
    from llm_consensus_tpu.obs import blackbox
    from llm_consensus_tpu.obs.blackbox import FlightRecorder
    from llm_consensus_tpu.parallel.mesh import make_mesh

    name = f"tiny-mixtral-kernel-tp{tp}"
    cfg = dataclasses.replace(get_config("tiny-mixtral"), name=name)
    mesh = make_mesh({"dp": 1, "tp": tp}, jax.devices()[:tp])
    ring = FlightRecorder(capacity=512)
    blackbox.install(ring)
    pool = ContinuousBatcher(
        Engine(cfg, max_seq=128, stream_interval=4, mesh=mesh), max_batch=2)
    try:
        out = pool.submit("a prompt", SamplingParams(max_new_tokens=8, ignore_eos=True))
        assert len(out.result(timeout=300).token_ids) == 8
        st = pool.snapshot()
    finally:
        pool.close()
    assert st["moe_layer_steps"] == st["decode_steps"] * cfg.n_expert_layers > 0
    assert st["moe_kernel_layer_steps"] == (
        st["moe_layer_steps"] if tp == 1 else 0)
    fetches = [e.args for e in ring.snapshot()
               if e.name == "pool.fetch" and e.tid == f"pool:{name}"]
    assert sum(a["moe_kernel_layer_steps"] for a in fetches) \
        == st["moe_kernel_layer_steps"]
    dense = ContinuousBatcher(Engine(get_config("tiny-llama"), max_seq=128), max_batch=2)
    try:
        assert "moe_kernel_layer_steps" not in dense.snapshot()
    finally:
        dense.close()
