"""The scope vocabulary (obs/scopes.py) against the programs that carry it.

Every hot program of every family is lowered on tiny presets: it must name
every scope of its family's vocabulary and no ``llmc.`` name outside it; an
operation of it that sits under NO scope is a part someone forgot to name;
and the scopes are metadata alone: the lowered text without locations is
the same with and without them.
"""

import contextlib
import re
import types

import jax
import jax.numpy as jnp
import pytest
from jax._src import source_info_util

from llm_consensus_tpu.engine import batcher as B
from llm_consensus_tpu.engine import engine as E
from llm_consensus_tpu.models import transformer as T
from llm_consensus_tpu.models.config import MODEL_PRESETS
from llm_consensus_tpu.obs import scopes
from llm_consensus_tpu.ops.quant import init_params_quantized

ROWS, SLOTS, CHUNK = 2, 256, 64

# (preset, weight and cache quantisation): dense, sliding-window int8,
# latent + routed, hybrid state-space, a routed model without a latent, and
# a stack whose every layer is one part (mixer, LatentMoE or attention), and
# one of delta-rule layers beside output-gated attention, each followed by
# routed experts.
FAMILIES = {
    "dense": ("tiny-llama", None),
    "window-int8": ("tiny-mistral", "int8"),
    "latent-routed": ("tiny-deepseek-v2", None),
    "hybrid-ssm": ("tiny-falcon-h1", None),
    "routed": ("tiny-mixtral", None),
    "one-part": ("tiny-nemotron-h", None),
    "delta-rule": ("tiny-solar-open2", None),
    # window and full attention layers in one stack, a sweep's scope a kind
    "mixed-windows": ("tiny-afmoe", None),
}
PROGRAMS = ("decode_chunk", "prefill_step", "prefill_chunks_loop")
CASES = [(f, p) for f in FAMILIES for p in PROGRAMS]

NAME = re.compile(r"llmc\.[a-z_]+(?:\.[a-z_]+)?")


def expected(cfg, program: str) -> set:
    """The scopes a program of this model has to name."""
    want = set(scopes.COMMON) - {"cache.splice", "sample", "sentinel",
                                 "chunk.tail", "mlp"}
    want |= set(scopes.MLA if cfg.is_latent else scopes.ATTN)
    if cfg.is_moe:
        want |= set(scopes.MOE)
        if not cfg.n_shared_experts:
            want.discard("moe.shared")
        if not cfg.moe_latent:
            want -= {"moe.latent_in", "moe.latent_out"}
    if not cfg.is_moe or cfg.n_dense_layers or cfg.n_mlp_layers:
        want.add("mlp")
    if cfg.has_ssm:
        want |= set(scopes.SSM) - {
            "ssm.scan" if program == "decode_chunk" else "ssm.step"}
    if cfg.has_kda:
        want |= set(scopes.KDA) - {
            "kda.scan" if program == "decode_chunk" else "kda.step"}
    if cfg.attn_out_gate:
        want |= set(scopes.ATTN_GATE)
    if "W" in cfg.layer_kinds:
        want |= set(scopes.ATTN_KINDS)
    if program == "decode_chunk":
        want |= {"sample", "sentinel", "chunk.tail"}
    if program == "prefill_chunks_loop":
        want.add("chunk.tail")
    return want


def _abstract(family: str, rows: int):
    name, quant = FAMILIES[family]
    cfg = MODEL_PRESETS[name]
    key = jax.random.PRNGKey(0)
    make = (lambda: init_params_quantized(cfg, key)) if quant else (
        lambda: T.init_params(cfg, key))
    params = jax.eval_shape(make)
    cache = jax.eval_shape(
        lambda: T.init_kv_cache(cfg, rows, SLOTS, quant=quant))
    return cfg, params, cache


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _call(family: str, program: str):
    """(the program as the engine jits it, args, kwargs) for abstract
    operands of a tiny pool."""
    if program == "decode_chunk":
        cfg, params, cache = _abstract(family, ROWS)
        return E._decode_chunk, (
            params, cfg, _i32(ROWS), _i32(), cache,
            jax.ShapeDtypeStruct((2,), jnp.uint32), 4, 0.0, None, None,
        ), dict(row_start=_i32(ROWS), kv_width=128, attn_impl="flash",
                sentinel=True, moe_stats=True)
    if program == "prefill_step":
        cfg, params, cache = _abstract(family, ROWS)
        return E._prefill_step, (
            params, cfg, _i32(ROWS, CHUNK), _i32(ROWS), cache,
        ), dict(attn_impl="flash", row_start=_i32(ROWS), kv_width=CHUNK,
                moe_stats=True,
                row_end=_i32(ROWS) if cfg.has_state else None)
    cfg, params, cache = _abstract(family, 1)
    return E._prefill_chunks_loop, (
        params, cfg, _i32(4, 1, CHUNK), _i32(), _i32(), _i32(1), cache, 4,
        SLOTS,
    ), dict(moe_stats=True)


def _fresh(program):
    """The program's plain function under a jit of its own, so that two
    traces of it (with and without scopes) share no cache."""
    if isinstance(program, E._NamedPrograms):
        fn, static = program._fn, program._static
    else:
        fn = program.__wrapped__
        static = ("cfg", "attn_impl", "mesh", "kv_width", "w8a8", "moe_stats")
    copy = types.FunctionType(
        fn.__code__, fn.__globals__, fn.__name__, fn.__defaults__,
        fn.__closure__)
    copy.__kwdefaults__ = fn.__kwdefaults__
    return jax.jit(copy, static_argnames=static)


def _leaves(jaxpr, out: list, outer: str = "") -> list:
    """(primitive, name stack, where) of every equation that holds no jaxpr
    and makes more than a scalar (a loop's counters belong to no part); an
    inner jaxpr's stacks are relative to the equation that holds it."""
    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        subs = []
        for v in eqn.params.values():
            for x in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(x, "jaxpr", x)
                if hasattr(inner, "eqns"):
                    subs.append(inner)
        if subs:
            for sub in subs:
                _leaves(sub, out, stack)
        elif any(v.aval.shape for v in eqn.outvars):
            out.append((eqn.primitive.name, stack,
                        source_info_util.summarize(eqn.source_info)))
    return out


@pytest.mark.parametrize("family,program", CASES)
def test_a_program_names_its_family_and_nothing_else(family, program):
    prog, args, kwargs = _call(family, program)
    text = prog.lower(*args, **kwargs).as_text(debug_info=True)
    named = {n[len(scopes.PREFIX):] for n in NAME.findall(text)}
    assert named <= set(scopes.SCOPES), named - set(scopes.SCOPES)
    assert named == expected(args[1], program)


@pytest.mark.parametrize("family,program", CASES)
def test_no_operation_of_a_program_is_without_a_scope(family, program):
    prog, args, kwargs = _call(family, program)
    jaxpr = _fresh(prog).trace(*args, **kwargs).jaxpr
    bare = sorted({
        (prim, where) for prim, stack, where in _leaves(jaxpr.jaxpr, [])
        if scopes.PREFIX not in stack})
    assert not bare, bare


@pytest.mark.parametrize("family,program", CASES)
def test_scopes_are_metadata_alone(family, program, monkeypatch):
    prog, args, kwargs = _call(family, program)
    with_scopes = _fresh(prog).lower(*args, **kwargs)
    monkeypatch.setattr(
        jax, "named_scope", lambda name: contextlib.nullcontext())
    without = _fresh(prog).lower(*args, **kwargs)
    assert scopes.PREFIX in with_scopes.as_text(debug_info=True)
    assert scopes.PREFIX not in without.as_text(debug_info=True)
    assert with_scopes.as_text() == without.as_text()


@pytest.mark.parametrize("program", ["_splice", "_splice_rows"])
def test_the_cache_hand_over_is_named(program):
    cfg = MODEL_PRESETS["tiny-falcon-h1"]
    pool = jax.eval_shape(lambda: T.init_kv_cache(cfg, 4, SLOTS))
    fresh = jax.eval_shape(lambda: T.init_kv_cache(cfg, 2, CHUNK))
    if program == "_splice":
        lowered = B._splice.lower(pool, fresh, _i32(), _i32(), width=CHUNK)
    else:
        lowered = B._splice_rows.lower(
            pool, fresh, _i32(2), _i32(2), _i32(2), k=2, width=CHUNK)
    named = set(NAME.findall(lowered.as_text(debug_info=True)))
    assert named == {"llmc.cache.splice"}


def test_the_kernels_carry_a_name():
    """A Mosaic kernel shows in a trace under its own name."""
    from llm_consensus_tpu.ops.pallas import decode_attention, flash_attention

    bf16 = lambda *s: jnp.zeros(s, jnp.bfloat16)  # noqa: E731
    q, kv = bf16(1, 128, 4, 128), bf16(1, 128, 2, 128)
    prefill = jax.make_jaxpr(
        lambda q, k, v: flash_attention(q, k, v, q_offset=0, scale=1.0)
    )(q, kv, kv)
    assert "llmc_flash_attention" in str(prefill)
    stack = bf16(2, 1, 128, 2, 128)
    decode = jax.make_jaxpr(
        lambda q, k, v: decode_attention(
            q, k, v, jnp.int32(5), jnp.int32(0), scale=1.0)
    )(bf16(1, 1, 4, 128), stack, stack)
    assert "llmc_decode_attention" in str(decode)


def test_a_name_outside_the_vocabulary_is_refused():
    with pytest.raises(ValueError, match="attn.swep"):
        scopes.scope("attn.swep")
    assert len(set(scopes.SCOPES)) == len(scopes.SCOPES)
