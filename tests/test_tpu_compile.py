"""The main path's kernels and step programs, compiled by the chip's own
compiler for a *described* TPU v5e — no chip attached.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
a block whose lane dim is not tiled in 128s, a reshape with no vector
layout, more VMEM than a kernel may use. libtpu compiles for a topology
that is described and not attached (``v5e:2x2``), so these cases run in
the CPU sandbox, a second or two each, and guard every later PR at no
chip time. A compile that passes is not a chip run: nothing executes, so
it says nothing about results or speed.

Widths are the published ones of ``llama-3.2-3b`` (Hq 24 / Hkv 8 / dh
128), ``llama-3-8b`` (32 / 8 / 128) and ``gemma-7b`` (16 / 16 / 256).
The ``gemma-7b`` int8-KV rows at B = 8 are the combination the support
predicate used to admit and the compiler refuse (a 32-slot kv block on
the lanes of the scale operand).

The DeepSeek-V2 cell's judge-prompt prefill (``_prefill_chunks_loop`` of
``benchmark/configs/deepseek-v2-ep8-trio-bf16.json``'s cut: four 512-token
chunks of a 2,048-slot bucket) is compiled whole, and its attention must
stay a ``conditional`` with a branch a width. Its 16-step decode chunk
(``_decode_chunk``: six rows, the 4,096-slot pool, the sentinel and the
routing sums, at 384 and 2,048 slots) is compiled whole too, and what its
text MATERIALISES is held: no copy of the pool inside a step, no pass over a
layer's ``wq_b``, the weight stacks read where they lie (PR 35).

Every dense model's decode chunk at its cell's own shapes (the trio's 3B, the
int8 7B, the 7B tensor-parallel over two described devices, the hybrid) and
the int8 7B's judge-prompt loop are read the same way for ``wq`` / ``wk`` /
``wv``: no stack relaid at a program's entry, no pass over a layer's three
leaves before their products (PR 38).

The three routed cells' decode chunks hold the kernel over the sorted
pairs (two calls an expert layer), no ``ragged-dot`` and no layer's slice
of an expert stack; their prefill programs, lowered at the cells' own sizes,
keep ``ragged_dot``; and every program the kernel is not in (the dense,
tp = 2 and hybrid configurations', the routed prefills) lowers to the text
of PR 46's parent (``tests/data/lowered_text_pins.json`` ``cell:*``).
A routed decode chunk whose expert stacks are SHARDED over two described
devices (Mixtral's widths, two layers) keeps ``ragged_dot``, which the
compiler partitions; it refuses a kernel there (``Mosaic kernels cannot be
automatically partitioned``), and interpret mode never says so.

The four routed cells' judge-prompt loops are also compiled at the width
the rule gives each judge (utils/flops.py ``prefill_chunk_width``: one chunk
of 2,048 tokens, two of 1,024 for the 128-head latent judge), and what each
asks for beside its operands is held under twice the score cap (PR 49).

All cases compile in ONE child process (this file run as a script) and
the tests read its report: loading libtpu and switching the persistent
compilation cache off (an entry written for a described device cannot be
read back without one) stay out of the process the other 900 tests share.
Skipped where the topology cannot be described (no libtpu).
"""

import functools
import json
import os
import re
import subprocess
import sys
import time

import pytest

WIDTHS = {  # preset -> (Hq, Hkv, dh)
    "llama-3.2-3b": (24, 8, 128),
    "llama-3-8b": (32, 8, 128),
    "gemma-7b": (16, 16, 256),
}
MAX_SEQ = 2048   # cache capacity, as chip_smoke.py caps it (LLMC_MAX_SEQ)
KV_WIDTH = 640   # an odd 128-multiple bucket: block_k cannot exceed 128
DECODE_CASES = [
    (preset, int8_kv, batch)
    for preset in sorted(WIDTHS) for int8_kv in (False, True) for batch in (1, 8)
]
# The benchmark cells' own decode shapes (BENCHMARK.json): pool rows, Q / KV
# heads (dh 128, bf16 cache) and the 128-slot buckets their traffic meets,
# in a 4,096-slot cache — so that no block the chooser picks for them is
# one Mosaic refuses on the chip.
CELL_MAX_SEQ = 4096
CELL_WIDTHS = (128, 256, 384, 1792, 1920, 2048)
CELL_SHAPES = {  # name -> (rows, Hq, Hkv)
    "qwen2.5-3b": (6, 16, 2),
    "qwen2.5-1.5b": (6, 12, 2),
    "mistral-7b": (6, 32, 8),
    "mistral-7b-tp2-shard": (8, 16, 4),
    "qwen2.5-1.5b-8rows": (8, 12, 2),  # the four-chip cell's panelist
}
CELL_CASES = [(name, w) for name in CELL_SHAPES for w in CELL_WIDTHS]
FLASH_T = (128, 2048)
STEP_CASES = {
    "llama-3.2-3b": "pallas",  # dh 128: both kernels
    "llama-3.2-1b": "xla",     # dh 64: the predicate routes decode to XLA
}
LATENT_CONFIG = "benchmark/configs/deepseek-v2-ep8-trio-bf16.json"
LATENT_CHUNK, LATENT_BUCKET = 512, 2048  # the judge prompt's program
LATENT_ROWS, LATENT_STEPS = 6, 16        # the judge pool's decode chunk
LATENT_DECODE_WIDTHS = (384, 2048)       # a panel phase's bucket, a judge phase's
HYBRID_CONFIG = "benchmark/configs/falcon-h1-34b-pp8-trio-bf16.json"
# The dense cells' judges: program -> (configuration file, int8 leaves, tp,
# pool rows, slots); the hybrid's decode chunk is ``_hybrid_ssm_programs``'.
DENSE_PROGRAMS = {
    "qwen2.5-3b:decode": (
        "benchmark/configs/qwen25-trio-bf16.json", False, 1, 6, 1920),
    "mistral-7b-int8:decode": (
        "benchmark/configs/mistral7b-trio-int8.json", True, 1, 6, 1920),
    "mistral-7b-tp2:decode": (
        "benchmark/configs/mistral7b-trio-bf16-x4.json", False, 2, 8, 1792),
    "mistral-7b-int8:prefill-loop": (
        "benchmark/configs/mistral7b-trio-int8.json", True, 1, 1, 2048),
}
HYBRID_DECODE = "falcon-h1-34b:decode"
ONE_PART_CONFIG = "benchmark/configs/nemotron3-super-ep8-trio-bf16.json"
DELTA_CONFIG = "benchmark/configs/solar-open2-ep8-trio-bf16.json"
ONE_PART_ROWS, ONE_PART_STEPS, ONE_PART_WIDTH = 6, 16, 384
MIXED_WINDOW_CONFIG = "benchmark/configs/trinity-mini-pp8-trio-bf16.json"
MIXED_WINDOW_WIDTHS = (384, 2176)  # a panel phase's bucket; a judge phase's, past the window
SHARDED_ROUTED = "mixtral-8x7b-2-layers-tp2:decode"
ROUTED_CONFIGS = {  # cell -> its configuration file
    "dsv2": LATENT_CONFIG, "nem3": ONE_PART_CONFIG, "solar2": DELTA_CONFIG}
# The four routed cells' judges: cell -> (configuration file, the width of a
# judge prompt's chunks by the rule, utils/flops.py, in a 2,048-slot bucket).
WIDE_PREFILL = {
    "trin": (MIXED_WINDOW_CONFIG, 2048), "solar2": (DELTA_CONFIG, 2048),
    "nem3": (ONE_PART_CONFIG, 2048), "dsv2": (LATENT_CONFIG, 1024)}
TEXT_PINS = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "data", "lowered_text_pins.json")


def _decode_id(preset, int8_kv, batch) -> str:
    return f"decode:{preset}:{'int8kv' if int8_kv else 'bf16'}:B{batch}"


def _cell_id(name, width) -> str:
    return f"decode-cell:{name}:kv{width}"


# -- the child: every compile, one report --------------------------------------


def _compile_all() -> dict:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as err:  # noqa: BLE001 — no libtpu, no topology
        return {"skip": f"cannot describe a v5e topology here: {err}"}
    chip = SingleDeviceSharding(topo.devices[0])

    from llm_consensus_tpu.engine.engine import _decode_chunk, _prefill_step
    from llm_consensus_tpu.models import get_config, init_kv_cache, init_params
    from llm_consensus_tpu.models.transformer import attention_routes
    from llm_consensus_tpu.ops.pallas import (
        decode_attention, decode_flash_supported, flash_attention)

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def shapes(fn):
        return jax.tree.map(
            lambda s: sds(s.shape, s.dtype), jax.eval_shape(fn)
        )

    def has_kernel(lowered, read=None) -> dict:
        """``read``: what else to take from the compiled text, if anything."""
        try:
            text = lowered.compile().as_text()
        except Exception as err:  # noqa: BLE001 — what the chip would raise
            return {"error": f"{type(err).__name__}: {str(err)[:300]}"}
        if read is not None:
            read(text)
        return {"kernel": "tpu_custom_call" in text}

    report: dict = {}
    for preset, int8_kv, batch in DECODE_CASES:
        hq, hkv, dh = WIDTHS[preset]
        codes = (2, batch, MAX_SEQ, hkv, dh)  # two layers: the stack is paged
        kv = sds(codes, jnp.bfloat16)
        if int8_kv:
            kv = {"q8": sds(codes, jnp.int8),
                  "s": sds((2, batch, hkv, MAX_SEQ), jnp.bfloat16)}
        entry = has_kernel(jax.jit(functools.partial(
            decode_attention, kv_width=KV_WIDTH, interpret=False,
        )).lower(
            sds((batch, 1, hq, dh), jnp.bfloat16), kv, kv, sds(()), sds(()),
            sds((batch,)),
        ))
        entry["predicate"] = decode_flash_supported(
            hq, hkv, dh, width=KV_WIDTH, quantized=int8_kv
        )
        report[_decode_id(preset, int8_kv, batch)] = entry
    for name, width in CELL_CASES:
        rows, hq, hkv = CELL_SHAPES[name]
        kv = sds((2, rows, CELL_MAX_SEQ, hkv, 128), jnp.bfloat16)
        entry = has_kernel(jax.jit(functools.partial(
            decode_attention, kv_width=width, interpret=False,
        )).lower(
            sds((rows, 1, hq, 128), jnp.bfloat16), kv, kv, sds(()), sds(()),
            sds((rows,)),
        ))
        entry["predicate"] = decode_flash_supported(hq, hkv, 128, width=width)
        report[_cell_id(name, width)] = entry
    hq, hkv, dh = WIDTHS["llama-3.2-3b"]
    for t in FLASH_T:
        q = sds((1, t, hq, dh), jnp.bfloat16)
        kv = sds((1, t, hkv, dh), jnp.bfloat16)
        report[f"flash:T{t}"] = has_kernel(jax.jit(functools.partial(
            flash_attention, q_offset=0, interpret=False,
        )).lower(q, kv, kv))

    # Whole prefill (T = 512) and decode-chunk (B = 4) programs, the
    # engine's own jitted steps at full width and depth. forward() and
    # the kernels ask jax.default_backend() — the CPU here — so the check
    # is steered; otherwise this would compile the interpreter and prove
    # nothing. (Steered here, in the test, not through a program option.)
    jax.default_backend = lambda: "tpu"
    for preset in STEP_CASES:
        cfg = get_config(preset)
        params = shapes(lambda: init_params(cfg, jax.random.PRNGKey(0)))

        def cache(rows):
            return shapes(lambda: init_kv_cache(
                cfg, batch=rows, max_seq=MAX_SEQ, dtype=jnp.bfloat16
            ))

        attention_routes.reset()
        prefill = has_kernel(_prefill_step.lower(
            params, cfg, sds((1, 512)), sds((1,)), cache(1), attn_impl="flash",
        ))
        decode = has_kernel(_decode_chunk.lower(
            params, cfg, sds((4,)), sds(()), cache(4),
            shapes(lambda: jax.random.PRNGKey(0)), n_steps=16,
            temperature=0.0, top_k=None, top_p=None, row_start=sds((4,)),
            kv_width=KV_WIDTH, attn_impl="flash",
        ))
        report[f"step:{preset}"] = {
            "prefill": prefill, "decode": decode,
            "routes": attention_routes.snapshot(preset),
        }
    report["latent-prefill-loop"] = _latent_prefill_branches(sds, shapes)
    for width in LATENT_DECODE_WIDTHS:
        report[f"latent-decode:kv{width}"] = _latent_decode_chunk(
            sds, shapes, width)
    report["one-part-decode"] = _one_part_decode_chunk(sds, shapes, ONE_PART_CONFIG)
    report["delta-decode"] = _one_part_decode_chunk(sds, shapes, DELTA_CONFIG)
    for width in MIXED_WINDOW_WIDTHS:
        report[f"mixed-window-decode:kv{width}"] = _mixed_window_decode_chunk(
            sds, shapes, width)
    reads: dict = {}
    report["hybrid-ssm"] = _hybrid_ssm_programs(sds, shapes, has_kernel, reads)
    for name in DENSE_PROGRAMS:
        _dense_program(name, topo, has_kernel, reads)
    report["state-step:hybrid"] = reads.pop(  # or what the chip would raise
        "state-step:hybrid", report["hybrid-ssm"]["decode"])
    for cell in ROUTED_CONFIGS:
        report[f"routed-prefill:{cell}"] = _routed_prefill_programs(
            sds, shapes, cell, reads)
    for cell, (config, _) in WIDE_PREFILL.items():
        report[f"wide-prefill:{cell}"] = _wide_prefill_loop(sds, shapes, config)
    report[SHARDED_ROUTED] = _sharded_routed_decode(topo, has_kernel, reads)
    report["moe-pairs:largest"] = _largest_pairs_kernel(sds, has_kernel)
    report["texts"] = {name[len("text:"):]: reads.pop(name)
                       for name in sorted(reads) if name.startswith("text:")}
    report.update({f"dense-proj:{name}": got for name, got in reads.items()})
    return report


def _hybrid_ssm_programs(sds, shapes, has_kernel, reads: dict) -> dict:
    """The Falcon-H1 cell's three hot programs at its own shapes: the judge
    prompt's prefill loop (four 512-token chunks, XLA attention at a traced
    start), a wave of six panel prompts (padded to eight rows of 256, the
    prefill kernel) and a 16-step decode chunk of six rows with the
    sentinel (the decode kernel), each with its per-row state stacks. What
    the decode chunk does to ``wq`` / ``wk`` / ``wv`` goes into ``reads``."""
    import jax
    import jax.numpy as jnp

    from llm_consensus_tpu.engine.engine import (
        _decode_chunk, _prefill_chunks_loop, _prefill_step)
    from llm_consensus_tpu.models import init_kv_cache, init_params

    cfg = _judge(HYBRID_CONFIG)
    params = shapes(lambda: init_params(cfg, jax.random.PRNGKey(0)))

    def cache(rows, slots=CELL_MAX_SEQ):
        return shapes(lambda: init_kv_cache(cfg, rows, slots, jnp.bfloat16))

    def pinned(program: str, lowered):
        reads[f"text:falcon-h1-34b:{program}"] = _digest(lowered)
        return lowered

    return {
        "loop": has_kernel(pinned("prefill-loop", _prefill_chunks_loop.lower(
            params, cfg, sds((4, 1, 512)), sds(()), sds(()), sds((1,)),
            cache(1), max_chunks=4, kv_width=2048))),
        "wave": has_kernel(pinned("wave", _prefill_step.lower(
            params, cfg, sds((8, 256)), sds((8,)), cache(8, 256),
            attn_impl="flash", row_end=sds((8,))))),
        "decode": has_kernel(pinned("decode", _decode_chunk.lower(
            params, cfg, sds((6,)), sds(()), cache(6),
            shapes(lambda: jax.random.PRNGKey(0)), n_steps=16,
            temperature=0.0, top_k=None, top_p=None, row_start=sds((6,)),
            kv_width=384, attn_impl="flash", sentinel=True)),
            read=lambda text: reads.update({
                HYBRID_DECODE: _projection_reads(text, cfg, params),
                "state-step:hybrid": _state_passes(text, cache(6)["ssm"]["state"])})),
    }


def _dense_program(name: str, topo, has_kernel, reads: dict) -> None:
    """One of ``DENSE_PROGRAMS`` compiled for the described chip (a ``tp``
    mesh of two described devices under ``param_specs`` / ``cache_specs``
    where the cell has one), and what it does to ``wq`` / ``wk`` / ``wv``
    into ``reads``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    from llm_consensus_tpu.engine.engine import _decode_chunk, _prefill_chunks_loop
    from llm_consensus_tpu.models import init_kv_cache, init_params
    from llm_consensus_tpu.ops.quant import init_params_quantized
    from llm_consensus_tpu.parallel.mesh import make_mesh
    from llm_consensus_tpu.parallel.sharding import cache_shardings, param_shardings

    config, int8, tp, rows, width = DENSE_PROGRAMS[name]
    cfg = _judge(config)
    mesh = make_mesh({"dp": 1, "tp": tp}, topo.devices[:tp]) if tp > 1 else None
    whole = (NamedSharding(mesh, PartitionSpec()) if mesh
             else SingleDeviceSharding(topo.devices[0]))

    def placed(make, shardings=None):
        tree = jax.eval_shape(make)
        return jax.tree.map(
            lambda s, where: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=where),
            tree, shardings(tree) if shardings else jax.tree.map(lambda _: whole, tree))

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=whole)

    key = jax.random.PRNGKey(0)
    params = placed(
        lambda: (init_params_quantized if int8 else init_params)(cfg, key),
        mesh and (lambda _: param_shardings(cfg, mesh)))
    cache = placed(
        lambda: init_kv_cache(cfg, rows, CELL_MAX_SEQ, jnp.bfloat16),
        mesh and (lambda tree: cache_shardings(cfg, mesh, tree)))
    if name.endswith(":decode"):
        lowered = _decode_chunk.lower(
            params, cfg, ints(rows), ints(), cache, placed(lambda: key),
            n_steps=16, temperature=0.0, top_k=None, top_p=None,
            row_start=ints(rows), kv_width=width, attn_impl="flash", mesh=mesh,
            sentinel=True)
    else:  # the judge prompt: four 512-token chunks of its bucket
        lowered = _prefill_chunks_loop.lower(
            params, cfg, ints(width // 512, 1, 512), ints(), ints(), ints(1),
            cache, max_chunks=width // 512, kv_width=width)
    reads[f"text:{name}"] = _digest(lowered)
    got = has_kernel(lowered, read=lambda text: reads.update({
        name: _projection_reads(text, cfg, params)}))
    reads.setdefault(name, got)  # what the chip would raise, if it would


def _digest(lowered) -> str:
    """Of the text a program lowers to (before the chip's compiler), less
    each kernel's serialized body: that carries the Python frames it was
    traced under, the checkout's own path among them."""
    import hashlib

    text = re.sub(r'(body\\22: \\22)[A-Za-z0-9+/=]+', r"\1", lowered.as_text())
    return hashlib.sha256(text.encode()).hexdigest()


def _experts_path(text: str) -> dict:
    """Which of the two forms a program's routed experts take, counted in
    its text, lowered or compiled: the kernel over the sorted pairs
    (ops/pallas/moe_pairs.py) or the grouped product."""
    return {"kernel": len(re.findall(r"llmc_moe_pairs[.\d]* = ", text))
            or text.count('kernel_name = "llmc_moe_pairs"'),
            "ragged-dot": len(re.findall(r"ragged[-_]dot", text))}


def _routed_prefill_programs(sds, shapes, cell: str, reads: dict) -> dict:
    """A routed cell's two prefill programs at the cell's own sizes, LOWERED
    and not compiled (which form the experts take is in the text before the
    chip's compiler): the judge prompt's loop of four 512-token chunks and a
    wave of six panel prompts padded to eight rows of 256. Their digests go
    into ``reads``."""
    import jax
    import jax.numpy as jnp

    from llm_consensus_tpu.engine.engine import _prefill_chunks_loop, _prefill_step
    from llm_consensus_tpu.models import init_kv_cache, init_params

    cfg = _judge(ROUTED_CONFIGS[cell])
    params = shapes(lambda: init_params(cfg, jax.random.PRNGKey(0)))

    def cache(rows, slots=CELL_MAX_SEQ):
        return shapes(lambda: init_kv_cache(cfg, rows, slots, jnp.bfloat16))

    ends = {"row_end": sds((8,))} if cfg.has_state else {}
    programs = {
        "prefill-loop": _prefill_chunks_loop.lower(
            params, cfg, sds((4, 1, 512)), sds(()), sds(()), sds((1,)),
            cache(1), max_chunks=4, kv_width=2048, moe_stats=True),
        "wave": _prefill_step.lower(
            params, cfg, sds((8, 256)), sds((8,)), cache(8, 256),
            attn_impl="flash", row_start=sds((8,)), kv_width=256,
            moe_stats=True, **ends),
    }
    reads.update({f"text:{cell}:{name}": _digest(lowered)
                  for name, lowered in programs.items()})
    return {name: _experts_path(lowered.as_text())
            for name, lowered in programs.items()}


def _wide_prefill_loop(sds, shapes, config: str) -> dict:
    """A routed cell's judge-prompt loop at the width the rule gives the
    judge on a v5e (utils/flops.py), compiled whole: its width, its chunks
    and the transients the chip's compiler counts for it."""
    import jax
    import jax.numpy as jnp

    from llm_consensus_tpu.engine.engine import _prefill_chunks_loop
    from llm_consensus_tpu.models import init_kv_cache, init_params
    from llm_consensus_tpu.utils import flops

    cfg = _judge(config)
    width = flops.prefill_chunk_width(
        flops.prefill_ridge_width(cfg, "TPU v5 lite"), cfg.n_heads, LATENT_BUCKET)
    chunks = LATENT_BUCKET // width
    try:
        compiled = _prefill_chunks_loop.lower(
            shapes(lambda: init_params(cfg, jax.random.PRNGKey(0))), cfg,
            sds((chunks, 1, width)), sds(()), sds(()), sds((1,)),
            shapes(lambda: init_kv_cache(cfg, 1, CELL_MAX_SEQ, jnp.bfloat16)),
            max_chunks=chunks, kv_width=LATENT_BUCKET, moe_stats=True,
        ).compile()
    except Exception as err:  # noqa: BLE001 — what the chip would raise
        return {"error": f"{type(err).__name__}: {str(err)[:300]}"}
    return {"width": width, "chunks": chunks,
            "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
            "experts-path": _experts_path(compiled.as_text())}


def _largest_pairs_kernel(sds, has_kernel) -> dict:
    """The kernel over the sorted pairs at the largest buffer the switch
    gives it (``PAIRS_KERNEL_MAX`` pairs) and Mixtral's widths, the most
    fast memory it is allowed of the shapes in the presets (90 MB by
    ``moe_pairs.fast_memory_bytes``): compiled; and what the switch says of
    widths twice those, which the chip's compiler refuses (146 MB of 128)."""
    import jax.numpy as jnp

    from llm_consensus_tpu.ops import moe
    from llm_consensus_tpu.ops.pallas.moe_pairs import experts_over_pairs

    def stacks(k, f):
        return [sds((2, 8, *shape), jnp.bfloat16)
                for shape in ((k, f), (k, f), (f, k))]

    pairs = moe.PAIRS_KERNEL_MAX
    got = has_kernel(experts_over_pairs.lower(
        sds((pairs, 4096), jnp.bfloat16), *stacks(4096, 14336), sds(()),
        sds((8,)), "silu", interpret=False))
    return {**got, "served": moe.pairs_kernel_serves(pairs, stacks(4096, 14336)[1]),
            "twice-served": moe.pairs_kernel_serves(pairs, stacks(8192, 28672)[1])}


def _sharded_routed_decode(topo, has_kernel, reads: dict) -> dict:
    """A routed model's 16-step decode chunk (Mixtral-8x7B's widths, two
    layers of its 32 so that two chips hold it; eight rows x 2 choices: 16
    pairs, a buffer the kernel would take on one device) under a tp = 2 mesh
    of two described devices, its expert stacks sharded as
    ``parallel/sharding.py`` shards them: compiled, and which form its
    experts take. Its digest goes into ``reads``."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from llm_consensus_tpu.engine.engine import _decode_chunk
    from llm_consensus_tpu.models import get_config, init_kv_cache, init_params
    from llm_consensus_tpu.parallel.mesh import make_mesh
    from llm_consensus_tpu.parallel.sharding import cache_shardings, param_shardings

    cfg = dataclasses.replace(get_config("mixtral-8x7b"), n_layers=2)
    mesh = make_mesh({"dp": 1, "tp": 2}, topo.devices[:2])
    whole = NamedSharding(mesh, PartitionSpec())

    def placed(make, shardings):
        tree = jax.eval_shape(make)
        return jax.tree.map(
            lambda s, where: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=where),
            tree, shardings(tree))

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=whole)

    key = jax.random.PRNGKey(0)
    lowered = _decode_chunk.lower(
        placed(lambda: init_params(cfg, key), lambda _: param_shardings(cfg, mesh)),
        cfg, ints(8), ints(),
        placed(lambda: init_kv_cache(cfg, 8, CELL_MAX_SEQ, jnp.bfloat16),
               lambda tree: cache_shardings(cfg, mesh, tree)),
        placed(lambda: key, lambda tree: whole), n_steps=16, temperature=0.0,
        top_k=None, top_p=None, row_start=ints(8), kv_width=1792,
        attn_impl="flash", mesh=mesh, sentinel=True, moe_stats=True)
    reads[f"text:{SHARDED_ROUTED}"] = _digest(lowered)
    path: dict = {}
    return {**has_kernel(lowered, read=lambda text: path.update(
        _experts_path(text))), "experts-path": path}


def _projection_reads(text: str, cfg, params) -> dict:
    """What a compiled program does to the dense attention block's input
    projections, by the shape a chip holds of them (int8 leaves: the codes).
    ``entry``: the entry computation's ``copy`` operations that produce an
    array shaped like a whole ``wq`` / ``wk`` / ``wv`` stack, ``leaf{layout}``
    each. ``layer``: the TOP-LEVEL instructions (not inside a fusion) of the
    loop bodies that produce one layer's, or several layers', whole leaf,
    ``leaf:operation{layout}`` each; a prefetch keeps the stored layout
    (``copy-done{2,1,0}``). ``wq`` and ``wo`` are one shape in these models:
    the shape is what is held."""
    stacks: dict = {}  # (type, per-chip dims) -> the leaves so shaped
    for leaf in ("wq", "wk", "wv"):
        held = params["layers"][leaf]
        held = held["q8"] if isinstance(held, dict) else held
        dims = held.sharding.shard_shape(held.shape)
        dtype = {"int8": "s8", "bfloat16": "bf16"}[str(held.dtype)]
        stacks.setdefault((dtype, dims), []).append(leaf)
    assert all(dims[0] == cfg.n_layers for _, dims in stacks)
    entry, layer = [], []
    for is_entry, dtype, dims, layout, op, _ in _top_level(text):
        for (held, stack), leaves in stacks.items():
            if dtype != held:
                continue
            if is_entry and op == "copy" and dims == stack:
                entry.append(f"{'|'.join(leaves)}{layout}")
            elif not is_entry and dims[-2:] == stack[1:] and op not in (
                    "parameter", "get-tuple-element", "bitcast"):
                layer.append(f"{'|'.join(leaves)}:{op}{layout}")
    return {"entry": sorted(entry), "layer": sorted(layer)}


def _computations(text: str) -> dict:
    """``{name: (is the entry, body text)}`` of a compiled module's text."""
    return {
        m.group(2): (bool(m.group(1)), m.group(3)) for m in re.finditer(
            r"^(ENTRY )?%?([\w.\-]+) \(.*?\) -> .*? \{\n(.*?)^\}", text, re.S | re.M)
    }


def _judge(config: str):
    """A cell's judge as its configuration file states it."""
    from benchmark import server

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, config)) as f:
        doc = json.load(f)
    return server.model_config(doc["judge"], doc["models"][doc["judge"]])


_PRODUCED = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = (\w+)\[([\d,]+)\](\{[\d,]*)\S* ([\w\-]+)\((.*)$",
    re.M)


def _top_level(text: str):
    """``(is the entry, type, dims, layout, operation, the line's rest)`` of
    every array a compiled module's entry computation and loop bodies
    produce at their top level (not inside a fusion)."""
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    for name, (is_entry, body) in _computations(text).items():
        if is_entry or name in bodies:
            for dtype, dims, layout, op, rest in _PRODUCED.findall(body):
                yield (is_entry, dtype, tuple(int(d) for d in dims.split(",")),
                       f"{layout}}}", op, rest)


def _leaves_by_shape(tree) -> dict:
    """``{dims as the compiled text prints them: [the leaves so shaped]}``."""
    import jax

    leaves: dict = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        leaves.setdefault(",".join(map(str, leaf.shape)), []).append(
            "/".join(str(getattr(k, "key", k)) for k in path))
    return leaves


def _writes_in_place(text: str) -> set:
    """The computations whose root is a ``dynamic-update-slice``."""
    return {
        name for name, (_, body) in _computations(text).items()
        if re.search(r"^\s*ROOT \S+ = \S+ dynamic-update-slice\(", body, re.M)
    }


def _instructions(body: str):
    """``(name, [(type, dims) of each array it produces], operation, the
    names of its operands, the line)`` of every instruction of a
    computation's text (a tuple's arrays in order)."""
    for line in body.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*)", line)
        if not m:
            continue
        rest, depth, at = m.group(2), 0, 0
        if rest.startswith("("):  # a tuple's type: up to its closing parenthesis
            for at, ch in enumerate(rest):
                depth += (ch == "(") - (ch == ")")
                if depth == 0:
                    break
        at = rest.index(" ", at)
        produced = [(dtype, tuple(int(d) for d in dims.split(",") if d))
                    for dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]", rest[:at])]
        op, _, operands = rest[at + 1:].partition("(")
        yield (m.group(1), produced, op,
               re.findall(r"%([\w.\-]+)", operands.split(")")[0]), line)


def _state_passes(text: str, stack) -> dict:
    """What a compiled decode chunk does to a mixer's state stack ``stack``
    [L, B, H, P, N] float32, of the TOP-LEVEL instructions (not inside a
    fusion) of its loop bodies. ``rows``: the operations that produce one
    layer's rows of state other than in place. ``passes``: the operations
    that take the whole stack as an operand, each a pass over one layer's
    rows: ``custom-call:in-place`` is the step's kernel with the stack
    aliased to its output, ``fusion:in-place`` a fusion whose root is the
    update, ``fusion`` one that only reads."""
    import math

    dims, writes_in_place = tuple(stack.shape), _writes_in_place(text)
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    carried = ("parameter", "get-tuple-element", "bitcast", "tuple", "while")
    rows, passes = [], []
    for name, (_, body) in _computations(text).items():
        if name not in bodies:
            continue
        produced_by = {}
        for result, produced, op, operands, line in _instructions(body):
            produced_by[result] = produced
            if op in carried:
                continue
            in_place = (
                "output_to_operand_aliasing" in line if op == "custom-call"
                else op == "dynamic-update-slice" or (
                    op == "fusion" and re.search(
                        r"calls=%?([\w.\-]+)", line).group(1) in writes_in_place))
            if any(math.prod(d) == math.prod(dims[1:]) and t == "f32"
                   for t, d in produced) and not in_place:
                rows.append(op)
            if any(produced_by.get(o) == [("f32", dims)] for o in operands):
                passes.append(op + ":in-place" * in_place)
    return {"rows": sorted(rows), "passes": sorted(passes)}


def _latent_decode_chunk(sds, shapes, width: int) -> dict:
    """What the DeepSeek-V2 cell's decode chunk at ``width`` slots
    materialises, read off its compiled text. Of the TOP-LEVEL instructions
    (not inside a fusion) of the loop bodies, the step's and the expert
    layers': ``pool`` the operations that produce an array of the pool's
    size other than the in-place write (a ``dynamic-update-slice``, bare or
    as a fusion's root); ``wq_b`` and ``wkv_b`` those that produce a
    layer's whole ``wq_b`` / ``wkv_b``, each with its layout (a prefetch
    keeps the stored one, ``{2,1,0}``). Of the entry computation:
    ``entry_copy_mb`` its copies (bf16), by the leaf of that shape.
    ``scores`` the types of the products shaped rows x heads x slots;
    ``routes`` what ``forward`` booked."""
    import math

    import jax

    from llm_consensus_tpu.engine.engine import _decode_chunk
    from llm_consensus_tpu.models import init_kv_cache, init_params
    from llm_consensus_tpu.models.transformer import attention_routes

    cfg = _judge(LATENT_CONFIG)
    attention_routes.reset()
    params = shapes(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    cache = shapes(lambda: init_kv_cache(cfg, LATENT_ROWS, CELL_MAX_SEQ))
    leaves = _leaves_by_shape({"params": params, "cache": cache})
    try:
        text = _decode_chunk.lower(
            params, cfg, sds((LATENT_ROWS,)), sds(()), cache,
            shapes(lambda: jax.random.PRNGKey(0)), n_steps=LATENT_STEPS,
            temperature=0.0, top_k=None, top_p=None,
            row_start=sds((LATENT_ROWS,)), kv_width=width, attn_impl="flash",
            sentinel=True, moe_stats=True,
        ).compile().as_text()
    except Exception as err:  # noqa: BLE001 — what the chip would raise
        return {"error": f"{type(err).__name__}: {str(err)[:300]}"}
    writes_in_place = _writes_in_place(text)
    sizes = {
        "pool": cfg.n_layers * LATENT_ROWS * CELL_MAX_SEQ * cfg.cache_width,
        "wq_b": cfg.q_lora_rank * cfg.n_heads * (cfg.qk_nope_dim + cfg.qk_rope_dim),
        "wkv_b": cfg.kv_lora_rank * cfg.n_heads * (cfg.qk_nope_dim + cfg.v_head_dim),
        "experts": cfg.n_experts * cfg.d_model * cfg.expert_width,  # a layer's
    }
    report: dict = {what: [] for what in sizes}
    entry_copies: dict = {}
    for is_entry, _, dims, layout, op, rest in _top_level(text):
        count = math.prod(dims)
        if is_entry:
            if op == "copy":
                shape = ",".join(map(str, dims))
                what = "|".join(leaves.get(shape, [shape]))
                entry_copies[what] = entry_copies.get(what, 0) + count * 2 / 1e6
            continue
        if op in ("parameter", "get-tuple-element", "bitcast", "while"):
            continue
        in_place = op == "dynamic-update-slice" or (
            op == "fusion" and re.search(r"calls=%?([\w.\-]+)", rest).group(1)
            in writes_in_place)
        for what, size in sizes.items():
            if count == size and not (what == "pool" and in_place):
                report[what].append(f"{op}{layout}")
    scores = {
        dtype for dtype, dims in re.findall(
            r"= (\w+)\[([\d,]+)\]\S* convolution\(", text)
        if sorted(int(d) for d in dims.split(",") if d != "1")
        == sorted((LATENT_ROWS, cfg.n_heads, width))
    }
    return {
        **{what: sorted(found) for what, found in report.items()},
        "entry_copy_mb": {k: round(v, 1) for k, v in sorted(entry_copies.items())},
        "scores": sorted(scores),
        "routes": attention_routes.snapshot(cfg.name),
        "experts-path": _experts_path(text),
    }


def _one_part_decode_chunk(sds, shapes, config: str) -> dict:
    """What the decode chunk of a cell whose judge is a stack of one-part
    layers (``config``: the Nemotron-H cell's, the Solar-Open2 cell's; six
    rows, 16 steps, the sentinel and the routing sums, at the cell's own
    sizes) materialises, read off its compiled text. Of the TOP-LEVEL instructions (not inside a
    fusion) of the entry computation and of the step's body: ``experts``
    the operations that produce an array of the size of a held expert stack
    (``w_up`` / ``w_down`` [5, 64, 1024, 2688]) or of one layer of it;
    ``state`` those that produce an array of the size of the state stack
    [5, 6, 128, 64, 128] other than the in-place write of one layer's rows
    (a ``dynamic-update-slice``, bare or as a fusion's root; the tail stack,
    1.8 MB, the compiler keeps in its fast memory); ``entry_copy_mb`` the entry's copies by the leaf of that shape.
    ``kernel`` and ``routes``: the decode kernel is there and booked."""
    import math

    import jax
    import jax.numpy as jnp

    from llm_consensus_tpu.engine.engine import _decode_chunk
    from llm_consensus_tpu.models import init_kv_cache, init_params
    from llm_consensus_tpu.models.transformer import attention_routes

    cfg = _judge(config)
    attention_routes.reset()
    params = shapes(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    cache = shapes(lambda: init_kv_cache(
        cfg, ONE_PART_ROWS, CELL_MAX_SEQ, jnp.bfloat16))
    leaves = _leaves_by_shape({"params": params, "cache": cache})
    try:
        text = _decode_chunk.lower(
            params, cfg, sds((ONE_PART_ROWS,)), sds(()), cache,
            shapes(lambda: jax.random.PRNGKey(0)), n_steps=ONE_PART_STEPS,
            temperature=0.0, top_k=None, top_p=None,
            row_start=sds((ONE_PART_ROWS,)), kv_width=ONE_PART_WIDTH,
            attn_impl="flash", sentinel=True, moe_stats=True,
        ).compile().as_text()
    except Exception as err:  # noqa: BLE001 — what the chip would raise
        return {"error": f"{type(err).__name__}: {str(err)[:300]}"}
    writes_in_place = _writes_in_place(text)
    one_expert_layer = cfg.n_experts * (cfg.moe_latent or cfg.d_model) * cfg.expert_width
    state = math.prod(cache["ssm"]["state"].shape)
    sizes = {
        "experts": (one_expert_layer, cfg.n_expert_layers * one_expert_layer),
        "state": (state,),
    }
    report: dict = {what: [] for what in sizes}
    entry_copies: dict = {}
    for is_entry, _, dims, layout, op, rest in _top_level(text):
        count = math.prod(dims)
        if is_entry and op == "copy":
            shape = ",".join(map(str, dims))
            what = "|".join(leaves.get(shape, [shape]))
            entry_copies[what] = entry_copies.get(what, 0) + count * 2 / 1e6
        if op in ("parameter", "get-tuple-element", "bitcast", "while", "tuple"):
            continue
        in_place = op == "dynamic-update-slice" or (
            op == "fusion" and re.search(r"calls=%?([\w.\-]+)", rest).group(1)
            in writes_in_place)
        for what, counts in sizes.items():
            if count in counts and not (what == "state" and in_place):
                report[what].append(f"{op}{layout}")
    return {
        **{what: sorted(found) for what, found in report.items()},
        "entry_copy_mb": {k: round(v, 1) for k, v in sorted(entry_copies.items())},
        "kernel": "tpu_custom_call" in text,
        "routes": attention_routes.snapshot(cfg.name),
        "state-step": _state_passes(text, cache["ssm"]["state"]),
        "experts-path": _experts_path(text),
    }


def _mixed_window_decode_chunk(sds, shapes, width: int) -> dict:
    """The decode chunk of the cell whose judge mixes window and full
    attention layers (Trinity-Mini's cut: four window layers and a full one,
    a dense MLP, four expert layers that hold all 128 experts; six rows, 16
    steps, the sentinel and the routing sums) at a bucket under the window
    and at one past it, compiled: how many decode-attention kernels a step
    holds, which form its experts take, and the route booked."""
    import jax
    import jax.numpy as jnp

    from llm_consensus_tpu.engine.engine import _decode_chunk
    from llm_consensus_tpu.models import init_kv_cache, init_params
    from llm_consensus_tpu.models.transformer import attention_routes

    cfg = _judge(MIXED_WINDOW_CONFIG)
    attention_routes.reset()
    params = shapes(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    cache = shapes(lambda: init_kv_cache(
        cfg, ONE_PART_ROWS, CELL_MAX_SEQ, jnp.bfloat16))
    try:
        text = _decode_chunk.lower(
            params, cfg, sds((ONE_PART_ROWS,)), sds(()), cache,
            shapes(lambda: jax.random.PRNGKey(0)), n_steps=ONE_PART_STEPS,
            temperature=0.0, top_k=None, top_p=None,
            row_start=sds((ONE_PART_ROWS,)), kv_width=width,
            attn_impl="flash", sentinel=True, moe_stats=True,
        ).compile().as_text()
    except Exception as err:  # noqa: BLE001 — what the chip would raise
        return {"error": f"{type(err).__name__}: {str(err)[:300]}"}
    experts = _experts_path(text)
    return {
        "experts-path": experts,
        "attention-kernels": text.count("tpu_custom_call") - experts["kernel"],
        "routes": attention_routes.snapshot(cfg.name),
        "layers": (cfg.n_attn_layers, cfg.n_window_layers, cfg.n_expert_layers),
    }


def _latent_prefill_branches(sds, shapes) -> dict:
    """The widths of the float32 score blocks in each branch of each
    ``conditional`` of the compiled judge-prompt prefill, a list a
    conditional."""
    import jax

    from llm_consensus_tpu.engine.engine import _prefill_chunks_loop
    from llm_consensus_tpu.models import init_kv_cache, init_params

    cfg = _judge(LATENT_CONFIG)
    chunks = LATENT_BUCKET // LATENT_CHUNK
    try:
        text = _prefill_chunks_loop.lower(
            shapes(lambda: init_params(cfg, jax.random.PRNGKey(0))), cfg,
            sds((chunks, 1, LATENT_CHUNK)), sds(()), sds(()), sds((1,)),
            shapes(lambda: init_kv_cache(cfg, 1, CELL_MAX_SEQ)),
            max_chunks=chunks, kv_width=LATENT_BUCKET, moe_stats=True,
        ).compile().as_text()
    except Exception as err:  # noqa: BLE001 — what the chip would raise
        return {"error": f"{type(err).__name__}: {str(err)[:300]}"}
    bodies = {name: body for name, (_, body) in _computations(text).items()}
    scores = re.compile(rf"f32\[1,{cfg.n_heads},{LATENT_CHUNK},(\d+)\]")
    return {"conditionals": [
        [sorted({int(w) for w in scores.findall(bodies[name.strip().lstrip("%")])})
         for name in branches.split(",")]
        for branches in re.findall(
            r" conditional\(.*?branch_computations=\{(.*?)\}", text)
    ]}


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    print("REPORT=" + json.dumps(_compile_all()))
    sys.exit(0)


# -- the tests: one case each, read from the child's report --------------------


@pytest.fixture(scope="module")
def report(tmp_path_factory):
    """The child's report — compiled once per test run. libtpu admits one
    process at a time (/tmp/libtpu_lockfile), so under pytest-xdist the
    workers share one child through a lock and a file in the run's
    common temp directory."""
    import fcntl

    run = os.environ.get("PYTEST_XDIST_TESTRUNUID", f"pid{os.getpid()}")
    shared = tmp_path_factory.getbasetemp().parent / f"tpu-compile-{run}.json"
    with open(f"{shared}.lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        # Another process of this run may hold libtpu for a moment (the
        # CLI case of test_bringup.py probes for a chip): try again.
        for _ in range(6):
            if shared.is_file():
                break
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                env={**os.environ, "JAX_PLATFORMS": "cpu"},
                capture_output=True, text=True, timeout=900,
            )
            lines = [
                ln for ln in proc.stdout.splitlines()
                if ln.startswith("REPORT=")
            ]
            assert proc.returncode == 0 and lines, proc.stderr[-2000:]
            if "libtpu multi-process lockfile" in lines[-1]:
                time.sleep(5)
                continue
            shared.write_text(lines[-1][len("REPORT="):])
        if not shared.is_file():
            pytest.skip("libtpu stayed locked by another process")
        doc = json.loads(shared.read_text())
    if "skip" in doc:
        pytest.skip(doc["skip"])
    return doc


@pytest.mark.parametrize(
    "case",
    [_decode_id(*c) for c in DECODE_CASES] + [_cell_id(*c) for c in CELL_CASES],
)
def test_decode_kernel_compiles(report, case):
    # What the predicate admits, the compiler must take.
    assert report[case] == {"kernel": True, "predicate": True}


@pytest.mark.parametrize("t", FLASH_T)
def test_flash_kernel_compiles(report, t):
    assert report[f"flash:T{t}"] == {"kernel": True}


@pytest.mark.parametrize("preset,decode_path", sorted(STEP_CASES.items()))
def test_step_programs_compile(report, preset, decode_path):
    """Whole prefill and decode programs hold the kernel exactly where
    forward() says it routed them."""
    assert report[f"step:{preset}"] == {
        "prefill": {"kernel": True},
        "decode": {"kernel": decode_path == "pallas"},
        "routes": {"prefill": {"pallas": 1}, "decode": {decode_path: 1}},
    }


def test_latent_prefill_loop_holds_a_branch_a_width(report):
    """The judge prompt's prefill of the DeepSeek-V2 cell, compiled for the
    described chip, holds its attention as a true ``conditional`` in each of
    its four places (the dense layer and the expert layers' scan, in the
    inline first chunk and in the chunk loop), and the four branches score
    512, 1,024, 1,536 and 2,048 slots: not one branch, and not a ``select``
    over the widest. This guards the program's SHAPE; it is no time and says
    nothing about which branch a chunk takes (tests/test_deepseek_v2.py
    does, on the CPU)."""
    assert report["latent-prefill-loop"] == {
        "conditionals": [[[512], [1024], [1536], [2048]]] * 4}


LATENT_DECODE_HOLDS = ("pool", "weights", "entry", "scores-and-route")


@pytest.mark.parametrize("width", LATENT_DECODE_WIDTHS)
@pytest.mark.parametrize("held", LATENT_DECODE_HOLDS)
def test_latent_decode_chunk_reads_its_operands_where_they_lie(report, width, held):
    """The DeepSeek-V2 cell's decode chunk, compiled for the described chip,
    consumes what it is given where it lies (PR 35; the parent's program
    failed the first three at both widths). This guards the program's SHAPE;
    the times are the chip's (PERF.md section 5).

    ``pool``: inside a step nothing produces an array of the pool's size
    but the in-place one-token writes: the dense layer and the expert
    layers' loop keep the pool in ONE layout (the parent: two copies a
    step, 1.49 ms of a 7.9 ms step on the chip).

    ``weights``: no layer's ``wq_b`` gets a pass of its own; what is left
    of that size is the dense layer's prefetch in the stored layout. Of
    ``wkv_b`` one pass an expert layer into the compiler's fast memory stays
    (its two products are batched over heads and the stack is stored
    ``[c, h, d]``): on the chip it streams at the read's own rate (33.6 MB
    in 0.045 ms, the two products after it 0.009 ms), which the issue
    takes as no cost, so that half of the case is dropped.

    ``entry``: nothing of ``wq_b`` is transposed a chunk. NOT the issue's
    16 MB: the pool's device layout as a parameter is slots-minor (576 is
    no multiple of the 128 lanes) while every product wants the latent
    minor, so the pool is relaid once in and once out (2 x 170 MB, 0.07 ms
    a step on the chip), and ``wkv_b``'s stacks are laid head-major for
    the pass above (201 MB); ``wkv_a`` and the router are 576 and 160
    wide. Only another leaf shape removes those (ROADMAP Queue 1 #11).

    ``scores-and-route``: float32 scores, booked as ``xla_latent_absorbed``."""
    got = report[f"latent-decode:kv{width}"]
    if held == "pool":
        assert got["pool"] == []
    elif held == "weights":
        assert set(got["wq_b"]) <= {"copy-done{2,1,0}"}
        assert got["wkv_b"].count("fusion{1,2,0}") <= 1
    elif held == "entry":
        copies = got["entry_copy_mb"]
        assert not [name for name in copies if name.endswith("/wq_b")]
        assert sum(copies.values()) < 600  # the parent: 1,032
    else:
        assert got["scores"] == ["f32"]
        assert got["routes"] == {"decode": {"xla_latent_absorbed": 1}}


ONE_PART_HOLDS = ("experts", "state", "entry", "kernel-and-route")


@pytest.mark.parametrize("held", ONE_PART_HOLDS)
def test_one_part_decode_chunk_reads_its_stacks_where_they_lie(report, held):
    """The Nemotron-H cell's decode chunk (PR 41: eleven one-part layers
    unrolled, each reading its leaves out of its kind's stack at a static
    index), compiled for the described chip at the cell's own sizes. This
    guards the program's SHAPE; the times are the chip's.

    ``experts``: no held expert stack (5 x 64 experts of 1,024 x 2,688: 1.76
    GB a leaf) and no layer of one (0.35 GB) is copied, relaid or sliced out
    a layer a step: the grouped products fetch the experts that were hit
    out of the stacks where they lie (ops/moe.py).

    ``state``: nothing produces an array of the state stack's size (126 MB)
    but the in-place writes of one layer's rows, five a step: no state
    stack is relaid.

    ``entry``: the chunk's entry copies under 64 MB in all (the token and
    key arrays, the keys' and values' one-layer stack).

    ``kernel-and-route``: the one attention layer decodes through the
    kernel, booked as ``pallas``."""
    got = report["one-part-decode"]
    assert "error" not in got, got
    if held == "experts":
        assert got["experts"] == []
    elif held == "state":
        assert got["state"] == []
    elif held == "entry":
        assert sum(got["entry_copy_mb"].values()) < 64
    else:
        assert got["kernel"] and got["routes"] == {"decode": {"pallas": 1}}


DELTA_HOLDS = ("experts", "state", "entry", "kernel-and-route")


@pytest.mark.parametrize("held", DELTA_HOLDS)
def test_delta_decode_chunk_reads_its_stacks_where_they_lie(report, held):
    """The Solar-Open2 cell's decode chunk (PR 44: eight one-part layers
    unrolled: one gated attention layer, three delta-rule layers, four
    expert halves), compiled for the described chip at the cell's own sizes.
    This guards the program's SHAPE; the times are the chip's.

    ``experts``: no held expert stack (4 x 40 experts of 4,096 x 1,280: 1.68
    GB a leaf) and no layer of one (0.42 GB) is copied, relaid or sliced out.

    ``state``: the state stack (3 x 6 rows x 64 heads of 128 x 128 float32,
    75 MB) fits the chip's fast memory, and the compiler stages it there:
    what produces an array of its size is that staging (``copy-done``, a
    ``ConcatBitcast`` of three layers' slices) and the in-place writes of one
    layer's rows; no ``copy``, ``transpose`` or fusion relays it.

    ``entry``: the chunk's entry copies under 64 MB in all.

    ``kernel-and-route``: the one attention layer decodes through the
    kernel, booked as ``pallas``."""
    got = report["delta-decode"]
    assert "error" not in got, got
    if held == "experts":
        assert got["experts"] == []
    elif held == "state":
        assert {op.split("{")[0] for op in got["state"]} <= {"copy-done", "custom-call"}
    elif held == "entry":
        assert sum(got["entry_copy_mb"].values()) < 64
    else:
        assert got["kernel"] and got["routes"] == {"decode": {"pallas": 1}}


def test_hybrid_ssm_programs_compile(report):
    """The Falcon-H1 cell's judge-prompt loop, panel wave and decode chunk
    compile for the described chip at the file's own sizes, the scans in
    plain XLA beside the attention kernels where forward() routes them.
    This guards that the programs EXIST for the chip; it is no time."""
    assert report["hybrid-ssm"] == {
        "loop": {"kernel": False}, "wave": {"kernel": True},
        "decode": {"kernel": True}}


STATE_STEPS = {"hybrid": 1, "one-part": 5}  # cell: mixer layers a loop body


@pytest.mark.parametrize("cell", STATE_STEPS)
def test_a_decoding_rows_state_is_read_once_and_written_once(report, cell):
    """The Falcon-H1 cell's decode chunk (eight scanned layers, a traced
    index) and the Nemotron-H cell's (five unrolled mixers, static indices),
    compiled for the described chip at the cells' own sizes: the state
    stack is an operand of ONE top-level operation a mixer layer, the step's
    kernel with the stack aliased to its output (PR 45,
    ops/pallas/ssm_step.py), and nothing produces one layer's rows of state
    ([6, 32, 128, 256] and [6, 128, 64, 128] float32) beside it. The
    parent's programs held two fusions a layer that each read the rows, one
    to reduce ``y`` and one to write the update: three passes for two. This
    guards the program's SHAPE; the times are the chip's (PERF.md section 5)."""
    got = (report["state-step:hybrid"] if cell == "hybrid"
           else report["one-part-decode"])
    assert "error" not in got, got
    got = got.get("state-step", got)
    assert got == {"rows": [], "passes": ["custom-call:in-place"] * STATE_STEPS[cell]}


# cell's decode chunk -> (its report, the kernel's calls in its text: two an
# expert layer, the layers unrolled or, in the latent cell, one scanned body)
ROUTED_DECODE = {
    "dsv2-kv384": ("latent-decode:kv384", 2),
    "dsv2-kv2048": ("latent-decode:kv2048", 2),
    "nem3": ("one-part-decode", 10),
    "solar2": ("delta-decode", 8),
}


@pytest.mark.parametrize("cell", ROUTED_DECODE)
def test_a_routed_decode_chunk_runs_its_experts_in_the_kernel(report, cell):
    """The three routed cells' decode chunks (six rows x 6 / 22 / 8 choices:
    a buffer of 48 / 144 / 48 pairs), compiled for the described chip at the
    cells' own sizes: the experts' products are the kernel over the sorted
    pairs (PR 46, ops/pallas/moe_pairs.py: the first pass and the second, a
    layer), no ``ragged-dot`` is left, and nothing produces an array of the
    size of a layer's slice of an expert stack: the kernel reads the stacks
    where they lie. This guards the program's SHAPE; the times are the
    chip's (PERF.md section 5)."""
    name, calls = ROUTED_DECODE[cell]
    got = report[name]
    assert "error" not in got, got
    assert got["experts-path"] == {"kernel": calls, "ragged-dot": 0}
    assert got["experts"] == []


@pytest.mark.parametrize("width", MIXED_WINDOW_WIDTHS)
def test_a_mixed_window_decode_chunk_holds_a_kernel_a_layer(report, width):
    """The Trinity-Mini cell's decode chunk (PR 48), compiled for the
    described chip at the cell's own sizes, under the window's width and
    past it: one decode-attention kernel an attention layer of either kind
    (each under ITS kind's sweep plan, made once a step), the four expert
    layers' products in the kernel over the sorted pairs (48 pairs: two
    calls a layer), no ``ragged-dot``; the route booked is the kernel's."""
    got = report[f"mixed-window-decode:kv{width}"]
    assert "error" not in got, got
    assert got["layers"] == [5, 4, 4]
    assert got["attention-kernels"] == 5
    assert got["experts-path"] == {"kernel": 8, "ragged-dot": 0}
    assert got["routes"] == {"decode": {"pallas": 1}}


@pytest.mark.parametrize("program", ["prefill-loop", "wave"])
@pytest.mark.parametrize("cell", ROUTED_CONFIGS)
def test_a_routed_prefill_keeps_the_grouped_product(report, cell, program):
    """A judge prompt's chunk (512 tokens x 6 / 22 / 8 choices) and a panel
    wave (eight rows of 256) are thousands of pairs: the switch
    (ops/moe.py ``pairs_kernel_serves``) leaves them ``ragged_dot``."""
    got = report[f"routed-prefill:{cell}"][program]
    assert got["kernel"] == 0 and got["ragged-dot"] > 0


@pytest.mark.parametrize("cell", WIDE_PREFILL)
def test_a_routed_judges_prompt_at_the_models_width_compiles(report, cell):
    """PR 49: a judge prompt of a routed cell is one chunk of 2,048 tokens
    (two of 1,024 where 128 heads' scores meet the cap), the whole loop
    compiles for the described chip, its experts stay grouped products
    (16-45k pairs a chunk), and what it asks beside its operands stays
    within twice the score cap (the scores, a layer's gathered rows and the
    experts' intermediate; 0.77-1.49 GB here against 0.17-0.93 at 512)."""
    from llm_consensus_tpu.utils.flops import PREFILL_SCORE_BYTES

    got = report[f"wide-prefill:{cell}"]
    assert "error" not in got, got
    assert (got["width"], got["chunks"]) == (
        WIDE_PREFILL[cell][1], 2048 // WIDE_PREFILL[cell][1])
    assert got["experts-path"]["kernel"] == 0 < got["experts-path"]["ragged-dot"]
    assert got["temp_bytes"] <= 2 * PREFILL_SCORE_BYTES


def test_the_largest_buffer_the_switch_gives_the_kernel_compiles(report):
    """The switch asks the shapes for the kernel's fast memory
    (``moe_pairs.fits_fast_memory``): what it admits at the largest pairs
    buffer compiles for the described chip, and widths that would not are
    left to ``ragged_dot``."""
    got = report["moe-pairs:largest"]
    assert got == {"kernel": True, "served": True, "twice-served": False}


def test_sharded_expert_stacks_keep_the_grouped_product(report):
    """Under a mesh of more than one device the expert stacks are sharded
    (w_gate / w_up ``P(None, ep, None, tp)``) and the chip's compiler
    partitions no kernel: the switch sees the mesh (``forward`` hands it
    down) and the chunk compiles with ``ragged_dot``, as at PR 46's parent.
    The attention kernels are there all the same (``shard_map`` over tp)."""
    got = report[SHARDED_ROUTED]
    assert "error" not in got, got
    assert got["kernel"]  # the decode attention kernel, under shard_map
    assert got["experts-path"]["kernel"] == 0
    assert got["experts-path"]["ragged-dot"] > 0


PARENTS_TEXTS = [
    *(f"{cell}:{program}" for cell in ROUTED_CONFIGS
      for program in ("prefill-loop", "wave")),
    *DENSE_PROGRAMS,
    *(f"falcon-h1-34b:{program}" for program in ("prefill-loop", "wave", "decode")),
    SHARDED_ROUTED,
]


@pytest.mark.parametrize("program", PARENTS_TEXTS)
def test_a_program_the_kernel_is_not_in_lowers_to_the_parents_text(report, program):
    """Every program of the dense, tp = 2 and hybrid configurations, and the
    routed cells' two prefill programs, at the cells' own sizes: the text
    each lowers to is PR 46's parent's, byte for byte (its digest, written
    from a checkout of that commit by this file run as a script there:
    the report's ``texts``). Re-pin only in a PR that means to change
    these programs."""
    with open(TEXT_PINS) as f:
        pins = json.load(f)
    assert report["texts"][program] == pins[f"cell:{program}"]


DENSE_PROJECTION_HOLDS = ("entry", "layer")
PREFETCHES = ("copy-done", "slice-done")  # asynchronous, in the stored layout


@pytest.mark.parametrize("held", DENSE_PROJECTION_HOLDS)
@pytest.mark.parametrize("program", [*DENSE_PROGRAMS, HYBRID_DECODE])
def test_dense_programs_read_wq_wk_wv_where_they_lie(report, program, held):
    """Every dense cell's judge, compiled for the described chip at the
    cell's own shapes, consumes its attention input projections where they
    lie (PR 38: the ``optimization_barrier`` in ``_layer`` between the three
    products and their split into heads; the parent's programs failed every
    case). This guards the programs' SHAPE; the times are the chip's
    (PERF.md section 5).

    ``entry``: no ``wq`` / ``wk`` / ``wv`` stack is copied into another
    layout once a program (the parent: 805 MB a decode chunk of the int8 7B,
    378 MB of the 3B, 294 MB of the hybrid).

    ``layer``: inside the loops nothing produces a layer's whole ``wq``,
    ``wk`` or ``wv`` (the parent: a ``fusion{1,2,0}`` each, a pass into the
    compiler's fast memory that ``attn.proj`` then read a second time; the
    hybrid's also a whole stack and four two-layer slices) but, at most,
    a prefetch in the stored layout."""
    got = report[f"dense-proj:{program}"]
    assert "error" not in got, got
    if held == "entry":
        assert got["entry"] == []
    else:
        stored = [f"{op}{{2,1,0}}" for op in PREFETCHES]
        assert [p for p in got["layer"] if p.split(":")[1] not in stored] == []
