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
stay a ``conditional`` with a branch a width.

All cases compile in ONE child process (this file run as a script) and
the tests read its report: loading libtpu and switching the persistent
compilation cache off (an entry written for a described device cannot be
read back without one) stay out of the process the other 900 tests share.
Skipped where the topology cannot be described (no libtpu).
"""

import functools
import json
import os
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
HYBRID_CONFIG = "benchmark/configs/falcon-h1-34b-pp8-trio-bf16.json"


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

    def has_kernel(lowered) -> dict:
        try:
            return {"kernel": "tpu_custom_call" in lowered.compile().as_text()}
        except Exception as err:  # noqa: BLE001 — what the chip would raise
            return {"error": f"{type(err).__name__}: {str(err)[:300]}"}

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
    report["hybrid-ssm"] = _hybrid_ssm_programs(sds, shapes, has_kernel)
    return report


def _hybrid_ssm_programs(sds, shapes, has_kernel) -> dict:
    """The Falcon-H1 cell's three hot programs at its own shapes: the judge
    prompt's prefill loop (four 512-token chunks, XLA attention at a traced
    start), a wave of six panel prompts (padded to eight rows of 256, the
    prefill kernel) and a 16-step decode chunk of six rows with the
    sentinel (the decode kernel), each with its per-row state stacks."""
    import jax
    import jax.numpy as jnp

    from benchmark import server
    from llm_consensus_tpu.engine.engine import (
        _decode_chunk, _prefill_chunks_loop, _prefill_step)
    from llm_consensus_tpu.models import init_kv_cache, init_params

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, HYBRID_CONFIG)) as f:
        doc = json.load(f)
    judge = doc["judge"]
    cfg = server.model_config(judge, doc["models"][judge])
    params = shapes(lambda: init_params(cfg, jax.random.PRNGKey(0)))

    def cache(rows, slots=CELL_MAX_SEQ):
        return shapes(lambda: init_kv_cache(cfg, rows, slots, jnp.bfloat16))

    return {
        "loop": has_kernel(_prefill_chunks_loop.lower(
            params, cfg, sds((4, 1, 512)), sds(()), sds(()), sds((1,)),
            cache(1), max_chunks=4, kv_width=2048)),
        "wave": has_kernel(_prefill_step.lower(
            params, cfg, sds((8, 256)), sds((8,)), cache(8, 256),
            attn_impl="flash", row_end=sds((8,)))),
        "decode": has_kernel(_decode_chunk.lower(
            params, cfg, sds((6,)), sds(()), cache(6),
            shapes(lambda: jax.random.PRNGKey(0)), n_steps=16,
            temperature=0.0, top_k=None, top_p=None, row_start=sds((6,)),
            kv_width=384, attn_impl="flash", sentinel=True)),
    }


def _latent_prefill_branches(sds, shapes) -> dict:
    """The widths of the float32 score blocks in each branch of each
    ``conditional`` of the compiled judge-prompt prefill, a list a
    conditional."""
    import re

    import jax

    from benchmark import server
    from llm_consensus_tpu.engine.engine import _prefill_chunks_loop
    from llm_consensus_tpu.models import init_kv_cache, init_params

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, LATENT_CONFIG)) as f:
        doc = json.load(f)
    judge = doc["judge"]
    cfg = server.model_config(judge, doc["models"][judge])
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
    bodies = {
        m.group(1): m.group(2) for m in re.finditer(
            r"^%?([\w.\-]+) \(.*?\) -> .*? \{\n(.*?)^\}", text, re.S | re.M)
    }
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


def test_hybrid_ssm_programs_compile(report):
    """The Falcon-H1 cell's judge-prompt loop, panel wave and decode chunk
    compile for the described chip at the file's own sizes, the scans in
    plain XLA beside the attention kernels where forward() routes them.
    This guards that the programs EXIST for the chip; it is no time."""
    assert report["hybrid-ssm"] == {
        "loop": {"kernel": False}, "wave": {"kernel": True},
        "decode": {"kernel": True}}

