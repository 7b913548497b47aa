"""Bring-up contract (PR 21): the program says where it ran, refuses to
run a ``tpu:`` model on a CPU nobody asked for, keeps its compile cache
where it is told (or at one fixed path in the checkout), and its
launchers leave the chip to their children.

All CPU-only. The subprocess cases need a fresh interpreter: backend
choice, cache placement and "did the parent import jax" are process-wide
facts.
"""

import json
import os
import subprocess
import sys
import warnings
from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest

from llm_consensus_tpu.models import forward, get_config, init_kv_cache, init_params
from llm_consensus_tpu.models.transformer import attention_routes
from llm_consensus_tpu.ops.pallas import decode_attention, decode_flash_supported
from llm_consensus_tpu.ops.pallas.decode_attention import (
    _choose_blocks, _legal_block_ks)
from llm_consensus_tpu.providers.tpu import DEFAULT_XLA_CACHE_DIR, TPUProvider
from llm_consensus_tpu.utils import flops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_argv, env_update=None, env_remove=(), timeout=600, cwd=REPO):
    env = {k: v for k, v in os.environ.items() if k not in env_remove}
    env.update(env_update or {})
    argv = (
        [sys.executable, "-c", code_or_argv]
        if isinstance(code_or_argv, str) else [sys.executable, *code_or_argv]
    )
    return subprocess.run(
        argv, cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout,
    )


# -- compile cache placement ---------------------------------------------------

_PLAN_AND_PRINT = (
    "import jax\n"
    "from llm_consensus_tpu.providers.tpu import TPUProvider\n"
    "TPUProvider().prepare(['tpu:tiny-llama'], None)\n"
    "print('CACHE_DIR=' + str(jax.config.jax_compilation_cache_dir))\n"
)


def _cache_dir_of(proc) -> str:
    assert proc.returncode == 0, proc.stderr[-2000:]
    return [
        ln for ln in proc.stdout.splitlines() if ln.startswith("CACHE_DIR=")
    ][-1][len("CACHE_DIR="):]


def test_default_cache_is_one_fixed_path_in_the_checkout():
    """No cache placed from outside: two processes use the SAME directory
    (the path is part of every cache key), and it lives in the checkout —
    not under a home, a temp dir, a uid or a pid."""
    dirs = {
        _cache_dir_of(_run(
            _PLAN_AND_PRINT, env_remove=("JAX_COMPILATION_CACHE_DIR",)
        ))
        for _ in range(2)
    }
    assert dirs == {os.path.join(REPO, ".cache", "xla")}
    assert DEFAULT_XLA_CACHE_DIR == os.path.join(REPO, ".cache", "xla")


def test_cache_placed_from_outside_is_left_alone(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the program sets nothing, and what
    it compiles lands there."""
    outside = tmp_path / "placed" / "xla"
    code = _PLAN_AND_PRINT + (
        "from llm_consensus_tpu.providers.base import Request\n"
        "from llm_consensus_tpu.providers.tpu import TPUProvider as P\n"
        "from llm_consensus_tpu.utils.context import Context\n"
        "p = P(max_seq=64)\n"
        "r = p.query(Context.background(), Request(model='tpu:tiny-llama', "
        "prompt='hi', max_tokens=2))\n"
        "assert r.content\n"
        "print('CACHE_DIR=' + str(jax.config.jax_compilation_cache_dir))\n"
    )
    proc = _run(code, env_update={
        "JAX_COMPILATION_CACHE_DIR": str(outside),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
    })
    assert _cache_dir_of(proc) == str(outside)
    assert os.listdir(outside), "nothing was cached where the cache was placed"


def test_multichip_placement_on_a_tpu_runs_without_the_persistent_cache(
        monkeypatch):
    """Executables of a tp=2 engine loaded from the persistent cache
    halted their slice on the chip (PR 21, 4 of 4 warm starts): a process
    that places a model across TPU chips switches the cache off. One
    chip, or the CPU backend, keeps it."""
    from jax.experimental.compilation_cache import compilation_cache

    from llm_consensus_tpu.providers.tpu import (
        _keep_multichip_programs_out_of_the_cache as guard)

    # The switch itself is recorded, not thrown: this process shares its
    # compilation cache with every other test.
    resets = []
    monkeypatch.setattr(
        compilation_cache, "reset_cache", lambda: resets.append(1)
    )
    assert jax.config.jax_enable_compilation_cache
    guard(2)  # CPU backend: untouched
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    guard(1)  # one chip: untouched
    assert jax.config.jax_enable_compilation_cache and not resets
    try:
        guard(2)
        assert not jax.config.jax_enable_compilation_cache and resets == [1]
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


def test_no_cache_knob_of_our_own_remains():
    from llm_consensus_tpu.utils import knobs

    assert not [k for k in knobs.REGISTRY if "CACHE" in k and "XLA" in k]


# -- no hidden CPU -------------------------------------------------------------


class _implicit_backend:
    """Make the live CPU backend look like one JAX fell back to: nobody
    named it in ``jax_platforms``."""

    def __enter__(self):
        self._was = jax.config.jax_platforms
        jax.config.update("jax_platforms", None)

    def __exit__(self, *exc):
        jax.config.update("jax_platforms", self._was)


def test_provider_refuses_an_implicit_cpu_backend():
    with _implicit_backend():
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
            TPUProvider().prepare(["tpu:tiny-llama"], None)
        with pytest.raises(RuntimeError, match="fell back to 'cpu'"):
            TPUProvider()._build_engine("tiny-llama")


def test_provider_accepts_an_explicit_cpu_backend():
    assert "cpu" in jax.config.jax_platforms  # conftest asked for it by name
    provider = TPUProvider(max_seq=64)
    provider.prepare(["tpu:tiny-llama"], None)
    stats = provider.device_stats()
    assert stats["platform"] == "cpu" and stats["count"] == len(jax.devices())
    assert stats["peak_flops"] is None  # a CPU has no published peaks
    provider.release()


def test_cli_fails_at_start_without_a_chip_or_a_named_cpu():
    """End to end: with no platform named, JAX finds no chip here and
    falls back to the CPU — the CLI must exit non-zero and say how to ask
    for the CPU, not answer from it."""
    proc = _run(
        ["-m", "llm_consensus_tpu", "--models", "tpu:tiny-llama",
         "--judge", "tpu:tiny-llama", "--json", "--no-save", "hello"],
        env_remove=("JAX_PLATFORMS",),
    )
    if "needs a TPU" not in proc.stderr and proc.returncode == 0:
        pytest.skip("JAX found an accelerator without being told to")
    assert proc.returncode != 0
    assert "JAX_PLATFORMS=cpu" in proc.stderr
    assert not proc.stdout.strip()


def test_pallas_kernels_interpret_only_on_a_named_cpu():
    q = jnp.zeros((1, 1, 2, 128), jnp.float32)
    kv = jnp.zeros((1, 1, 8, 2, 128), jnp.float32)
    args = (q, kv, kv, jnp.int32(0))
    assert decode_attention(*args).shape == q.shape  # named CPU: interpreted
    with _implicit_backend():
        with pytest.raises(RuntimeError, match="Pallas TPU kernel needs a TPU"):
            decode_attention(*args)


def test_unknown_tpu_kind_is_an_error_not_a_default():
    for lookup in (flops.device_peak_flops, flops.device_peak_hbm_bw,
                   flops.device_peak_int8_ops):
        assert lookup("cpu") is None
        with pytest.raises(flops.UnknownDeviceError, match="TPU v9x"):
            lookup("TPU v9x")
    with pytest.raises(flops.UnknownDeviceError):
        flops.decode_mfu(get_config("tiny-llama"), 100.0, "TPU v9x")


def test_one_chip_plan_does_not_warn_about_sharing():
    """Three models on the only chip is the one-chip deployment, not a
    planning accident: no RuntimeWarning (chip_smoke.py fails on one)."""
    from llm_consensus_tpu.parallel.mesh import plan_panel

    cfg = get_config("tiny-llama")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        plan = plan_panel(
            [("a", cfg), ("b", cfg)], ("j", cfg), devices=jax.devices()[:1]
        )
    assert [p.n_devices for p in plan.placements] == [1, 1, 1]


# -- attention: routing decided by forward(), reported, never swallowed --------


@pytest.mark.parametrize("batch", [1, 8, 16, 32])
@pytest.mark.parametrize("width", [128, 256, 384, 640, 1024, 2048])
def test_int8_kv_blocks_tile_the_scale_operand(batch, width):
    """gemma-7b widths (Hkv 16, dh 256) with int8 KV: the chooser used to
    pick a 32-slot kv block at B >= 8 — legal for the codes, refused by
    Mosaic for the seq-minor scale block, whose LANES are block_k."""
    assert decode_flash_supported(16, 16, 256, width=width, quantized=True)
    b_block, block_k = _choose_blocks(batch, width, 16, 256, 1, True)
    assert block_k % 128 == 0 and width % block_k == 0 and batch % b_block == 0


def test_predicate_is_the_block_chooser():
    # A span with no legal int8 block: 96 = 3·32 has no 128-multiple
    # divisor and is not one whole pow2 block.
    assert _legal_block_ks(96, quantized=True) == []
    assert not decode_flash_supported(16, 8, 128, width=96, quantized=True)
    assert _legal_block_ks(96, quantized=False) == [32, 16, 8]
    assert _legal_block_ks(64, quantized=True) == [64]  # one whole block
    # Nothing fits VMEM even at one row: routed to XLA, not to the guard.
    assert not decode_flash_supported(128, 128, 1024, width=2048)


def test_forward_books_the_attention_path_it_traced():
    cfg = replace(get_config("tiny-llama", head_dim=128), name="routes-probe")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    cache = init_kv_cache(cfg, batch=1, max_seq=128, dtype=jnp.float32)
    attention_routes.reset()
    step = jax.jit(
        lambda tok, pos, cache, impl: forward(
            params, cfg, tok, cache, start_pos=pos, attn_impl=impl
        ),
        static_argnames=("impl",),
    )
    tok = jnp.zeros((1, 1), jnp.int32)
    for _ in range(3):  # three calls, ONE traced program
        _, cache = step(tok, jnp.int32(0), cache, impl="flash")
    step(tok, jnp.int32(0), cache, impl="xla")
    forward(params, cfg, jnp.zeros((1, 16), jnp.int32), cache,
            start_pos=0, attn_impl="flash")
    assert attention_routes.snapshot("routes-probe") == {
        "decode": {"pallas": 1, "xla": 1}, "prefill": {"pallas": 1},
    }
    # dh = 32 (the real tiny-llama): the predicate routes decode to XLA.
    small = replace(get_config("tiny-llama"), name="routes-probe-dh32")
    forward(init_params(small, jax.random.PRNGKey(0), dtype=jnp.float32),
            small, tok, init_kv_cache(small, 1, 64, jnp.float32),
            start_pos=jnp.int32(0), attn_impl="flash")
    assert attention_routes.snapshot("routes-probe-dh32") == {
        "decode": {"xla": 1},
    }


# -- launchers stay off the chip -----------------------------------------------

_BENCH_PARENT = r"""
import json, sys
sys.argv = ["bench.py"]
import bench
bench.REPO = {tmp!r}
calls = []
def fake(argv, timeout=900, env=None):
    assert "jax" not in sys.modules, "launcher imported jax before " + str(argv)
    calls.append(argv[1])
    if argv[1] == {fail!r}:
        raise RuntimeError("phase blew up")
    if argv[1] == "headline":
        return {{"value": 10.0, "platform": "cpu", "device": "cpu"}}
    return {{argv[1].replace("-", "_") + "_ran": 1}}
bench._run_phase_subprocess = fake
rc = bench.main()
assert "jax" not in sys.modules, "launcher imported jax"
print("RESULT=" + json.dumps({{"rc": rc, "calls": calls}}))
"""


@pytest.mark.parametrize("fail", ["", "pressure"])
def test_bench_launcher_never_imports_jax_and_fails_when_a_phase_raised(
        tmp_path, fail):
    proc = _run(_BENCH_PARENT.format(tmp=str(tmp_path), fail=fail))
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.splitlines()
    result = json.loads(
        [ln for ln in lines if ln.startswith("RESULT=")][-1][len("RESULT="):]
    )
    assert result["calls"][0] == "headline"  # the platform comes from a child
    assert {"pressure", "elastic", "integrity"} <= set(result["calls"])
    assert result["rc"] == (1 if fail else 0)
    record = json.loads((tmp_path / "BENCH_DETAIL.json").read_text())
    assert record["elastic_ran"] == 1  # later phases still ran and recorded
    assert ("pressure_error" in record) == bool(fail)


# -- chip_smoke.py, rehearsed on the CPU ---------------------------------------


def _json_lines(stdout: str) -> list:
    return [json.loads(ln) for ln in stdout.splitlines() if ln.startswith("{")]


def test_chip_smoke_rehearsal_walks_every_phase_then_refuses_the_cpu(tmp_path):
    proc = _run(
        ["chip_smoke.py", "--models", "tpu:tiny-llama,tpu:tiny-qwen2",
         "--judge", "tpu:tiny-llama"],
        env_update={
            "JAX_PLATFORMS": "cpu",
            "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla"),
            # tiny programs compile in milliseconds; cache them anyway so
            # the second process has something to find
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        },
        env_remove=("XLA_FLAGS",),
    )
    docs = _json_lines(proc.stdout)
    assert proc.returncode != 0
    assert docs[-1] == {
        "ok": False, "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    serve, cli, failed = docs[0], docs[1], docs[2]
    assert serve["phase"] == "serve" and cli["phase"] == "cli"
    # The ONLY thing wrong with this run is where it ran.
    assert failed == {"failures": ["platform is 'cpu', not 'tpu'"]}
    for label in ("cold_json_s", "concurrent_json_s", "concurrent_sse_s",
                  "warm_json_s"):
        assert serve[label] > 0
    assert serve["tokens_out"] == 4 * 2 * 64  # 4 requests × 2 models × 64
    assert serve["integrity"]["checks"]["logits"] > 0
    assert serve["attention"]["tiny-llama"]["fallbacks"] == 0
    assert set(serve["attention"]["tiny-llama"]["paths"]) == {"prefill", "decode"}
    cache = serve["compile_cache"]
    assert cache["dir"] == str(tmp_path / "xla")
    assert cache["entries_after"] > cache["entries_before"] == 0
    assert cli["compile_cache"]["hits_written_by_serve"] > 0


def test_chip_smoke_four_chip_rehearsal_compares_the_two_placements():
    proc = _run(
        ["chip_smoke.py", "--chips", "4",
         "--models", "tpu:tiny-llama,tpu:tiny-qwen2,tpu:tiny-gemma",
         "--judge", "tpu:tiny-gemma"],
        env_update={
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        },
    )
    docs = _json_lines(proc.stdout)
    assert proc.returncode != 0
    assert docs[-1]["ok"] is False and docs[-1]["device"]["count"] == 4
    planned, single, compare = docs[0], docs[1], docs[2]
    assert {p: v["devices"] for p, v in planned["placements"].items()} == {
        "tiny-llama": [0], "tiny-qwen2": [1], "tiny-gemma": [2, 3],
    }
    assert planned["placements"]["tiny-gemma"]["tp"] == 2
    assert planned["judge_decode_step"]["all_reduce"] > 0
    assert {tuple(v["devices"]) for v in single["placements"].values()} == {(0,)}
    assert compare["same_tp_models"] == ["tiny-llama", "tiny-qwen2"]
    assert all(compare["greedy_text_identical"].values())
    assert compare["judge_logits"]["max_abs_diff"] <= compare["judge_logits"]["tolerance"]
    # Every failure is the CPU's: no kernel, no device memory, wrong platform.
    for msg in compare["failures"]:
        assert ("platform is 'cpu'" in msg or "lost the kernel" in msg
                or "holds almost nothing" in msg), msg
