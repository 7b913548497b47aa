"""Fused decode-attention kernel vs the XLA reference path.

Round 1 shipped this kernel with a Mosaic-invalid K/V BlockSpec that only
surfaced on real TPU (interpret mode executes the kernel program without
the tiling checks), taking down the whole bench. This file closes both
gaps the advisor flagged:

  * interpret-mode parity at production head_dim=128 — covering row_start,
    sliding_window, logit_softcap, non-block-multiple widths, and pos=0 —
    against the exact mask semantics transformer.forward builds for the
    XLA decode path;
  * cross-platform **TPU lowering** smoke tests: ``jax.export`` with
    ``platforms=["tpu"]`` runs the Mosaic lowering (including BlockSpec
    tiling validation) on the CPU test mesh, so a kernel that cannot
    compile for TPU fails CI instead of failing the fleet.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from llm_consensus_tpu.ops.attention import attention, make_attention_mask
from llm_consensus_tpu.ops.pallas import decode_attention, decode_flash_supported


def _reference(q, k, v, pos, row_start=None, sliding_window=None,
               logit_softcap=None):
    """The XLA decode path: attention() under the T=1 cache mask that
    transformer.forward builds (row-relative positions, kv_valid frontier)."""
    b = q.shape[0]
    s = k.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    rs = jnp.zeros((b,), jnp.int32) if row_start is None else row_start
    q_pos = jnp.broadcast_to(pos[None, None], (b, 1)) - rs[:, None]
    kv_slots = jnp.arange(s, dtype=jnp.int32)[None, :]
    kv_valid = jnp.broadcast_to(kv_slots < pos + 1, (b, s))
    kv_valid = jnp.logical_and(kv_valid, kv_slots >= rs[:, None])
    kv_pos = jnp.broadcast_to(kv_slots, (b, s)) - rs[:, None]
    mask = make_attention_mask(q_pos, kv_pos, kv_valid, sliding_window)
    return attention(q, k, v, mask, logit_softcap=logit_softcap)


def _qkv(key, b, w, hq, hkv, dh, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    return (
        jax.random.normal(kq, (b, 1, hq, dh), dtype),
        jax.random.normal(kk, (b, w, hkv, dh), dtype),
        jax.random.normal(kv, (b, w, hkv, dh), dtype),
    )


def _stack(x):
    """Per-layer entry -> 1-layer stacked cache (the kernel's new operand
    form: [L, B, S, Hkv, dh] with layer selection via scalar prefetch)."""
    import jax as _jax
    return _jax.tree.map(lambda a: a[None], x)


CASES = [
    # (b, w, hq, hkv, pos, window, softcap, row_start)
    (1, 512, 8, 8, 300, None, None, None),    # MHA, mid-cache frontier
    (2, 512, 16, 8, 511, None, None, None),   # GQA g=2, full width
    (2, 300, 8, 2, 150, None, None, (0, 37)), # non-block-multiple width + pads
    (1, 512, 8, 1, 0, None, None, None),      # MQA, pos=0 (first decode step)
    (2, 512, 8, 8, 400, 128, 50.0, None),     # sliding window + softcap
    (4, 96, 8, 4, 95, None, None, (3, 0, 10, 90)),  # small ragged batch
    (1, 24, 4, 2, 20, 8, None, None),         # width below one kv block
]


@pytest.mark.parametrize("case", CASES)
def test_decode_matches_xla_reference_f32(case):
    b, w, hq, hkv, pos, window, cap, rs = case
    dh = 128  # production head_dim — the size the kernel auto-enables for
    q, k, v = _qkv(jax.random.PRNGKey(0), b, w, hq, hkv, dh)
    row_start = None if rs is None else jnp.asarray(rs, jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = decode_attention(
            q, _stack(k), _stack(v), jnp.int32(pos), 0, row_start,
            sliding_window=window, logit_softcap=cap, interpret=True,
        )
        want = _reference(q, k, v, pos, row_start, window, cap)
    assert got.shape == want.shape
    assert jnp.allclose(got, want, atol=1e-5, rtol=1e-5), (
        float(jnp.abs(got - want).max())
    )


# A pool row without a stream carries a row_start past every frontier
# (engine/batcher.py DEAD_ROW): no valid slot, so the sweep plan neither
# fetches nor computes it and its output is zeros.
DEAD = 1 << 30

SWEEP_CASES = [
    # (id, b, w, hq, hkv, pos, window, row_start, int8_kv)
    ("1of6-perhead", 6, 1920, 16, 2, 1850, None,
     (DEAD, DEAD, 96, DEAD, DEAD, DEAD), False),
    ("1of6-first", 6, 1920, 16, 2, 1850, None,
     (300, DEAD, DEAD, DEAD, DEAD, DEAD), False),
    ("1of6-last", 6, 384, 16, 2, 200, None,
     (DEAD, DEAD, DEAD, DEAD, DEAD, 17), False),
    ("1of8-qstruct", 8, 1920, 16, 4, 1800, None,
     (DEAD, DEAD, DEAD, 64, DEAD, DEAD, DEAD, DEAD), False),
    ("2of8-qstruct-between", 8, 640, 16, 4, 600, None,
     (DEAD, 10, DEAD, DEAD, 500, DEAD, DEAD, DEAD), False),
    ("start-in-last-block", 6, 1920, 16, 2, 1900, None,
     (1800, DEAD, 1899, 0, DEAD, 1900), False),
    ("pos-in-first-block", 6, 2048, 16, 2, 40, None,
     (0, DEAD, 33, DEAD, 40, DEAD), False),
    ("window-shorter", 6, 1920, 16, 2, 1850, 256,
     (DEAD, 0, 1700, DEAD, DEAD, DEAD), False),
    ("window-qstruct", 8, 1024, 32, 8, 1000, 300,
     (DEAD, DEAD, 0, DEAD, 900, DEAD, DEAD, DEAD), False),
    ("1of6-int8", 6, 1920, 16, 2, 1850, None,
     (DEAD, DEAD, 96, DEAD, DEAD, DEAD), True),
    ("1of8-qstruct-int8", 8, 640, 16, 4, 600, None,
     (DEAD, DEAD, DEAD, DEAD, DEAD, DEAD, 130, DEAD), True),
    ("all-dead", 6, 256, 16, 2, 100, None, (DEAD,) * 6, False),
    ("rows-blocked", 6, 256, 16, 2, 200, None,
     (DEAD, DEAD, DEAD, 150, DEAD, DEAD), False),  # b_block 2 at 256
    ("rows-blocked-qstruct", 8, 128, 16, 4, 100, None,
     (DEAD, DEAD, DEAD, DEAD, DEAD, 20, DEAD, 90), True),  # b_block 8
]


@pytest.mark.parametrize("return_state", [False, True], ids=["out", "state"])
@pytest.mark.parametrize("case", SWEEP_CASES, ids=[c[0] for c in SWEEP_CASES])
def test_decode_sweeps_only_live_rows(case, return_state):
    """Live rows equal the XLA reference whatever lies around them; rows
    without a stream give zeros and, with ``return_state``, the absent
    state ``(NEG_INF, 0)`` the shared-prefix merge drops."""
    from llm_consensus_tpu.ops.pallas.decode_attention import NEG_INF
    from llm_consensus_tpu.ops.quant import kv_read

    _, b, w, hq, hkv, pos, window, rs, int8_kv = case
    q, k, v = _qkv(jax.random.PRNGKey(11), b, w, hq, hkv, 128)
    # Garbage wherever no row may look: a fetched-but-masked slot shows.
    k = k.at[:, pos + 1:].set(jnp.nan)
    v = v.at[:, pos + 1:].set(jnp.nan)
    kk, vv, tol = k, v, 1e-5
    if int8_kv:
        k, v = jnp.nan_to_num(k), jnp.nan_to_num(v)  # codes cannot be NaN
        kk, vv, tol = _quantize_entry(k), _quantize_entry(v), 2e-4
        k, v = kv_read(kk, jnp.float32), kv_read(vv, jnp.float32)
    row_start = jnp.asarray(rs, jnp.int32)
    live = [i for i, r in enumerate(rs) if r <= pos]
    dead = [i for i, r in enumerate(rs) if r > pos]
    with jax.default_matmul_precision("highest"):
        got = decode_attention(
            q, _stack(kk), _stack(vv), jnp.int32(pos), 0, row_start,
            sliding_window=window, interpret=True, return_state=return_state,
        )
        want = _reference(
            q, jnp.nan_to_num(k), jnp.nan_to_num(v), pos, row_start, window
        )
    if return_state:
        got, m, l = got
        assert bool((l[jnp.asarray(live, int)] > 0).all())
        if dead:
            d = jnp.asarray(dead)
            assert bool((m[d] <= NEG_INF).all()) and bool((l[d] == 0).all())
    assert bool(jnp.isfinite(got).all())
    if live:
        lv = jnp.asarray(live)
        assert jnp.allclose(got[lv], want[lv], atol=tol, rtol=tol), (
            float(jnp.abs(got[lv] - want[lv]).max())
        )
    if dead:
        assert bool((got[jnp.asarray(dead)] == 0).all())


def _parent_blocks(b, width, hkv, dh, kv_item, quantized):
    """The block chooser as it stood before kv blocks followed the live
    sweep (power-of-two divisors up to 512, most bytes an iteration):
    what the new chooser must not fall below."""
    from llm_consensus_tpu.ops.pallas.decode_attention import (
        _fits, _pow2_block)

    floor = 128 if quantized else 8
    ks, bk = [], _pow2_block(width, 512)
    top = bk
    while bk >= floor:
        ks.append(bk)
        bk //= 2
    if not ks and top == width:
        ks = [width]
    best = None
    for cand_b in (8, 4, 2, 1):
        if b % cand_b:
            continue
        for cand_k in ks:
            if _fits(cand_b, cand_k, hkv, dh, kv_item, quantized):
                if best is None or cand_b * cand_k > best[0] * best[1]:
                    best = (cand_b, cand_k)
                break
    return best


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("hkv", [2, 4, 8])
@pytest.mark.parametrize("b", [1, 6, 8, 18])
def test_block_chooser_over_the_bucket_ladder(b, hkv, quantized):
    """Every bucket of the 128-slot ladder: a block wherever the parent
    found one, dividing the span, inside VMEM, and covering no fewer
    slots of a live row an iteration than the parent's did."""
    from llm_consensus_tpu.ops.pallas.decode_attention import (
        _choose_blocks, _fits)

    item = 1 if quantized else 2
    for width in range(128, 4096 + 1, 128):
        parent = _parent_blocks(b, width, hkv, 128, item, quantized)
        got = _choose_blocks(b, width, hkv, 128, item, quantized)
        assert parent is not None and got is not None, width
        b_block, block_k = got
        assert width % block_k == 0 and b % b_block == 0, (width, got)
        assert _fits(b_block, block_k, hkv, 128, item, quantized), (width, got)
        assert block_k >= parent[1], (width, got, parent)
        assert block_k % 128 == 0, (width, got)


def test_block_chooser_does_not_cut_a_judge_bucket_into_128s():
    """1,920 = 15 x 128 is no longer fifteen blocks a row, and rows of a
    wide bucket are swept one by one."""
    from llm_consensus_tpu.ops.pallas.decode_attention import (
        _choose_blocks, _legal_block_ks)

    assert _legal_block_ks(1920, False)[:3] == [640, 384, 128]
    assert _legal_block_ks(1792, True) == [896, 256, 128]
    assert _choose_blocks(6, 1920, 2, 128, 2, False) == (1, 640)   # Qwen2.5
    assert _choose_blocks(6, 2048, 2, 128, 2, False) == (1, 1024)
    assert _choose_blocks(6, 1920, 8, 128, 2, False) == (1, 384)   # Mistral
    assert _choose_blocks(8, 1920, 4, 128, 2, False) == (1, 640)   # tp = 2
    assert _choose_blocks(6, 256, 2, 128, 2, False) == (2, 256)    # short


def test_decode_never_reads_beyond_frontier():
    """NaNs in unwritten cache slots must not leak into the output."""
    b, w, hq, hkv, dh, pos = 1, 512, 8, 4, 128, 100
    q, k, v = _qkv(jax.random.PRNGKey(1), b, w, hq, hkv, dh)
    k = k.at[:, pos + 1:].set(jnp.nan)
    v = v.at[:, pos + 1:].set(jnp.nan)
    got = decode_attention(
        q, _stack(k), _stack(v), jnp.int32(pos), interpret=True
    )
    assert not bool(jnp.isnan(got).any())


def test_decode_traced_pos_one_program():
    """pos is data, not shape: one jitted program serves every step."""
    b, w, hq, hkv, dh = 1, 256, 8, 4, 128
    q, k, v = _qkv(jax.random.PRNGKey(2), b, w, hq, hkv, dh)

    @jax.jit
    def f(q, k, v, pos):
        return decode_attention(q, _stack(k), _stack(v), pos, interpret=True)

    with jax.default_matmul_precision("highest"):
        for pos in (0, 17, 255):
            got = f(q, k, v, jnp.int32(pos))
            want = _reference(q, k, v, pos)
            assert jnp.allclose(got, want, atol=1e-5, rtol=1e-5)


def test_decode_flash_supported_gate():
    assert decode_flash_supported(16, 8, 128)    # consensus-1b
    assert decode_flash_supported(8, 1, 128)     # MQA
    assert decode_flash_supported(32, 8, 256)    # gemma-ish dh
    assert not decode_flash_supported(16, 8, 32)   # lane dim not 128-aligned
    assert not decode_flash_supported(15, 8, 128)  # ragged GQA
    # width legality: the grid must cover the span in Mosaic-legal blocks
    assert decode_flash_supported(16, 8, 128, width=4096)
    assert decode_flash_supported(16, 8, 128, width=96)       # 32-divisible
    assert not decode_flash_supported(16, 8, 128, width=300)  # pow2 divisor 4
    assert decode_flash_supported(16, 8, 128, width=24)       # full-ish bk=8
    assert not decode_flash_supported(16, 8, 128, width=24, quantized=True)
    assert decode_flash_supported(16, 8, 128, width=4096, quantized=True)


def test_decode_layer_selection():
    """layer_idx pages the right layer's K/V out of the stack."""
    b, w, hq, hkv, dh, pos = 2, 128, 8, 4, 128, 100
    key = jax.random.PRNGKey(7)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, 1, hq, dh), jnp.float32)
    k_stack = jax.random.normal(kk, (3, b, w, hkv, dh), jnp.float32)
    v_stack = jax.random.normal(kv, (3, b, w, hkv, dh), jnp.float32)
    with jax.default_matmul_precision("highest"):
        for li in range(3):
            got = decode_attention(
                q, k_stack, v_stack, jnp.int32(pos), jnp.int32(li),
                interpret=True,
            )
            want = _reference(q, k_stack[li], v_stack[li], pos)
            assert jnp.allclose(got, want, atol=1e-5, rtol=1e-5), li


def test_engine_decode_flash_same_tokens():
    """Engine with the fused decode kernel emits the identical greedy
    sequence as the XLA attention path at production head_dim."""
    from llm_consensus_tpu.engine import Engine, SamplingParams
    from llm_consensus_tpu.models import get_config

    cfg = get_config("tiny-llama", head_dim=128)
    base = Engine(cfg, dtype=jnp.float32, max_seq=128, attn_impl="xla")
    flash = Engine(
        cfg, params=base.params, dtype=jnp.float32, max_seq=128,
        attn_impl="flash",
    )
    sampling = SamplingParams(max_new_tokens=12, ignore_eos=True)
    prompt = "the quick brown fox jumps over the lazy dog"
    assert (
        base.generate(prompt, sampling).token_ids
        == flash.generate(prompt, sampling).token_ids
    )


# ---------------------------------------------------------------------------
# TPU lowering smoke tests (the round-1 escape: interpret mode cannot catch
# Mosaic tiling violations; cross-platform export runs the real lowering).
# ---------------------------------------------------------------------------

def _lower_for_tpu(fn, *args):
    jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)


@pytest.mark.parametrize(
    "b,w,hq,hkv,dh",
    [
        (1, 512, 16, 8, 128),   # consensus-1b decode shape (round-1 crash)
        (1, 512, 24, 8, 128),   # consensus-3b
        (2, 64, 8, 8, 128),     # width below the default kv block
        (1, 1024, 16, 16, 256), # MHA, wide head
        (8, 512, 16, 8, 128),   # continuous-batching layout
    ],
)
def test_decode_kernel_lowers_for_tpu(b, w, hq, hkv, dh):
    q, k, v = _qkv(jax.random.PRNGKey(0), b, w, hq, hkv, dh, jnp.bfloat16)
    rs = jnp.zeros((b,), jnp.int32)
    _lower_for_tpu(
        functools.partial(
            decode_attention, interpret=False, sliding_window=None,
        ),
        q, _stack(k), _stack(v), jnp.int32(3), jnp.int32(0), rs,
    )


def test_prefill_kernel_lowers_for_tpu():
    from llm_consensus_tpu.ops.pallas import flash_attention

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (1, 128, 16, 128), jnp.bfloat16)
    k = jax.random.normal(kk, (1, 512, 8, 128), jnp.bfloat16)
    v = jax.random.normal(kv, (1, 512, 8, 128), jnp.bfloat16)
    _lower_for_tpu(
        functools.partial(flash_attention, q_offset=0, interpret=False),
        q, k, v,
    )


# ---------------------------------------------------------------------------
# int8 KV entries consumed directly (dequant per block in VMEM)
# ---------------------------------------------------------------------------


def _quantize_entry(x):
    """[B, W, H, dh] → {"q8", "s"} with the engine's per-row scaling."""
    from llm_consensus_tpu.ops.quant import quantize_kv

    q8, s = quantize_kv(x)
    # seq-minor scale layout [B, H, W] (the cache's storage form)
    return {"q8": q8, "s": jnp.swapaxes(s[..., 0], 1, 2)}


@pytest.mark.parametrize(
    "b,w,hq,hkv,pos,window,rs",
    [
        (1, 512, 16, 8, 300, None, None),
        (2, 300, 8, 2, 150, None, (0, 37)),   # ragged width + row pads
        (2, 512, 8, 8, 400, 128, None),       # sliding window
        (1, 512, 8, 1, 0, None, None),        # MQA, first step
    ],
)
def test_decode_int8_kv_matches_dequantized(b, w, hq, hkv, pos, window, rs):
    """The kernel consuming int8 {"q8","s"} entries must equal the float
    kernel over the dequantized arrays — the quantization error itself is
    shared, so outputs match tightly."""
    from llm_consensus_tpu.ops.quant import kv_read

    dh = 128
    q, k, v = _qkv(jax.random.PRNGKey(3), b, w, hq, hkv, dh)
    kq, vq = _quantize_entry(k), _quantize_entry(v)
    k_deq, v_deq = kv_read(kq, jnp.float32), kv_read(vq, jnp.float32)
    row_start = None if rs is None else jnp.asarray(rs, jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = decode_attention(
            q, _stack(kq), _stack(vq), jnp.int32(pos), 0, row_start,
            sliding_window=window, interpret=True,
        )
        want = decode_attention(
            q, _stack(k_deq), _stack(v_deq), jnp.int32(pos), 0, row_start,
            sliding_window=window, interpret=True,
        )
    assert jnp.allclose(got, want, atol=2e-4, rtol=2e-4), (
        float(jnp.abs(got - want).max())
    )


def test_engine_decode_flash_int8_kv_same_tokens():
    """Engine with int8 KV cache + the fused decode kernel (which reads
    codes directly) emits the identical greedy sequence to the XLA path
    over the same int8 cache."""
    from llm_consensus_tpu.engine import Engine, SamplingParams
    from llm_consensus_tpu.models import get_config

    cfg = get_config("tiny-llama", head_dim=128)
    base = Engine(cfg, dtype=jnp.float32, max_seq=192, attn_impl="xla",
                  kv_quant="int8")
    flash = Engine(
        cfg, params=base.params, dtype=jnp.float32, max_seq=192,
        attn_impl="flash", kv_quant="int8",
    )
    sampling = SamplingParams(max_new_tokens=12, ignore_eos=True)
    prompt = "int8 cache direct decode parity"
    assert (
        base.generate(prompt, sampling).token_ids
        == flash.generate(prompt, sampling).token_ids
    )


def test_decode_kernel_int8_lowers_for_tpu():
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, 512, 16, 8, 128, jnp.bfloat16)
    kq, vq = _quantize_entry(k), _quantize_entry(v)
    rs = jnp.zeros((2,), jnp.int32)
    _lower_for_tpu(
        functools.partial(decode_attention, interpret=False),
        q, _stack(kq), _stack(vq), jnp.int32(3), jnp.int32(0), rs,
    )


def test_decode_kernel_b_block8_lowers_for_tpu():
    """The production large-batch serving shape (int8 KV, bucket 128,
    b >= 8) selects b_block=8 — the full batch-row-blocked kernel with
    unrolled row-start selects must pass Mosaic lowering, not just the
    b_block<=2 shapes the other smoke cases reach."""
    q, k, v = _qkv(jax.random.PRNGKey(0), 16, 128, 16, 8, 128, jnp.bfloat16)
    kq, vq = _quantize_entry(k), _quantize_entry(v)
    rs = jnp.arange(16, dtype=jnp.int32)
    _lower_for_tpu(
        functools.partial(decode_attention, interpret=False),
        q, _stack(kq), _stack(vq), jnp.int32(100), jnp.int32(0), rs,
    )


def test_decode_b_block8_parity_ragged_rows():
    """Interpret-mode parity at a shape that selects b_block=8 with
    ragged per-row frontiers (every row of a block having a different
    row_start exercises the unrolled scalar-select mask build). w=64
    keeps the f32 K/V blocks inside the VMEM budget at b_block=8 —
    wider f32 shapes would silently degrade to b_block=4."""
    b, w, hq, hkv, dh, pos = 16, 64, 16, 8, 128, 60
    q, k, v = _qkv(jax.random.PRNGKey(5), b, w, hq, hkv, dh)
    rs = jnp.asarray([i * 3 % 40 for i in range(b)], jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = decode_attention(
            q, _stack(k), _stack(v), jnp.int32(pos), 0, rs, interpret=True
        )
        want = _reference(q, k, v, pos, rs)
    assert jnp.allclose(got, want, atol=1e-5, rtol=1e-5), (
        float(jnp.abs(got - want).max())
    )


def test_tp_sharded_decode_flash_int8_kv_same_tokens():
    """TP shard_map over the decode kernel with an int8 KV cache: the 4-D
    seq-minor scale leaves need a 4-axis spec (heads on axis 2) — a 5-axis
    spec crashes shard_map with a message _flash_guard cannot classify as
    a lowering failure, so this path must work, not fall back."""
    import numpy as np
    from jax.sharding import Mesh

    from llm_consensus_tpu.engine import Engine, SamplingParams
    from llm_consensus_tpu.models import get_config, init_params

    cfg = get_config("tiny-llama", head_dim=128)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))
    base = Engine(cfg, params=params, dtype=jnp.float32, max_seq=128,
                  attn_impl="xla", kv_quant="int8", mesh=mesh)
    flash = Engine(cfg, params=params, dtype=jnp.float32, max_seq=128,
                   attn_impl="flash", kv_quant="int8", mesh=mesh)
    s = SamplingParams(max_new_tokens=10, ignore_eos=True)
    prompt = "tp int8 kv decode flash parity"
    got = flash.generate(prompt, s)
    assert flash.attn_impl == "flash", "kernel fell back to XLA under tp"
    assert got.token_ids == base.generate(prompt, s).token_ids


@pytest.mark.parametrize(
    "window,cap,rs",
    [
        (None, None, None),            # plain
        (128, None, None),             # sliding window
        (None, 50.0, None),            # logit softcap
        (None, None, (0, 37, 5, 90)),  # ragged per-row frontiers
    ],
)
def test_w8a8_scores_close_to_float(monkeypatch, window, cap, rs):
    """Opt-in int8×int8 MXU scores: output stays within the combined
    int8-KV + q-rounding error envelope of the float kernel across the
    masking variants (window / softcap / row_start) so the w8a8 path's
    shared-tail wiring is actually executed, not just the default."""
    b, w, hq, hkv, dh, pos = 4, 256, 16, 8, 128, 200
    q, k, v = _qkv(jax.random.PRNGKey(9), b, w, hq, hkv, dh)
    kq, vq = _quantize_entry(k), _quantize_entry(v)
    row_start = None if rs is None else jnp.asarray(rs, jnp.int32)
    kwargs = dict(sliding_window=window, logit_softcap=cap, interpret=True)
    with jax.default_matmul_precision("highest"):
        monkeypatch.setenv("LLMC_DECODE_W8A8", "1")
        got = decode_attention(
            q, _stack(kq), _stack(vq), jnp.int32(pos), 0, row_start, **kwargs
        )
        monkeypatch.setenv("LLMC_DECODE_W8A8", "0")
        want = decode_attention(
            q, _stack(kq), _stack(vq), jnp.int32(pos), 0, row_start, **kwargs
        )
    err = float(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)).max())
    rel = err / float(jnp.abs(want).max())
    assert rel < 2e-2, rel


def test_w8a8_kernel_lowers_for_tpu(monkeypatch):
    monkeypatch.setenv("LLMC_DECODE_W8A8", "1")
    q, k, v = _qkv(jax.random.PRNGKey(0), 8, 512, 16, 8, 128, jnp.bfloat16)
    kq, vq = _quantize_entry(k), _quantize_entry(v)
    rs = jnp.zeros((8,), jnp.int32)
    _lower_for_tpu(
        functools.partial(decode_attention, interpret=False),
        q, _stack(kq), _stack(vq), jnp.int32(100), jnp.int32(0), rs,
    )
