"""On-device inference engine: prefill + streamed decode over a KV cache.

This is the compute half of the framework's ``tpu`` provider — the
replacement for the reference's remote HTTP calls (SURVEY.md §7, build step
3). Design notes, TPU-first:

  * **Two compiled programs** dominate steady state: a per-bucket prefill
    (prompts padded to the next power of two so recompiles are logarithmic
    in prompt length) and a ``stream_interval``-step decode *chunk* — a
    ``lax.scan`` over the single decode step, so each dispatch advances
    many tokens (a 1-step variant serves the cache tail). The KV cache is
    donated through all of them, so XLA updates it in place in HBM.
  * **Sampling happens on device** inside the decode step (greedy/temp/
    top-k/top-p), so the host only ever fetches token ids — one int32 per
    step — never logits.
  * **One fetch per chunk**: the host fetches ``stream_interval`` sampled
    tokens per dispatch (a transfer per step would make the device wait
    on the host between steps). EOS is therefore detected with up to
    interval-1 steps of speculative overshoot, which are dropped — cheap
    next to per-token syncs; text drains through the StreamDecoder
    between chunks.
  * **Cancellation**: the run context is checked at every fetch boundary;
    a deadline/cancel mid-generation returns the partial result with
    ``finish_reason`` set, and the provider layer decides whether partials
    surface or the model is marked failed (reference parity: failed).
"""

from __future__ import annotations

import threading
import time
import types
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from llm_consensus_tpu.analysis import sanitizer
from llm_consensus_tpu.engine.tokenizer import ByteTokenizer, StreamDecoder, load_tokenizer
from llm_consensus_tpu.models import forward, init_kv_cache, init_params
from llm_consensus_tpu.obs.attrib import tag as _attrib_tag
from llm_consensus_tpu.models.config import ModelConfig
from llm_consensus_tpu.ops.latent_attention import prefill_sweep_width
from llm_consensus_tpu.ops.quant import kv_seq_axis, kv_tree_map, w8a8_scope
from llm_consensus_tpu.obs.scopes import scope
from llm_consensus_tpu.ops.sampling import sample_token
from llm_consensus_tpu.utils.context import Context
from llm_consensus_tpu.utils import knobs


@dataclass(frozen=True)
class SamplingParams:
    max_new_tokens: int = 256
    temperature: float = 0.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    seed: int = 0
    ignore_eos: bool = False  # benchmarking: fixed-length decode


@dataclass
class GenerateResult:
    token_ids: list[int]
    text: str
    finish_reason: str  # "eos" | "length" | "deadline" | "cancelled"
    prompt_tokens: int
    latency_ms: float
    truncated_prompt: bool = False
    # Steady-state decode measurement (tokens after the first chunk fetch,
    # which forces prefill + first-chunk completion): the pair the provider
    # turns into real tokens/sec and decode MFU. Zero when the whole
    # generation fit in one chunk.
    decode_tokens: int = 0
    decode_s: float = 0.0
    # Speculative-decode telemetry for THIS generation (engine/
    # speculative.py fills it: rounds, accepted, acceptance EMA, governor
    # state); None on the plain paths, so consumers pay one None-check.
    spec: Optional[dict] = None
    # The paged KV pool truncated this generation's prefix publish
    # (arena exhausted / squeezed): reuse of THIS context is degraded.
    # Surfaced per response so operators see silent reuse loss at the
    # request level, not just in lifetime counters.
    kv_truncated: bool = False
    # The pressure scheduler preempted (and resumed) this stream at
    # least once — rides the Response so the live-metrics plane can
    # label the request's latency outcome honestly.
    preempted: bool = False
    # Clock reads (``time.monotonic_ns``) of a POOLED stream's way through
    # its batcher, taken from the spans that carried it: ``admit_ns``,
    # ``first_token_ns``, ``first_chunk_ns`` (engine/batcher.py _Stream).
    # None on the single-stream paths. The serving tier turns the judge's
    # into the result's ``timings``.
    marks: Optional[dict] = None


def _with_moe(out):
    """A step program's result from ``forward``'s: the one position's
    logits in place of [B, 1, V], and a routed model's sums (``moe_stats``)
    kept last."""
    with scope("head"):
        return (out[0][:, 0], *out[1:])


@partial(
    jax.jit,
    static_argnames=("cfg", "attn_impl", "mesh", "kv_width", "w8a8", "moe_stats"),
    donate_argnames=("cache",),
)
def _prefill_step(params, cfg: ModelConfig, tokens, last_index, cache,
                  attn_impl="xla", mesh=None, row_start=None, kv_width=None,
                  prefix=None, prefix_len=None, w8a8: bool = False,
                  moe_stats: bool = False, row_end=None):
    """Prefill ``tokens`` (padded) into the cache; return last real logits.

    ``row_end`` [B] (a state-space model's programs only; ``_row_end``)
    says where each row's real tokens end inside the padded bucket, so that
    the padding does not advance the row's recurrent state.

    ``row_start`` serves the right-aligned batch path (left-padded rows,
    per-row position offsets); ``kv_width`` bounds attention to the prompt
    bucket instead of cache capacity. ``prefix`` (with ``prefix_len``)
    prefills SUFFIX rows against a shared-prefix KV: every token attends
    the prefix plus its own causal window, with positions offset by the
    prefix length (the pool's one-prompt fan-out pattern). ``w8a8`` (a
    STATIC arg, so part of program identity — a bare env read would let
    a stale cached executable ignore the flag) scopes the activation-
    quantized matmul lane for everything traced inside. ``moe_stats``
    (static): a routed model's program also returns its routing sums,
    int32[3], last (models/transformer.py ``forward``)."""
    with w8a8_scope(w8a8):
        out = forward(
            params, cfg, tokens, cache, start_pos=0, attn_impl=attn_impl,
            mesh=mesh, logits_index=last_index, row_start=row_start,
            kv_width=kv_width, prefix=prefix, prefix_len=prefix_len,
            moe_stats=moe_stats, row_end=row_end,
        )
    return _with_moe(out)


def _row_end(cfg: ModelConfig, place: Callable, ends):
    """``forward``'s ``row_end`` for a prefill of rows whose real tokens end
    at slots ``ends``: an operand of a state-space model's programs alone
    (None keeps every other model's program as it was)."""
    return place(jnp.asarray(ends, jnp.int32)) if cfg.has_state else None


@partial(jax.jit, static_argnames=("cfg", "mesh"), donate_argnames=("cache",))
def _sp_prefill_step(params, cfg: ModelConfig, tokens, last_index, cache, mesh):
    """Sequence-parallel one-shot prefill: ring attention over the mesh's
    sp axis (models/transformer.py _forward_ring_prefill)."""
    logits, cache = forward(
        params, cfg, tokens, cache, start_pos=0, attn_impl="ring",
        mesh=mesh, logits_index=last_index,
    )
    return logits[:, 0], cache


@jax.jit
def _restore_prefix(saved, n_valid):
    """Working cache from a saved prompt snapshot: positions < ``n_valid``
    keep the saved K/V, the rest zero. One fused elementwise pass over the
    cache (bandwidth ≈ one cache read+write) replaces re-prefilling the
    whole shared prefix; the traced length means one compiled program for
    every prefix length. Per-leaf seq axes follow ops.quant.kv_seq_axis
    (seq-minor int8 scale stacks vs 5-D code/bf16 stacks). A state-space
    model's per-row state leaves have no positions to mask and a state cut
    at ``n_valid`` does not exist: its engine never restores a prefix
    (``Engine._refuse_ssm``)."""
    with scope("cache.splice"):
        return kv_tree_map(lambda src: _mask_beyond(src, n_valid), saved)


@partial(jax.jit, donate_argnames=("saved",))
def _restore_prefix_owned(saved, n_valid):
    """:func:`_restore_prefix` for a PRIVATE input (the KV pool's freshly
    gathered cache, discarded right after): donating ``saved`` lets XLA
    mask in place instead of materializing a second full-capacity cache —
    the pool hit path would otherwise pay the gather's HBM cost twice.
    The classic path must keep the non-donating twin: its input is the
    shared snapshot slot, which later reuses read again."""
    with scope("cache.splice"):
        return kv_tree_map(lambda src: _mask_beyond(src, n_valid), saved)


def _mask_beyond(src, n_valid):
    """Zero ``src``'s positions ≥ ``n_valid`` along its seq axis — the
    single owner of the prefix-restore masking invariant (used by both
    _restore_prefix and _fork_prefix so a cache-layout change cannot
    diverge them)."""
    ax = kv_seq_axis(src)
    shape = [1] * src.ndim
    shape[ax] = src.shape[ax]
    keep = (jnp.arange(src.shape[ax], dtype=jnp.int32) < n_valid).reshape(shape)
    return jnp.where(keep, src, jnp.zeros_like(src))


@partial(jax.jit, static_argnames=("k", "width"))
def _fork_prefix(saved, n_valid, k: int, width: int):
    """Fork a [1, max_seq] prompt snapshot into a [k, width] admission
    prefill cache: slice to the wave's bucket, zero positions ≥
    ``n_valid``, and replicate across the k rows. One program per
    (k, width); the copy costs k × bucket bytes — what the wave saves is
    re-COMPUTING the shared prefix chunks through the model."""
    def leaf(src):
        sl = jax.lax.slice_in_dim(src, 0, width, axis=kv_seq_axis(src))
        return jnp.repeat(_mask_beyond(sl, n_valid), k, axis=1)

    with scope("cache.splice"):
        return kv_tree_map(
            leaf, saved, state=lambda src: jnp.repeat(src, k, axis=1))


@partial(jax.jit, static_argnames=("width",))
def _extract_row0(template, pcache, width: int):
    """Row 0 of a [k, width] admission prefill cache, re-padded into a
    full-capacity [1, max_seq] snapshot (``template`` is fresh zeros). A
    state-space model's per-row state leaves are carried whole."""
    def copy(dst, src):
        if kv_seq_axis(src) == 2:
            return jax.lax.dynamic_update_slice(
                dst, src[:, :1, :width], (0, 0, 0, 0, 0)
            )
        return jax.lax.dynamic_update_slice(
            dst, src[:, :1, :, :width], (0, 0, 0, 0)
        )

    with scope("cache.splice"):
        return kv_tree_map(
            copy, template, pcache, state=lambda dst, src: src[:, :1])


def _prefill_chunk(params, cfg: ModelConfig, tokens, start_pos, last_index,
                   cache, kv_width: int, row_start=None, prefix=None,
                   prefix_len=None, w8a8: bool = False,
                   moe_stats: bool = False, row_end=None):
    """One fixed-size prefill chunk at a *traced* ``start_pos``.

    ``row_end`` [B] (a state-space model's programs only; ``_row_end``):
    the slot after each row's last real token, in the cache's coordinates,
    so that neither a chunk's padded tail nor a whole chunk past a short
    row's end advances that row's recurrent state.

    The dynamic start means ONE compiled program (per prompt bucket) serves
    every chunk of a long prompt, and peak attention memory is
    [chunk × kv_width] scores instead of one-shot O(T²). ``kv_width`` is
    the prompt's power-of-two bucket — a static prefix slice of the cache —
    so per-chunk attention cost scales with the prompt, never with a large
    ``max_seq`` cache capacity (a 128k-context preset prefilling a 1k
    prompt attends 1k wide, not 128k). The traced offset rules out the
    Pallas kernel (static q_offset), so this always takes the XLA attention
    path, which GSPMD also partitions for TP-sharded engines. A dense
    model's chunk sweeps the whole ``kv_width`` whatever its start; a latent
    model's sweeps the narrowest of the program's static widths (chunk,
    2 × chunk, ... ``kv_width``) that covers its frontier, chosen at run
    time from ``start_pos`` (ops/latent_attention.py ``prefill_sweep``).
    """
    with w8a8_scope(w8a8):
        out = forward(
            params, cfg, tokens, cache, start_pos=start_pos,
            kv_width=kv_width, logits_index=last_index, row_start=row_start,
            prefix=prefix, prefix_len=prefix_len, moe_stats=moe_stats,
            row_end=row_end,
        )
    return _with_moe(out)


def _prefill_chunks_loop(params, cfg: ModelConfig, tokens, base, n_real,
                         last_index, cache, max_chunks: int, kv_width: int,
                         w8a8: bool = False, moe_stats: bool = False):
    """Every chunk of one prompt's prefill as ONE device program.

    The per-chunk jit form pays one host dispatch + one token transfer
    per chunk, which at batch 1 can bind the judge-prompt prefill. A
    ``fori_loop`` with a TRACED trip count over a
    [max_chunks, 1, chunk] token array (padded to the kv_width bucket —
    a few KB) keeps program identity at (kv_width, chunk), exactly the
    per-chunk program's keying: serving admission with varied prompt
    lengths must NOT compile per n_chunks value (a multi-second
    full-model compile mid-admission). Junk chunks past ``n_real`` are
    never executed. Chunk 0 runs inline so the carry's logits dtype
    matches forward's exactly — greedy ties must not flip between this
    and the per-chunk path. The last chunk's padded tail must not advance
    a state-space model's recurrent state: the prompt's real end follows
    from what the program is given already (``n_real`` chunks, the last
    real token at ``last_index`` of the last one).
    """
    chunk = tokens.shape[-1]
    with scope("chunk.tail"):
        row_end = (
            base + (n_real - 1) * chunk + last_index + 1
            if cfg.has_state else None)
        toks0 = tokens[0]
    with w8a8_scope(w8a8):
        logits0, cache, *moe = forward(
            params, cfg, toks0, cache, start_pos=base,
            kv_width=kv_width, logits_index=last_index, moe_stats=moe_stats,
            row_end=row_end,
        )

    def body(i, carry):
        cache, _, *moe = carry
        with scope("chunk.tail"):
            toks = jax.lax.dynamic_index_in_dim(tokens, i, 0, keepdims=False)
            start = base + i * chunk
        with w8a8_scope(w8a8):
            logits, cache, *more = forward(
                params, cfg, toks, cache, start_pos=start,
                kv_width=kv_width, logits_index=last_index,
                moe_stats=moe_stats, row_end=row_end,
            )
        with scope("head"):
            last = logits[:, 0]
        with scope("chunk.tail"):
            return (cache, last, *(a + b for a, b in zip(moe, more)))

    with scope("head"):
        last0 = logits0[:, 0]
    with scope("chunk.tail"):  # the loop's own: its counter and carry
        cache, last_logits, *moe = jax.lax.fori_loop(
            1, n_real, body, (cache, last0, *moe),
        )
    return (last_logits, cache, *moe)


def _decode_chunk(params, cfg: ModelConfig, token, pos, cache, key,
                  n_steps, temperature, top_k, top_p, row_start=None,
                  kv_width=None, attn_impl="xla", mesh=None,
                  prefix=None, prefix_len=None, prefix_rows=None,
                  w8a8: bool = False, sentinel: bool = False,
                  poison_row=None, moe_stats: bool = False):
    """``n_steps`` decode steps as ONE device program (lax.scan).

    One dispatch and one host fetch per chunk instead of per token: fewer
    launches means the device does not wait on the host between steps.
    Returns the tokens [n_steps, B] sampled
    on device; EOS is detected host-side after the fetch, so up to
    n_steps-1 speculative steps are wasted at end-of-sequence — cheap next
    to a per-step sync.

    ``kv_width`` (static, ≥ pos + n_steps) bounds every step's attention
    to the cache prefix actually written, instead of full capacity: at
    short contexts the cache read is a large share of decode's HBM traffic
    (a 4096-capacity consensus-1b cache is ~270 MB/step against ~820 MB of
    int8 weights), so the bound is a direct throughput win. The caller
    rounds it to power-of-two buckets so programs stay cached.

    ``sentinel=True`` (static) adds the integrity plane's finite-logit
    sentinel: one fused ``jnp.isfinite`` all-reduce per step over the
    last-position logits, AND-folded across the chunk into a per-row
    verdict returned as a fourth output — the verdict rides the SAME
    host fetch as the tokens (it is [B] bools next to an [n_steps, B]
    token matrix), so a poisoned row is detected for free on the
    existing transfer. ``poison_row`` (traced, or None) is the
    ``nan_logits`` fault's injection operand: that row's logits become
    NaN before sampling, exactly what a corrupted accumulator emits.

    ``moe_stats=True`` (static) on a routed model returns, last, the
    chunk's routing sums over its steps and expert layers, int32[3]:
    (token, chosen expert) pairs, pairs on held experts, held experts that
    took at least one row. They ride the same fetch as the tokens.
    """
    moe_stats = moe_stats and cfg.is_moe

    def body(carry, _):
        token, pos, cache, ok, *moe = carry
        with scope("chunk.tail"):
            column = token[:, None]
        logits, cache, *more = forward(
            params, cfg, column, cache, start_pos=pos,
            row_start=row_start, kv_width=kv_width, attn_impl=attn_impl,
            mesh=mesh, prefix=prefix, prefix_len=prefix_len,
            prefix_rows=prefix_rows, moe_stats=moe_stats,
        )
        with scope("chunk.tail"):
            moe = [a + b for a, b in zip(moe, more)]
        with scope("head"):
            last = logits[:, -1]
        with scope("sentinel"):
            if poison_row is not None:
                rows = jnp.arange(last.shape[0], dtype=jnp.int32)
                last = jnp.where(
                    (rows == poison_row)[:, None], jnp.nan, last
                )
            if sentinel:
                ok = ok & jnp.all(jnp.isfinite(last), axis=-1)
        with scope("chunk.tail"):
            step_key = jax.random.fold_in(key, pos)
        next_token = sample_token(
            last, step_key,
            temperature=temperature, top_k=top_k, top_p=top_p,
        )
        with scope("chunk.tail"):
            return (next_token, pos + 1, cache, ok, *moe), next_token

    with scope("chunk.tail"):
        ok0 = jnp.ones((token.shape[0],), dtype=bool)
        moe0 = [jnp.zeros((3,), jnp.int32)] if moe_stats else []
        pos = jnp.asarray(pos, jnp.int32)
    # The step scan's own work (the tokens stacked a step, its counter)
    # is the chunk's tail.
    with w8a8_scope(w8a8), scope("chunk.tail"):
        (token, pos, cache, ok, *moe), toks = jax.lax.scan(
            body, (token, pos, cache, ok0, *moe0), None, length=n_steps,
        )
    if sentinel:
        return (token, toks, cache, ok, *moe)
    return (token, toks, cache, *moe)


def _kvw(args, kwargs, idx: int):
    return kwargs.get("kv_width", args[idx] if len(args) > idx else None)


class _NamedPrograms:
    """One hot program family, jitted under names that say what runs.

    A device trace names a program by the function that was jitted, so one
    ``jax.jit`` per family made every model's decode chunk at every width
    the same ``jit__decode_chunk(<id>)``. Here the plain function is
    jitted once per (model, kv_width[, steps]) under
    ``<stem>__<model>__kv<width>[__s<steps>]`` (``kv0`` = the whole
    cache) — values that were static arguments already, so there is the
    same number of compiles as before, only findable by name. The other
    static arguments (sampling, attention impl, mesh, ...) still key the
    named program's own jit cache.

    The cache is per family and process-wide, not per engine: two engines
    of one model share the named program exactly as they shared the one
    jit. ``lower`` / ``_cache_size`` keep the jit-like surface the tests
    and chip_smoke.py introspect.
    """

    def __init__(self, fn, stem: str, static: tuple, name_key: Callable):
        self._fn = fn
        self._stem = stem
        self._static = static
        # (args, kwargs) -> (model, kv_width[, steps]): what the name says.
        self._key = name_key
        self._programs: dict = {}
        self._lock = sanitizer.make_lock(f"engine.programs.{stem}")
        self.__name__ = stem
        self.__doc__ = fn.__doc__

    def name_of(self, key: tuple) -> str:
        model, kv_width, *steps = key
        safe = "".join(c if c.isalnum() else "_" for c in model)
        name = f"{self._stem}__{safe}__kv{kv_width or 0}"
        return f"{name}__s{steps[0]}" if steps else name

    def program(self, *args, **kwargs):
        """The named jit these arguments run under."""
        key = self._key(args, kwargs)
        prog = self._programs.get(key)
        if prog is None:
            with self._lock:
                prog = self._programs.get(key)
                if prog is None:
                    prog = self._programs[key] = self._build(key)
        return prog

    def _build(self, key: tuple):
        fn, name = self._fn, self.name_of(key)
        named = types.FunctionType(
            fn.__code__, fn.__globals__, name, fn.__defaults__,
            fn.__closure__,
        )
        named.__kwdefaults__ = fn.__kwdefaults__
        named.__qualname__ = name
        named.__doc__ = fn.__doc__
        return jax.jit(named, static_argnames=self._static,
                       donate_argnames=("cache",))

    def __call__(self, *args, **kwargs):
        return self.program(*args, **kwargs)(*args, **kwargs)

    def lower(self, *args, **kwargs):
        return self.program(*args, **kwargs).lower(*args, **kwargs)

    def _cache_size(self) -> int:
        with self._lock:
            programs = list(self._programs.values())
        return sum(p._cache_size() for p in programs)


_prefill_chunk = _NamedPrograms(
    _prefill_chunk, "prefill_chunk", ("cfg", "kv_width", "w8a8", "moe_stats"),
    lambda a, k: (a[1].name, _kvw(a, k, 6)),
)
_prefill_chunks_loop = _NamedPrograms(
    _prefill_chunks_loop, "prefill_chunks_loop",
    ("cfg", "max_chunks", "kv_width", "w8a8", "moe_stats"),
    lambda a, k: (a[1].name, _kvw(a, k, 8)),
)
_decode_chunk = _NamedPrograms(
    _decode_chunk, "decode_chunk",
    ("cfg", "n_steps", "temperature", "top_k", "top_p", "kv_width",
     "attn_impl", "mesh", "w8a8", "sentinel", "moe_stats"),
    lambda a, k: (
        a[1].name, _kvw(a, k, 11),
        int(k["n_steps"] if "n_steps" in k else a[6]),
    ),
)


class Prefilled(NamedTuple):
    """What one prefill dispatched, padding rows and padding inside rows
    and all: ``chunks`` programs over ``slot_tokens`` token slots, whose
    attention swept ``pairs_swept`` (query, key) pairs
    (``prefill_pairs_swept``), on top of ``reused`` positions a row that
    were restored from a retained prefix and not prefilled."""
    chunks: int
    slot_tokens: int
    pairs_swept: int
    reused: int


def scan_positions_swept(cfg: ModelConfig, did: Prefilled, rows: int) -> int:
    """Positions the scans of a state-space model's prefill ``did`` of
    ``rows`` rows (padding rows included) ran over: every token slot of
    every program, with a program's T rounded up to whole scan chunks
    (ops/ssm.py ``ssd_chunked``, ops/delta.py ``kda_chunked``). 0 for a
    model whose rows hold no state."""
    if not cfg.has_state:
        return 0
    t = did.slot_tokens // (rows * did.chunks)  # one program's width
    return rows * did.chunks * (-(-t // cfg.scan_chunk) * cfg.scan_chunk)


def prefill_pairs_swept(cfg: ModelConfig, rows: int, t: int, slots: int,
                        end: int, prefix_slots: int = 0) -> int:
    """(Query, key) pairs the attention of ONE prefill program sweeps:
    ``rows`` x ``t`` query slots x the cache slots each is scored against.
    A dense model's XLA route scores the whole ``slots`` it is given (its
    bucket), and a shared prefix's ``prefix_slots`` beside them; a latent
    model's prefill form the width its frontier ``end`` picks
    (ops/latent_attention.py ``prefill_sweep``, the rule the program's own
    branches come from)."""
    if cfg.is_latent:
        slots = prefill_sweep_width(t, slots, end)
    return rows * t * (slots + prefix_slots)


def refuse_ssm(cfg: ModelConfig, what: str) -> None:
    """Refuse, by name, a path that would cut, fork or move a state-space
    model's cache at a length its recurrent state was not computed to
    (``cfg.has_state``: a mixer's state or a delta rule's alike)."""
    if cfg.has_state:
        raise ValueError(
            f"{cfg.name}: no {what} for a state-space model: keys and values "
            "can be cut at any length, its recurrent state exists only at "
            "the length it was computed to")


def _bucket(n: int, cap: int) -> int:
    b = 16
    while b < n:
        b *= 2
    return min(b, cap)


def _is_pallas_lowering_error(e: Exception) -> bool:
    """A *compile-time* failure in the Pallas/Mosaic kernel path (as
    opposed to a genuine model or runtime error). Python-side lowering
    checks raise ValueError/LoweringError with 'Pallas'/'Mosaic' in the
    message — e.g. round 1's "The Pallas TPU lowering currently requires
    that the last two dimensions of your block shape...". The Mosaic
    compiler proper rejects a kernel as XlaRuntimeError("... Mosaic
    failed to compile ...") — still at jit compile time, before any
    executable runs, so still retryable. A *runtime* XlaRuntimeError
    (kernel fault mid-execution) is NOT retryable: executables already
    ran, so donated buffers may be consumed — for those only the exact
    compile-stage PHRASES match (a runtime fault whose message merely
    contains 'mosaic' plus the word 'compile' must not be re-dispatched
    onto consumed buffers)."""
    s = str(e).lower()
    if "pallas" not in s and "mosaic" not in s:
        return False
    if type(e).__name__ == "XlaRuntimeError":
        return any(
            phrase in s
            for phrase in (
                "failed to compile",
                "failed to lower",
                "lowering failed",
                "internal error during lowering",
                "unsupported lowering",
                "error during compilation",
            )
        )
    return True


class Engine:
    """Single-model inference engine (one decode stream per generate call).

    ``params`` defaults to random initialization — real checkpoints load via
    engine/checkpoint.py. ``mesh`` pins the engine to a device slice: params
    and KV cache get Megatron-style TP NamedShardings (parallel/sharding.py)
    and host-created inputs (tokens, PRNG key) are placed replicated on the
    slice, so the whole decode loop — and the collectives GSPMD inserts for
    the row-parallel matmuls — runs on that slice's chips and ICI links
    only. ``shard_fn`` overrides the derived placement when given.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params: Optional[dict] = None,
        *,
        tokenizer=None,
        dtype=jnp.bfloat16,
        max_seq: Optional[int] = None,
        seed: int = 0,
        mesh=None,
        shard_fn: Optional[Callable] = None,
        stream_interval: int = 16,
        attn_impl: Optional[str] = None,
        prefill_chunk: Optional[int] = None,
        prefill_width: Optional[int] = None,
        quant: Optional[str] = None,
        kv_quant: Optional[str] = None,
        kv_pool: bool = True,
    ):
        t_build_ns = time.monotonic_ns()
        self.cfg = cfg
        self.mesh = mesh
        caller_shard_fn = shard_fn is not None
        if mesh is not None and shard_fn is None:
            from llm_consensus_tpu.parallel.sharding import make_shard_fn

            shard_fn = make_shard_fn(cfg, mesh)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            replicated = NamedSharding(mesh, PartitionSpec())
            self._place = lambda x: jax.device_put(x, replicated)
        else:
            self._place = lambda x: x
        self.max_seq = max_seq or cfg.max_seq_len
        self.tokenizer = tokenizer if tokenizer is not None else load_tokenizer(None)
        self.stream_interval = max(1, stream_interval)
        self._dtype = dtype
        # Attention: the fused Pallas kernels on a TPU, XLA on a CPU that
        # was asked for (Pallas interpret mode is correct but slow).
        # LLMC_FLASH=1/0 forces it either way. forward() owns the per-shape
        # and per-mesh gating: TP-sharded engines run the kernels under
        # shard_map over the head axis (pallas_call has no GSPMD rule);
        # unsupported tilings/meshes take the XLA path, and
        # attention_stats() reports which path each phase traced.
        if attn_impl is None:
            env = knobs.get_str("LLMC_FLASH")
            if env == "1":
                attn_impl = "flash"
            elif env == "0":
                attn_impl = "xla"
            else:
                attn_impl = (
                    "flash" if jax.default_backend() == "tpu" else "xla"
                )
        self.attn_impl = attn_impl
        # What the engine was BUILT with, next to what it runs now: they
        # differ only after _flash_guard caught a kernel the compiler
        # refused — counted here, and a failure wherever it is checked.
        self.attn_built = attn_impl
        self.flash_fallbacks = 0
        # Long-prompt prefill: past this length, prefill runs as fixed-size
        # chunks through one compiled program (see _prefill_chunk) instead
        # of one-shot per-bucket programs. 0 disables chunking.
        # That number does two jobs: the LENGTH past which a prompt leaves
        # the one-shot program (and a wave's rows are admitted one by one),
        # and the WIDTH of a chunk. Given by the caller or the environment
        # it does both, as it always has. Left to its default, the width of
        # the ONE-ROW path's chunks (``_chunk_width``) is the model's own
        # (utils/flops.py ``prefill_ridge_width``, once the leaves' stored
        # type is known below): a routed model's chunk feeds each held
        # expert a sixteenth to a fortieth of its tokens, and 512 wide
        # streams every expert once a chunk for a dozen rows. Batched and
        # paced waves, the prefix store and the sessions keep one number.
        chunk_given = prefill_chunk is not None or knobs.is_set(
            "LLMC_PREFILL_CHUNK")
        if prefill_chunk is None:
            prefill_chunk = knobs.get_int("LLMC_PREFILL_CHUNK")
        self.prefill_chunk = max(0, prefill_chunk)
        # Decode attention width: bucket over the causal frontier (floor
        # LLMC_DECODE_KV_MIN, default 128; 0 disables, reading full
        # capacity). Measured on v5e consensus-1b int8: 256 beats 512
        # both single-stream (437 vs 425 tok/s) and at batch 32 (KV
        # reads scale with batch×bucket, so the bucket is the lever:
        # 5.2k vs 4.4k tok/s aggregate), and 128-granule buckets beat
        # 256 at serving batch (B=256 long-gen decode-phase 17.6k →
        # 18.8k tok/s: shared-prefix suffix windows spend much of a
        # generation between granule boundaries) while single-stream
        # measures identical (interleaved A/B pairs 459/441 vs 461/434
        # tok/s — odd multiples cap the kernel's block_k at 128, but at
        # B=1 the whole sweep is a handful of iterations either way). Finer buckets mean
        # more compiled chunk programs, amortized by the persistent XLA
        # cache; every 128-multiple width factors into Mosaic-legal kv
        # blocks.
        self._decode_kv_min = knobs.get_int("LLMC_DECODE_KV_MIN")
        # Quantization modes (ops/quant.py): `quant` = weight-only int8
        # (halves decode's HBM weight streaming) or int4 (quarters it,
        # group-wise scales), `kv_quant` = int8 KV cache (halves cache
        # capacity + read bandwidth, quantized on write). "bf16"/"none" =
        # explicitly off, overriding the env; validated here, before any
        # multi-GB param build can be wasted on a typo'd mode.
        def resolve_mode(value: Optional[str], env: str, knob: str,
                         allowed: tuple) -> Optional[str]:
            if value is None:
                value = knobs.get_str(env) or None
            if value in ("bf16", "none"):
                value = None
            if value not in (None, *allowed):
                raise ValueError(
                    f"unknown {knob} mode {value!r} (expected one of {allowed})"
                )
            return value

        self.quant = resolve_mode(quant, "LLMC_QUANT", "quant", ("int8", "int4"))
        self.kv_quant = resolve_mode(kv_quant, "LLMC_KV_QUANT", "kv_quant", ("int8",))
        quant = self.quant
        # bytes a stored weight takes
        weight_itemsize = {"int8": 1, "int4": 0.5}.get(
            quant, jnp.dtype(dtype).itemsize)
        if prefill_width is None:
            prefill_width = self.prefill_chunk
            if self.prefill_chunk and not chunk_given:
                from llm_consensus_tpu.utils.flops import prefill_ridge_width

                device = (mesh.devices.flat[0] if mesh is not None
                          else jax.devices()[0])
                prefill_width = prefill_ridge_width(
                    cfg, device.device_kind, weight_itemsize,
                    self.prefill_chunk)
        self.prefill_width = max(self.prefill_chunk, prefill_width)
        # Opt-in W8A8 matmuls (ops/quant._w8a8_einsum): resolved ONCE at
        # engine build and threaded into every jitted program as a STATIC
        # arg — program identity must carry it, or a cached executable
        # compiled under the other setting would silently serve this
        # engine (jit keys don't include the environment).
        self.w8a8 = (
            self.quant == "int8"
            and knobs.get_bool("LLMC_W8A8")
        )
        # Prefix KV-cache reuse: the post-prefill prompt KV is snapshotted
        # per engine, and the next generate restores the longest common
        # token prefix instead of re-prefilling it — the win for
        # --rounds / --continue / repeated judge prompts, which share long
        # prefixes. LLMC_PREFIX_CACHE=0 disables; snapshots are skipped
        # above LLMC_PREFIX_CACHE_MAX_MB (default 2048) so a 128k-context
        # cache can't silently double its HBM footprint.
        self.prefix_cache_enabled = knobs.get_bool("LLMC_PREFIX_CACHE")
        if cfg.is_latent:
            self._refuse_latent(mesh)
            # The retained prefix snapshot is not built over a latent yet:
            # off, as pooled prefix sharing is (engine/batcher.py).
            self.prefix_cache_enabled = False
        if cfg.has_state:
            self._refuse_ssm(mesh)
            # Keys and values can be cut at any length; a recurrent state
            # exists only at the length it was saved at. So no retained
            # prefix snapshot (``Prefilled.reused`` stays 0), as no pooled
            # prefix sharing (engine/batcher.py).
            self.prefix_cache_enabled = False
        elif cfg.layer_kinds:
            self._refuse_one_part(mesh)
            # The retained prefix snapshot and the radix arena have not been
            # asked of a stack whose attention layers differ in their window:
            # off and refused, as every other one-part stack's are.
            self.prefix_cache_enabled = False
        self._prefix_max_bytes = (
            knobs.get_float("LLMC_PREFIX_CACHE_MAX_MB") * 1e6
        )
        self._prefix_ids: Optional[tuple] = None
        self._prefix_cache = None
        self._prefix_lock = sanitizer.make_lock("engine.prefix")
        # Cross-request paged KV pool (kv/): behind LLMC_KV_POOL the
        # pool REPLACES the single snapshot slot above — _reusable_prefix
        # becomes a radix match + block gather, _retain_prefix a block
        # publish — so every reuse path (single-stream restore, wave
        # fork, batcher prefix establishment) shares KV across requests,
        # streams, and consensus rounds. None (the default) keeps the
        # classic paths byte-for-byte. The pool_for(self) call at the
        # end of __init__ does the real binding — it must run after
        # _dtype/kv_quant/_shard_fn are set so the arena shards like a
        # working cache.
        self._kv_pool = None
        self._cache_makers: dict = {}  # (rows, slots) -> new_cache's program
        caller_params = params is not None
        if params is None and caller_shard_fn:
            # A caller's own placement function can only be handed a
            # whole tree; quantization follows it (the spec tree matches
            # the unquantized structure).
            params = shard_fn(
                init_params(cfg, jax.random.PRNGKey(seed), dtype=dtype)
            )
        elif params is None:
            # Each leaf is made under its own sharding on the engine's
            # mesh (one-chip meshes included: the planner pins those too,
            # and replication on one device is the device itself), and
            # quantized there as it is made: no chip ever holds more than
            # its share of the stored tree plus one leaf, and none outside
            # the mesh holds anything. Init-then-shard built the whole
            # tree on the default device first: an 8B ladder OOM'd there
            # before quantization (round 4), and a 14.5 GB bf16 tree for a
            # tp=2 slice did beside another engine's weights (PR 22).
            shardings = None
            if mesh is not None:
                from llm_consensus_tpu.parallel.sharding import param_shardings

                shardings = param_shardings(cfg, mesh)
            make = init_params
            if quant in ("int8", "int4"):
                from llm_consensus_tpu.ops.quant import init_params_quantized

                make = partial(init_params_quantized, mode=quant)
            params = make(
                cfg, jax.random.PRNGKey(seed), dtype=dtype, shardings=shardings
            )
        elif shard_fn is not None:
            params = shard_fn(params)
        if quant in ("int8", "int4"):
            from llm_consensus_tpu.ops.quant import quantize_params

            # Donate only params we created: device_put in shard_fn can
            # alias (not copy) when shardings already match, so even
            # post-shard trees may share buffers with a caller's arrays.
            # Idempotent for the leaf-by-leaf init above (is_quantized
            # leaves pass through).
            params = quantize_params(params, donate=not caller_params, mode=quant)
        self.params = params
        self._shard_fn = shard_fn
        # Dispatch is asynchronous: the tree is there when this returns.
        jax.block_until_ready(params)
        init_s = (time.monotonic_ns() - t_build_ns) / 1e9
        # Live weight hot-swap (flywheel): double-buffered checkpoint
        # flip. ``swap_weights`` prepares the incoming version to the
        # side (shard + quantize, never under a lock), then flips
        # ``self.params`` the instant no stream holds a pin. Pins are a
        # refcount taken at stream admission and released at retirement
        # — per-stream weight-version pinning, so every in-flight stream
        # finishes on the exact buffer it started with. pin/unpin never
        # block (the batcher's scheduler thread pins on its hot path);
        # the flip rides whichever unpin drains the count to zero. Lock
        # order: callers may hold the batcher lock while (un)pinning —
        # the swap lock is a LEAF, nothing under it calls back out.
        self._swap_lock = sanitizer.make_lock("engine.swap")
        self._swap_cv = sanitizer.make_condition("engine.swap", self._swap_lock)
        self.weight_version = 0
        self.weight_meta: dict = {}
        self._pins = 0
        self._pending_swap: Optional[tuple] = None  # (version, params, meta)
        self._prev_weights: Optional[tuple] = None  # (version, params)
        self._swap_requested = 0.0
        self._swap_stats = {
            "swaps": 0, "swap_rejects": 0, "swap_queued": 0,
            "rollbacks": 0, "last_vacate_ms": 0.0, "last_prep_ms": 0.0,
        }
        # Fault injection (faults/): resolved ONCE here so the dispatch
        # loops below pay a single None-check when LLMC_FAULTS is unset —
        # no injector code on the hot path unless a plan is installed.
        from llm_consensus_tpu import faults as _faults

        self._faults = _faults.plan()
        # Telemetry (obs/): same pattern — bound once, so disabled runs
        # consult nothing beyond this None on the decode/fetch hot loops.
        from llm_consensus_tpu import obs as _obs

        self._obs = _obs.recorder()
        # Spans go through the one emitter (obs/spans.py): recorder, flight
        # ring and — inside a profiler window — the device trace's clock.
        self._spans = _obs.emitter()
        # What the last prefill (``_prefill_ids`` or an admission wave)
        # dispatched, padding included: the pool's admission counts from it.
        self.last_prefill = Prefilled(0, 0, 0, 0)
        # Host ns the last ``_prefill_ids`` spent making its row cache
        # (``new_cache(1)``; 0 where a retained prefix was restored into
        # one): the pool's ``pool.admit`` span takes its ``alloc_ms`` here.
        self.last_prefill_alloc_ns = 0
        # A routed model's prefill programs return their routing sums
        # (``moe_stats``) only while someone collects them: the pool
        # scheduler opens this bank and drains it into its fetches
        # (engine/batcher.py); None, nothing is asked of the programs.
        self._moe_bank: Optional[list] = None
        # Chip-time attribution (obs/attrib): single-stream prefill and
        # decode walls book here; the weights register as a modeled
        # resident-HBM component for the watermark sentinel.
        self._attrib = _obs.attrib.ledger()
        if self._attrib is not None:
            try:
                from llm_consensus_tpu.utils.flops import param_count

                self._attrib.update_component(
                    f"weights:{cfg.name}",
                    int(param_count(cfg) * weight_itemsize))
            except Exception:  # noqa: BLE001 — modeling only
                pass
        from llm_consensus_tpu.kv import pool_for

        # ``kv_pool=False`` opts this engine out even when LLMC_KV_POOL
        # is on: a disaggregated PREFILL-ONLY engine must not allocate a
        # second arena nobody gathers from (its output publishes into
        # the DECODE engine's pool — engine/handoff.py), and duplicate
        # same-preset arenas would collide on the HBM-watermark
        # component key. Classic single-snapshot prefix reuse still
        # applies, so shared-prefix handoff waves keep their fork reuse.
        self._kv_pool = pool_for(self) if kv_pool else None
        # What the build cost and where the tree lives (/statsz
        # ``device.engines.<model>``, and the ``engine.build`` span).
        per_chip: dict = {}
        for leaf in jax.tree.leaves(params):
            for shard in leaf.addressable_shards:
                per_chip[shard.device.id] = (
                    per_chip.get(shard.device.id, 0) + shard.data.nbytes
                )
        from llm_consensus_tpu.utils.flops import (
            cache_bytes_per_token, state_bytes_per_row)

        self.build_stats = {
            "tp": int(dict(mesh.shape).get("tp", 1)) if mesh is not None else 1,
            "param_bytes_per_chip": max(per_chip.values(), default=0),
            "build_s": round((time.monotonic_ns() - t_build_ns) / 1e9, 3),
            # What a share of an expert-parallel layer holds (0 and 0 for a
            # model without a router), and what a token costs the cache.
            "experts_held": cfg.n_experts,
            "router_width": cfg.n_router,
            "cache_bytes_per_token": cache_bytes_per_token(
                cfg, 1 if self.kv_quant == "int8"
                else jnp.dtype(dtype).itemsize),
            # What a ROW costs beside its slots, and in how many layers (0
            # and 0 for a model without a state-space mixer).
            "state_bytes_per_row": state_bytes_per_row(
                cfg, jnp.dtype(dtype).itemsize),
            "ssm_layers": cfg.n_ssm_layers,
            "kda_layers": cfg.n_kda_layers,
            # Layers by what they hold in the cache: keys and values, and
            # nothing at all (a one-part expert layer); with ``ssm_layers``
            # they sum to the depth only where every layer is one part.
            "attn_layers": cfg.n_attn_layers,
            "expert_layers": cfg.n_expert_layers,
            # The widest chunk of the one-row path (on a TPU the model's own).
            "prefill_width": self.prefill_width,
        }
        self._spans.complete(
            "engine.build", t_build_ns, "engine", model=cfg.name,
            devices=sorted(per_chip), init_s=round(init_s, 3),
            **{k: v for k, v in self.build_stats.items() if k != "build_s"},
        )

    def _refuse_latent(self, mesh) -> None:
        """What a latent-attention (MLA) model does not get yet is refused
        by name when its engine is built, not computed wrongly."""
        from llm_consensus_tpu.kv import pool_enabled
        from llm_consensus_tpu.models.transformer import refuse_latent_mesh

        name = self.cfg.name
        if self.kv_quant is not None:
            raise ValueError(
                f"{name}: no {self.kv_quant} cache for a latent (MLA) model; "
                "unset LLMC_KV_QUANT / kv_quant")
        if pool_enabled():
            raise ValueError(
                f"{name}: the radix KV arena (LLMC_KV_POOL) does not hold a "
                "latent (MLA) cache")
        refuse_latent_mesh(self.cfg, mesh)

    def _refuse_ssm(self, mesh) -> None:
        """What a state-space model does not get yet is refused by name
        when its engine is built, not computed wrongly. (What is entered
        elsewhere refuses there through ``refuse_ssm``: speculation, the
        prefill session, the handoff of a live row.)"""
        from llm_consensus_tpu.kv import pool_enabled
        from llm_consensus_tpu.models.transformer import refuse_ssm_mesh

        name = self.cfg.name
        if self.kv_quant is not None:
            raise ValueError(
                f"{name}: no {self.kv_quant} cache for a state-space model; "
                "unset LLMC_KV_QUANT / kv_quant")
        if pool_enabled():
            raise ValueError(
                f"{name}: the radix KV arena (LLMC_KV_POOL) does not hold a "
                "state-space model's cache: a block of slots has no state")
        refuse_ssm_mesh(self.cfg, mesh)

    def _refuse_one_part(self, mesh) -> None:
        """What a stack of one-part layers without a state (window and full
        attention layers in one stack) does not get yet is refused by name
        when its engine is built."""
        from llm_consensus_tpu.kv import pool_enabled
        from llm_consensus_tpu.models.transformer import refuse_one_part_mesh

        if pool_enabled():
            raise ValueError(
                f"{self.cfg.name}: the radix KV arena (LLMC_KV_POOL) does not "
                "hold the cache of a stack of one-part layers (layer_kinds "
                f"{self.cfg.layer_kinds!r}): a block of slots is not reused "
                "across layers of different windows yet")
        refuse_one_part_mesh(self.cfg, mesh)

    @property
    def _moe_on(self) -> bool:
        return self._moe_bank is not None

    def _bank_moe(self, out: tuple) -> tuple:
        """Strip the routing sums a prefill program returned last (asked
        for with ``moe_stats=self._moe_on``) into the bank; the rest is
        what the program returns without them."""
        if not self._moe_on:  # opened for routed models only
            return out
        self._moe_bank.append(out[-1])
        return out[:-1]

    def _flash_guard(self, dispatch: Callable[[str], tuple]):
        """Run a jitted dispatch parameterized on attention impl; if the
        Pallas path fails to lower, pin this engine to XLA and retry.

        Routing a shape to XLA is forward()'s decision, taken from the
        kernels' support predicates, which agree with the compiler for
        every shape the compile tests cover (tests/test_tpu_compile.py).
        This guard is for the kernel the predicate admitted and Mosaic
        still refused: a serving process keeps answering through the
        always-correct XLA path instead of dying at first dispatch — and
        says so. The fallback warns, is counted in ``flash_fallbacks``
        (``attention_stats`` → /statsz ``device`` block), and
        chip_smoke.py and bench.py fail on a non-zero count. Retry is
        safe under buffer donation: a lowering error raises at compile
        time, before any donated buffer is consumed by an executable.
        """
        if self.attn_impl != "flash":
            return dispatch(self.attn_impl)
        try:
            return dispatch("flash")
        except Exception as e:  # noqa: BLE001 — filtered just below
            if not _is_pallas_lowering_error(e):
                raise
            import warnings

            warnings.warn(
                f"Pallas kernel failed to lower for {self.cfg.name}; "
                f"falling back to XLA attention for this engine: {e}",
                RuntimeWarning,
                stacklevel=2,
            )
            self.attn_impl = "xla"
            self.flash_fallbacks += 1
            return dispatch("xla")

    def new_cache(self, batch: int, max_seq: Optional[int] = None) -> dict:
        """A zeroed key/value cache of ``batch`` rows (``max_seq``: the
        engine's capacity unless given), made where it will live: on an
        engine with a mesh one jitted program per shape writes each
        chip's shard in place (``out_shardings``), so a pool's or a wave's
        cache never exists on the default device first — six rows of a 7B
        tp=2 pool were 3.2 GB made on chip 0 and then moved, and every
        judge admission half a gigabyte more (PR 25, on the chip)."""
        key = (batch, max_seq or self.max_seq)
        make = self._cache_makers.get(key)
        if make is None:
            make = partial(
                init_kv_cache, self.cfg, batch=batch, max_seq=key[1],
                dtype=self._dtype, quant=self.kv_quant,
            )
            if self.mesh is not None:
                from llm_consensus_tpu.parallel.sharding import cache_shardings

                make = jax.jit(make, out_shardings=cache_shardings(
                    self.cfg, self.mesh, jax.eval_shape(make)
                ))
            elif self._shard_fn is not None:
                whole, place = make, self._shard_fn
                make = lambda: place(whole())  # noqa: E731 — a caller's own placement
            self._cache_makers[key] = make
        return make()

    def attention_stats(self) -> dict:
        """The attention impl this engine was built with and runs now,
        how often the guard fell back, and the path forward() traced for
        this model's prefill and decode programs (``{phase: {path:
        programs}}`` — process-wide per model name, since compiled
        programs are shared between engines of one config)."""
        from llm_consensus_tpu.models.transformer import attention_routes

        return {
            "built": self.attn_built,
            "impl": self.attn_impl,
            "fallbacks": self.flash_fallbacks,
            "paths": attention_routes.snapshot(self.cfg.name),
        }

    def _decode_width(self, frontier: int) -> Optional[int]:
        """Static attention-width bucket covering ``frontier`` cache slots.

        Buckets are multiples of the floor's granule (128 by default —
        not powers of two): decode attention reads scale with batch ×
        width and the paged kernel runs near its bytes bound, so a
        616-slot frontier reading a 1024-wide pow2 bucket wastes ~40%
        of the attention bandwidth a 640-wide bucket doesn't; at serving
        batch the 128-granule beat 256 by ~7% decode-phase (shared-
        prefix suffix windows live between granule boundaries most of a
        generation). Finer buckets mean more compiled chunk programs as
        context grows (≤ max_seq/granule, amortized by the persistent
        XLA cache); every 128-multiple factors into Mosaic-legal kv
        blocks. None = full capacity (bucketing disabled, or the bucket
        reached capacity anyway — keeps the long-context program
        identical to the unbucketed one)."""
        if self._decode_kv_min <= 0:
            return None
        g = min(256, self._decode_kv_min)
        b = max(self._decode_kv_min, -(-frontier // g) * g)
        return None if b >= self.max_seq else b

    # -- live weight hot-swap ------------------------------------------------

    def pin_weights(self) -> int:
        """Refcount the RESIDENT weight buffer; returns its version.

        Non-blocking by contract: the batcher's scheduler thread pins at
        admission and must never wait behind a swap. Nesting is fine —
        ``generate_ids`` pins around a whole generation while the
        batcher pins per stream; the refcount composes."""
        with self._swap_lock:
            self._pins += 1
            return self.weight_version

    def unpin_weights(self) -> None:
        """Release one pin; the LAST unpin applies any pending swap.

        Extra unpins are ignored (the batcher's removal sites are
        idempotent per stream, but a crash path may race a retire)."""
        flipped = None
        with self._swap_lock:
            if self._pins > 0:
                self._pins -= 1
            if self._pins == 0 and self._pending_swap is not None:
                version, params, meta = self._pending_swap
                self._pending_swap = None
                flipped = version
                self._flip_locked(version, params, meta)
        if flipped is not None:
            self._post_flip()

    def swap_pending(self) -> bool:
        """True while a prepared version waits for pins to drain — the
        batcher's admission gate: new streams hold at the queue head so
        the resident set vacates instead of re-pinning forever."""
        with self._swap_lock:
            return self._pending_swap is not None

    def swap_weights(
        self,
        version: int,
        params,
        *,
        wait: bool = False,
        meta: Optional[dict] = None,
        prepared: bool = False,
    ) -> bool:
        """Install ``params`` as weight ``version`` (monotone int > the
        resident version; anything else is rejected and counted).

        Preparation — sharding onto this engine's mesh and quantization
        to its resident mode — happens OUTSIDE the swap lock under the
        ``swap`` attribution tag, so decode dispatch never stalls behind
        a device_put. The flip itself is immediate when no stream is
        pinned; otherwise the pair parks in the double buffer and the
        last ``unpin_weights`` applies it (``wait=True`` blocks up to
        LLMC_SWAP_WAIT_S for that). Returns True when the swap was
        ACCEPTED (applied or parked), False on rejection.

        ``prepared=True`` skips preparation — the rollback path hands
        back the previous resident buffer, which is already sharded and
        quantized (shard_fn cannot re-run on a quantized tree).
        """
        if self._faults is not None:
            fs = self._faults.fire(
                "swap", phase="apply", model=self.cfg.name, version=version
            )
            if fs is not None and fs.kind == "swap_mid_stream":
                # Hold the apply long enough that live streams are
                # mid-decode when it lands — forces the pending/double-
                # buffer path instead of an idle-engine instant flip.
                time.sleep(float(fs.param("s", 0.05)))
        with self._swap_lock:
            if int(version) <= self.weight_version or (
                self._pending_swap is not None
                and int(version) <= self._pending_swap[0]
            ):
                self._swap_stats["swap_rejects"] += 1
                return False
        t_prep = time.monotonic()
        if not prepared:
            with _attrib_tag("swap"):
                if self._shard_fn is not None:
                    params = self._shard_fn(params)
                if self.quant in ("int8", "int4"):
                    from llm_consensus_tpu.ops.quant import quantize_params

                    # donate: the incoming tree is the swap's private
                    # copy (checkpoint restore or caller handoff), and
                    # shard_fn above re-placed it; idempotent if the
                    # caller already quantized.
                    params = quantize_params(params, donate=True, mode=self.quant)
        prep_ms = (time.monotonic() - t_prep) * 1000.0
        flipped = False
        with self._swap_lock:
            if int(version) <= self.weight_version or (
                self._pending_swap is not None
                and int(version) <= self._pending_swap[0]
            ):
                # Lost a race to a concurrent swap while preparing: it
                # either already flipped, or parked this version (or a
                # newer one) in the double buffer — accepting too would
                # double-report one resident version. A strictly NEWER
                # version falls through and replaces the parked pair:
                # the freshest accepted checkpoint wins the flip.
                self._swap_stats["swap_rejects"] += 1
                return False
            self._swap_stats["last_prep_ms"] = prep_ms
            self._swap_requested = time.monotonic()
            if self._pins == 0:
                self._flip_locked(int(version), params, meta)
                flipped = True
            else:
                self._pending_swap = (int(version), params, meta)
                self._swap_stats["swap_queued"] += 1
                if wait:
                    deadline = (
                        time.monotonic() + knobs.get_float("LLMC_SWAP_WAIT_S")
                    )
                    while self.weight_version < int(version):
                        remaining = deadline - time.monotonic()
                        if remaining <= 0:
                            break
                        self._swap_cv.wait(timeout=remaining)
        if flipped:
            self._post_flip()
        return True

    def rollback_weights(self, meta: Optional[dict] = None) -> Optional[int]:
        """Swap BACK to the previous resident buffer (canary rollback).

        Version ids stay monotone — the restored buffer ships under a
        NEW version carrying ``rolled_back_to`` metadata, so routers and
        metrics never see a version number reappear. Returns the new
        version, or None when there is nothing to roll back to."""
        with self._swap_lock:
            if self._prev_weights is None:
                return None
            prev_version, prev_params = self._prev_weights
            new_version = self.weight_version + 1
            from_version = self.weight_version
        m = dict(meta or {})
        m.setdefault("rolled_back_to", prev_version)
        m.setdefault("rolled_back_from", from_version)
        if not self.swap_weights(
            new_version, prev_params, prepared=True, meta=m
        ):
            return None
        with self._swap_lock:
            self._swap_stats["rollbacks"] += 1
        return new_version

    def _flip_locked(self, version: int, params, meta: Optional[dict]) -> None:
        """The actual buffer flip; caller holds ``_swap_lock``."""
        self._prev_weights = (self.weight_version, self.params)
        self.params = params
        self.weight_version = version
        self.weight_meta = dict(meta or {})
        vacate_ms = max(
            0.0, (time.monotonic() - self._swap_requested) * 1000.0
        )
        self._swap_stats["swaps"] += 1
        self._swap_stats["last_vacate_ms"] = vacate_ms
        self._swap_cv.notify_all()
        try:
            from llm_consensus_tpu.obs import live as _live

            lm = _live.metrics()
            if lm is not None:
                lm.observe(
                    "swap_vacate", vacate_ms / 1000.0,
                    model=self.cfg.name, version=str(version),
                )
        except Exception:  # noqa: BLE001 — telemetry only
            pass

    def _post_flip(self) -> None:
        """Post-swap cache hygiene, OUTSIDE the swap lock.

        Every cached KV byte was computed by the OLD weights: the prefix
        snapshot drops, and the paged pool evicts everything cold. Pins
        guarantee no stream is resident at flip time, so no lease holds
        stale blocks hostage; the batcher additionally stamps its
        established prefix with the version it saw and re-establishes on
        mismatch (engine/batcher.py)."""
        with self._prefix_lock:
            self._prefix_ids = None
            self._prefix_cache = None
        pool = self._kv_pool
        if pool is not None:
            try:
                pool.evict_cold(0.0)
            except Exception:  # noqa: BLE001 — reuse degrades, never fatal
                pass

    def swap_stats(self) -> dict:
        """Swap counters + live pin state for /statsz and the bench."""
        with self._swap_lock:
            out = dict(self._swap_stats)
            out["weight_version"] = self.weight_version
            out["pins"] = self._pins
            out["swap_pending"] = 1 if self._pending_swap is not None else 0
            return out

    # -- prefix KV-cache -----------------------------------------------------

    def _reusable_prefix(self, prompt_ids: list[int]):
        """(common-prefix length, saved cache) against the last snapshot.

        The pair is read atomically so a concurrent generate can't leave a
        cache that doesn't match the ids it was compared against. Length is
        capped at n_prompt-1: at least one token must prefill to produce
        the next-token logits.
        """
        if not self.prefix_cache_enabled:
            return 0, None
        if self._kv_pool is not None:
            # Paged-pool path: radix match + block gather in place of the
            # single snapshot. min_tokens = the chunk length, mirroring
            # the classic reuse_ok gating (reuse below one chunk never
            # pays), so a sub-chunk match costs no gather dispatch.
            return self._kv_pool.lookup(
                prompt_ids, min_tokens=self.prefill_chunk or 1,
                shard_fn=self._shard_fn,
            )
        with self._prefix_lock:
            saved_ids, saved_cache = self._prefix_ids, self._prefix_cache
        if saved_ids is None or saved_cache is None:
            return 0, None
        import numpy as np

        max_l = min(len(saved_ids), len(prompt_ids) - 1)
        if max_l <= 0:
            return 0, None
        a = np.asarray(saved_ids[:max_l], dtype=np.int64)
        b = np.asarray(prompt_ids[:max_l], dtype=np.int64)
        neq = a != b
        lcp = int(np.argmax(neq)) if neq.any() else max_l
        return lcp, saved_cache

    def _retain_prefix(self, ids: list[int], cache) -> bool:
        """Keep the finished generation's cache for the next reuse.
        Returns True when a paged-pool publish was TRUNCATED (arena
        exhausted) — the per-response ``kv.truncated`` signal.

        Zero-copy: decode only ever writes at positions ≥ the ids it has
        produced, so the cache's [0, len(ids)) region is exactly the KV of
        ``ids`` (prompt + generated) — retaining the buffer costs no
        bandwidth, only residency, which LLMC_PREFIX_CACHE_MAX_MB caps so
        a huge-context cache can't silently double its HBM footprint.
        """
        if not self.prefix_cache_enabled:
            return False
        if self._kv_pool is not None:
            # Paged-pool path: scatter the finished cache's whole blocks
            # into the arena and index them (incremental — a repeated
            # prompt costs a host walk and no device work). The arena
            # budget (LLMC_KV_POOL_MB) replaces the single-snapshot byte
            # cap: residency is bounded however many prefixes are live.
            _wrote, truncated = self._kv_pool.publish(ids, cache)
            return truncated
        nbytes = sum(
            leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(cache)
        )
        if nbytes > self._prefix_max_bytes:
            return False
        with self._prefix_lock:
            self._prefix_ids = tuple(ids)
            self._prefix_cache = cache
        return False

    def _chunked_prefill(self, prompt_ids, n_prompt: int, cache, base: int,
                         chunk: int):
        """Prefill ``prompt_ids[base:]`` in fixed chunks (one compiled
        program, traced start; see _prefill_chunk). ``base`` > 0 resumes
        on top of restored prefix KV."""
        tail = n_prompt - base
        n_tail = -(-tail // chunk)
        padded = prompt_ids[base:] + [0] * (n_tail * chunk - tail)
        kv_width = _bucket(base + n_tail * chunk, self.max_seq)
        last_in_chunk = self._place(jnp.asarray([(tail - 1) % chunk]))
        # max_chunks is derived from kv_width alone, so the one-dispatch
        # program below is keyed exactly like the per-chunk program —
        # per (kv_width, chunk), never per prompt length.
        max_chunks = kv_width // chunk
        use_scan = (
            max_chunks >= n_tail
            and knobs.get_bool("LLMC_PREFILL_SCAN")
        )
        if use_scan:
            toks = self._place(
                jnp.asarray(
                    padded + [0] * ((max_chunks - n_tail) * chunk),
                    jnp.int32,
                ).reshape(max_chunks, 1, chunk)
            )
            last_logits, cache = self._bank_moe(_prefill_chunks_loop(
                self.params, self.cfg, toks,
                self._place(jnp.asarray(base, jnp.int32)),
                self._place(jnp.asarray(n_tail, jnp.int32)),
                last_in_chunk, cache, max_chunks=max_chunks,
                kv_width=kv_width, w8a8=self.w8a8, moe_stats=self._moe_on,
            ))
        else:
            for i in range(n_tail):
                toks = self._place(jnp.asarray(
                    padded[i * chunk:(i + 1) * chunk], jnp.int32
                )[None, :])
                last_logits, cache = self._bank_moe(_prefill_chunk(
                    self.params, self.cfg, toks,
                    self._place(jnp.asarray(base + i * chunk, jnp.int32)),
                    last_in_chunk, cache, kv_width=kv_width,
                    w8a8=self.w8a8, moe_stats=self._moe_on,
                    row_end=_row_end(self.cfg, self._place, [n_prompt]),
                ))
        # What was prefilled, for the caller's accounting.
        self.last_prefill = Prefilled(
            n_tail, n_tail * chunk,
            sum(prefill_pairs_swept(
                self.cfg, 1, chunk, kv_width, base + (i + 1) * chunk)
                for i in range(n_tail)),
            base)
        return last_logits, cache

    def _prefill_ids(self, prompt_ids: list[int]):
        """Prefill ``prompt_ids`` into a fresh (or prefix-restored) cache.

        Returns ``(last_logits [1, V], cache)``. Chooses between prefix
        reuse, sequence-parallel (ring) prefill, chunked prefill, and
        one-shot per-bucket prefill — shared by the single-stream decode
        loop and the continuous batcher's admission path.
        ``self.last_prefill`` then says what was dispatched (``Prefilled``).
        """
        if self._faults is not None:
            self._faults.check("prefill")  # injected device OOM / loss
        with self._spans.span(
            "prefill", "engine", model=self.cfg.name, tokens=len(prompt_ids),
        ) as sp:
            last_logits, cache, reused = self._prefill_ids_body(prompt_ids)
            sp.set(reused=reused)
        return last_logits, cache

    def _prefill_ids_body(self, prompt_ids: list[int]):
        cfg = self.cfg
        n_prompt = len(prompt_ids)
        sp = 1 if self.mesh is None else dict(self.mesh.shape).get("sp", 1)
        chunk_len = self.prefill_chunk
        n_chunks = -(-n_prompt // chunk_len) if chunk_len else 1
        sp_bucket = _bucket(max(n_prompt, sp), self.max_seq) if sp > 1 else 0
        # Prefix reuse needs the chunk program, so prefill_chunk=0 (the
        # documented chunking off-switch) disables it too.
        reuse_len, saved_cache = (
            self._reusable_prefix(prompt_ids) if chunk_len else (0, None)
        )
        n_tail = -(-(n_prompt - reuse_len) // chunk_len) if chunk_len else 0
        reuse_ok = (
            chunk_len > 0
            and reuse_len >= chunk_len
            and reuse_len + n_tail * chunk_len <= self.max_seq
        )
        self.last_prefill_alloc_ns = 0
        if not reuse_ok:
            t_alloc = time.monotonic_ns()
            cache = self.new_cache(1)
            self.last_prefill_alloc_ns = time.monotonic_ns() - t_alloc
        # Ring attention shards the bucket over sp; a bucket clamped to a
        # non-divisible max_seq can't, so it falls through to the
        # replicated-over-sp paths below (correct, just not seq-sharded).
        if reuse_ok:
            # Prefix reuse: restore the saved KV up to the common prefix
            # (one masked pass) and prefill only the tail — the
            # repeated-prefix pattern of --rounds / --continue / judge
            # refinements pays for the new tokens only.
            restore = (
                _restore_prefix_owned if self._kv_pool is not None
                else _restore_prefix
            )
            cache = restore(
                saved_cache, self._place(jnp.asarray(reuse_len, jnp.int32))
            )
            last_logits, cache = self._chunked_prefill(
                prompt_ids, n_prompt, cache, reuse_len, chunk_len
            )
        elif sp > 1 and sp_bucket % sp == 0:
            # Sequence-parallel prefill: the prompt shards over the sp
            # axis (ring attention), so per-chip prefill activation
            # footprint drops by the sp factor.
            bucket = sp_bucket
            padded = prompt_ids + [0] * (bucket - n_prompt)
            tokens = self._place(jnp.asarray(padded, jnp.int32)[None, :])
            last_logits, cache = _sp_prefill_step(
                self.params, cfg, tokens,
                self._place(jnp.asarray([n_prompt - 1])),
                cache, mesh=self.mesh,
            )
            self.last_prefill = Prefilled(1, bucket, bucket * bucket, 0)
        elif chunk_len and n_prompt > chunk_len and n_chunks * chunk_len <= self.max_seq:
            # Chunked prefill: the same compiled program dispatched per
            # chunk, dynamic start offset. Dispatches pipeline (no fetch
            # until the first decode chunk), so the host loop never stalls
            # the device. Padding junk in the final chunk lands at cache
            # positions ≥ n_prompt, which decode overwrites before its
            # causal frontier reaches them — same invariant the bucketed
            # path relies on.
            last_logits, cache = self._chunked_prefill(
                prompt_ids, n_prompt, cache, 0, self._chunk_width(n_prompt)
            )
        else:
            bucket = _bucket(n_prompt, self.max_seq)
            padded = prompt_ids + [0] * (bucket - n_prompt)
            tokens = self._place(jnp.asarray(padded, jnp.int32)[None, :])
            last_logits, cache = self._bank_moe(
                self._flash_guard(lambda impl: _prefill_step(
                    self.params, cfg, tokens,
                    self._place(jnp.asarray([n_prompt - 1])),
                    cache, attn_impl=impl, mesh=self.mesh, w8a8=self.w8a8,
                    moe_stats=self._moe_on,
                    row_end=_row_end(cfg, self._place, [n_prompt]),
                )))
            # The kernel is handed the prompt's bucket; the XLA routes are
            # given the cache, which here has the engine's whole capacity.
            slots = bucket if (
                self.attn_impl == "flash" and not cfg.is_latent
            ) else self.max_seq
            self.last_prefill = Prefilled(
                1, bucket, prefill_pairs_swept(cfg, 1, bucket, slots, bucket), 0)
        return last_logits, cache, reuse_len if reuse_ok else 0

    def _chunk_width(self, n_prompt: int) -> int:
        """The width of the chunks a prompt of ``n_prompt`` tokens past
        ``prefill_chunk`` prefills in on the one-row path: the model's
        (``prefill_width``) under the prompt's bucket and the score
        transient's cap (utils/flops.py ``prefill_chunk_width``), and
        narrower where a capacity that is no power of two would not hold
        the last chunk's padding."""
        from llm_consensus_tpu.utils.flops import prefill_chunk_width

        width = prefill_chunk_width(
            self.prefill_width, self.cfg.n_heads,
            _bucket(n_prompt, self.max_seq), self.prefill_chunk)
        while width > self.prefill_chunk and (
                -(-n_prompt // width) * width > self.max_seq):
            width //= 2
        return width

    def _rows_bucket(self, n_max: int) -> int:
        """Cache capacity ``_prefill_rows`` will allocate for a wave whose
        longest prompt is ``n_max`` — the batcher's admission width check
        must agree with it exactly (it splices full-capacity rows)."""
        bucket = _bucket(n_max, self.max_seq)
        chunk_len = self.prefill_chunk
        if (
            chunk_len
            and bucket > chunk_len
            and -(-bucket // chunk_len) * chunk_len <= self.max_seq
        ):
            bucket = -(-bucket // chunk_len) * chunk_len
        return bucket

    def admission_session(self, rows: list[list[int]], prefix_cache=None,
                          prefix_len: int = 0) -> "AdmissionPrefill":
        """A resumable batched admission prefill over ``rows``.

        The one-shot wrappers ``_prefill_rows`` / ``_prefill_rows_suffix``
        drive this session to completion in a single ``step(None)``; the
        continuous batcher's interleaved-admission path paces ``step``
        with a token budget so decode chunks dispatch BETWEEN prefill
        chunks (prefill never stalls an active decode frontier)."""
        return AdmissionPrefill(self, rows, prefix_cache, prefix_len)

    def prefill_session(self) -> "PrefillSession":
        """An incremental prefill session: token chunks append to one
        growing KV cache as they become known (the judge-overlap half of
        the prefill/decode overlap mechanism)."""
        refuse_ssm(self.cfg, "incremental prefill session")
        return PrefillSession(self)

    def _prefill_rows(self, rows: list[list[int]]):
        """Batched admission prefill: k prompts in ONE set of dispatches
        (left-aligned rows padded to a shared bucket).

        Serving bursts admit many streams at once; prefilling them
        row-by-row streams the full weights k times (batch-1 prefill is
        as HBM-bound as decode), while one [k, bucket] prefill streams
        them once — the admission-side analog of ``generate_batch``. Left
        alignment keeps absolute positions row-relative (no ``row_start``),
        so each KV row splices into the continuous batcher's
        shared-frontier cache unchanged (batcher ``_splice_row``); pad
        junk past a row's prompt lands at source slots its splice width
        maps to positions ≥ the shared frontier, which decode overwrites
        before reading. Returns ``(last_logits [k, V], cache)``; the
        cache's capacity is the bucket, not ``max_seq`` — the caller
        copies rows out, so full-capacity residency would be wasted HBM.
        """
        session = AdmissionPrefill(self, rows)
        session.step(None)
        last_logits, cache, _ = session.finish()
        return last_logits, cache

    def _prefill_rows_suffix(self, rows_sfx: list[list[int]], prefix_cache,
                             plen: int):
        """Batched SUFFIX admission prefill against a shared-prefix KV.

        The continuous batcher's one-prompt fan-out pattern: when every
        stream of a wave shares the pool's established prompt prefix,
        only the per-stream tails need to run through the model — each
        suffix token attends the prefix (via the exact two-source
        softmax merge, ops/attention.py) plus its own causal window,
        with positions offset by ``plen``. Returns ``(last_logits [k, V],
        cache [k, ws], ws)`` where the cache holds ONLY suffix KV —
        admission splices it behind the prefix semantics, so a wave's
        prefill compute scales with the NEW tokens, not the shared
        prompt (measured as the dominant serving wall at large batch:
        ~1.2 s per 128×512-token wave).
        """
        session = AdmissionPrefill(self, rows_sfx, prefix_cache, plen)
        session.step(None)
        return session.finish()

    # -- token-level API -----------------------------------------------------

    def generate_ids(
        self,
        prompt_ids: list[int],
        sampling: SamplingParams = SamplingParams(),
        ctx: Optional[Context] = None,
        on_token: Optional[Callable[[int], None]] = None,
    ) -> GenerateResult:
        # Pin the resident weights for the whole generation: a hot-swap
        # landing mid-stream parks in the double buffer until this (and
        # every other pinned) stream retires — the single-stream half of
        # the batcher's per-stream version pinning.
        self.pin_weights()
        try:
            return self._generate_ids_pinned(prompt_ids, sampling, ctx, on_token)
        finally:
            self.unpin_weights()

    def _generate_ids_pinned(
        self,
        prompt_ids: list[int],
        sampling: SamplingParams,
        ctx: Optional[Context],
        on_token: Optional[Callable[[int], None]],
    ) -> GenerateResult:
        ctx = ctx or Context.background()
        start_time = time.monotonic()
        n_prompt = len(prompt_ids)
        if n_prompt == 0:
            raise ValueError("empty prompt")
        if n_prompt >= self.max_seq:
            raise ValueError(
                f"prompt length {n_prompt} exceeds max sequence length {self.max_seq}"
            )
        max_new = min(sampling.max_new_tokens, self.max_seq - n_prompt)
        if max_new <= 0:
            return GenerateResult(
                token_ids=[], text="", finish_reason="length",
                prompt_tokens=n_prompt,
                latency_ms=(time.monotonic() - start_time) * 1000,
            )

        t_pf = time.monotonic()
        with _attrib_tag("prefill"):
            last_logits, cache = self._prefill_ids(prompt_ids)
        if self._attrib is not None:
            # Single-stream prefill wall (dispatch-synchronous on CPU;
            # on-device residue surfaces in the first decode interval).
            self._attrib.observe_device("prefill", time.monotonic() - t_pf)
        return self._decode_stream(
            prompt_ids, last_logits, cache, sampling, ctx, on_token,
            start_time,
        )

    def _decode_stream(
        self,
        prompt_ids: list[int],
        last_logits,
        cache,
        sampling: SamplingParams,
        ctx: Context,
        on_token: Optional[Callable[[int], None]],
        start_time: float,
    ) -> GenerateResult:
        """The streamed decode loop over an ESTABLISHED cache — shared by
        ``generate_ids`` (one-shot prefill) and :class:`PrefillSession`
        (incremental prefill), so both prefill forms feed token-for-token
        the same decode pipeline (one-chunk lookahead, fetch-boundary
        rate clock, prefix retention)."""
        cfg = self.cfg
        n_prompt = len(prompt_ids)
        max_new = min(sampling.max_new_tokens, self.max_seq - n_prompt)
        key = self._place(jax.random.PRNGKey(sampling.seed))
        token = sample_token(
            last_logits, jax.random.fold_in(key, n_prompt - 1),
            temperature=sampling.temperature, top_k=sampling.top_k, top_p=sampling.top_p,
        )

        eos = -1 if sampling.ignore_eos else self.tokenizer.eos_id
        out_ids: list[int] = []
        finish = "length"
        pos = n_prompt
        chunk = self.stream_interval
        sample_args = (sampling.temperature, sampling.top_k, sampling.top_p)

        def emit(tok_ids) -> bool:
            """Accept fetched token ids; True if generation should stop."""
            nonlocal finish
            for tok_id in tok_ids:
                if tok_id == eos:
                    finish = "eos"
                    return True
                if len(out_ids) >= max_new:
                    return True
                out_ids.append(tok_id)
                if attrib is not None:
                    # Goodput ledger: the single-stream twin of the
                    # batcher's one-useful-per-appended-token invariant.
                    attrib.token_event("useful", 1)
                if on_token is not None:
                    on_token(tok_id)
            return False

        # The prefill-sampled token rides down with the first chunk fetch.
        first: Optional[jax.Array] = token
        stopped = False
        # Decode-rate clock: starts at the first fetch boundary (prefill +
        # chunk 1 forced complete), so it measures steady-state decode only.
        t_first_fetch: Optional[float] = None
        n_at_first_fetch = 0
        t_last_fetch = 0.0
        n_at_last_fetch = 0

        def tick_decode_clock() -> None:
            """Advance the rate clock at a fetch boundary (tokens already
            emitted); tokens and window always snapshot together."""
            nonlocal t_first_fetch, n_at_first_fetch, t_last_fetch, n_at_last_fetch
            now = time.monotonic()
            if t_first_fetch is None:
                t_first_fetch = now
                n_at_first_fetch = len(out_ids)
            else:
                t_last_fetch = now
                n_at_last_fetch = len(out_ids)
        # Telemetry: the emitter was bound at engine construction
        # (obs/__init__.py); per chunk, one span at dispatch and one at
        # fetch, each written once to the sinks that were on then.
        spans = self._spans
        # Chip-time attribution: fetch-to-fetch intervals are the
        # single-stream decode wall (the batcher's arrival-interval twin).
        attrib = self._attrib
        t_attr = time.monotonic()

        def fetch(toks) -> None:
            """Fetch one dispatched chunk's token ids and emit them; the
            prefill-sampled token rides down with the first fetch."""
            nonlocal first, stopped
            # The span covers transfer + emit (the documented span names).
            with spans.span("fetch", "engine", model=cfg.name) as sp:
                if first is not None:
                    first_id, tok_mat = jax.device_get((first, toks))
                    fetched = (
                        [int(first_id[0])] + [int(t) for t in tok_mat[:, 0]]
                    )
                    first = None
                else:
                    fetched = [int(t) for t in jax.device_get(toks)[:, 0]]
                stopped = emit(fetched)
                sp.set(tokens=len(fetched))
            if attrib is not None:
                nonlocal t_attr
                now = time.monotonic()
                attrib.observe_device("decode", now - t_attr)
                t_attr = now
            tick_decode_clock()

        # Pipelined decode, one chunk of lookahead: chunk N+1 is dispatched
        # BEFORE chunk N's tokens are fetched, so the device starts the next
        # program while the host waits on the transfer and runs the emit
        # callbacks. At EOS/max_new/cancel up
        # to one chunk of speculative steps is dropped — cheap next to the
        # device idling at every fetch. Inside the last chunk's worth of
        # cache slots, dispatches shrink to a cached 1-step program.
        inflight: Optional[jax.Array] = None  # dispatched, unfetched tokens
        inflight_n = 0
        while not stopped:
            pending = inflight_n + (1 if first is not None else 0)
            need = max_new - len(out_ids) - pending
            if need <= 0:
                break  # already dispatched everything needed; drain below
            # Cancellation only aborts outstanding work — a deadline that
            # lands while the final tokens drain must not mark a complete
            # generation as failed.
            if ctx.done():
                finish = "deadline" if ctx.remaining() == 0.0 else "cancelled"
                stopped = True
                break
            toks = None
            if pos < self.max_seq:
                if self._faults is not None:
                    self._faults.check("decode")  # injected device loss
                    if self.weight_version > 0:
                        # Canary-regression injection: a swapped-in
                        # (version > 0) engine's decode slows by @s per
                        # chunk — the regression the CanaryWatcher must
                        # catch and roll back.
                        fs = self._faults.fire(
                            "swap", phase="decode", model=cfg.name,
                            version=self.weight_version,
                        )
                        if fs is not None and fs.kind == "canary_regress":
                            time.sleep(float(fs.param("s", 0.05)))
                n_steps = chunk if pos + chunk <= self.max_seq else 1
                # Host dispatch wall (the async enqueue, not device
                # time — the ~40%-host-on-dispatch finding's signal).
                with spans.span(
                    "decode", "engine", model=cfg.name, steps=n_steps,
                ), _attrib_tag("decode"):
                    token, toks, cache = self._flash_guard(
                        lambda impl: _decode_chunk(
                            self.params, cfg, token, pos, cache, key, n_steps,
                            *sample_args,
                            kv_width=self._decode_width(pos + n_steps),
                            attn_impl=impl, mesh=self.mesh, w8a8=self.w8a8,
                        )
                    )
                pos += n_steps
            if inflight is not None:
                fetch(inflight)  # overlaps the just-dispatched program
            elif toks is None:
                break  # nothing running and nothing left to dispatch
            inflight, inflight_n = toks, (n_steps if toks is not None else 0)
        if not stopped and inflight is not None:
            fetch(inflight)
        if not stopped and first is not None and len(out_ids) < max_new:
            emit([int(jax.device_get(first)[0])])

        # Retain the finished cache for prefix reuse: its [0, len(ids))
        # region holds exactly the KV of prompt + emitted tokens (decode
        # writes beyond may include dropped speculative steps, which the
        # ids cap excludes from any future match).
        kv_truncated = self._retain_prefix(prompt_ids + out_ids, cache)

        decode_tokens = 0
        decode_s = 0.0
        if t_first_fetch is not None and t_last_fetch > t_first_fetch:
            decode_tokens = n_at_last_fetch - n_at_first_fetch
            decode_s = t_last_fetch - t_first_fetch
        return GenerateResult(
            token_ids=out_ids,
            text=self.tokenizer.decode(out_ids),
            finish_reason=finish,
            prompt_tokens=n_prompt,
            latency_ms=(time.monotonic() - start_time) * 1000,
            decode_tokens=decode_tokens,
            decode_s=decode_s,
            kv_truncated=bool(kv_truncated),
        )

    # -- batched API ---------------------------------------------------------

    def generate_batch(
        self,
        prompts: list[str],
        sampling: SamplingParams = SamplingParams(),
        ctx: Optional[Context] = None,
    ) -> list[GenerateResult]:
        """Decode ``len(prompts)`` streams in one batch.

        Single-stream decode is HBM-bound — the weights stream from HBM
        once per step regardless of batch — so batching multiplies
        aggregate tokens/sec almost for free until the MXU saturates.
        Rows are right-aligned (left-padded) to one bucket with per-row
        position offsets, so heterogeneous prompt lengths share every
        compiled program; finished rows keep stepping (their output is
        dropped) until all rows finish, the standard static-shape trade.
        The consensus CLI drives one stream per panel model; this is the
        serving-throughput API.
        """
        self.pin_weights()  # whole batch finishes on one weight version
        try:
            return self._generate_batch_pinned(prompts, sampling, ctx)
        finally:
            self.unpin_weights()

    def _generate_batch_pinned(
        self,
        prompts: list[str],
        sampling: SamplingParams,
        ctx: Optional[Context],
    ) -> list[GenerateResult]:
        ctx = ctx or Context.background()
        start_time = time.monotonic()
        cfg = self.cfg
        if not prompts:
            return []
        rows: list[list[int]] = []
        truncated: list[bool] = []
        for p in prompts:
            ids, trunc = self._budget_prompt(
                self.tokenizer.encode(p), sampling.max_new_tokens
            )
            if not ids:
                raise ValueError("empty prompt")
            rows.append(ids)
            truncated.append(trunc)
        n_max = max(len(r) for r in rows)
        if n_max >= self.max_seq:
            raise ValueError(
                f"prompt length {n_max} exceeds max sequence length {self.max_seq}"
            )
        b = len(rows)
        bucket = _bucket(n_max, self.max_seq)
        if bucket >= self.max_seq:
            # Decode slots start at the shared bucket, so a bucket that
            # rounds up to max_seq would leave zero room; exact-fit keeps
            # max_seq - n_max steps (one compile per distinct n_max, but
            # only in this boundary regime).
            bucket = n_max
        # Long buckets prefill in chunks like the single-stream path —
        # one-shot XLA attention would materialize [B, H, bucket, bucket]
        # scores. Rows stay right-aligned to a chunk multiple.
        chunk_len = self.prefill_chunk
        use_chunks = bool(chunk_len) and bucket > chunk_len
        if use_chunks:
            pad_to = -(-bucket // chunk_len) * chunk_len
            if pad_to >= self.max_seq:
                use_chunks = False
            else:
                bucket = pad_to
        max_new = min(sampling.max_new_tokens, self.max_seq - bucket)
        row_start_list = [bucket - len(r) for r in rows]
        padded = [[0] * s + r for s, r in zip(row_start_list, rows)]
        row_start = self._place(jnp.asarray(row_start_list, jnp.int32))
        last_index = self._place(jnp.full((b,), bucket - 1, jnp.int32))
        cache = self.new_cache(b)
        if use_chunks:
            n_chunks = bucket // chunk_len
            last_in_chunk = self._place(
                jnp.full((b,), (bucket - 1) % chunk_len, jnp.int32)
            )
            for i in range(n_chunks):
                toks = self._place(jnp.asarray(
                    [r[i * chunk_len:(i + 1) * chunk_len] for r in padded],
                    jnp.int32,
                ))
                last_logits, cache = _prefill_chunk(
                    self.params, cfg, toks,
                    self._place(jnp.asarray(i * chunk_len, jnp.int32)),
                    last_in_chunk, cache, kv_width=bucket,
                    row_start=row_start, w8a8=self.w8a8,
                )
        else:
            tokens = self._place(jnp.asarray(padded, jnp.int32))
            last_logits, cache = _prefill_step(
                self.params, cfg, tokens, last_index, cache,
                attn_impl="xla", mesh=None, row_start=row_start,
                kv_width=bucket, w8a8=self.w8a8,
            )
        key = self._place(jax.random.PRNGKey(sampling.seed))
        token = sample_token(
            last_logits, jax.random.fold_in(key, bucket - 1),
            temperature=sampling.temperature, top_k=sampling.top_k,
            top_p=sampling.top_p,
        )

        eos = -1 if sampling.ignore_eos else self.tokenizer.eos_id
        out_ids: list[list[int]] = [[] for _ in range(b)]
        finish = ["length"] * b
        done = [max_new <= 0] * b
        pos = bucket
        chunk = self.stream_interval
        sample_args = (sampling.temperature, sampling.top_k, sampling.top_p)

        def emit(step_tokens) -> None:
            for i in range(b):
                if done[i]:
                    continue
                tok = int(step_tokens[i])
                if tok == eos:
                    finish[i] = "eos"
                    done[i] = True
                    continue
                out_ids[i].append(tok)
                if len(out_ids[i]) >= max_new:
                    done[i] = True

        # One-chunk lookahead like the single-stream loop: chunk N+1 is
        # dispatched before chunk N's tokens are fetched. Chunks are only
        # ever chunk-sized or 1-step (cache tail), so the compile set
        # stays fixed; dispatch overshoot past EOS/max_new is dropped by
        # emit, cheap next to the device idling at every fetch.
        first = token if max_new > 0 else None
        inflight = None
        steps_needed = max_new - 1  # tokens beyond the prefill-sampled one
        steps_dispatched = 0

        def fetch(toks) -> None:
            nonlocal first
            if first is not None:
                first_ids, mat = jax.device_get((first, toks))
                emit(first_ids)
                first = None
            else:
                mat = jax.device_get(toks)
            for step in mat:
                emit(step)

        while not all(done):
            if ctx.done():
                reason = "deadline" if ctx.remaining() == 0.0 else "cancelled"
                for i in range(b):
                    if not done[i]:
                        finish[i] = reason
                break
            toks = None
            if steps_dispatched < steps_needed and pos < self.max_seq:
                n_steps = chunk if pos + chunk <= self.max_seq else 1
                token, toks, cache = self._flash_guard(
                    lambda impl: _decode_chunk(
                        self.params, cfg, token, pos, cache, key, n_steps,
                        *sample_args, row_start=row_start,
                        kv_width=self._decode_width(pos + n_steps),
                        attn_impl=impl, mesh=self.mesh, w8a8=self.w8a8,
                    )
                )
                steps_dispatched += n_steps
                pos += n_steps
            if inflight is not None:
                fetch(inflight)
            elif toks is None:
                break
            inflight = toks
        # Every loop exit leaves inflight drained (fetches happen inside
        # the iteration); only the prefill-sampled token can still be
        # pending, when max_new == 1 dispatched no chunks at all.
        if not all(done) and first is not None and not ctx.done():
            emit(jax.device_get(first))

        return [
            GenerateResult(
                token_ids=out_ids[i],
                text=self.tokenizer.decode(out_ids[i]),
                finish_reason=finish[i],
                prompt_tokens=len(rows[i]),
                latency_ms=(time.monotonic() - start_time) * 1000,
                truncated_prompt=truncated[i],
            )
            for i in range(b)
        ]

    # -- text-level API ------------------------------------------------------

    def _prompt_budget(self, max_new: int) -> int:
        """Prompt tokens the context window affords next to a ``max_new``
        decode reserve — the single owner of the truncation threshold,
        shared by ``_budget_prompt`` and the judge-overlap shim (which
        must FALL BACK to the truncating path at exactly the length the
        classic path would truncate)."""
        budget = self.max_seq - 1 - min(max_new, max(16, self.max_seq // 4))
        # Tiny max_seq can drive the reserve above max_seq; always keep at
        # least half the window for the prompt (generate_ids re-clamps
        # max_new against what remains).
        return max(budget, self.max_seq // 2, 1)

    def _budget_prompt(self, prompt_ids: list[int], max_new: int) -> tuple[list[int], bool]:
        """Middle-out truncation when the prompt exceeds the context budget.

        The judge prompt concatenates every panel answer (consensus/judge.py,
        reference template judge.go:21-25) with no length cap, so it can
        outgrow max_seq. Keeping head + tail preserves the instruction
        preamble and the final answers + closing directive; the middle is
        the least load-bearing. Long-term fix for big models is sharded
        long-prefill (parallel/ring.py) — this is the single-chip fallback.
        """
        budget = self._prompt_budget(max_new)
        if len(prompt_ids) <= budget:
            return prompt_ids, False
        head = budget // 2
        tail = budget - head
        return prompt_ids[:head] + prompt_ids[-tail:], True

    def generate(
        self,
        prompt: str,
        sampling: SamplingParams = SamplingParams(),
        ctx: Optional[Context] = None,
        on_text: Optional[Callable[[str], None]] = None,
    ) -> GenerateResult:
        prompt_ids = self.tokenizer.encode(prompt)
        prompt_ids, truncated = self._budget_prompt(
            prompt_ids, sampling.max_new_tokens
        )
        decoder = StreamDecoder(self.tokenizer)
        parts: list[str] = []

        def on_token(tok_id: int) -> None:
            text = decoder.push(tok_id)
            if text:
                parts.append(text)
                if on_text is not None:
                    on_text(text)

        result = self.generate_ids(prompt_ids, sampling, ctx, on_token)
        tail = decoder.flush()
        if tail:
            parts.append(tail)
            if on_text is not None:
                on_text(tail)
        result.text = "".join(parts)
        result.truncated_prompt = truncated
        return result


class AdmissionPrefill:
    """Resumable batched admission prefill (one wave of k rows).

    Exactly the dispatches ``_prefill_rows`` / ``_prefill_rows_suffix``
    always made — same chunk programs, same buckets, same wave
    prefix-snapshot reuse — but ``step(token_budget)`` lets the CALLER
    pace them: the continuous batcher dispatches one budget's worth of
    prefill chunks between decode chunks, so resident streams keep
    decoding while a new wave establishes its KV (the interleaved-
    admission half of the prefill/decode overlap mechanism). ``step``
    always dispatches at least one chunk, so progress is guaranteed;
    ``step(None)`` runs to completion, which IS the classic path —
    byte-identical dispatch sequence, one caller frame deeper.

    ``prefix_cache`` switches the wave to SUFFIX form: rows are suffixes
    prefilled against the pool's shared-prefix KV (positions offset by
    ``prefix_len``), and the finished cache holds only suffix KV.
    """

    def __init__(self, engine: Engine, rows: list[list[int]],
                 prefix_cache=None, prefix_len: int = 0):
        if engine._faults is not None:
            engine._faults.check("prefill")  # injected device OOM / loss
        self._eng = engine
        self.rows = rows
        self.k = len(rows)
        self._prefix_cache = prefix_cache
        self._plen = prefix_len
        self._suffix = prefix_cache is not None
        n_max = max(len(r) for r in rows)
        chunk_len = engine.prefill_chunk
        self._chunk_len = chunk_len
        if self._suffix:
            self.width = _bucket(n_max, engine.max_seq)
        else:
            self.width = engine._rows_bucket(n_max)
        # Long buckets prefill in fixed chunks (same program each chunk,
        # traced start) so peak attention memory is [k, chunk, width]
        # scores, never [k, width, width]. A bucket capped at a
        # non-chunk-multiple max_seq cannot chunk (flooring n_chunks
        # would silently drop the tail tokens) and takes the one-shot
        # path instead.
        self._use_chunks = (
            bool(chunk_len)
            and self.width > chunk_len
            and self.width % chunk_len == 0
        )
        # Wave prefix reuse (the panel's one-prompt fan-out pattern): when
        # every row shares the engine snapshot's prefix for at least one
        # whole chunk, fork the snapshot across the k rows and prefill
        # only the tail chunks — prefill compute scales with the NEW
        # tokens, not the shared prompt. Whole chunks only, so the tail
        # loop stays on the same compiled program. (Full-prompt waves
        # only: suffix waves already carry the pool's prefix.)
        reuse_base = 0
        saved_cache = None
        self._common: list = []
        if not self._suffix and self._use_chunks and engine.prefix_cache_enabled:
            common = rows[0]
            for r in rows[1:]:
                m = min(len(common), len(r))
                i = 0
                while i < m and common[i] == r[i]:
                    i += 1
                common = common[:i]
            self._common = common
            lcp, snap = engine._reusable_prefix(list(common))
            base = (lcp // chunk_len) * chunk_len
            if base >= chunk_len and snap is not None:
                reuse_base, saved_cache = base, snap
        if saved_cache is not None:
            cache = _fork_prefix(
                saved_cache,
                engine._place(jnp.asarray(reuse_base, jnp.int32)),
                self.k, self.width,
            )
            if engine._shard_fn is not None:
                cache = engine._shard_fn(cache)
        else:
            cache = engine.new_cache(self.k, self.width)
        self._cache = cache
        self._padded = [r + [0] * (self.width - len(r)) for r in rows]
        self._plen_dev = (
            engine._place(jnp.asarray(prefix_len, jnp.int32))
            if self._suffix else None
        )
        self._n_chunks = self.width // chunk_len if self._use_chunks else 1
        self._first_chunk = reuse_base // chunk_len if self._use_chunks else 0
        self._next_chunk = self._first_chunk
        self._per_chunk: list = []
        self._last_logits = None
        self._done = False

    @property
    def chunks(self) -> int:
        """Chunk programs this wave dispatches (1 for a one-shot bucket);
        chunks forked from a retained prefix are not dispatched."""
        return self._n_chunks - self._first_chunk

    @property
    def slot_tokens(self) -> int:
        """Token slots the wave's dispatches cover: rows × chunks × chunk
        length (rows × bucket for a one-shot) — padding rows, padding
        inside rows and all. The real tokens are the caller's to count."""
        per_row = (
            self.chunks * self._chunk_len if self._use_chunks else self.width
        )
        return self.k * per_row

    @property
    def pairs_swept(self) -> int:
        """(Query, key) pairs the wave's dispatches sweep
        (``prefill_pairs_swept``, a program at a time), padding as in
        ``slot_tokens``."""
        prefix_slots = self._prefix_cache["k"].shape[2] if self._suffix else 0
        t = self._chunk_len if self._use_chunks else self.width
        return sum(
            prefill_pairs_swept(
                self._eng.cfg, self.k, t, self.width, (c + 1) * t, prefix_slots)
            for c in range(self._first_chunk, self._n_chunks)
        )

    @property
    def remaining_tokens(self) -> int:
        """Total prompt tokens (rows × chunk length) not yet dispatched —
        the batcher's credit ledger sizes its interleave pacing off this."""
        if self._done:
            return 0
        if not self._use_chunks:
            return self.k * self.width
        return self.k * self._chunk_len * (self._n_chunks - self._next_chunk)

    def step(self, token_budget: Optional[int]) -> bool:
        """Dispatch prefill chunks until ``token_budget`` TOTAL prompt
        tokens (rows × chunk length) have been enqueued this call — at
        least one chunk regardless, so a tiny budget still progresses.
        ``None`` runs to completion. Returns True once every dispatch for
        the wave has been made (``finish`` may then be called)."""
        if self._done:
            return True
        eng = self._eng
        place = eng._place
        cfg = eng.cfg
        # Where each (left-aligned) row's real tokens end: a state-space
        # model's state must not run on into the padding.
        row_end = _row_end(cfg, place, [len(r) for r in self.rows])
        if not self._use_chunks:
            # One-shot per-bucket program: indivisible by construction.
            tokens = place(jnp.asarray(self._padded, jnp.int32))
            last_index = place(
                jnp.asarray([len(r) - 1 for r in self.rows], jnp.int32)
            )
            if self._suffix:
                self._last_logits, self._cache = eng._bank_moe(_prefill_step(
                    eng.params, cfg, tokens, last_index, self._cache,
                    attn_impl="xla", mesh=eng.mesh,
                    prefix=self._prefix_cache, prefix_len=self._plen_dev,
                    w8a8=eng.w8a8, moe_stats=eng._moe_on,
                ))
            else:
                self._last_logits, self._cache = eng._bank_moe(eng._flash_guard(
                    lambda impl: _prefill_step(
                        eng.params, cfg, tokens, last_index, self._cache,
                        attn_impl=impl, mesh=eng.mesh, w8a8=eng.w8a8,
                        moe_stats=eng._moe_on, row_end=row_end,
                    )
                ))
            self._done = True
            return True
        chunk_len = self._chunk_len
        spent = 0
        while self._next_chunk < self._n_chunks:
            c = self._next_chunk
            toks = place(jnp.asarray(
                [p[c * chunk_len:(c + 1) * chunk_len]
                 for p in self._padded],
                jnp.int32,
            ))
            # Per-row "last token in THIS chunk" index, clamped: rows
            # whose last token lies elsewhere produce a logit nobody
            # reads; the gather in finish() selects each row's real
            # chunk.
            idx = place(jnp.asarray(
                [min(max(len(r) - 1 - c * chunk_len, 0), chunk_len - 1)
                 for r in self.rows],
                jnp.int32,
            ))
            lg, self._cache = eng._bank_moe(_prefill_chunk(
                eng.params, cfg, toks,
                place(jnp.asarray(c * chunk_len, jnp.int32)),
                idx, self._cache, kv_width=self.width,
                prefix=self._prefix_cache, prefix_len=self._plen_dev,
                w8a8=eng.w8a8, moe_stats=eng._moe_on, row_end=row_end,
            ))
            self._per_chunk.append(lg)
            self._next_chunk += 1
            spent += self.k * chunk_len
            if token_budget is not None and spent >= token_budget:
                break
        if self._next_chunk >= self._n_chunks:
            self._done = True
        return self._done

    def finish(self):
        """(last_logits [k, V], cache, width): gather each row's real
        last-token logits, retain the wave snapshot (full-prompt waves
        whose rows share a chunk-sized prefix)."""
        eng = self._eng
        if self._use_chunks:
            if len(self._per_chunk) == 1:
                last_logits = self._per_chunk[0]
            else:
                stacked = jnp.stack(self._per_chunk)  # [C - first, k, V]
                sel = jnp.asarray(
                    [(len(r) - 1) // self._chunk_len - self._first_chunk
                     for r in self.rows],
                    jnp.int32,
                )
                last_logits = stacked[sel, jnp.arange(self.k)]
        else:
            last_logits = self._last_logits
        cache = self._cache
        # Retain row 0 as the next wave's snapshot (re-padded to full
        # capacity so the single-stream reuse invariants hold): bursts of
        # consensus traffic share the prompt across waves, and without
        # batcher-side retention a pool that never runs a single-stream
        # generate would never build a snapshot at all. ONLY waves whose
        # rows themselves share a chunk-sized prefix retain — a wave of
        # unrelated prompts has no evidence of prefix traffic, and
        # overwriting the single snapshot slot with it would evict a
        # single-stream user's (e.g. --continue's) live prefix while
        # paying a full-capacity copy for nothing.
        # Lone-row waves retain only under the pool: overwriting the
        # single snapshot slot with an unrelated prompt would evict a
        # live prefix, but a pool publish evicts nobody — and repeat
        # single-request traffic (coalescing near-misses) is exactly
        # what the radix exists to make near-free. The staleness check
        # sits LAST: under the pool it is a radix walk behind the pool
        # lock (covers() — retain unless the radix already holds row
        # 0's publishable whole-block span, the snapshot-equality gate's
        # analog), and suffix/non-chunked waves that can never retain
        # must not contend on it.
        if (
            not self._suffix
            and self._use_chunks
            and eng.prefix_cache_enabled
            and (len(self.rows) > 1 or eng._kv_pool is not None)
            and len(self._common) >= self._chunk_len
            and (
                not eng._kv_pool.covers(self.rows[0])
                if eng._kv_pool is not None
                else eng._prefix_ids != tuple(self.rows[0])
            )
        ):
            template = eng.new_cache(1)
            eng._retain_prefix(
                self.rows[0], _extract_row0(template, cache, self.width)
            )
        # What the wave dispatched: the pool's ``pool.admit`` span and its
        # counters read it (there is no span of the session's own).
        eng.last_prefill = Prefilled(
            self.chunks, self.slot_tokens, self.pairs_swept,
            self._first_chunk * self._chunk_len)
        return last_logits, cache, self.width


class PrefillSession:
    """Incremental prefill: append token chunks to ONE growing KV cache.

    The judge-overlap half of the prefill/decode overlap mechanism
    (consensus/overlap.py): the judge prompt's header and each panel
    answer prefill the moment they exist — through the SAME compiled
    ``_prefill_chunk`` program the engine's chunked prefill uses (traced
    ``start_pos``, so one program per (kv_width, chunk)) — instead of
    serially after the last answer lands. ``generate`` pads + prefills
    the residue shorter than a chunk, then runs the engine's standard
    decode loop on the session cache, so decode is token-for-token the
    one-shot path's.

    Per-chunk ``kv_width`` grows with the content (power-of-two buckets),
    so attention cost tracks what has actually been appended; the causal
    mask makes the wider-window lanes exact zeros, but wider matmul
    tilings may reassociate float sums — logits agree with the one-shot
    path to numerical tolerance, not bitwise (asserted in
    tests/test_overlap.py). Thread-safe: appends serialize on one lock.

    HBM cost: the session allocates one full-capacity [1, max_seq] cache
    at construction (chunk programs are keyed on the cache shape, and the
    final prompt length is unknowable up front), pinned until ``generate``
    consumes it. Concurrent serving with judge overlap holds one such
    cache per in-flight request — size the judge's ``LLMC_MAX_SEQ`` (and
    the admission concurrency cap) with that in the budget.
    """

    def __init__(self, engine: Engine):
        self._eng = engine
        chunk = engine.prefill_chunk
        if not chunk:
            raise ValueError(
                "PrefillSession requires chunked prefill "
                "(LLMC_PREFILL_CHUNK > 0)"
            )
        self._chunk = chunk
        self._lock = sanitizer.make_lock("engine.session")
        self._ids: list[int] = []
        self._base = 0          # ids already prefilled (chunk multiple)
        self._last_logits = None
        self._closed = False
        self.overflowed = False
        # Sessions prefill incrementally UNPINNED (a session may be
        # abandoned without ever generating — a pin here could wedge
        # swaps forever); generate() pins, then re-prefills from zero if
        # a swap landed between appends, so the cache never mixes KV
        # from two weight versions.
        self._weight_version = engine.weight_version
        self._cache = engine.new_cache(1)

    @property
    def tokens(self) -> int:
        """Tokens appended so far (prefilled + residue)."""
        with self._lock:
            return len(self._ids)

    @property
    def prefilled(self) -> int:
        """Tokens whose prefill has been DISPATCHED (whole chunks)."""
        with self._lock:
            return self._base

    def append_text(self, text: str) -> int:
        """Tokenize and append; returns the number of tokens appended.

        Pieces CONCATENATE into one prompt: a leading BOS the tokenizer
        emits is kept only for the session's FIRST piece — one BOS per
        appended block would condition the model on a token stream the
        one-shot encode of the same concatenation never contains (the
        strip form works for any tokenizer; HF wrappers don't take an
        ``add_bos`` kwarg)."""
        eng = self._eng
        ids = eng.tokenizer.encode(text)
        bos = getattr(eng.tokenizer, "bos_id", None)
        with self._lock:
            if self._ids and ids and bos is not None and ids[0] == bos:
                ids = ids[1:]
            self._append_locked(ids)
        return len(ids)

    def append(self, ids: list[int]) -> None:
        """Append ``ids``; every whole chunk they complete is dispatched
        immediately (async — the host returns as soon as the programs are
        enqueued). Ids past the context budget set ``overflowed`` and are
        retained un-prefilled: the session cannot middle-out truncate a
        cache already written, so the caller falls back to the classic
        (truncating) path."""
        with self._lock:
            self._append_locked(ids)

    def _append_locked(self, ids: list[int]) -> None:
        eng = self._eng
        if self._closed:
            raise RuntimeError("PrefillSession already consumed")
        self._ids.extend(ids)
        chunk = self._chunk
        # Overflow = the FINAL (padded) chunk's write window would
        # end past cache capacity — the session analog of the classic
        # paths' n_chunks*chunk <= max_seq guards. Without it a
        # max_seq that is not a chunk multiple lets the clamped
        # dynamic_update_slice silently shift the residue chunk onto
        # earlier positions, corrupting the cache.
        if (
            len(self._ids) >= eng.max_seq
            or -(-len(self._ids) // chunk) * chunk > eng.max_seq
        ):
            self.overflowed = True
        if self.overflowed:
            return
        while len(self._ids) - self._base >= chunk:
            toks = eng._place(jnp.asarray(
                self._ids[self._base:self._base + chunk], jnp.int32,
            )[None, :])
            kv_width = _bucket(self._base + chunk, eng.max_seq)
            self._last_logits, self._cache = _prefill_chunk(
                eng.params, eng.cfg, toks,
                eng._place(jnp.asarray(self._base, jnp.int32)),
                eng._place(jnp.asarray([chunk - 1], jnp.int32)),
                self._cache, kv_width=kv_width, w8a8=eng.w8a8,
            )
            self._base += chunk

    def sync(self) -> None:
        """Block until every dispatched prefill chunk has completed on
        device (the bench's overlap-hidden clock reads this boundary)."""
        with self._lock:
            lg = self._last_logits
        if lg is not None:
            jax.block_until_ready(lg)

    def generate(
        self,
        sampling: SamplingParams = SamplingParams(),
        ctx: Optional[Context] = None,
        on_text: Optional[Callable[[str], None]] = None,
    ) -> GenerateResult:
        """Prefill the residue (one padded final chunk) and decode.

        Single-use: the cache is consumed by the decode loop's donation.
        Junk in the final chunk's padding lands at positions ≥ the prompt
        length, which decode overwrites before its causal frontier
        reaches them — the chunked-prefill invariant."""
        eng = self._eng
        eng.pin_weights()
        try:
            return self._generate_pinned(sampling, ctx, on_text)
        finally:
            eng.unpin_weights()

    def _generate_pinned(
        self,
        sampling: SamplingParams,
        ctx: Optional[Context],
        on_text: Optional[Callable[[str], None]],
    ) -> GenerateResult:
        eng = self._eng
        ctx = ctx or Context.background()
        start_time = time.monotonic()
        with self._lock:
            if self._closed:
                raise RuntimeError("PrefillSession already consumed")
            if self.overflowed:
                raise ValueError(
                    "session overflowed the context window; use the "
                    "classic (truncating) prompt path"
                )
            n = len(self._ids)
            if n == 0:
                raise ValueError("empty prompt")
            if n >= eng.max_seq:
                raise ValueError(
                    f"prompt length {n} exceeds max sequence length "
                    f"{eng.max_seq}"
                )
            if self._base > 0 and eng.weight_version != self._weight_version:
                # A hot-swap landed between appends: chunks already in
                # the cache carry old-version KV. Migrate by re-running
                # the whole prefill under the now-pinned version — the
                # session retains every id, so this costs one extra
                # prompt pass, never correctness.
                self._base = 0
                self._last_logits = None
                self._cache = eng.new_cache(1)
                self._weight_version = eng.weight_version
                pending = self._ids
                self._ids = []
                self._append_locked(pending)
            self._closed = True
            chunk = self._chunk
            residue = n - self._base
            if residue > 0:
                if self._base + chunk > eng.max_seq:
                    # Unreachable behind the append-side overflow guard;
                    # a clamped out-of-capacity write would corrupt the
                    # cache silently, so refuse loudly instead.
                    raise ValueError(
                        "residue chunk would overrun cache capacity"
                    )
                padded = self._ids[self._base:] + [0] * (chunk - residue)
                kv_width = _bucket(self._base + chunk, eng.max_seq)
                self._last_logits, self._cache = _prefill_chunk(
                    eng.params, eng.cfg,
                    eng._place(jnp.asarray(padded, jnp.int32)[None, :]),
                    eng._place(jnp.asarray(self._base, jnp.int32)),
                    eng._place(jnp.asarray([residue - 1], jnp.int32)),
                    self._cache, kv_width=kv_width, w8a8=eng.w8a8,
                )
                self._base = n
            ids = list(self._ids)
            last_logits, cache = self._last_logits, self._cache
            self._cache = None  # consumed (donated) by the decode loop
        decoder = StreamDecoder(eng.tokenizer)
        parts: list[str] = []

        def on_token(tok_id: int) -> None:
            text = decoder.push(tok_id)
            if text:
                parts.append(text)
                if on_text is not None:
                    on_text(text)

        result = eng._decode_stream(
            ids, last_logits, cache, sampling, ctx, on_token, start_time,
        )
        tail = decoder.flush()
        if tail:
            parts.append(tail)
            if on_text is not None:
                on_text(tail)
        result.text = "".join(parts)
        return result
