"""Continuous batching: admission and eviction of decode streams mid-flight.

``Engine.generate_batch`` ships static batching — all streams start and
pad together. This module adds the serving-grade form: a fixed pool of
``max_batch`` slots decodes as one batched program while new requests are
admitted into free slots *between decode chunks* and finished streams are
evicted without stopping their neighbors. Decode is HBM-bound (the weight
stream per step is shared by every slot), so keeping slots full multiplies
aggregate tokens/sec nearly for free.

TPU-first mechanics — the scheduler reuses the exact decode program
``generate_batch`` compiles (shared write position + per-row ``row_start``
offsets), because a per-slot write-position vector measurably loses: XLA
lowers per-row cache writes to serialized tiny-loop updates (~1 ms/step
at batch 8 on consensus-1b, profiled), while the shared-position form is
one fused dynamic-update-slice.

  * **Admission = prefill + aligned splice.** A new prompt prefills
    through the engine's single-stream path (buckets, chunking, prefix
    reuse — Engine._prefill_ids) into a [1, S] cache; its prompt KV
    [0, n) is spliced into the slot's row at offset ``pos − n`` so the
    prompt *ends exactly at the shared frontier*. RoPE needs no fixup:
    positions are row-relative (``row_start = pos − n``), which is
    precisely what the prefill wrote.
  * A prompt longer than the current frontier waits in the queue until
    the frontier passes it (or the pool drains and the frontier resets) —
    admission never teleports the shared position, so no row ever has a
    masked-valid hole of junk.
  * **Eviction is free.** A finished slot keeps stepping (static shapes)
    but its outputs are dropped; an owner-identity check prevents a
    reused slot from leaking its predecessor's in-flight tokens.
  * **Compaction, not death, at the waterline.** The shared frontier
    only advances; when it nears cache capacity with streams still
    active, each live row's window slides left (a traced-shift roll —
    one compiled program), row_starts re-align, and the pool gets fresh
    runway. Relative positions are preserved, so no re-RoPE.
  * **Fetch and emit run on a dedicated worker thread** behind a
    depth-2 dispatch pipeline: the scheduler dispatches chunk N+1 (and
    admissions) while the worker blocks on chunk N's device transfer
    and runs the Python emit loop, so that host time (the transfer
    wait plus an emit loop that grows with serving batch) stays off
    the dispatch path. Prefill-sampled first tokens still ride down
    with their wave's next chunk fetch instead of paying their own
    round trip.
  * Sampling shape (temperature/top_k/top_p) is **per-batcher** (static
    structure in the compiled program, validated at ``submit``);
    per-stream ``max_new_tokens`` and ``ignore_eos`` are honored
    host-side. ``seed`` only seeds the prefill-sampled first token:
    decode steps draw from the batcher's own key stream (per-step fold
    over the shared frontier), so sampled runs are statistically
    independent across slots but not seed-reproducible against the
    single-stream engine. Greedy streams (the default) produce exactly
    the tokens the single-stream engine would.

The reference has no analog (its "streams" are remote HTTP calls —
SURVEY.md §2); this is the serving-throughput extension of the roadmap.
"""

from __future__ import annotations

import atexit
import threading
import time
import warnings
from concurrent.futures import Future, InvalidStateError
from contextlib import contextmanager
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from llm_consensus_tpu.engine.engine import (
    Engine, GenerateResult, SamplingParams, _bucket, _decode_chunk, refuse_ssm,
    scan_positions_swept)
from llm_consensus_tpu.engine.speculative import (
    AdaptiveK, SpecGovernor, _install_spec_rows, _junk_propose,
    _lookup_propose, _oracle_propose, _plain_chunk_masked, _roll_valid,
    _spec_verify_batch)
from llm_consensus_tpu.engine.tokenizer import StreamDecoder
from llm_consensus_tpu.obs.attrib import tag as _attrib_tag
from llm_consensus_tpu.obs.scopes import scope
from llm_consensus_tpu.ops.moe import pairs_kernel_serves
from llm_consensus_tpu.ops.quant import kv_seq_axis as _seq_axis
from llm_consensus_tpu.ops.quant import kv_tree_map as _kv_tree_map
from llm_consensus_tpu.ops.sampling import sample_token
from llm_consensus_tpu.utils.context import Context
from llm_consensus_tpu.utils.flops import cache_bytes_per_token
from llm_consensus_tpu.analysis import sanitizer
from llm_consensus_tpu.utils import knobs


@dataclass
class _Stream:
    """Host-side state of one admitted or queued stream."""
    future: Future
    sampling: SamplingParams
    ctx: Context
    on_text: Optional[Callable[[str], None]]
    prompt_tokens: int
    decoder: StreamDecoder
    submitted: float
    truncated: bool
    max_new: int
    out_ids: list = field(default_factory=list)
    parts: list = field(default_factory=list)
    finish: str = "length"
    # Tokens covered by dispatched work: 1 (the prefill-sampled first
    # token) plus n_steps per decode chunk dispatched while this stream
    # was live. Exact for ignore_eos streams, an upper bound otherwise —
    # either way, planned >= max_new means more dispatch is dead
    # stepping (the overshoot gate / final-chunk clamp below).
    planned: int = 1
    # Write-ahead journal entry (recovery/): None unless journaling is on
    # for this stream, so the emit hot path pays one attribute None-check.
    jentry: object = None
    # Per-stream acceptance EMA (spec-enabled pools, telemetry only —
    # the pool-wide controller drives k, since the verify program's k is
    # shared static program identity across every row).
    spec_ema: float = 0.0
    # Priority class (pressure/priority.py: HIGH=0 < NORMAL=1 < LOW=2).
    # Orders admission within a drain (stable sort — FIFO within a
    # class) and selects preemption victims: a lower class never blocks
    # a queued higher class when preemption is enabled.
    priority: int = 1
    # The ORIGINAL budgeted prompt ids (without any replayed prefix) —
    # what a preempted stream's resume re-submits; one tuple ref per
    # stream, paid only at submit.
    pids: tuple = ()
    # Preempted-and-resumed at least once: rides GenerateResult →
    # Response so the serving tier labels this request's latency
    # outcome "preempted" in the live histograms.
    preempted: bool = False
    # Cross-hop trace id (obs/live): carried into the journal entry so
    # one id links both batcher residencies of a preempted stream.
    trace: Optional[str] = None
    # Weight version this stream is pinned to (engine.pin_weights), -1
    # while unpinned (queued / preempted / retired). A resident stream
    # always finishes on the version it admitted under — a hot-swap
    # parks until every pin releases (flywheel). Preempted streams
    # unpin and RE-pin at resume, so they may continue on the new
    # version: that is the journal-backed migration path, and greedy
    # byte-identity is promised only to streams that stay resident.
    weight_version: int = -1
    # Clock reads of this stream's way through the pool, in
    # ``time.monotonic_ns``, taken from the spans that carried it:
    # ``admit_ns`` (start of the first pool.admit wave holding it),
    # ``first_token_ns`` (start of the pool.emit that handed out its
    # first token), ``first_chunk_ns`` (its first text pushed to
    # ``on_text``) and ``last_token_ns`` (its last token handed out: the
    # stream resolves); beside the first and the last, ``first_step`` and
    # ``last_step``: the decode steps of this pool whose tokens had landed
    # on the host by then (``_steps_landed``), so their difference is the
    # steps the pool ran between the two, for whichever rows. They ride
    # GenerateResult.marks to the serving tier's per-run ``timings``.
    marks: dict = field(default_factory=dict)


def _wave_counts(admit: dict) -> dict:
    """The counter deltas of one landed admission wave, from its
    ``pool.admit`` span's arguments: the ``prefill_*`` counters (and
    ``admit_tokens``) are those arguments summed over waves. A state-space
    model's span also says what its scans ran over (``_ssm_admit``)."""
    counts = {
        "admit_tokens": admit["tokens_real"], "prefill_waves": 1,
        "prefill_rows_real": admit["rows_real"],
        "prefill_rows_padded": admit["rows_padded"],
        "prefill_slot_tokens": admit["slot_tokens"],
        "prefill_chunks": admit["chunks"],
        "prefill_kv_pairs_swept": admit["pairs_swept"],
        "prefill_kv_pairs_live": admit["pairs_live"],
    }
    if "ssm_swept" in admit:
        counts.update(ssm_positions_swept=admit["ssm_swept"],
                      ssm_positions_live=admit["ssm_live"])
    return counts


def _ssm_admit(cfg, did, rows: int, tokens_real: int) -> dict:
    """What a ``pool.admit`` span of a state-space model says beside the
    rest: the positions the scans of its prefill ``did`` of ``rows`` rows
    ran over (``scan_positions_swept``: rows x slots, padding included) and
    the real tokens among them. Nothing for a model without a mixer."""
    if not cfg.has_state:
        return {}
    return {"ssm_swept": scan_positions_swept(cfg, did, rows),
            "ssm_live": tokens_real}


def _ssm_decode(cfg, steps: int, rows: int) -> dict:
    """What a ``pool.decode`` span and the decode counters of a state-space
    model say beside the rest: the rows whose state the dispatch's steps
    read and write (every row the pool holds, with a stream or not).
    Nothing for a model without a mixer."""
    return {"ssm_state_row_steps": steps * rows} if cfg.has_state else {}


# What a pool row WITHOUT a stream carries as its device ``row_start``:
# past any frontier, so the row has no valid cache slot. The decode
# kernel's sweep plan (ops/pallas/decode_attention.py _sweep_plan) then
# neither fetches nor computes it, and the XLA decode route masks its
# every slot (finite logits; the row's tokens are dropped, and the
# finite-logit sentinel skips rows without an owner). It is data beside
# ``pos``, not shape: no program per occupancy.
DEAD_ROW = 1 << 30


@jax.jit
def _mark_dead(row_start, dead):
    return jnp.where(dead, DEAD_ROW, row_start)


def kv_slots_live(pos: int, steps: int, stride: int, row_starts: Sequence[int],
                  window: Optional[int] = None) -> int:
    """Cache slots the live rows' attention may read over one decode
    dispatch: forward ``t`` of ``steps`` (each advancing the frontier by
    ``stride`` slots from ``pos``) reads a row's slots from its
    ``row_start`` to the frontier it writes, a sliding ``window`` of them
    at most. The ``decode_kv_slots_live`` counter sums this; against
    ``decode_kv_slots_swept`` (steps x pool rows x bucket width) it is the
    share of the sweep the traffic leaves to do."""
    ends = [pos + (t + 1) * stride for t in range(steps)]
    if window is None or not row_starts or ends[-1] - min(row_starts) <= window:
        return sum(ends) * len(row_starts) - steps * sum(row_starts)
    return sum(min(e - rs, window) for rs in row_starts for e in ends)


def _window_decode(cfg, pos: int, steps: int, row_starts: Sequence[int]) -> dict:
    """What the decode counters of a pool whose attention layers differ in
    their window (``cfg.attn_kinds``: "W" layers beside "*") say beside the
    rest, over one dispatch of ``steps`` from frontier ``pos``: the slots ONE
    "W" layer sweeps for the live rows (``kv_slots_live`` under the window;
    ``decode_kv_slots_live`` is what a full layer sweeps for such a model).
    Nothing for any other model."""
    if "W" not in cfg.layer_kinds:
        return {}
    return dict(decode_kv_slots_window_layer=kv_slots_live(
        pos, steps, 1, row_starts, cfg.sliding_window))


def causal_pairs(lengths: Sequence[int], base: int = 0) -> int:
    """(Query, key) pairs causality needs to prefill rows of ``lengths``
    real tokens whose first ``base`` positions are already in a cache (a
    shared or a restored prefix): ``n (n + 1) / 2`` a row, less the pairs
    among the ``base``. The ``prefill_kv_pairs_live`` counter sums this;
    against ``prefill_kv_pairs_swept`` (what the prefill programs' attention
    scored, engine.py ``prefill_pairs_swept``) it is the share of the sweep
    that was needed."""
    return sum(n * (n + 1) // 2 for n in lengths) - (
        len(lengths) * (base * (base + 1) // 2))


def fits(n: int, pos: int, width: int, max_seq: int) -> bool:
    """THE fit rule — the one safety condition of admission, written
    once: a row of ``n`` tokens admitted at frontier ``pos`` splices a
    ``width``-wide bucket at ``pos - n``, so its window
    ``[pos - n, pos - n + width)`` must lie inside ``[0, max_seq]``. A
    prompt longer than the frontier (``n > pos``) waits for the frontier
    to pass it; an overrun past capacity makes ``dynamic_update_slice``
    CLAMP, which silently misaligns the row. ``width`` and ``pos`` are
    those of the splice the caller is about to make."""
    return n <= pos and (pos - n) + width <= max_seq


def wave_k_pad(k: int, max_batch: int) -> int:
    """Pad the wave to a power of two, FLOORED at max_batch/4: every
    distinct padded size is a compiled program (admission prefill +
    fused splice), and nondeterministic burst splits otherwise keep
    discovering new sizes — a fresh full-model compile landing
    inside serving traffic. The floor caps the variant set at 3 per
    pool; padding rows repeat row 0 (idempotent). They are real
    rows to the device: nearly free while a row is at most one
    prefill chunk (the wave is weights-bound), full price beyond it
    (measured on a v5e: a lone 1.7k-token prompt padded to six rows
    took six times one row's prefill), which is why
    ``singles_cover_fewer`` sends such waves row by row."""
    k_pad = 1 << (k - 1).bit_length()
    return min(max(k_pad, max_batch // 4, 8), max_batch)


def singles_cover_fewer(lens: list, max_batch: int, chunk: int,
                        rows_bucket: Callable[[int], int]) -> bool:
    """Whether a full-prompt wave of rows ``lens`` long should be
    admitted row by row (``_admit``) instead of as one padded wave
    (``_admit_batch``): the path that dispatches fewer token slots,
    and on a tie row by row — a full wave of long rows that fill
    their bucket covers the same slots either way, and the batched
    chunk program is the slower one per token (measured on a v5e:
    six 2k rows 0.84 s batched, 0.51 s one by one) with k times one
    row's scratch to load. Where ``max_seq`` is no multiple of the
    chunk the bucket is not chunk-padded and the wave does cover
    fewer: it stays.

    Only for waves whose rows are EACH longer than one prefill
    chunk: a chunk already streams the weights once per
    ``prefill_chunk`` tokens, so such a wave is compute-bound and
    batching its rows buys nothing, while every padding row and
    every slot a short row is padded to the longest's bucket costs
    what a real one does. Shorter rows are weights-bound: one padded
    wave streams the weights once for all of them, and stays."""
    if not chunk or min(lens) <= chunk:
        return False
    batched = wave_k_pad(len(lens), max_batch) * rows_bucket(max(lens))
    return sum(-(-n // chunk) * chunk for n in lens) <= batched


def idle_frontier(lens: list, shared_prefix: bool, max_seq: int,
                  bucket: Callable[[int], int],
                  rows_bucket: Callable[[int], int]) -> int:
    """Frontier for an idle pool's wave: the longest prompt among
    the LEADING candidates (admission order) that can right-align to
    one frontier within cache capacity.

    A row of n tokens at frontier ``pos`` splices a w-wide bucket at
    ``pos - n``, so it fits only while ``(pos - n) + w <= max_seq``.
    When the wave's prompts are long enough that w saturates
    capacity, only rows AT the frontier fit: resetting the frontier
    to the longest prompt then requeues every shorter row — and if a
    shorter row heads the queue, the no-leapfrog rule requeues the
    longer ones behind it too, nothing is admitted, the pool stays
    idle and the pass repeats forever (found on the chip: two
    concurrent ~1.7k-token judge prompts in a 2048-slot cache hung
    until their deadlines). Stopping at the first candidate that
    breaks the fit always admits the queue head; the rest wait for
    the frontier or the next idle pool, as any long prompt does."""
    # Every member fits one frontier and width exactly when the SHORTEST
    # does (it starts furthest in), so the wave is its two extremes.
    n_min = n_max = lens[0]
    for n in lens[1:]:
        top = max(n_max, n)  # the frontier this wave would take
        if shared_prefix:
            w = bucket(top)
        else:
            w = max(bucket(top), rows_bucket(top))
        if not fits(min(n_min, n), top, w, max_seq):
            break
        n_min, n_max = min(n_min, n), top
    return n_max


class Pending(NamedTuple):
    """What admission policy may know of one queued stream."""

    ids: Sequence        # prompt ids (only their length, with sharing off)
    priority: int = 1    # pressure/priority.py: HIGH=0 < NORMAL=1 < LOW=2
    done: bool = False   # deadline passed or cancelled while queued
    max_new: int = 1     # tokens it may still decode (<= 0: nothing to do)


class AdmissionPlan(NamedTuple):
    """The decision of one admission pass (``plan_admission``). Streams
    are named by their index in the ``pending`` the planner was given."""

    # Resolved without prefill: expired/cancelled, or ``max_new <= 0``.
    resolve: tuple = ()
    # Drop the pool's shared prefix (nothing can use it any more).
    clear_prefix: bool = False
    # Establish these ids (the wave's common prefix) as the pool's shared
    # prefix before the wave (empty: don't). The rest of the plan ASSUMES
    # it lands; where it does not, the pass is planned again with
    # sharing off.
    establish: tuple = ()
    # Shared-prefix length the wave's rows admit under (0: full prompts).
    wave_p: int = 0
    # The frontier the wave splices at (an idle pool's resets to fit it).
    pos: int = 0
    # (pending index, slot) in admission order.
    admitted: tuple = ()
    # How the admitted rows prefill: "rows" (one padded wave) or "single"
    # (row by row); None when nothing is admitted. ``interleave``: first
    # try to open a paced wave between decode chunks instead.
    route: Optional[str] = None
    interleave: bool = False
    # Back to the queue head, in this order.
    requeue: tuple = ()


def plan_admission(
    pending: Sequence[Pending], free: Sequence[int], pos: int,
    max_seq: int, pool_idle: bool, *, max_batch: int, chunk: int,
    bucket: Callable[[int], int], rows_bucket: Callable[[int], int],
    prefix_enabled: bool = False, prefix_ids: Optional[tuple] = None,
    prefix_min: int = 0,
    resident_prefix_len: Optional[Callable[[list], int]] = None,
    sp_degree: int = 1, may_interleave: bool = False,
) -> AdmissionPlan:
    """Admission POLICY, all of it, as a pure function: given the drained
    queue (``pending``, in queue order), the ``free`` rows, the shared
    frontier ``pos`` and the pool's prefix state, what is admitted, where
    and how. No engine, no lock, no clock, no spans — the scheduler
    carries the plan out (``ContinuousBatcher._execute_admission``).

    ``bucket(n)`` is the single-stream splice width of an n-token row,
    ``rows_bucket(n)`` the shared width of a wave whose longest row is n;
    ``chunk`` the prefill chunk; ``prefix_ids`` the established shared
    prefix (None: none); ``resident_prefix_len(ids)`` how much of ``ids``
    the paged KV pool already holds (None: no pool); ``may_interleave``
    whether a paced wave may open (a budget, no wave pending, live rows
    to overlap with)."""
    # Priority-ordered admission (pressure/): a stable sort,
    # so FIFO survives WITHIN a class while a higher class
    # drained in the same pass takes slots first. Requeued
    # streams keep their no-leapfrog fairness per class; a
    # higher class overtaking a requeued lower one is the
    # point.
    order = sorted(range(len(pending)), key=lambda i: pending[i].priority)
    candidates = [
        pending[i].ids for i in order
        if not pending[i].done and pending[i].max_new > 0
    ]
    # Shared-prefix mode for THIS wave (the one-prompt fan-out
    # pattern): all-or-nothing per wave. Pool idle → establish
    # (or re-establish) from the wave's own common prefix;
    # pool busy → join the established prefix only if every
    # candidate starts with it. A wave that can't share
    # admits full-prompt rows; establishment failure degrades
    # the same way.
    wave_p = est_p = 0
    # No live row can reference the prefix any more and
    # sharing is off (env, or the failure fallback): drop it so
    # decode returns to the cheaper no-prefix program.
    clear_prefix = pool_idle and not prefix_enabled and prefix_ids is not None
    if prefix_enabled and candidates:
        p0 = len(prefix_ids) if prefix_ids is not None else 0
        if prefix_ids is not None and all(
            len(r) > p0 and tuple(r[:p0]) == prefix_ids for r in candidates
        ):
            # Join the established prefix (idle or busy, any
            # wave size) — no re-establishment churn.
            wave_p = p0
        elif pool_idle:
            common = candidates[0]
            for r in candidates[1:]:
                m = min(len(common), len(r))
                i = 0
                while i < m and common[i] == r[i]:
                    i += 1
                common = common[:i]
            p = min(len(common), min(len(r) for r in candidates) - 1)
            if p >= prefix_min and len(candidates) > 1:
                est_p = p
            if (
                not est_p
                and resident_prefix_len is not None
                and p >= prefix_min
            ):
                # Radix consult (paged pool on): a wave with
                # no intra-wave sharing — a lone candidate is
                # the common case — still establishes when
                # the pool already holds its prefix, sized to
                # the resident span so establishment is a
                # block gather, not a prefill. Rows then
                # admit as SUFFIXES: the wave prefills only
                # unmatched tail tokens and its decode window
                # shrinks to the suffix, which is where the
                # pooled max-resident-streams headroom
                # comes from.
                hit = resident_prefix_len(list(candidates[0][:p]))
                if hit >= prefix_min:
                    est_p = hit
            if est_p:
                wave_p = est_p
            else:
                # No qualifying shared prefix: drop back to
                # the cheaper no-prefix decode program.
                clear_prefix = True
    if pool_idle and candidates:
        # Idle frontier resets to the wave's longest prompt
        # (suffix length under shared-prefix admission) so
        # the whole wave can right-align to one frontier.
        pos = idle_frontier(
            [len(ids) - wave_p for ids in candidates][:max_batch],
            bool(wave_p), max_seq, bucket, rows_bucket,
        )
    free = list(free)
    resolve: list = []
    admitted: list = []
    requeue: list = []
    # Shortest and longest window admitted so far (none yet: the bounds
    # any admissible window lies within).
    n_min, n_max = max_seq, 0
    for i in order:
        item = pending[i]
        if item.done or item.max_new <= 0:
            # Expired while queued, or nothing to decode: resolve
            # without prefill.
            resolve.append(i)
            continue
        if requeue or not free:
            # FIFO fairness: once any stream this round was
            # requeued (frontier/capacity/slots), later
            # arrivals must not leapfrog it — under sustained
            # load a long prompt would otherwise starve until
            # the pool fully drained.
            requeue.append(i)
            continue
        n = len(item.ids) - wave_p  # window the row will occupy
        # Capacity must hold for the admission form in play:
        # full-prompt waves splice rows_bucket(n) wide (and
        # may fall back to the single-stream bucket(n)
        # splice), shared-prefix waves splice their suffix
        # bucket.
        w_req = bucket(n) if wave_p else max(bucket(n), rows_bucket(n))
        if not fits(n, pos, w_req, max_seq):
            requeue.append(i)
            continue
        # Batched waves splice rows at one shared width, so
        # every member must also fit THAT width; a candidate
        # that would push the wave width past some member's
        # capacity requeues instead of corrupting the splice.
        # (The shortest member starts furthest in: if it fits, all do;
        # for the wave's first row this is the fit above again.)
        low, top = min(n_min, n), max(n_max, n)
        w_new = bucket(top) if wave_p else rows_bucket(top)
        if not fits(low, pos, w_new, max_seq):
            requeue.append(i)
            continue
        n_min, n_max = low, top
        admitted.append((i, free.pop(0)))
    route = None
    if admitted and sp_degree > 1:
        # sp meshes keep ring prefill (batched admission is
        # plain left-aligned prefill): row by row.
        route = "single"
    elif admitted:
        # Long rows, few of them: the one-row path covers fewer token
        # slots than the padded wave. (Suffix waves stay batched: a
        # single row cannot join the pool's prefix.)
        route = "single" if not wave_p and singles_cover_fewer(
            [len(pending[i].ids) for i, _ in admitted],
            max_batch, chunk, rows_bucket,
        ) else "rows"
    return AdmissionPlan(
        resolve=tuple(resolve), clear_prefix=clear_prefix,
        establish=tuple(candidates[0][:est_p]) if est_p else (),
        wave_p=wave_p, pos=pos, admitted=tuple(admitted), route=route,
        # Interleaved admission (prefill/decode overlap) where it may
        # open: an idle pool admits classically — there is no decode
        # to overlap, and the stall-free first chunk matters more than
        # pacing.
        interleave=bool(admitted) and sp_degree == 1 and may_interleave,
        requeue=tuple(requeue),
    )


@dataclass
class _PendingWave:
    """One interleaved admission wave mid-establishment: its reserved
    (slot, prompt ids, stream) triples, the shared-prefix length it was
    planned under, the padded row count, and the engine prefill session
    whose chunks the scheduler paces between decode dispatches."""

    batch: list  # [(slot, ids, stream)]
    wave_p: int
    k_pad: int
    session: object  # engine.AdmissionPrefill


@dataclass
class _SpecState:
    """Device + host state of a spec-enabled pool (one per batcher).

    ``controller``/``governor`` are POOL-wide: the batched verify
    program's ``k`` is static program identity shared by every row, so
    the adaptive ladder walks on the MEAN per-row acceptance, and the
    governor A/Bs pooled tokens/s (per-stream EMAs live on the streams,
    telemetry only). No separate window-base state: with per-row holes
    the DEVICE ``row_start`` absorbs hole counts and no longer names the
    window start, but the batcher's host-side ``_row_start_host`` is
    only ever written at admission/compaction/moves — never synced to
    the device values — so in spec mode it already holds each slot's
    first PHYSICAL cache slot, which is exactly what compaction's
    retire/reclaim arithmetic needs. The counters are written by the
    fetch worker and read lock-free (GIL-atomic int bumps, telemetry
    only).
    """

    cfg: object         # speculative.SpecConfig
    controller: object  # speculative.AdaptiveK (pool-wide)
    governor: object    # speculative.SpecGovernor (pool-wide)
    valid: object       # [B, S] bool written-slot bitmap (device)
    buf: object         # [B, S] i32 logical token buffer (device)
    obuf: object        # [B, S] i32 oracle continuations (tests/bench)
    blen: object        # [B] i32 logical lengths (device)
    # Governor warm-up discard: the first qualifying arrival after pool
    # build (and after each probe-mode switch) carries one-off JIT
    # compile walls for that mode's programs — feeding it would skew the
    # drafted-vs-plain A/B toward whichever mode probed second (warm).
    skip_feed: bool = True
    rounds: int = 0           # round dispatches fetched
    row_rounds: int = 0       # live (row, round) pairs fetched
    accepted: int = 0         # accepted tokens across live rows
    disables: int = 0         # governor locked plain (0/1)
    collapse_faults: int = 0  # injected acceptance_collapse rounds


@partial(jax.jit, static_argnames=("width",), donate_argnames=("batch_cache",))
def _splice(batch_cache, prefill_cache, slot, dst, width: int):
    """Copy ``prefill_cache``'s slots [0, width) into ``batch_cache``'s
    row ``slot`` at offset ``dst``. Junk past the prompt inside the
    bucket lands at slots ≥ the shared frontier, which decode overwrites
    before reading. A state-space model's per-row state leaves (no
    sequence axis) replace the pool row's WHOLE: the row starts from its
    own prefill's state, never from what its last tenant left."""
    def copy(bdst, src):
        if _seq_axis(src) == 2:
            return jax.lax.dynamic_update_slice(
                bdst, src[:, :, :width], (0, slot, dst, 0, 0)
            )
        return jax.lax.dynamic_update_slice(
            bdst, src[..., :width], (0, slot, 0, dst)
        )

    def state(bdst, src):
        return jax.lax.dynamic_update_slice_in_dim(
            bdst, src[:, :1].astype(bdst.dtype), slot, axis=1)

    with scope("cache.splice"):
        return _kv_tree_map(copy, batch_cache, prefill_cache, state=state)


@partial(jax.jit, static_argnames=("k", "width"), donate_argnames=("batch_cache",))
def _splice_rows(batch_cache, prefill_cache, src_rows, slots, dsts,
                 k: int, width: int):
    """Copy ``k`` rows of a batched admission prefill cache
    (Engine._prefill_rows full prompts, or Engine._prefill_rows_suffix
    suffix-only rows — both left-aligned, bucket capacity ``width``) into
    ``batch_cache`` — row ``src_rows[i]`` lands at slot ``slots[i]``,
    offset ``dsts[i]``. ONE program per (k, width): a per-row jitted
    splice measured catastrophic under burst admission — each queued
    call pins its own input+output cache pair until it executes, so a
    16-wide wave held 32 full cache copies (8.6 GB at batch 16) while
    the splices waited behind the admission prefill. Fused, the wave
    holds one in/out pair. Traced index arrays keep slot/offset values
    out of the program identity; padding rows (k padded to a power of
    two) repeat row 0's splice, which is idempotent. A state-space
    model's per-row state leaves are copied whole, row for row."""
    def state(bdst, src):
        for i in range(k):
            row = jax.lax.dynamic_slice_in_dim(src, src_rows[i], 1, axis=1)
            bdst = jax.lax.dynamic_update_slice_in_dim(
                bdst, row.astype(bdst.dtype), slots[i], axis=1)
        return bdst

    def copy(bdst, src):
        seq2 = _seq_axis(src) == 2
        for i in range(k):
            if seq2:
                row = jax.lax.dynamic_slice(
                    src, (0, src_rows[i], 0, 0, 0),
                    (src.shape[0], 1, width) + src.shape[3:],
                )
                bdst = jax.lax.dynamic_update_slice(
                    bdst, row, (0, slots[i], dsts[i], 0, 0)
                )
            else:
                row = jax.lax.dynamic_slice(
                    src, (0, src_rows[i], 0, 0),
                    (src.shape[0], 1, src.shape[2], width),
                )
                bdst = jax.lax.dynamic_update_slice(
                    bdst, row, (0, slots[i], 0, dsts[i])
                )
        return bdst

    with scope("cache.splice"):
        return _kv_tree_map(copy, batch_cache, prefill_cache, state=state)


@partial(jax.jit, static_argnames=("p_cap",))
def _extract_prefix(pcache, p_cap: int):
    """Slots [0, p_cap) of a [1, S] prefill cache → the pool's shared-
    prefix KV stack [L, 1, p_cap, Hkv, dh], DENSE compute dtype.

    int8 entries are dequantized here, once: the prefix is read-only and
    one row (tens of MB), so densifying at establishment deletes the
    per-layer-per-step dequant chain from every decode step, where the
    pool cache's int8 form exists to halve B-scaled HBM — a concern a
    single shared row doesn't have. Content past the true prefix length
    is masked by the traced ``prefix_len`` at attention time."""
    def entry(e):
        if isinstance(e, dict):  # int8 codes + seq-minor scales
            q8 = jax.lax.slice_in_dim(e["q8"], 0, p_cap, axis=2)
            sc = jax.lax.slice_in_dim(e["s"], 0, p_cap, axis=3)
            return q8.astype(sc.dtype) * jnp.swapaxes(sc, 2, 3)[..., None]
        return jax.lax.slice_in_dim(e, 0, p_cap, axis=2)

    return {"k": entry(pcache["k"]), "v": entry(pcache["v"])}


@partial(jax.jit, static_argnames=("k", "temperature", "top_k", "top_p"))
def _admit_finish(last_logits, token, row_start, prefix_rows, slots, dsts,
                  actives, seeds, ns, k: int, temperature, top_k, top_p):
    """Post-prefill admission state update as ONE program: per-row
    first-token sampling (per-stream seed keys) plus the token/row_start/
    prefix-participation scatters. The per-row form dispatched ~3 tiny
    device ops per admitted stream — host-side dispatch latency that
    grows with the wave. Padding rows repeat row 0 (idempotent
    scatter)."""
    def one(lg, seed, n):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), n)
        return sample_token(
            lg[None], key, temperature=temperature, top_k=top_k, top_p=top_p,
        )[0]

    samples = jax.vmap(one)(last_logits[:k], seeds, ns)
    with scope("chunk.tail"):
        token = token.at[slots].set(samples)
        row_start = row_start.at[slots].set(dsts)
        prefix_rows = prefix_rows.at[slots].set(actives)
    return samples, token, row_start, prefix_rows


@partial(jax.jit, donate_argnames=("cache",))
def _move_row(cache, src, dst):
    """Copy row ``src``'s full window onto row ``dst`` (one program for
    all moves; traced indices). Used to compact live rows into the low
    slots before the pool's row capacity shrinks — the row carries its
    ``row_start``-relative positions with it, so no re-RoPE. Every leaf
    has its rows on axis 1, a state-space model's per-row state leaves
    too: they move with the row (as they shrink and grow with the pool)."""
    def leaf(x):
        row = jax.lax.dynamic_slice_in_dim(x, src, 1, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(x, row, dst, axis=1)

    return jax.tree.map(leaf, cache)


@partial(jax.jit, static_argnames=("rows",), donate_argnames=("cache",))
def _shrink_rows(cache, rows: int):
    """Drop rows ≥ ``rows`` from the pool cache (donated, so the old
    allocation is freed once the slice lands)."""
    return jax.tree.map(
        lambda x: jax.lax.slice_in_dim(x, 0, rows, axis=1), cache
    )


@partial(jax.jit, static_argnames=("rows",), donate_argnames=("leaf",))
def _grow_leaf(leaf, rows: int):
    """Zero-pad ONE pool-cache leaf's row axis out to ``rows`` (donated:
    the old leaf frees as soon as the concat lands). Growing leaf by
    leaf bounds the regrow transient to old-tree + one new leaf — a
    whole-tree template next to the old cache could RESOURCE_EXHAUSTED a
    capacity-tuned pool (8B weights + near-full KV) that shrank at low
    occupancy, failing every live stream on the next burst's regrow.
    Sharding rides GSPMD propagation from the input leaf (batch-axis
    concat never crosses a sharded axis: KV shards over heads/seq)."""
    pad = jnp.zeros(
        leaf.shape[:1] + (rows - leaf.shape[1],) + leaf.shape[2:], leaf.dtype
    )
    return jnp.concatenate([leaf, pad], axis=1)


@partial(jax.jit, donate_argnames=("cache",))
def _compact_cache(cache, shift):
    """Slide every row's window left by ``shift`` slots (traced shift, one
    program for all compactions). The shift is the same for all rows by
    construction — every live window ends at the shared frontier — and
    junk that wraps around lands at slots ≥ the new frontier, which the
    valid mask excludes and future decode writes overwrite. A state-space
    model's per-row state leaves have no slots to slide and stay as they
    are: a row's state does not depend on where its window lies."""
    return _kv_tree_map(
        lambda leaf: jnp.roll(leaf, -shift, axis=_seq_axis(leaf)), cache
    )


class ContinuousBatcher:
    """Continuous-batching scheduler over one Engine.

    ``submit()`` returns a ``Future[GenerateResult]``; a background
    scheduler thread owns the batch cache and runs the fetch → retire →
    admit → dispatch loop. ``close()`` cancels queued submissions, lets
    in-flight streams finish, and stops the loop.
    """

    def __init__(self, engine: Engine, max_batch: int = 8,
                 prefill_budget: Optional[int] = None, spec=None):
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self.engine = engine
        self.max_batch = max_batch
        # Batched speculative decoding (engine/speculative.py): ``spec``
        # is a SpecConfig naming a buffer drafter (prompt lookup, or an
        # oracle in tests/bench). When present — and the pool's sampling
        # template turns out greedy — decode dispatches become spec
        # ROUNDS: one drafter program + ONE target forward verifying
        # k+1 positions for every resident row (B×(k+1) tokens per
        # weight stream, the batch-1 verification MFU fix), with
        # per-row acceptance as data. The pool keeps its shared write
        # frontier (admission splicing, capacity checks, and compaction
        # keep their arithmetic — the frontier advances k+1 per round,
        # host-known); rejected slots become per-row HOLES masked by a
        # written-slot bitmap (the forward's kv_mask path), and
        # ``row_start`` absorbs each row's hole count so positions stay
        # per-row exact. None (the default) keeps every dispatch path
        # byte-identical to the classic batcher.
        self._spec_cfg = spec
        self._spec = None
        if spec is not None and engine.cfg.is_latent:
            raise ValueError(
                f"{engine.cfg.name}: no speculative pool decode over a "
                "latent (MLA) cache")
        if spec is not None:
            refuse_ssm(engine.cfg, "speculative pool decode")
        if spec is not None and engine.cfg.sliding_window is not None:
            # Same warn-once courtesy the model-draft+batching case gets
            # (providers/tpu.py): an operator who configured speculation
            # must not silently get classic decode forever.
            warnings.warn(
                f"speculative pool decode disabled for "
                f"{engine.cfg.name!r}: kv_mask holes do not compose "
                "with sliding_window attention",
                RuntimeWarning,
                stacklevel=2,
            )
        elif spec is not None:
            place_ = engine._place
            s_cap = engine.max_seq
            self._spec = _SpecState(
                cfg=spec,
                controller=AdaptiveK(spec.k, adaptive=spec.adaptive),
                governor=SpecGovernor(
                    probe_tokens=spec.probe_tokens, enabled=spec.governor,
                ),
                valid=place_(jnp.zeros((max_batch, s_cap), bool)),
                buf=place_(jnp.zeros((max_batch, s_cap), jnp.int32)),
                obuf=(
                    place_(jnp.zeros((max_batch, s_cap), jnp.int32))
                    if spec.kind == "oracle" else None
                ),
                blen=place_(jnp.zeros((max_batch,), jnp.int32)),
            )
        # Interleaved admission prefill (LLMC_PREFILL_BUDGET / the
        # --prefill-budget flag): > 0 splits each admission wave's
        # prefill into bounded token-budget chunk groups dispatched
        # BETWEEN decode chunks, so resident streams keep decoding while
        # a new wave establishes its KV — prefill never stalls an active
        # decode frontier. 0/unset keeps the classic stall-the-pool
        # admission (byte-identical token streams; asserted in
        # tests/test_overlap.py). The budget counts TOTAL prompt tokens
        # (rows × chunk length) dispatched per decode-chunk interval.
        if prefill_budget is None:
            prefill_budget = knobs.get_int("LLMC_PREFILL_BUDGET")
        self._prefill_budget = max(0, prefill_budget)
        # The one in-flight interleaved wave (admission is skipped while
        # it establishes, so waves never overlap); its slots stay None in
        # self._slots until the wave splices + installs.
        self._pending_wave: Optional[_PendingWave] = None
        # Cross-thread batcher state (submit side, governor, fetch
        # worker) is condition-guarded; scheduler-owned state (_slots,
        # _pending_wave, the prefix pool fields) deliberately is not —
        # the scheduler thread is its single writer. Enforced by the
        # static guarded-state checker (analysis/guarded_state.py);
        # under LLMC_SANITIZE=1 the named lock joins the runtime
        # lock-order graph (analysis/sanitizer.py).
        self._lock = sanitizer.make_lock("engine.batcher")
        self._work = sanitizer.make_condition("engine.batcher", self._lock)
        self._queue: list[tuple[list, _Stream]] = []  # guarded by: _work
        self._slots: list[Optional[_Stream]] = [None] * max_batch
        self._closed = False
        self._template: Optional[tuple] = None  # (temperature, top_k, top_p)
        place = engine._place
        self._token = place(jnp.zeros((max_batch,), jnp.int32))
        self._row_start = place(jnp.zeros((max_batch,), jnp.int32))
        self._row_start_host = [0] * max_batch
        # Which rows of the device ``row_start`` carry DEAD_ROW (a tuple of
        # bools, one a pool row; None: not known, mark again). Slots are
        # freed on the fetch worker and the device vector is the
        # scheduler's, so the marks are brought up to ``_slots`` where the
        # next decode chunk is dispatched (_mark_dead_rows).
        self._dead_marked: Optional[tuple] = None
        self._pos = 0  # shared frontier (host int; traced into the chunk)
        self._key = place(jax.random.PRNGKey(0))
        # Shared-prefix pool state (the one-prompt fan-out pattern): when
        # a wave's prompts share a long common prefix, ONE [1, P] prefix
        # KV is established for the pool; participating rows hold only
        # their suffix in the batch cache and decode merges prefix +
        # suffix attention exactly (models/transformer.py). Decode HBM
        # traffic for the prefix drops from B replicated cache streams to
        # one MXU matmul, and the per-row width bucket shrinks to the
        # suffix. Gated off for sliding-window models (the window would
        # span the seam) and for meshes with a non-trivial non-tp axis:
        # trivial meshes (the planner pins even 1-chip engines to one)
        # and tp-only shardings both compose — the decode kernel's merge
        # state rides shard_map over the head axis and the prefix
        # attention/prefill paths are plain XLA that GSPMD partitions —
        # while sp/pp axes would put the prefix on an axis the splice
        # and ring-prefill layouts don't model.
        mesh_ok = engine.mesh is None or all(
            s == 1 for k, s in dict(engine.mesh.shape).items() if k != "tp"
        )
        self._prefix_enabled = (
            knobs.get_bool("LLMC_POOL_PREFIX")
            and engine.cfg.sliding_window is None
            # No prefix-merge form over a latent (MLA) cache yet: off.
            and not engine.cfg.is_latent
            # A shared prefix has no state for a row to start from: off.
            and not engine.cfg.has_state
            and mesh_ok
            # Spec rounds hold each row's FULL prompt in its own window
            # (the batched verify program has no prefix-merge form);
            # prefix sharing is disabled rather than silently mixing
            # decode programs per wave.
            and self._spec is None
        )
        self._prefix_min = knobs.get_int("LLMC_POOL_PREFIX_MIN")
        self._prefix_ids: Optional[tuple] = None
        self._prefix_cache = None       # [L, 1, P_cap, Hkv, dh] stacks
        self._prefix_len_host = 0
        self._prefix_weight_version = -1  # engine version that built it
        self._plen = place(jnp.zeros((), jnp.int32))
        self._prefix_rows = place(jnp.zeros((max_batch,), jnp.bool_))
        self._cache = engine.new_cache(max_batch)
        # Occupancy row-bucketing (the dead-slot-stepping fix): the pool
        # cache starts at full capacity, but when occupancy falls below
        # half the CURRENT row capacity for a few consecutive chunks,
        # live rows compact into the low slots and the cache physically
        # shrinks to the occupancy's power-of-two bucket — decode
        # attention bytes and matmul batch scale with live streams, not
        # pool capacity. Growth is admission-driven (a burst that needs
        # more slots re-allocates before its wave splices). Row moves
        # preserve row_start-relative positions, so no re-RoPE; every
        # resize drains the fetch pipeline first so no in-flight chunk's
        # owner snapshot can misattribute a moved row's tokens.
        # LLMC_POOL_BUCKET=0 disables. The floor bounds the compiled
        # program variants at log2(max_batch/floor)+1 row sizes.
        self._rows_cap = max_batch
        self._min_rows = max(8, max_batch // 8)
        self._shrink_patience = 0
        self._rows_bucket_enabled = (
            knobs.get_bool("LLMC_POOL_BUCKET")
            and max_batch > self._min_rows
        )
        # Steady-state decode-phase accounting: live tokens emitted and
        # wall time across chunk ARRIVAL intervals (device_get return to
        # device_get return on the fetch worker) in which the device ran
        # ONLY a decode chunk (no admission prefills, no compaction).
        # With fetch+emit off the dispatch path, consecutive arrivals
        # are one device chunk apart when the device is the bottleneck —
        # so unlike round 3's fetch-to-fetch sums this EXCLUDES the
        # host fetch/emit time the pipeline overlaps, and the rate it
        # implies upper-bounds (not trails) the end-to-end aggregate.
        # Updated by atomic dict replacement (a bench thread snapshots
        # concurrently).
        # Per-phase wall accounting (VERDICT r4 #3): the dict is REPLACED
        # atomically under self._work on every update, so readers may
        # snapshot it lock-free. decode_s counts pure arrival-to-arrival
        # intervals with live emits (steady-state decode); tail_s the
        # pure intervals whose chunk emitted nothing (tail overshoot
        # dead-stepping); establish_s/admit_s the scheduler-side
        # shared-prefix establishment and admission-prefill walls;
        # absorb_s the bounded idle-pool burst-absorb pauses.
        # admit_tokens counts prompt tokens actually prefilled (suffix
        # lengths under shared-prefix admission), for prefill-inclusive
        # rates.
        # impure_s/impure_tokens: arrival intervals NOT preceded by pure
        # decode — the device time of admission prefills, establishment,
        # and compactions lands here (their HOST dispatch walls are
        # establish_s/admit_s; dispatch is async, so the device-side
        # cost only surfaces as a longer next arrival).
        self.stats = {  # guarded by: _work (atomic dict swap)
            "decode_tokens": 0, "decode_s": 0.0, "tail_s": 0.0,
            "impure_s": 0.0, "impure_tokens": 0,
            "establish_s": 0.0, "admit_s": 0.0, "admit_tokens": 0,
            "absorb_s": 0.0, "preemptions": 0,
            # Inside admit_s, the one-row route alone (``_admit``): the
            # dispatches it made and what held the scheduler thread in
            # them: the row cache's allocation, the prefill programs'
            # dispatch, the splice's (``pool.admit``'s ``*_ms`` summed).
            "admit_single_dispatches": 0, "admit_alloc_s": 0.0,
            "admit_dispatch_s": 0.0, "admit_splice_s": 0.0,
            # Counted where the work is dispatched: decode chunks, Σ steps
            # and Σ steps × live rows (row fill = row_steps ÷ (steps ×
            # rows)); admission waves, their real and padded rows, and
            # the token slots their prefill programs covered (padding
            # share = 1 − admit_tokens ÷ prefill_slot_tokens).
            "decode_chunks": 0, "decode_steps": 0, "decode_row_steps": 0,
            # Cache slots the decode steps' attention spanned as pool rows
            # x bucket width (what a sweep of every row reads) and the
            # slots of rows with a stream inside their own windows
            # (kv_slots_live): their ratio is what is left to sweep.
            "decode_kv_slots_swept": 0, "decode_kv_slots_live": 0,
            "prefill_waves": 0, "prefill_rows_real": 0,
            "prefill_rows_padded": 0, "prefill_slot_tokens": 0,
            # Runs of the whole stack the waves' prefills made: a one-shot
            # wave one, a prompt past ``prefill_chunk`` one a chunk of the
            # engine's width (engine.py ``_chunk_width``).
            "prefill_chunks": 0,
            # (Query, key) pairs the waves' prefill programs' attention
            # scored, and the pairs causality needed for their real tokens
            # (causal_pairs): their ratio is what a prefill sweeps in vain.
            "prefill_kv_pairs_swept": 0, "prefill_kv_pairs_live": 0,
        }
        if engine.cfg.has_state:
            # A state-space model's pool: the positions its prefill
            # programs' scans ran over (rows x slots, padding and whole scan
            # chunks included) and the real tokens among them; and the rows
            # whose state a decode step read and wrote (every row the pool
            # holds, with a stream or not: the one-step form carries them
            # all), summed over steps, beside ``decode_steps``.
            self.stats.update(
                ssm_positions_swept=0, ssm_positions_live=0,
                ssm_state_row_steps=0,
            )
        # A pool whose attention layers differ in their window: what one
        # "W" layer sweeps a dispatch (``_window_decode``).
        if "W" in engine.cfg.layer_kinds:
            self.stats["decode_kv_slots_window_layer"] = 0
        if engine.cfg.is_moe:
            # A routed model's programs return their routing sums
            # (ops/moe.py), which ride each fetch: (token, chosen expert)
            # pairs and those on experts held here, over decode chunks and
            # prefill programs alike; held experts that took at least one
            # row, one count an expert layer a decode step, beside the
            # (expert layer, decode step)s counted; and the held pairs of
            # the prefill programs alone, whose every program reads nearly
            # every held expert; and the (expert layer, decode step)s whose
            # products ran in the kernel over the sorted pairs, by the rule
            # the program itself was traced under (ops/moe.py).
            self.stats.update(
                moe_pairs_total=0, moe_pairs_held=0, moe_expert_reads=0,
                moe_layer_steps=0, moe_prefill_pairs_held=0,
                moe_kernel_layer_steps=0,
            )
            engine._moe_bank = []  # the engine's prefills bank theirs here
        # Priority-aware preemption (pressure/): when a queued stream of
        # a strictly higher class is blocked on a slot, the scheduler
        # preempts the lowest-priority / least-progress resident stream
        # — its slot and KV window release, its journal entry seals, and
        # it requeues for byte-identical resume through the same
        # prompt+emitted-prefix re-prefill contract replay uses
        # (submit_ids replay_ids). LLMC_PRESSURE_PREEMPT=0 disables;
        # single-class pools never preempt either way.
        self._preempt_enabled = (
            knobs.get_bool("LLMC_PRESSURE_PREEMPT")
        )
        self._preempt_req = 0  # guarded by: _work
        # Brownout (pressure governor): spec-enabled pools dispatch
        # bitmap-maintaining plain windows while set — speculation is a
        # speed lever, and under brownout degraded-but-predictable wins.
        self._brownout = False
        self._prev_arrival: Optional[float] = None
        # Telemetry (obs/): bound once like the engine's fault plan, so a
        # disabled run's scheduler/fetch loops consult only this None.
        from llm_consensus_tpu import obs as _obs

        self._obs = _obs.recorder()
        # Flight recorder (obs/blackbox): the ALWAYS-ON bounded ring —
        # decode/fetch/admit spans land here even with events off, so an
        # engine crash dumps the seconds of timeline that explain it.
        self._bb = _obs.blackbox.ring()
        # Every span goes through the one emitter (obs/spans.py), once:
        # to the recorder, the ring and — inside a profiler window — the
        # profiler's trace, on a row of this pool's own.
        self._spans = _obs.emitter()
        self._model = engine.cfg.name
        self._tid = f"pool:{engine.cfg.name}"
        # Chip-time attribution (obs/attrib): device time per program
        # family from the arrival intervals the fetch worker already
        # measures, the goodput token ledger, and host-gap (bubble)
        # detection between a drained pipeline and the next dispatch.
        self._attrib = _obs.attrib.ledger()
        if self._attrib is not None:
            try:
                self._attrib.update_component(
                    f"pool_cache:{engine.cfg.name}",
                    sum(
                        leaf.size * leaf.dtype.itemsize
                        for leaf in jax.tree.leaves(self._cache)
                    ),
                )
            except Exception:  # noqa: BLE001 — modeling only
                pass
        # Host-gap state: _idle_at marks the arrival that drained the
        # pipeline while the batcher still had work (device idle starts);
        # _gap_phase names the scheduler phase that ran during the gap.
        self._idle_at: Optional[float] = None
        self._gap_phase = "schedule"
        # What kind of non-decode device work made the next arrival
        # interval impure ("prefill" admission / "compact" compaction),
        # so impure intervals book against the right family.
        self._impure_kind = "prefill"
        # Stream journal (recovery/): bound once, same zero-cost pattern —
        # with LLMC_JOURNAL unset every stream's jentry stays None and the
        # emit loop carries a single per-token None-check.
        from llm_consensus_tpu import recovery as _recovery

        self._journal = _recovery.journal()
        # Integrity plane (integrity/): with the plane on, classic decode
        # chunks dispatch with the fused finite-logit sentinel and the
        # per-row verdict rides the existing fetch — a poisoned row fails
        # only its own stream (typed IntegrityError), neighbors emit
        # byte-identically.
        from llm_consensus_tpu import integrity as _integrity

        self._integrity = _integrity.plane()
        # Pool-death evidence the supervisor classifies on: set by the
        # scheduler's pool-fatal exception path and by abandon(). None on
        # a healthy (or cleanly closed) pool.
        self.failed_exc: Optional[BaseException] = None
        # Decode heartbeat: advanced by submissions, admissions, decode
        # dispatches, and fetch arrivals. A BUSY pool whose heartbeat
        # goes stale is wedged (stuck transfer, hung compile) — the
        # supervisor's watchdog reads heartbeat_age()/busy().
        self._beat = time.monotonic()
        # Dispatch pipeline state (guarded by self._work): chunks
        # dispatched whose tokens the worker has not finished emitting.
        # Depth capped at 2 — one chunk running on device, one being
        # fetched/emitted — so speculative overshoot past EOS stays
        # bounded like the old single-lookahead loop.
        self._unfetched = 0  # guarded by: _work
        # Decode steps whose tokens have landed on the host (plain chunks;
        # the fetch thread's own count, read into the streams' marks).
        self._steps_landed = 0
        self._nondecode_work = False  # admission/compaction since last dispatch
        # [(slot list, samples array, owner list)] per admission wave
        # since the last dispatch — attached to the next dispatched
        # chunk so prefill-sampled tokens ride down with its fetch (they
        # persist across iterations that skip dispatching).
        # Scheduler-owned.
        self._firsts: list[tuple] = []
        self._worker_exc: Optional[BaseException] = None  # guarded by: _work
        from queue import SimpleQueue

        self._fetch_q: SimpleQueue = SimpleQueue()
        self._fetch_thread = threading.Thread(
            target=self._fetch_worker, name="llmc-batcher-fetch", daemon=True
        )
        self._fetch_thread.start()
        self._thread = threading.Thread(
            target=self._run, name="llmc-batcher", daemon=True
        )
        self._thread.start()
        # A daemon scheduler still dispatching while the interpreter tears
        # down the JAX runtime aborts the process; close cleanly at exit.
        atexit.register(self.close)

    # -- public API ----------------------------------------------------------

    def submit(
        self,
        prompt: str,
        sampling: SamplingParams = SamplingParams(),
        ctx: Optional[Context] = None,
        on_text: Optional[Callable[[str], None]] = None,
        *,
        priority: int = 1,
        trace_id: Optional[str] = None,
    ) -> "Future[GenerateResult]":
        """Queue a prompt; the Future resolves to the same GenerateResult
        shape the single-stream API returns."""
        eng = self.engine
        prompt_ids, truncated = eng._budget_prompt(
            eng.tokenizer.encode(prompt), sampling.max_new_tokens
        )
        return self.submit_ids(
            prompt_ids, sampling, ctx=ctx, on_text=on_text,
            truncated=truncated, priority=priority, trace_id=trace_id,
        )

    def submit_ids(
        self,
        prompt_ids: list,
        sampling: SamplingParams = SamplingParams(),
        ctx: Optional[Context] = None,
        on_text: Optional[Callable[[str], None]] = None,
        *,
        truncated: bool = False,
        replay_ids: "tuple | list" = (),
        jentry=None,
        priority: int = 1,
        trace_id: Optional[str] = None,
    ) -> "Future[GenerateResult]":
        """Token-level submit (``prompt_ids`` already budgeted).

        ``replay_ids`` resumes a stream a previous pool incarnation
        decoded partway (recovery/): the emitted prefix becomes part of
        the PREFILL context — re-established at admission, not
        re-decoded — and counts against ``max_new`` exactly as if this
        pool had produced it, so a greedy stream continues byte-identical
        from the recorded frontier. The prefix is pre-fed through the
        stream decoder (and ``on_text``, which the supervisor's shim
        dedups) so the final text covers the full generation. ``jentry``
        carries the caller's journal entry; without one, an enabled
        journal opens a fresh entry here.
        """
        eng = self.engine
        shape = (sampling.temperature, sampling.top_k, sampling.top_p)
        if not prompt_ids:
            raise ValueError("empty prompt")
        if jentry is None and self._journal is not None:
            jentry = self._journal.record(
                list(prompt_ids), sampling, trace=trace_id
            )
        stream = _Stream(
            future=Future(),
            sampling=sampling,
            ctx=ctx or Context.background(),
            on_text=on_text,
            prompt_tokens=len(prompt_ids),
            decoder=StreamDecoder(eng.tokenizer),
            submitted=time.monotonic(),
            truncated=truncated,
            max_new=min(sampling.max_new_tokens, eng.max_seq - len(prompt_ids)),
        )
        stream.jentry = jentry
        stream.priority = int(priority)
        stream.pids = tuple(prompt_ids)
        stream.trace = trace_id
        ids = list(prompt_ids)
        if replay_ids:
            # Goodput ledger: a crash-recovery resubmission re-prefills
            # the prior incarnation's emitted prefix — work the fleet
            # already did once.
            if self._attrib is not None:
                self._attrib.token_event("crash_replay", len(replay_ids))
            ids += list(replay_ids)
            stream.out_ids = list(replay_ids)
            # The prefill-sampled first token covers one NEW step on top
            # of the replayed prefix.
            stream.planned = 1 + len(replay_ids)
            for tok in replay_ids:
                if on_text is not None:
                    text = stream.decoder.push(tok)
                    if text:
                        stream.parts.append(text)
                        on_text(text)
            if len(stream.out_ids) >= stream.max_new:
                # The dead incarnation had already produced everything it
                # was allowed to; nothing left to decode.
                stream.finish = "length"
                stream.future.set_result(self._result(stream))
                return stream.future
        with self._work:
            if self._closed:
                if jentry is not None:
                    jentry.close("rejected")
                raise RuntimeError("batcher is closed")
            if self._template is None:
                self._template = shape
            elif shape != self._template:
                # temperature/top_k/top_p are static structure in the
                # compiled decode program; one batcher = one sampling shape.
                if jentry is not None:
                    jentry.close("rejected")
                raise ValueError(
                    f"sampling shape {shape} does not match this batcher's "
                    f"{self._template} (temperature/top_k/top_p are "
                    "per-batcher; max_new_tokens/ignore_eos are per-stream)"
                )
            # Deliberately no heartbeat here: client submissions are not
            # pool PROGRESS — beating on submit would let sustained
            # traffic mask a wedged scheduler forever. The watchdog's
            # two-strike read covers the idle→busy transition instead.
            self._queue.append((ids, stream))
            self._work.notify()
        return stream.future

    def close(self) -> None:
        atexit.unregister(self.close)
        with self._work:
            self._closed = True
            for _, s in self._queue:
                s.future.cancel()
                if s.jentry is not None:
                    s.jentry.close("cancelled")
            self._queue.clear()
            self._work.notify()
        self._thread.join(timeout=120)
        if self._thread.is_alive():
            # In-flight streams outlived the shutdown window: the daemon
            # scheduler keeps dispatching and its batch cache stays
            # allocated — a caller about to rebuild engines on these
            # devices (re-plan, elastic recovery) is now double-booking
            # HBM. Say so instead of failing silently.
            warnings.warn(
                "ContinuousBatcher scheduler still running 120s after "
                "close(); its KV cache remains allocated until in-flight "
                "streams finish",
                RuntimeWarning,
                stacklevel=2,
            )

    # -- recovery hooks (recovery/supervisor.py) -----------------------------

    def heartbeat_age(self) -> float:
        """Seconds since the pool last made observable progress (a
        submission, admission, decode dispatch, or fetch arrival)."""
        return time.monotonic() - self._beat

    def busy(self) -> bool:
        """True when the pool has work that SHOULD be advancing the
        heartbeat. The wedge predicate lives in the supervisor's
        watchdog: busy AND stale measured from the LATER of the last
        beat and the start of the current busy stretch — an idle pool's
        old heartbeat is not evidence of anything, and a pool that just
        went busy gets a full heartbeat period to make first progress."""
        # Deliberately lock-free (lint-ok below): the supervisor's
        # watchdog calls this to detect a WEDGED pool — if the scheduler
        # wedged while holding _work, a locking read here would hang the
        # one thread that can recover it. Stale reads only delay the
        # two-strike wedge call by a poll period.
        return (
            self._unfetched > 0  # lint-ok: GS01 watchdog must not block
            or self._pending_wave is not None
            or any(s is not None for s in self._slots)
            or bool(self._queue)  # lint-ok: GS01 watchdog must not block
        )

    def abandon(self, exc: BaseException) -> None:
        """Declare this pool dead WITHOUT joining its threads (they may
        be wedged inside device code that never returns): record the
        failure evidence, fail every live future, clear the slots so a
        later-waking fetch worker's owner-identity checks drop its stale
        tokens, and leave the (daemon) threads to exit on their own.
        Journal entries stay OPEN — they are exactly the replay set the
        replacement pool re-establishes. Idempotent; close() remains the
        graceful path."""
        atexit.unregister(self.close)
        first_evidence = False
        with self._work:
            if self.failed_exc is None:
                self.failed_exc = exc
                first_evidence = True
            self._closed = True
            queued = list(self._queue)
            self._queue.clear()
            live = [s for s in self._slots if s is not None]
            for i in range(len(self._slots)):
                self._slots[i] = None
            wave, self._pending_wave = self._pending_wave, None
            self._work.notify_all()
        for s in live:
            self._unpin_stream(s)
        if wave is not None:
            for _, _, s in wave.batch:
                self._unpin_stream(s)
        if first_evidence and self._bb is not None:
            # A wedge abandonment (the supervisor's watchdog) is the
            # FIRST death evidence this pool has: snapshot the ring. A
            # recovery teardown after a crash already dumped.
            self._spans.instant(
                "engine_abandon", self._tid, model=self._model,
                error=repr(exc),
            )
            self._bb.dump("engine_wedge", extra={"error": repr(exc)})
        wave_streams = [s for _, _, s in wave.batch] if wave is not None else []
        if self._attrib is not None and live:
            # Goodput ledger: a dead pool's live streams carry emitted
            # tokens whose work is lost (replay regenerates them).
            self._attrib.token_event(
                "abandoned", sum(len(s.out_ids) for s in live)
            )
        for _, s in queued:
            if not s.future.cancel() and not s.future.done():
                try:
                    s.future.set_exception(exc)
                except InvalidStateError:
                    pass
        for s in live + wave_streams:
            if not s.future.done():
                try:
                    s.future.set_exception(exc)
                except InvalidStateError:
                    pass

    # -- preemption (pressure/) ----------------------------------------------

    def preempt(self, max_victims: int = 1) -> None:
        """Request graceful preemption — abandon()'s GENTLE sibling.

        Where abandon() fails every live future, preempt() asks the
        scheduler to suspend up to ``max_victims`` of the lowest-
        priority / least-progress resident streams at its next safe
        point (after a fetch drain, so no in-flight token is lost): the
        victims' slots and KV windows release, their journal entries
        seal into fresh replay-seeded entries, and they requeue for
        byte-identical resume via the prompt+emitted-prefix re-prefill
        replay contract — their futures stay pending and resolve when
        the resumed stream finishes. The scheduler only acts when queued
        work of a strictly HIGHER class is actually blocked, so an
        unjustified nudge (the governor's rung fires fleet-wide) is a
        no-op.
        """
        with self._work:
            if self._closed:
                return
            self._preempt_req = max(self._preempt_req, max(1, max_victims))
            self._work.notify()

    # The governor's provider-facing spelling.
    request_preempt = preempt

    def set_brownout(self, on: bool) -> None:
        """Pressure-governor brownout: spec-enabled pools dispatch plain
        (bitmap-maintaining) windows while set. Classic pools ignore it
        — there is nothing cheaper than their plain program."""
        self._brownout = bool(on)

    def pressure_snapshot(self) -> dict:
        """Headroom signal for the pressure governor: live streams,
        row capacity, queue depth, and lifetime preemptions. The
        lock-guarded fields (queue, stats) read under ``_work`` — the
        governor samples at 0.5 s cadence, so contention is nil — while
        the scheduler-owned fields (_slots, _pending_wave, _rows_cap)
        stay GIL-atomic snapshot reads."""
        # Against the streams' flow (queue → wave → rows): a stream the
        # scheduler moves on between two reads is then missed once, not
        # counted twice — the governor reads streams beyond ``cap`` as
        # waiting for a row.
        live = sum(1 for s in self._slots if s is not None)
        wave = self._pending_wave  # one read: the scheduler may clear it
        # Bounded acquire, like snapshot(): the governor ladder must
        # keep sampling OTHER pools even when this one wedged holding
        # its lock — a hung governor thread would freeze the whole
        # gateway's overload response.
        got = self._work.acquire(timeout=0.2)
        try:
            queued = len(self._queue)  # lint-ok: GS01 bounded-acquire fallback
            preemptions = self.stats.get(  # lint-ok: GS01 bounded-acquire fallback
                "preemptions", 0
            )
        finally:
            if got:
                self._work.release()
        return {
            "live": live,
            "cap": self._rows_cap,
            "queued": queued
            + (len(wave.batch) if wave is not None else 0),
            "preemptions": preemptions,
        }

    def _plan_preempt(self, requeue: list) -> list:
        """Scheduler-side preemption decision: when the slots are full
        and blocked (requeued/queued) streams outrank resident ones,
        pick victims — lowest class first, least progress first within a
        class, one victim per blocked higher-class stream, and never a
        victim at or above the class it would unblock. Returns the
        resumed queue entries (empty when preemption is unjustified)."""
        with self._work:
            ext = self._preempt_req
            self._preempt_req = 0
            queued_pri = [s.priority for _, s in self._queue]
        live = [
            (i, s) for i, s in enumerate(self._slots[:self._rows_cap])
            if s is not None
        ]
        if not live:
            return []
        slots_full = (
            len(live) == self._rows_cap and self._pending_wave is None
        )
        if not slots_full and not ext:
            return []
        blocked = sorted(
            [s.priority for _, s in requeue] + queued_pri
        )
        if not blocked:
            return []
        cand = sorted(
            live, key=lambda t: (-t[1].priority, len(t[1].out_ids))
        )
        # Victim budget: slot-full preemption frees one slot per blocked
        # higher-class stream; a governor NUDGE alone honors its own
        # max_victims cap (preempt(n) promises "up to n") — resume
        # re-prefill is real work, and one nudge must not multiply it.
        budget = len(blocked) if slots_full else min(ext, len(blocked))
        victims: list[int] = []
        bi = 0
        for slot, s in cand:
            if bi >= len(blocked) or len(victims) >= budget:
                break
            if s.priority > blocked[bi]:
                victims.append(slot)
                bi += 1
        if not victims:
            return []
        # No fetched token may be lost: the victims' emitted prefixes
        # become their resume context, so the pipeline drains first.
        self._drain_fetches()
        self._mark_nondecode("preempt", "prefill")
        return self._preempt_slots(victims)

    def _preempt_slots(self, victims: list) -> list:
        """Suspend the victim slots (scheduler thread, pipeline drained):
        release the row, seal-and-reopen the journal entry, and build
        the resume queue entry — prompt ids + the emitted prefix, which
        re-admission prefills so a greedy stream continues
        byte-identically from its recorded frontier."""
        entries: list = []
        for slot in victims:
            s = self._slots[slot]
            if s is None:
                continue  # retired between planning and here
            self._slots[slot] = None
            # Leaving residency releases the weight pin; the resume
            # RE-pins at admission, so a preempted stream may continue
            # on a swapped-in version (the journal-backed migration
            # path — its replayed prefix re-prefills under new weights).
            self._unpin_stream(s)
            snapshot = list(s.out_ids)
            if len(snapshot) >= s.max_new:
                # Nothing left to decode — resolve, don't resume.
                s.finish = "length"
                if not s.future.done():
                    try:
                        s.future.set_result(self._result(s))
                    except InvalidStateError:
                        pass
                continue
            if s.jentry is not None and self._journal is not None:
                # Seal the old incarnation's entry (late stale appends
                # drop) and open a fresh one seeded with the snapshot —
                # the exact prefix the resume re-prefills — so crash
                # recovery across a preemption still replays the full
                # stream.
                old = s.jentry
                old.seal()
                s.jentry = self._journal.record(
                    list(s.pids), s.sampling, tokens=snapshot,
                    replay_of=old, trace=s.trace,
                )
                old.close("preempted")
            # The resume prefill covers the replayed prefix plus one
            # freshly sampled token — the same accounting submit_ids
            # applies to replay_ids.
            s.planned = len(snapshot) + 1
            s.preempted = True
            entries.append((list(s.pids) + snapshot, s))
            if self._attrib is not None:
                # Goodput ledger: the emitted prefix re-prefills at
                # resume — preemption's recompute cost, booked at the
                # decision point.
                self._attrib.token_event("preempt_replay", len(snapshot))
            self._spans.instant(
                "preempt", self._tid, model=self._model, slot=slot,
                priority=s.priority, progress=len(snapshot), trace=s.trace,
            )
            if self._obs is not None:
                self._obs.count("pressure.preemptions")
        if entries:
            self._stat_add(preemptions=len(entries))
        return entries

    # -- scheduler internals -------------------------------------------------

    def _admit(self, slot: int, prompt_ids: list, s: _Stream, sp) -> tuple:
        """Prefill and splice so the prompt ends at the shared frontier.

        Returns the firsts entry ``(slots, samples, owners)`` whose
        (device) prefill-sampled first token rides down with the next
        fetch. ``sp`` is the caller's open ``pool.admit`` span, as in
        ``_admit_batch``.
        """
        eng = self.engine
        n = len(prompt_ids)
        self._pin_stream(s)  # before the prefill reads eng.params
        t_prefill = time.monotonic_ns()
        try:
            last_logits, pcache = eng._prefill_ids(prompt_ids)
        except BaseException:
            # Failed prefill fails THIS stream (caller handles); it
            # never became resident, so its pin must not park a swap.
            self._unpin_stream(s)
            raise
        t_splice = time.monotonic_ns()
        dst = self._pos - n
        self._cache = _splice(
            self._cache, pcache, slot, dst, _bucket(n, eng.max_seq)
        )
        # What held the scheduler thread: the row cache's allocation, the
        # prefill programs' dispatch (the jitted calls' return: the device
        # runs on) and the splice's.
        alloc_ns = eng.last_prefill_alloc_ns
        sp.set(alloc_ms=alloc_ns / 1e6,
               dispatch_ms=(t_splice - t_prefill - alloc_ns) / 1e6,
               splice_ms=(time.monotonic_ns() - t_splice) / 1e6)
        tok = sample_token(
            last_logits,
            jax.random.fold_in(jax.random.PRNGKey(s.sampling.seed), n - 1),
            temperature=s.sampling.temperature,
            top_k=s.sampling.top_k, top_p=s.sampling.top_p,
        )
        self._token = self._token.at[slot].set(tok[0])
        self._row_start = self._row_start.at[slot].set(dst)
        if self._prefix_cache is not None:
            # Single-stream admissions carry their whole prompt in their
            # own window; the slot must not attend the pool prefix.
            self._prefix_rows = self._prefix_rows.at[slot].set(False)
        self._row_start_host[slot] = dst
        if self._spec is not None:
            self._spec_install(
                [(slot, prompt_ids, s)], 1,
                eng._place(jnp.asarray([slot], jnp.int32)),
                eng._place(jnp.asarray([dst], jnp.int32)),
                tok,
            )
        self._slots[slot] = s
        did = eng.last_prefill
        sp.set(chunks=did.chunks, slot_tokens=did.slot_tokens,
               pairs_swept=did.pairs_swept,
               pairs_live=causal_pairs([n], did.reused),
               **_ssm_admit(eng.cfg, did, 1, n))
        return ([slot], tok, [s])

    def _establish_prefix(self, prefix_ids: list[int]) -> bool:
        """Prefill the wave's common prefix ONCE and install it as the
        pool's shared-prefix KV (pool must be idle). The [1, S] prefill
        rides the engine's snapshot-reuse path, so repeated bursts with
        the same prompt restore it in one masked pass instead of
        recomputing; the prefix is retained as that snapshot afterwards.
        Returns False (state cleared) on any failure."""
        eng = self.engine
        refuse_ssm(eng.cfg, "pooled shared-prefix admission")
        p = len(prefix_ids)
        # 128-granule cap (not 256): prefix-attention compute scales with
        # p_cap — the XLA path has no Mosaic tiling constraint, and lanes
        # stay aligned at 128 (a 266-token prefix pays 384, not 512).
        p_cap = min(-(-p // 128) * 128, eng.max_seq)
        if p_cap < p:
            self._clear_prefix()  # don't hold a stale prior prefix
            return False
        # The dense [L, 1, p_cap, Hkv, dh] compute-dtype copy is HBM the
        # comment in _extract_prefix budgets as "tens of MB"; a
        # near-max_seq prefix on a large model is not that. Bound it by
        # the same cap the retained snapshot honors and fall back to
        # no-sharing rather than silently holding hundreds of MB. The
        # caller only establishes pool-idle, so clearing any PRIOR prefix
        # here is safe — and required: leaving it resident would keep the
        # exact HBM this cap exists to bound, plus the costlier
        # prefix-merge decode program, with no row ever using it.
        dense_bytes = p_cap * cache_bytes_per_token(
            eng.cfg, jnp.dtype(eng._dtype).itemsize)
        if dense_bytes > eng._prefix_max_bytes:
            self._clear_prefix()
            return False
        try:
            _, pcache = eng._prefill_ids(prefix_ids)
            eng._retain_prefix(prefix_ids, pcache)
            self._prefix_cache = _extract_prefix(pcache, p_cap)
        except Exception:  # noqa: BLE001 — establishment is an optimization
            self._clear_prefix()
            # Without this, every subsequent idle wave with a qualifying
            # common prefix re-runs the same failing full-prefix prefill
            # before degrading — repeated wasted prefill under sustained
            # bursts. Disable like the failed suffix-wave path does.
            warnings.warn(
                "shared-prefix establishment prefill failed; disabling "
                "pool prefix sharing for this batcher",
                RuntimeWarning,
                stacklevel=2,
            )
            self._prefix_enabled = False
            return False
        self._prefix_ids = tuple(prefix_ids)
        self._prefix_len_host = p
        # Stamp the weight version whose params computed this KV: the
        # scheduler clears the prefix when a hot-swap changes it.
        self._prefix_weight_version = eng.weight_version
        self._plen = eng._place(jnp.asarray(p, jnp.int32))
        return True

    def _clear_prefix(self) -> None:
        self._prefix_cache = None
        self._prefix_ids = None
        self._prefix_len_host = 0
        self._prefix_weight_version = -1

    def _admit_batch(self, batch: list[tuple[int, list, _Stream]],
                     prefix_p: int, sp) -> Optional[tuple]:
        """Admit several streams with ONE batched prefill.

        A burst of k admissions prefilled row-by-row streams the full
        weights k times; Engine._prefill_rows streams them once (measured
        as the dominant serving-vs-generate_batch gap at large batch).
        Rows are padded to a power-of-two count so the compile set stays
        logarithmic in burst size. ``prefix_p`` > 0 means every row of
        this wave starts with the pool's established ``prefix_p``-token
        shared prefix: only the SUFFIXES prefill (through the prefix-
        merge attention path) and only suffix KV lands in the pool —
        wave prefill compute scales with the new tokens, not the shared
        prompt. Returns the firsts entry, or None when the
        batched prefill itself failed (caller falls back to one-by-one
        admission). ``sp`` is the caller's open ``pool.admit`` span: what
        the wave dispatched (padded rows, chunks, the token slots they
        cover) is written on it, and the caller counts from there.
        """
        eng = self.engine
        rows = [ids for _, ids, _ in batch]
        k_pad = wave_k_pad(len(rows), self.max_batch)
        pad_rows = rows + [rows[0]] * (k_pad - len(rows))
        for _, _, s in batch:
            self._pin_stream(s)  # before the prefill reads eng.params
        try:
            if prefix_p:
                last_logits, pcache, width = eng._prefill_rows_suffix(
                    [r[prefix_p:] for r in pad_rows],
                    self._prefix_cache, prefix_p,
                )
            else:
                last_logits, pcache = eng._prefill_rows(pad_rows)
                width = eng._rows_bucket(max(len(r) for r in rows))
            did = eng.last_prefill
            sp.set(rows_padded=k_pad, chunks=did.chunks,
                   slot_tokens=did.slot_tokens, pairs_swept=did.pairs_swept,
                   pairs_live=causal_pairs(
                       [len(r) for r in rows], prefix_p or did.reused),
                   **_ssm_admit(eng.cfg, did, k_pad,
                                sum(len(r) - prefix_p for r in rows)))
        except Exception as exc:  # noqa: BLE001
            # The fallback below hides the failure from everyone but the
            # span: say what it was.
            sp.set(rows_padded=k_pad, error=type(exc).__name__)
            # Batched prefill failed (OOM on the k-row bucket, a bad
            # row) before any state changed: the caller re-admits
            # one-by-one so a failure costs one stream, not the wave.
            # Splice/sample failures below stay fatal — state is
            # already partially applied, and they indicate the same
            # engine-level breakage a decode dispatch failure would.
            for _, _, s in batch:
                self._unpin_stream(s)  # one-by-one retry re-pins
            return None
        return self._install_wave(
            batch, prefix_p, k_pad, last_logits, pcache, width,
        )

    def _install_wave(self, batch, prefix_p: int, k_pad: int,
                      last_logits, pcache, width: int) -> tuple:
        """Splice a finished wave's prefill cache into the pool at the
        CURRENT frontier and install its streams: the fused row splice,
        the one-program post-prefill state update (_admit_finish), and
        the host-side slot bookkeeping. Shared by the classic
        (_admit_batch) and interleaved (_advance_wave) admission paths —
        the splice itself is frontier-relative, so it accepts rows whose
        prefill was established many decode chunks ago. Returns the
        firsts entry ``(slots, samples, owners)``."""
        eng = self.engine
        k = len(batch)
        slots = [slot for slot, _, _ in batch]
        dsts = [self._pos - (len(ids) - prefix_p) for _, ids, _ in batch]
        pad = k_pad - k  # padding entries repeat row 0 (idempotent)
        place = eng._place
        slots_arr = place(jnp.asarray(slots + [slots[0]] * pad, jnp.int32))
        dsts_arr = place(jnp.asarray(dsts + [dsts[0]] * pad, jnp.int32))
        self._cache = _splice_rows(
            self._cache, pcache,
            place(jnp.asarray(list(range(k)) + [0] * pad, jnp.int32)),
            slots_arr, dsts_arr, k_pad, width,
        )
        sp = batch[0][2].sampling
        # Seeds ride as uint32 (PRNGKey folds them identically); a raw
        # int32 cast would raise on seeds >= 2**31 — and from here an
        # exception is pool-fatal, not per-stream.
        seeds = [s.sampling.seed & 0xFFFFFFFF for _, _, s in batch]
        ns = [len(ids) - 1 for _, ids, _ in batch]
        actives = [bool(prefix_p)] * k
        samples, self._token, self._row_start, self._prefix_rows = _admit_finish(
            last_logits, self._token, self._row_start, self._prefix_rows,
            slots_arr, dsts_arr,
            place(jnp.asarray(actives + [actives[0]] * pad, jnp.bool_)),
            place(jnp.asarray(seeds + [seeds[0]] * pad, jnp.uint32)),
            place(jnp.asarray(ns + [ns[0]] * pad, jnp.int32)),
            k_pad, sp.temperature, sp.top_k, sp.top_p,
        )
        if self._spec is not None:
            # wave_p is structurally 0 here: spec pools disable prefix
            # sharing at construction, so every row holds its full prompt.
            self._spec_install(batch, k_pad, slots_arr, dsts_arr, samples)
        owners = []
        for i, (slot, ids, s) in enumerate(batch):
            self._row_start_host[slot] = dsts[i]
            self._slots[slot] = s
            owners.append(s)
        return (slots, samples, owners)

    def _spec_install(self, batch, k_pad: int, slots_arr, dsts_arr,
                      samples) -> None:
        """Install admitted rows' speculative state in ONE program
        (_install_spec_rows): bitmap row = the spliced prompt window,
        token buffer = prompt ids + the prefill-sampled first token,
        blen = n + 1. Prompt rows are padded to the engine's width
        bucket so program variants stay logarithmic. Oracle continuations
        (tests/bench only) scatter host-side — admission is not the hot
        path there."""
        sp = self._spec
        eng = self.engine
        place = eng._place
        s_cap = eng.max_seq
        idlists = [ids for _, ids, _ in batch]
        w = min(_bucket(max(len(i) for i in idlists), s_cap), s_cap)
        rows = [(list(i) + [0] * w)[:w] for i in idlists]
        nlens = [len(i) for i in idlists]
        pad = k_pad - len(batch)
        rows += [rows[0]] * pad
        nlens += [nlens[0]] * pad
        sp.valid, sp.buf, sp.blen = _install_spec_rows(
            sp.valid, sp.buf, sp.blen, slots_arr, dsts_arr, self._pos,
            place(jnp.asarray(rows, jnp.int32)),
            place(jnp.asarray(nlens, jnp.int32)),
            samples, k_pad,
        )
        for _slot, _ids, s in batch:
            s.spec_ema = 0.0
        if sp.obuf is not None and sp.cfg.oracle is not None:
            for slot, ids, _s in batch:
                cont = list(sp.cfg.oracle(list(ids)))
                row = (list(ids) + cont + [0] * s_cap)[:s_cap]
                sp.obuf = sp.obuf.at[slot].set(
                    place(jnp.asarray(row, jnp.int32))
                )

    # -- interleaved admission (prefill/decode overlap) ----------------------

    def _begin_wave(self, batch, wave_p: int) -> bool:
        """Start an interleaved admission wave: open the engine prefill
        session whose chunks ``_advance_wave`` paces between decode
        dispatches. Returns False — caller admits classically — when the
        wave would not fit the frontier AFTER the decode growth its own
        interleaving implies, or when the session cannot open."""
        eng = self.engine
        rows = [ids for _, ids, _ in batch]
        k_pad = wave_k_pad(len(rows), self.max_batch)
        pad_rows = rows + [rows[0]] * (k_pad - len(rows))
        if wave_p:
            w_req = _bucket(
                max(len(r) - wave_p for r in rows), eng.max_seq
            )
        else:
            w_req = eng._rows_bucket(max(len(r) for r in rows))
        # Frontier headroom: the splice happens at the frontier the pool
        # reaches when the LAST prefill chunk has been dispatched — one
        # decode chunk per budget of prefill, plus the depth-2 pipeline's
        # slack. A wave that would overrun capacity then admits
        # classically now (which fits at the current frontier by the
        # admission checks) instead of wasting its prefill.
        total = sum(len(r) - wave_p for r in pad_rows)
        steps = max(1, -(-total // max(1, self._prefill_budget)))
        growth = (steps + 2) * eng.stream_interval
        if not all(
            fits(len(ids) - wave_p, self._pos + growth, w_req, eng.max_seq)
            for _, ids, _ in batch
        ):
            return False
        for _, _, s in batch:
            self._pin_stream(s)  # the session's chunks read eng.params
        try:
            if wave_p:
                session = eng.admission_session(
                    [r[wave_p:] for r in pad_rows],
                    prefix_cache=self._prefix_cache, prefix_len=wave_p,
                )
            else:
                session = eng.admission_session(pad_rows)
        except Exception:  # noqa: BLE001 — classic path has the fallback
            for _, _, s in batch:
                self._unpin_stream(s)  # classic retry re-pins
            return False
        self._pending_wave = _PendingWave(
            batch=batch, wave_p=wave_p, k_pad=k_pad, session=session,
        )
        return True

    def _advance_wave(self, exhaust: bool) -> None:
        """Dispatch one prefill credit (``LLMC_PREFILL_BUDGET`` total
        prompt tokens) of the pending wave — or, with ``exhaust`` (pool
        has nothing live to overlap with), run it to completion. On the
        final credit: splice at the CURRENT frontier, install the
        streams, and attach their first tokens to the next dispatched
        chunk's fetch. Each credit is one ``pool.admit`` span
        (``interleaved``), booked whichever way it ends; the wave's
        counters are booked with the credit that installs it."""
        eng = self.engine
        wave = self._pending_wave
        with self._booked(
            "prefill", "pool.admit", "admit_s", route="rows",
            interleaved=True, exhaust=exhaust, rows_real=len(wave.batch),
            rows_padded=wave.k_pad, prefix=wave.wave_p,
            traces=[s.trace for _, _, s in wave.batch if s.trace],
        ) as (sp, deltas):
            # Marked AFTER the gap closed, as at pool.establish.
            self._mark_nondecode("admit", "prefill")
            for _, _, s in wave.batch:
                s.marks.setdefault("admit_ns", sp.t0_ns)
            try:
                done = wave.session.step(
                    None if exhaust else self._prefill_budget
                )
                sp.set(done=done)
                if not done:
                    return
                last_logits, pcache, width = wave.session.finish()
            except Exception:  # noqa: BLE001
                # Prefill-side failure (the _admit_batch try's territory):
                # requeue the wave's streams and drop to classic admission,
                # whose per-stream fallback ladder always progresses.
                self._wave_fallback(wave)
                return
            # Frontier re-check at install time: decode advanced while the
            # wave established. The headroom check in _begin_wave makes an
            # overrun rare; when it happens anyway (stragglers broke the
            # depth gate and extra chunks dispatched), requeue — wasted
            # prefill, never a clamped (misaligned) splice.
            if not all(
                fits(len(ids) - wave.wave_p, self._pos, width, eng.max_seq)
                for _, ids, _ in wave.batch
            ):
                self._requeue_wave(wave)
                return
            # The wave stays pending until the install LANDS: a pool-fatal
            # splice/sample failure propagates to _run, whose cleanup reaches
            # these streams only through self._pending_wave (they are in
            # neither the queue nor — fully — the slots); the final
            # credit's wall is booked either way (ADVICE r5 parity with
            # the classic sites).
            entry = self._install_wave(
                wave.batch, wave.wave_p, wave.k_pad, last_logits, pcache,
                width,
            )
            tokens_real = sum(
                len(ids) - wave.wave_p for _, ids, _ in wave.batch)
            sp.set(ok=True, tokens_real=tokens_real,
                   **_ssm_admit(
                       eng.cfg, eng.last_prefill, wave.k_pad, tokens_real),
                   chunks=wave.session.chunks,
                   slot_tokens=wave.session.slot_tokens,
                   pairs_swept=wave.session.pairs_swept,
                   pairs_live=causal_pairs(
                       [len(ids) for _, ids, _ in wave.batch],
                       wave.wave_p or eng.last_prefill.reused))
            deltas.update(_wave_counts(sp.args))
            self._pending_wave = None
        self._firsts.append(entry)

    def _wave_fallback(self, wave: "_PendingWave") -> None:
        """An interleaved wave's prefill failed: requeue its streams and
        disable interleaving for this batcher, so the retry takes the
        classic admission path (whose one-by-one fallback fails at most
        one stream) instead of re-entering the same failing session."""
        warnings.warn(
            "interleaved admission prefill failed; reverting to classic "
            "admission for this batcher",
            RuntimeWarning,
            stacklevel=2,
        )
        self._prefill_budget = 0
        self._requeue_wave(wave)

    def _requeue_wave(self, wave: "_PendingWave") -> None:
        """Give up the pending wave: its streams go back to the queue
        head and re-pin at re-admission."""
        self._pending_wave = None
        for _, _, s in wave.batch:
            self._unpin_stream(s)
        with self._work:
            self._queue[:0] = [(ids, s) for _, ids, s in wave.batch]
            self._work.notify()

    def _result(self, s: _Stream) -> GenerateResult:
        if s.on_text is None:
            # No streaming consumer: tokens were accumulated raw (see
            # _emit) and decode ONCE here — per-token incremental
            # decoding is pure Python overhead at serving batch sizes
            # (~16k decoder.push calls per 128-stream fire).
            text = self.engine.tokenizer.decode(s.out_ids)
        else:
            tail = s.decoder.flush()
            if tail:
                s.parts.append(tail)
                s.on_text(tail)
            text = "".join(s.parts)
        if s.jentry is not None:
            # Every successful resolution funnels through here: the
            # journal entry retires with the stream's finish reason, so
            # only streams that DIDN'T resolve remain replay candidates.
            s.jentry.close(s.finish)
        return GenerateResult(
            token_ids=s.out_ids,
            text=text,
            finish_reason=s.finish,
            prompt_tokens=s.prompt_tokens,
            latency_ms=(time.monotonic() - s.submitted) * 1000,
            truncated_prompt=s.truncated,
            preempted=s.preempted,
            marks=s.marks or None,
        )

    # -- weight-version pinning (flywheel hot-swap) --------------------------

    def _pin_stream(self, s: _Stream) -> None:
        """Pin ``s`` to the engine's resident weight version BEFORE its
        prefill touches ``eng.params`` — once pinned, a concurrent
        ``swap_weights`` parks in the double buffer instead of flipping
        under the admission's feet. Idempotent per stream."""
        if s.weight_version < 0:
            s.weight_version = self.engine.pin_weights()

    def _unpin_stream(self, s: Optional[_Stream]) -> None:
        """Release ``s``'s pin (idempotent — every removal path calls
        this, and retire can race a crash path). The LAST unpin applies
        any parked swap, so calling this is what lets a pending weight
        version land."""
        if s is not None and s.weight_version >= 0:
            s.weight_version = -1
            self.engine.unpin_weights()

    def _retire(self, slot: int, finish: str) -> None:
        s = self._slots[slot]
        if s is None:
            return
        s.finish = finish
        self._slots[slot] = None
        self._unpin_stream(s)
        if s.marks:
            s.marks.setdefault("last_token_ns", time.monotonic_ns())
            s.marks.setdefault("last_step", self._steps_landed)
        # First-writer-wins (ADVICE r4): if _run's exception path timed
        # out joining a hung fetch worker and failed this future, a
        # later worker emit must not abort mid-chunk. done()-then-set is
        # not atomic against that path, so the set itself tolerates a
        # concurrent resolution.
        if not s.future.done():
            try:
                s.future.set_result(self._result(s))
            except InvalidStateError:
                pass

    def _fail_slot(self, slot: int, exc: BaseException,
                   finish: str = "integrity") -> None:
        """Fail exactly one slot's stream with ``exc`` (the integrity
        plane's containment unit): the slot frees, the journal entry
        retires with the typed finish reason so the replay path never
        resurrects a poisoned stream, and no other slot is touched."""
        s = self._slots[slot]
        if s is None:
            return
        s.finish = finish
        self._slots[slot] = None
        self._unpin_stream(s)
        if s.jentry is not None:
            s.jentry.close(finish)
        if not s.future.done():
            try:
                s.future.set_exception(exc)
            except InvalidStateError:
                pass

    def _emit(self, slot: int, tok: int, eos: int) -> None:
        s = self._slots[slot]
        if s is None:
            return
        if tok == eos and not s.sampling.ignore_eos:
            self._retire(slot, "eos")
            return
        s.out_ids.append(tok)
        if self._attrib is not None:
            # Goodput ledger: exactly one "useful" per token APPENDED to
            # a stream — the reconciliation invariant the chip-attrib
            # lane gates on (useful == Σ emitted tokens).
            self._attrib.token_event("useful", 1)
        if s.jentry is not None:
            s.jentry.append(tok)  # write-ahead journal (recovery/)
        if s.on_text is not None:
            text = s.decoder.push(tok)
            if text:
                first = not s.parts
                s.parts.append(text)
                s.on_text(text)
                if first:
                    # The first text is with its consumer (the SSE writer
                    # under serving): the run's first-chunk mark.
                    s.marks["first_chunk_ns"] = time.monotonic_ns()
        if len(s.out_ids) >= s.max_new:
            self._retire(slot, "length")

    def _close_gap(self, now: Optional[float] = None) -> None:
        """Close an armed device-idle gap at ``now`` — called BEFORE
        booking drained-pipeline device work (admission/establishment/
        compaction walls), whose time must land in device_s, never
        double-counted as bubble when the next dispatch closes the gap.
        Safe without the lock at these sites: the pipeline is drained,
        so the fetch worker (the only other _idle_at writer) is idle."""
        if self._attrib is None or self._idle_at is None:
            return
        if now is None:
            now = time.monotonic()
        gap = now - self._idle_at
        self._idle_at = None
        phase, self._gap_phase = self._gap_phase, "schedule"
        if gap > 0:
            self._attrib.gap(gap, phase)

    def _mark_nondecode(self, phase: str, family: str) -> None:
        # Any non-decode device work makes the next arrival interval
        # impure for decode-phase accounting — even if it fails and
        # emits no firsts — and names the family that interval books
        # against and the scheduler phase a host gap belongs to.
        self._nondecode_work = True
        self._impure_kind = family
        self._gap_phase = phase

    @contextmanager
    def _booked(self, family: str, span_name: str,
                wall_stat: Optional[str] = None, **span_args):
        """Book one non-decode dispatch of the scheduler thread: the ONE
        place such a dispatch feeds the phase walls, the attribution
        ledger and the host-gap account. Yields the span (on this pool's
        row, ``span_args`` its opening arguments) and the ``stats``
        deltas booked with the wall at exit, for the body to add to."""
        # ADVICE r5: the wall starts BEFORE the work and is accumulated
        # in a finally — a failed prefill's wall, or a pool-fatal
        # splice/sample failure's, is booked exactly like a successful
        # one's (admission work is admission work whether or not it
        # lands).
        t0 = time.monotonic()
        # lint-ok: GS01 — scheduler-monotone read: only this thread
        # increments _unfetched, so ==0 here is stable; a stale >0 just
        # skips one gap-telemetry close.
        drained = self._unfetched == 0  # lint-ok: GS01 monotone read
        if drained:
            # The armed bubble ends where this drained dispatch's
            # DEVICE window begins.
            self._close_gap(t0)
        deltas: dict = {}
        with self._spans.span(
            span_name, self._tid, model=self._model, **span_args
        ) as sp, _attrib_tag(family):
            try:
                yield sp, deltas
            finally:
                if wall_stat is not None:
                    deltas[wall_stat] = time.monotonic() - t0
                if deltas:
                    self._stat_add(**deltas)
                if self._attrib is not None and drained:
                    # Drained pipeline: nothing else was on the device
                    # clock, so the host wall IS this dispatch's device
                    # window (busy-pipeline work books through the
                    # impure arrival interval instead).
                    self._attrib.observe_device(
                        family, time.monotonic() - t0
                    )

    def _stat_add_locked(self, **deltas) -> None:
        sanitizer.assert_held(self._work)
        """Under ``self._work``: accumulate phase-accounting deltas with
        an atomic dict replacement — the ONE stats write form (every
        update site routes here), so ``snapshot`` readers always see a
        consistent dict without taking the lock."""
        st = self.stats
        self.stats = {**st, **{k: st[k] + v for k, v in deltas.items()}}
        # Every phase-accounting update is observable progress: advance
        # the decode heartbeat so the wedge watchdog only fires on a pool
        # that has genuinely stopped (no admissions, no fetch arrivals).
        self._beat = time.monotonic()

    def _stat_add(self, **deltas) -> None:
        """Locking wrapper over ``_stat_add_locked`` for callers outside
        the scheduler/fetch critical sections. Must NOT hold _work."""
        with self._work:
            self._stat_add_locked(**deltas)

    def snapshot(self) -> dict:
        """A consistent copy of the phase-accounting stats. Writers
        replace the dict atomically under ``_work``; the BOUNDED acquire
        gives normal-case readers a barrier-clean handoff (the lock is
        only ever held for µs) while a WEDGED scheduler — died or stuck
        holding ``_work``, exactly when /statsz matters most — degrades
        to the stale-tolerant atomic-dict-swap read instead of hanging
        the stats thread (the same reasoning busy() documents)."""
        got = self._work.acquire(timeout=0.2)
        try:
            return dict(self.stats)  # lint-ok: GS01 bounded-acquire, swap-read fallback
        finally:
            if got:
                self._work.release()

    def spec_snapshot(self) -> Optional[dict]:
        """Pool speculation state (/statsz ``spec`` block, metrics.json);
        None when this batcher runs classic decode. Counters are written
        by the fetch worker with GIL-atomic bumps — a snapshot is
        consistent enough for telemetry, which is all it feeds."""
        sp = self._spec
        if sp is None:
            return None
        return {
            "kind": sp.cfg.kind,
            "k": sp.controller.k,
            "rounds": sp.rounds,
            "accepted": sp.accepted,
            "mean_accepted": (
                round(sp.accepted / sp.row_rounds, 3)
                if sp.row_rounds else None
            ),
            "accept_ema": round(sp.controller.ema, 3),
            "governor": sp.governor.state,
            "governor_disables": sp.disables,
            "collapse_faults": sp.collapse_faults,
            "stream_emas": [
                round(s.spec_ema, 3)
                for s in self._slots if s is not None
            ],
        }

    def _rows_target(self, n: int) -> int:
        """Power-of-two row bucket covering ``n`` live streams, floored
        at ``_min_rows`` and capped at pool capacity."""
        t = self._min_rows
        while t < n:
            t *= 2
        return min(t, self.max_batch)

    def _resize_to(self, target: int) -> None:
        """Re-shape the pool's decode row capacity. Caller must have
        drained the fetch pipeline: a live row moving slots would
        otherwise fail the in-flight owner-identity checks and silently
        drop its fetched tokens."""
        eng = self.engine
        place = eng._place
        if target == self._rows_cap:
            return
        if target < self._rows_cap:
            # Compact live rows ≥ target into free low slots, stream
            # object and host state moving with the row.
            frees = [i for i in range(target) if self._slots[i] is None]
            movers = [
                i for i in range(target, self._rows_cap)
                if self._slots[i] is not None
            ]
            for src in movers:
                dst = frees.pop(0)
                self._cache = _move_row(
                    self._cache,
                    place(jnp.asarray(src, jnp.int32)),
                    place(jnp.asarray(dst, jnp.int32)),
                )
                self._token = self._token.at[dst].set(self._token[src])
                self._row_start = self._row_start.at[dst].set(
                    self._row_start[src]
                )
                self._prefix_rows = self._prefix_rows.at[dst].set(
                    self._prefix_rows[src]
                )
                self._row_start_host[dst] = self._row_start_host[src]
                if self._spec is not None:
                    sp = self._spec
                    sp.valid = sp.valid.at[dst].set(sp.valid[src])
                    sp.buf = sp.buf.at[dst].set(sp.buf[src])
                    if sp.obuf is not None:
                        sp.obuf = sp.obuf.at[dst].set(sp.obuf[src])
                    sp.blen = sp.blen.at[dst].set(sp.blen[src])
                self._slots[dst] = self._slots[src]
                self._slots[src] = None
            self._cache = _shrink_rows(self._cache, target)
            self._token = self._token[:target]
            self._row_start = self._row_start[:target]
            self._prefix_rows = self._prefix_rows[:target]
            if self._spec is not None:
                sp = self._spec
                sp.valid = sp.valid[:target]
                sp.buf = sp.buf[:target]
                if sp.obuf is not None:
                    sp.obuf = sp.obuf[:target]
                sp.blen = sp.blen[:target]
        else:
            # Streamed per-leaf regrow (ADVICE r4): old refs are dropped
            # leaf by leaf so only one old/new leaf pair is ever
            # co-resident on top of the rest of the tree.
            leaves, treedef = jax.tree.flatten(self._cache)
            self._cache = None
            with warnings.catch_warnings():
                # The donated old leaf can't alias the larger output —
                # donation here is for the early free, not aliasing.
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable"
                )
                for i in range(len(leaves)):
                    pre = leaves[i].sharding
                    leaves[i] = _grow_leaf(leaves[i], target)
                    # The regrow relies on GSPMD propagating the input
                    # leaf's sharding through the jitted concat (the row
                    # axis is never sharded — KV shards over heads/seq).
                    # A replicated or altered output sharding on a tp
                    # mesh would surface only as HBM blowup plus a
                    # per-sharding decode recompile, so pin it here: a
                    # drifted leaf is re-placed onto its pre-grow
                    # sharding before the pool can cache it.
                    post = leaves[i].sharding
                    if post != pre and not post.is_equivalent_to(
                        pre, leaves[i].ndim
                    ):
                        leaves[i] = jax.device_put(leaves[i], pre)
                        post = leaves[i].sharding
                    # ADVICE r5: the pin above must leave the regrown
                    # leaf on EXACTLY its pre-grow sharding — a drift
                    # surviving the device_put would surface only as HBM
                    # blowup + a per-sharding decode recompile, so fail
                    # loudly here instead.
                    assert post == pre or post.is_equivalent_to(
                        pre, leaves[i].ndim
                    ), (
                        f"regrown pool-cache leaf {i} sharding drifted: "
                        f"{pre} -> {post}"
                    )
            self._cache = jax.tree.unflatten(treedef, leaves)
            pad = target - self._rows_cap
            self._token = jnp.concatenate(
                [self._token, place(jnp.zeros((pad,), jnp.int32))]
            )
            self._row_start = jnp.concatenate(
                [self._row_start, place(jnp.zeros((pad,), jnp.int32))]
            )
            self._prefix_rows = jnp.concatenate(
                [self._prefix_rows, place(jnp.zeros((pad,), jnp.bool_))]
            )
            if self._spec is not None:
                sp = self._spec
                s_cap = eng.max_seq
                sp.valid = jnp.concatenate(
                    [sp.valid, place(jnp.zeros((pad, s_cap), bool))]
                )
                sp.buf = jnp.concatenate(
                    [sp.buf, place(jnp.zeros((pad, s_cap), jnp.int32))]
                )
                if sp.obuf is not None:
                    sp.obuf = jnp.concatenate(
                        [sp.obuf, place(jnp.zeros((pad, s_cap), jnp.int32))]
                    )
                sp.blen = jnp.concatenate(
                    [sp.blen, place(jnp.zeros((pad,), jnp.int32))]
                )
        self._rows_cap = target
        self._dead_marked = None  # rows moved, cut or zero-padded

    def _maybe_shrink(self) -> None:
        """Shrink the decode row bucket when occupancy has stayed below
        half the current capacity for a few dispatches (hysteresis, so a
        transient dip doesn't thrash resize copies)."""
        live_n = sum(1 for s in self._slots if s is not None)
        target = self._rows_target(live_n)
        if live_n and target * 2 <= self._rows_cap:
            self._shrink_patience += 1
            if self._shrink_patience >= 3:
                self._shrink_patience = 0
                self._drain_fetches()
                self._mark_nondecode("resize", "compact")
                self._resize_to(target)
        else:
            self._shrink_patience = 0

    def _compact(self) -> None:
        """Give active rows fresh runway when the frontier hits capacity:
        slide every window left by the common reclaimable amount (the
        shift is identical for all rows — each live window ends at the
        shared frontier), re-align row_starts, pull the frontier back.
        Windows keep their internal offsets, so RoPE'd KV stays valid."""
        eng = self.engine
        # _row_start_host is each row's first PHYSICAL slot in both
        # modes: classic rows' device row_start equals it, spec rows'
        # device row_start has absorbed hole counts and diverged — but
        # this host list is only written at admission/compaction/moves,
        # so it still names the window base (see _SpecState).
        # Rows already occupying the full cache cannot shrink: retire.
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            if self._pos - self._row_start_host[i] >= eng.max_seq:
                self._retire(i, "length")
        vlens = [
            self._pos - self._row_start_host[i]
            for i, s in enumerate(self._slots) if s is not None
        ]
        if not vlens:
            return
        shift = self._pos - max(vlens)
        if shift <= 0:
            return  # nothing to reclaim
        self._cache = _compact_cache(self._cache, jnp.asarray(shift))
        self._row_start_host = [r - shift for r in self._row_start_host]
        self._row_start = self._row_start - shift
        self._dead_marked = None  # the shift moved the dead rows' mark too
        self._pos -= shift
        if self._spec is not None:
            # The bitmap slides with the KV it describes; slots that wrap
            # around came from below every live row's base, so they carry
            # False and cannot leak stale validity. The token buffer and
            # blen are LOGICAL (no holes) — untouched by compaction.
            self._spec.valid = _roll_valid(
                self._spec.valid, jnp.asarray(shift)
            )

    def _mark_dead_rows(self) -> None:
        """Bring the device ``row_start`` up to ``_slots`` before a decode
        dispatch: every row without a stream (never admitted, retired,
        failed, preempted) gets DEAD_ROW. Admission writes a row's real
        start over the mark (_admit, _admit_finish); compaction and row
        moves forget the marks (``_dead_marked = None``), and a spec
        round's hole count only moves a dead row's start further out."""
        dead = tuple(s is None for s in self._slots[:self._rows_cap])
        if dead != self._dead_marked:
            self._row_start = _mark_dead(
                self._row_start, self.engine._place(jnp.asarray(dead))
            )
            self._dead_marked = dead

    def _plan_steps(self, chunk: int) -> int:
        """The n_steps policy, shared by the classic dispatch path and a
        spec pool's governor-plain windows (the two must stay in
        lockstep): cache-tail parity with the single-stream loop (inside
        the last chunk's worth of slots, 1-step programs so no stream
        loses tokens it could still decode); the final-chunk clamp (the
        pool's last chunk runs only the steps someone still needs,
        pow2-bucketed so program variants stay bounded at log2(chunk));
        and the idle short opener (first chunk after an idle period with
        the pool under half full — a burst's stragglers land during this
        chunk's flight and can only admit when it ends, so a full chunk
        makes most of the pool wait `chunk` underfilled steps; measured:
        22 of 32 streams idling through a 128-step chunk. Warm pools
        keep the cheap full-chunk cadence, so steady state pays
        nothing)."""
        eng = self.engine
        n_steps = chunk if self._pos + chunk <= eng.max_seq else 1
        need = max(
            (s.max_new - s.planned
             for s in self._slots if s is not None),
            default=0,
        )
        if 0 < need < n_steps:
            n_steps = min(1 << max(need - 1, 0).bit_length(), n_steps)
        if (
            n_steps == chunk
            and self._unfetched == 0  # lint-ok: GS01 monotone read (heuristic only)
            and chunk > 32
            and sum(
                1 for s in self._slots if s is not None
            ) * 2 < self.max_batch
        ):
            n_steps = 32
        return n_steps

    def _dispatch_spec(self, chunk: int):
        """Dispatch one speculative ROUND GROUP — or, while the governor
        probes/locks plain (or the frontier can't fit a round), one
        bitmap-maintaining plain chunk.

        A round is one drafter program (prompt lookup / oracle — tiny
        vector ops over the device token buffer) + ONE target forward
        verifying k+1 positions for every resident row: B×(k+1) tokens
        per weight stream, the batch-1 verification MFU fix. Rounds
        chain on device (the carry never round-trips); the group's
        (out, a) pairs ride down with one fetch. The shared frontier
        advances k+1 per round HOST-KNOWN — admission splicing, capacity
        checks, and compaction keep their arithmetic — while per-row
        acceptance is data: rejected slots become holes the ``valid``
        bitmap masks, and ``row_start`` absorbs each row's hole count so
        positions stay per-row exact.

        Returns ``(fetch payload, guaranteed per-stream token coverage,
        mode)``.
        """
        eng = self.engine
        sp = self._spec
        k = sp.controller.k
        if (
            sp.governor.mode == "plain"
            or self._brownout  # pressure governor: drafting off
            or self._pos + (k + 1) > eng.max_seq
        ):
            # Governor plain window (or cache tail): the engine's chunk
            # shape plus the written-slot bitmap and token-buffer append,
            # so a later return to spec mode has current state. This IS
            # the plain baseline the A/B compares against — a holey pool
            # cache cannot drop the bitmap, so masked-plain is the
            # fastest correct plain program available to it. Step policy
            # (_plan_steps) is shared with the classic path: a
            # plain-locked spec pool must not dead-step full chunks past
            # every stream's need or hold a burst's stragglers behind a
            # full first chunk.
            n_steps = self._plan_steps(chunk)
            width = eng._decode_width(min(self._pos + n_steps, eng.max_seq))
            (self._token, toks, sp.blen, self._cache, sp.valid,
             sp.buf) = _plain_chunk_masked(
                eng.params, eng.cfg, self._token, self._pos,
                self._row_start, sp.blen, self._cache, sp.valid, sp.buf,
                n_steps, kv_width=width, w8a8=eng.w8a8,
            )
            self._pos += n_steps
            return toks, n_steps, "plain"
        rounds = max(1, chunk // (k + 1))
        need = max(
            (s.max_new - s.planned for s in self._slots if s is not None),
            default=0,
        )
        if 0 < need < rounds:
            # A round advances every stream >= 1 token: `need` rounds
            # suffice even at floor acceptance (the spec twin of the
            # final-chunk clamp; rounds is a host loop count, not
            # program identity, so no pow2 bucketing is needed).
            rounds = need
        while rounds > 1 and self._pos + rounds * (k + 1) > eng.max_seq:
            rounds -= 1
        width = eng._decode_width(
            min(self._pos + rounds * (k + 1), eng.max_seq)
        )
        vocab = eng.cfg.vocab_size
        outs = []
        for _ in range(rounds):
            fault = None
            if eng._faults is not None:
                fs = eng._faults.fire("spec", model=eng.cfg.name)
                if fs is not None:
                    if fs.kind == "draft_stall":
                        # Host dispatcher stall (@s= seconds): the round
                        # cadence collapses, which is exactly the signal
                        # the governor's A/B must absorb.
                        time.sleep(float(fs.param("s", 0.05)))
                    elif fs.kind == "acceptance_collapse":
                        sp.collapse_faults += 1
                        fault = "acceptance_collapse"
            with _attrib_tag("draft"):
                if fault == "acceptance_collapse":
                    # Junk proposals: greedy output is exact for ANY
                    # proposals (acceptance only keeps matches), so this
                    # is purely a speed fault — acceptance pins to ~1.
                    drafts = _junk_propose(sp.buf, sp.blen, k, vocab)
                elif sp.cfg.kind == "oracle":
                    drafts = _oracle_propose(
                        sp.obuf, sp.blen, k, vocab,
                        accept=sp.cfg.oracle_accept,
                    )
                else:
                    drafts = _lookup_propose(
                        sp.buf, sp.blen, k, sp.cfg.ngram
                    )
            (out, a, self._token, self._row_start, sp.blen, self._cache,
             sp.valid, sp.buf) = _spec_verify_batch(
                eng.params, eng.cfg, self._token, drafts, self._pos,
                self._row_start, sp.blen, self._cache, sp.valid, sp.buf,
                k, kv_width=width, w8a8=eng.w8a8,
            )
            self._pos += k + 1
            outs.append((out, a))
        return ("spec", outs, k), rounds, "spec"

    def _run(self) -> None:
        try:
            self._loop()
        except BaseException as exc:  # noqa: BLE001 — fail every future
            # Pool-death evidence FIRST: futures fail below, and the
            # recovery supervisor classifies those failures by this
            # attribute — set after would race the waiters.
            self.failed_exc = exc
            if self._bb is not None:
                # Blackbox dump at the moment of death: the ring holds
                # the decode/fetch spans leading up to the crash —
                # recorded even with --events off.
                self._spans.instant(
                    "engine_crash", self._tid, model=self._model,
                    error=repr(exc),
                )
                self._bb.dump("engine_crash", extra={"error": repr(exc)})
            # Stop the fetch worker BEFORE failing futures: it may still
            # be emitting (and resolving) streams from queued chunks, and
            # those completions are legitimate — only what remains after
            # it drains gets the exception.
            self._fetch_q.put(None)
            self._fetch_thread.join(timeout=120)
            with self._work:
                self._closed = True
                queued = list(self._queue)
                self._queue.clear()
            for _, s in queued:
                if not s.future.cancel():
                    s.future.set_exception(exc)
            for i, s in enumerate(self._slots):
                if s is not None:
                    self._slots[i] = None
                    self._unpin_stream(s)
                    if not s.future.done():
                        try:
                            s.future.set_exception(exc)
                        except InvalidStateError:
                            # A revived fetch worker resolved it first —
                            # that completion is legitimate; don't let
                            # the collision mask the root cause below.
                            pass
            wave = self._pending_wave
            self._pending_wave = None
            if wave is not None:
                # A mid-establishment interleaved wave's streams are in
                # neither the queue nor the slots — fail them explicitly
                # or their futures hang forever.
                for _, _, s in wave.batch:
                    self._unpin_stream(s)
                    if not s.future.done():
                        try:
                            s.future.set_exception(exc)
                        except InvalidStateError:
                            pass
            raise
        else:
            self._fetch_q.put(None)
            self._fetch_thread.join(timeout=120)

    def _fetch_get(self, toks, firsts) -> tuple:
        """The blocking half of a fetch: one device→host transfer of a
        dispatched chunk's tokens plus any prefill-sampled first tokens
        riding along. Returns ``(first_vals, body)`` for ``_fetch_emit``;
        ``body`` is the fetched payload in its dispatch form.

        ``firsts`` entries are per-WAVE: (slot list, samples array,
        owner list) — one device array per admission wave, fetched in
        the same transfer as the chunk.

        A spec ROUND GROUP's payload is ``("spec", [(out, a), ...], k)``
        instead of a token matrix: per round, row i emits its accepted
        prefix ``out[i, :a[i]]`` — acceptance is data, fetched with the
        tokens."""
        samples = [smp for _, smp, _ in firsts]
        if isinstance(toks, tuple) and toks and toks[0] == "spec":
            _, rounds, k_used = toks
            first_vals, fetched = jax.device_get((samples, rounds))
            return first_vals, ("spec", fetched, k_used)
        if isinstance(toks, tuple) and toks and toks[0] == "sentinel":
            _, toks, verdict = toks
            first_vals, mat, fin = jax.device_get((samples, toks, verdict))
            return first_vals, ("tokens", mat, fin)
        first_vals, mat = jax.device_get((samples, toks))
        return first_vals, ("tokens", mat, None)

    def _fetch_emit(self, first_vals, body: tuple, owners, firsts,
                    eos: int, t_emit_ns: int) -> int:
        """The host half of a fetch: emit the fetched tokens to their
        streams (first tokens first). Returns the live tokens emitted.
        ``t_emit_ns`` is the enclosing ``pool.emit`` span's start — the
        clock read a stream's ``first_token_ns`` mark takes."""
        if body[0] == "spec":
            return self._emit_spec(
                first_vals, body[1], body[2], owners, firsts, eos, t_emit_ns,
            )
        _, mat, fin = body
        self._steps_landed += len(mat)
        if fin is not None and self._integrity is not None:
            # Finite-logit sentinel verdict: contain BEFORE the emit
            # loop so a poisoned row's garbage tokens never reach its
            # consumer — the stream fails typed, the row's slot frees,
            # and every neighbor emits byte-identically below.
            self._integrity.check("logits")
            for i, row_ok in enumerate(fin.tolist()):
                if row_ok or i >= len(owners) or owners[i] is None:
                    continue
                if self._slots[i] is not owners[i]:
                    continue
                self._integrity.failure(
                    "logits", f"non-finite logits in decode row {i}"
                )
                from llm_consensus_tpu import integrity as _integrity

                self._fail_slot(i, _integrity.IntegrityError(
                    "logits",
                    f"non-finite logits detected in decode row {i}",
                ))
        emitted = self._emit_firsts(firsts, first_vals, eos, t_emit_ns)
        # One bulk ndarray→list conversion: the per-element form
        # (int(mat[step, i]) × chunk × B numpy-scalar extractions) costs
        # tens of host-ms per chunk at serving batch sizes.
        cols = mat.T.tolist()  # [B][chunk] python ints
        overshoot = 0
        for i, owner in enumerate(owners):
            if owner is None:
                continue
            col = cols[i]
            taken = 0
            for step in range(len(col)):
                # Owner identity: stop if this slot's stream was retired
                # (and possibly replaced) mid-chunk — a reused slot must
                # never leak predecessor tokens.
                if self._slots[i] is not owner:
                    break
                self._emit(i, col[step], eos)
                emitted += 1
                taken += 1
            # Dead stepping: slots this live-at-dispatch row computed
            # that no stream consumed (retired mid-chunk / tail trim).
            overshoot += len(col) - taken
        if overshoot and self._attrib is not None:
            self._attrib.token_event("overshoot", overshoot)
        return emitted

    def _emit_firsts(self, firsts, first_vals, eos, t_emit_ns: int) -> int:
        """Emit prefill-sampled first tokens that rode down with this
        chunk's fetch (owner-checked per wave) — shared by the classic
        and spec fetch paths."""
        emitted = 0
        for (slots, _, wave_owners), vals in zip(firsts, first_vals):
            for slot, owner, val in zip(slots, wave_owners, vals.tolist()):
                if self._slots[slot] is owner:
                    owner.marks.setdefault("first_token_ns", t_emit_ns)
                    owner.marks.setdefault("first_step", self._steps_landed)
                    self._emit(slot, val, eos)
                    emitted += 1
        return emitted

    def _emit_spec(self, first_vals, fetched, k_used: int, owners, firsts,
                   eos, t_emit_ns: int) -> int:
        """Emit one fetched spec round group (see _fetch_get): the pool
        controller observes the mean per-row acceptance while each
        stream's EMA tracks its own."""
        emitted = self._emit_firsts(firsts, first_vals, eos, t_emit_ns)
        sp = self._spec
        total_acc = 0
        rejected = 0
        for out, a in fetched:
            alist = a.tolist()
            olist = out.tolist()
            live = 0
            acc = 0
            for i, owner in enumerate(owners):
                if owner is None:
                    continue
                ai = int(alist[i])
                if self._slots[i] is owner:
                    # Acceptance accounting only for rows whose stream is
                    # STILL live: a retired row keeps being stepped
                    # (static shapes) and its post-EOS repetition is
                    # exactly what n-gram lookup over-accepts — feeding
                    # it would let dead rows drive the pool's k ladder.
                    owner.spec_ema += 0.25 * (ai - owner.spec_ema)
                    live += 1
                    acc += ai
                row = olist[i]
                for step in range(ai):
                    # Owner identity — same contract as the classic
                    # emit loop above.
                    if self._slots[i] is not owner:
                        break
                    self._emit(i, row[step], eos)
                    emitted += 1
            sp.rounds += 1
            sp.row_rounds += live
            total_acc += acc
            # Verify positions the round threw away: each live row had
            # k+1 candidate slots, kept acc of them.
            rejected += live * (k_used + 1) - acc
            if live:
                sp.controller.observe(acc / live, k_used)
        sp.accepted += total_acc
        if rejected and self._attrib is not None:
            self._attrib.token_event("spec_rejected", rejected)
        if self._obs is not None:
            self._obs.count("spec.rounds", len(fetched))
            self._obs.count("spec.accepted", total_acc)
        return emitted

    def _fetch_worker(self) -> None:
        """Fetch-side half of the dispatch pipeline (dedicated thread).

        Blocks on each dispatched chunk's device transfer, runs the emit
        loop, retires finished/cancelled streams, and keeps the
        decode-phase arrival clock. Slot handoff discipline makes this
        safe without a lock around emits: the scheduler only ever writes
        a slot None→stream (admission), this thread only ever writes
        stream→None (retirement), and every emit checks owner identity —
        the same snapshot invariant the old synchronous fetch relied on.
        """
        eos = self.engine.tokenizer.eos_id
        while True:
            item = self._fetch_q.get()
            if item is None:
                return
            toks, owners, firsts, pure, t_dispatch, mode, moe = item
            if self._worker_exc is not None:  # lint-ok: GS01 own-write read
                # A prior chunk's fetch failed: emitting later chunks
                # would resolve streams "successfully" with the failed
                # chunk's tokens silently missing. Drain without
                # emitting; the scheduler fails every live stream with
                # the recorded exception.
                with self._work:
                    self._unfetched -= 1
                    self._work.notify_all()
                continue
            try:
                # The blocking device→host transfer of one chunk.
                with self._spans.span(
                    "pool.fetch", self._tid, model=self._model, pure=pure,
                ) as sp:
                    first_vals, body = self._fetch_get(toks, firsts)
                    sp.set(tokens=int(getattr(body[1], "size", 0)))
                    if moe is not None:
                        sp.set(**self._book_moe(*moe))
            except BaseException as exc:  # noqa: BLE001
                self._fetch_failed(exc)
                continue  # keep draining so the scheduler never deadlocks
            # Taken when device_get returns, BEFORE the emit loop, so
            # arrival-to-arrival intervals measure the device/transfer
            # pipeline, not Python emit time.
            t_arrival = time.monotonic()
            # Host-side handling of the fetched tokens until the scheduler
            # can issue the next dispatch: EOS, detokenise, stream push,
            # retirement, the arrival booking and its notify. Fetch + emit
            # together are the host time the dispatch pipeline overlaps.
            with self._spans.span(
                "pool.emit", self._tid, model=self._model,
            ) as sp:
                try:
                    emitted = self._fetch_emit(
                        first_vals, body, owners, firsts, eos, sp.t0_ns,
                    )
                except BaseException as exc:  # noqa: BLE001
                    self._fetch_failed(exc)
                    continue
                sp.set(tokens=emitted)
                # Cancellation/deadlines: after the emit so a cancel never
                # discards tokens already decoded (it wastes at most the
                # chunks still in the pipeline).
                for i, s in enumerate(self._slots):
                    if s is not None and s.ctx.done():
                        self._retire(
                            i,
                            "deadline" if s.ctx.remaining() == 0.0 else "cancelled",
                        )
                self._book_arrival(pure, mode, emitted, t_arrival, t_dispatch)

    def _book_moe(self, decode, layer_steps: int, kernel_steps: int,
                  prefills: list) -> dict:
        """A routed model's sums, fetched with their chunk: into the
        counters, and back as the ``pool.fetch`` span's arguments (the
        chunk's own values: they exist only on the device while
        ``pool.decode`` and ``pool.admit`` are open)."""
        decode, prefills = jax.device_get((decode, prefills))
        pre_total = sum(int(p[0]) for p in prefills)
        pre_held = sum(int(p[1]) for p in prefills)
        if decode is None:  # a speculative round group returns no sums
            total = held = reads = layer_steps = kernel_steps = 0
        else:
            total, held, reads = (int(v) for v in decode)
        self._stat_add(
            moe_pairs_total=total + pre_total, moe_pairs_held=held + pre_held,
            moe_expert_reads=reads, moe_layer_steps=layer_steps,
            moe_prefill_pairs_held=pre_held,
            moe_kernel_layer_steps=kernel_steps,
        )
        return {
            "moe_pairs": total, "moe_pairs_held": held,
            "moe_expert_reads": reads, "moe_layer_steps": layer_steps,
            "moe_kernel_layer_steps": kernel_steps,
            "moe_prefill_pairs_held": pre_held,
        }

    def _fetch_failed(self, exc: BaseException) -> None:
        """A chunk's fetch or emit raised: record it for the scheduler
        (which fails every live stream with it) and release the chunk's
        pipeline slot."""
        with self._work:
            self._worker_exc = exc
            self._unfetched -= 1
            self._prev_arrival = None
            self._work.notify_all()

    def _book_arrival(self, pure: bool, mode, emitted: int,
                      t_arrival: float, t_dispatch: float) -> None:
        """Phase accounting for one chunk's arrival, then the notify that
        lets the scheduler dispatch past the depth gate."""
        with self._work:
            if pure:
                # `emitted` gate: a chunk whose streams all retired
                # mid-pipeline (tail overshoot — owners dropped every
                # token) is dead stepping, not steady-state decode;
                # counting its ~chunk-length interval against zero
                # tokens drags the decode-phase rate far below the
                # real chunk cadence (measured: 17k reported vs 33k
                # traced at B=256). Partially-live chunks still
                # count in full — occupancy holes are real serving.
                # Zero-emit intervals are accounted as tail_s so the
                # bench can bisect the e2e-vs-decode-phase gap.
                # ADVICE r5 (batcher.py:963 area): pure chunks with
                # no prior arrival (first dispatch after a pipeline
                # drain — post-drain decode, or the overshoot gate's
                # fall-through dead-step) reference their own
                # dispatch time, mirroring the impure branch:
                # dispatch→arrival covers exactly that chunk's
                # device + transfer wall (nothing but the chunk ran
                # since the drain — pure guarantees no admission
                # work), so neither post-drain decode nor gate
                # dead-stepping is silently dropped from the phase
                # accounting.
                ref = (
                    self._prev_arrival
                    if self._prev_arrival is not None else t_dispatch
                )
                dt = t_arrival - ref
                if emitted:
                    self._stat_add_locked(
                        decode_tokens=emitted, decode_s=dt
                    )
                else:
                    self._stat_add_locked(tail_s=dt)
                if self._attrib is not None:
                    # Chip-time attribution: a PURE arrival interval
                    # is the device + transfer wall of exactly one
                    # decode (or spec round-group) dispatch.
                    self._attrib.observe_device(
                        "spec_verify" if mode == "spec" else "decode",
                        dt,
                    )
                sp = self._spec
                if (
                    sp is not None and mode is not None and emitted
                    and sp.governor.state in ("spec_probe",
                                              "plain_probe")
                    and mode == sp.governor.mode
                ):
                    # Governor A/B: only PURE arrival intervals whose
                    # chunk ran in the mode being probed count —
                    # admission/compaction noise and stale pipelined
                    # chunks from the prior mode would skew the
                    # drafted-vs-plain rate comparison. The first
                    # arrival per mode is discarded as compile
                    # warm-up (see _SpecState.skip_feed).
                    if sp.skip_feed:
                        sp.skip_feed = False
                    elif sp.governor.feed(emitted, dt):
                        sp.skip_feed = True  # new mode: fresh compile
                    if sp.governor.disabled_spec and sp.disables == 0:
                        sp.disables = 1
                        self._spans.instant(
                            "spec_governor_disable", self._tid,
                            model=self._model,
                            ema=round(sp.controller.ema, 3),
                        )
            else:
                # No prev arrival after an idle drain: reference the
                # chunk's dispatch time instead — the interval still
                # covers the admission prefill the device ran just
                # before it (dispatched back-to-back on the host).
                ref = (
                    self._prev_arrival
                    if self._prev_arrival is not None else t_dispatch
                )
                self._stat_add_locked(
                    impure_s=t_arrival - ref, impure_tokens=emitted
                )
                if self._attrib is not None:
                    # Impure interval: the device ran admission
                    # prefill / compaction work plus the chunk —
                    # booked against the non-decode family that made
                    # it impure (the dominant term by construction).
                    self._attrib.observe_device(
                        self._impure_kind, t_arrival - ref
                    )
            self._prev_arrival = t_arrival
            self._unfetched -= 1
            if self._unfetched == 0:
                # Pipeline drained: the next arrival interval spans
                # device idle time, not a chunk — don't count it.
                self._prev_arrival = None
                if self._attrib is not None and (
                    any(s is not None for s in self._slots)
                    or self._queue
                    or self._pending_wave is not None
                ):
                    # Device idle begins on a batcher that still has
                    # work: host-gap (bubble) detection arms — the
                    # next dispatch closes and attributes it.
                    self._idle_at = t_arrival
                    self._gap_phase = "schedule"
            self._work.notify_all()

    def _drain_fetches(self) -> None:
        """Wait until every dispatched chunk's tokens are emitted — the
        barrier before compaction (full-row retires must not lose
        fetched tokens) and before the scheduler hand-retires slots."""
        with self._spans.span(
            "pool.drain", self._tid, model=self._model,
        ) as sp, self._work:
            while self._unfetched > 0 and self._worker_exc is None:
                self._work.wait(0.1)
                sp.slice()
            if self._worker_exc is not None:
                raise self._worker_exc

    def _drain_queue_locked(self) -> list:
        """Under ``self._work``: take everything still queued (including
        items the scheduler had popped and requeued) so shutdown can
        cancel them — no Future may hang forever."""
        sanitizer.assert_held(self._work)
        queued = list(self._queue)
        self._queue.clear()
        return queued

    def _idle_locked(self) -> bool:
        """Under ``self._work``: nothing to admit, dispatch or interleave
        (and not closing)."""
        sanitizer.assert_held(self._work)
        return (
            self._worker_exc is None
            and not self._queue
            and not any(s is not None for s in self._slots)
            and self._pending_wave is None
            and not (self._closed and self._unfetched == 0)
        )

    def _plan_admission(self, pending: list,
                        share: bool = True) -> AdmissionPlan:
        """``plan_admission`` over this pool's state (``share`` False:
        with prefix sharing off for this pass)."""
        eng = self.engine
        kvp = getattr(eng, "_kv_pool", None)
        pool_idle = not any(st is not None for st in self._slots)
        mesh = getattr(eng, "mesh", None)
        sp_degree = dict(mesh.shape).get("sp", 1) if mesh is not None else 1
        return plan_admission(
            [
                Pending(ids, s.priority, s.ctx.done(), s.max_new)
                for ids, s in pending
            ],
            [i for i in range(self._rows_cap) if self._slots[i] is None],
            self._pos, eng.max_seq, pool_idle,
            max_batch=self.max_batch, chunk=eng.prefill_chunk,
            bucket=lambda n: _bucket(n, eng.max_seq),
            rows_bucket=eng._rows_bucket,
            prefix_enabled=share and self._prefix_enabled,
            prefix_ids=self._prefix_ids, prefix_min=self._prefix_min,
            resident_prefix_len=None if kvp is None else kvp.match_len,
            sp_degree=sp_degree,
            may_interleave=self._prefill_budget > 0
            and self._pending_wave is None and not pool_idle,
        )

    def _execute_admission(self, plan: AdmissionPlan,
                           pending: list) -> tuple:
        """Carry one plan out — the one admission dispatcher: establish
        the prefix it asks for, resolve, then ``rows``, falling to
        ``single``. Returns ``(requeue, admitted)``: the streams that go
        back to the queue, and whether the pass admitted classically (an
        interleaved wave that opened ends the pass: one wave at a time,
        later arrivals queue until it installs)."""
        eng = self.engine
        if plan.establish:
            with self._booked(
                "prefill", "pool.establish", "establish_s",
                prefix=len(plan.establish),
            ) as (sp, _):
                # Marked AFTER the gap closed: that gap keeps the phase
                # that ran during it (the absorb, as a rule).
                self._mark_nondecode("establish", "prefill")
                est_ok = self._establish_prefix(list(plan.establish))
                sp.set(ok=est_ok)
            if not est_ok:
                # Establishment failure degrades to full-prompt rows:
                # the plan assumed the prefix, so plan again without.
                plan = self._plan_admission(pending, share=False)
        if plan.clear_prefix:
            self._clear_prefix()
        self._pos = plan.pos
        for i in plan.resolve:
            _, stream = pending[i]
            if stream.ctx.done():
                # Expired while queued: resolve without prefill.
                stream.finish = (
                    "deadline" if stream.ctx.remaining() == 0.0
                    else "cancelled"
                )
            stream.future.set_result(self._result(stream))
        requeue = [pending[i] for i in plan.requeue]
        batch = [(slot, *pending[i]) for i, slot in plan.admitted]
        route = plan.route
        if plan.interleave and self._begin_wave(batch, plan.wave_p):
            # The wave's prefill session is open; _advance_wave
            # paces its chunks between the decode dispatches,
            # so resident streams never stall behind
            # this wave's prefill. (Classic admission instead
            # when the wave wouldn't fit the projected
            # frontier or the session can't open.)
            return requeue, False
        if route == "rows" and not self._dispatch_admit(
            "rows", batch, plan.wave_p
        ):
            route = "single"
            if plan.wave_p:
                # A failed SUFFIX-wave prefill would
                # retry forever: the single-stream
                # fallback can't fit a full prompt into
                # the suffix-sized frontier, the rows
                # requeue, and the next pass re-enters
                # the same failing prefix path. Disable
                # pool sharing (the established KV stays
                # for rows already live on it) so the
                # retry degrades to full-prompt
                # admission, which always progresses.
                warnings.warn(
                    "shared-prefix wave prefill failed; "
                    "disabling pool prefix sharing for "
                    "this batcher",
                    RuntimeWarning,
                    stacklevel=2,
                )
                self._prefix_enabled = False
        if route == "single":
            for row in batch:
                # The one-row path (the plan's choice, or the fallback of
                # a failed wave) splices the FULL prompt (it never
                # joins the shared prefix), so a row that was
                # admitted under suffix accounting must re-check the
                # full-window fit before _admit can misalign it.
                n = len(row[1])
                if not fits(n, self._pos, _bucket(n, eng.max_seq),
                            eng.max_seq):
                    requeue.append(row[1:])
                    continue
                self._dispatch_admit("single", [row], 0)
        return requeue, bool(batch)

    def _dispatch_admit(self, route: str, batch: list, wave_p: int) -> bool:
        """One admission wave, from its dispatch to its last chunk
        dispatched (the device runs on): ``rows`` is one padded wave
        (``_admit_batch``), ``single`` one row through the engine's
        single-stream prefill (``_admit``). False where its prefill
        failed: a failed wave leaves every row to the caller's one-row
        fallback; a failed one-row prefill (bad prompt, OOM on a new
        bucket) fails THAT stream and the pool keeps serving the others."""
        opening = {"rows_padded": 1} if route == "single" else {}
        entry = None
        self._mark_nondecode("admit", "prefill")
        with self._booked(
            "prefill", "pool.admit", "admit_s", route=route,
            rows_real=len(batch), **opening,
            tokens_real=sum(len(ids) - wave_p for _, ids, _ in batch),
            prefix=wave_p,
            traces=[s.trace for _, _, s in batch if s.trace],
        ) as (sp, deltas):
            for _, _, s in batch:
                s.marks.setdefault("admit_ns", sp.t0_ns)
            try:
                if route == "single":
                    (slot, ids, stream), = batch
                    try:
                        entry = self._admit(slot, ids, stream, sp)
                    except Exception as exc:  # noqa: BLE001
                        stream.future.set_exception(exc)
                        if stream.jentry is not None:
                            # Terminal for this stream on a HEALTHY
                            # pool: not a replay candidate.
                            stream.jentry.close("failed")
                else:
                    entry = self._admit_batch(batch, wave_p, sp)
            finally:
                sp.set(ok=entry is not None)
                if entry is not None:
                    deltas.update(_wave_counts(sp.args))
                    self._firsts.append(entry)
                if "alloc_ms" in sp.args:
                    # A one-row dispatch got as far as its splice: what
                    # held this thread in it, beside ``admit_s``.
                    deltas.update(
                        admit_single_dispatches=1,
                        admit_alloc_s=sp.args["alloc_ms"] / 1e3,
                        admit_dispatch_s=sp.args["dispatch_ms"] / 1e3,
                        admit_splice_s=sp.args["splice_ms"] / 1e3)
        return entry is not None

    def _loop(self) -> None:
        eng = self.engine
        chunk = eng.stream_interval
        # Scheduler half of the dispatch pipeline. Steady-state iteration
        # order is admit → dispatch N+1 → hand chunk N+1 to the fetch
        # worker: the worker's device_get + emit of chunk N overlap both
        # the dispatch host work here AND chunk N+1's device execution.
        # Dispatch depth is capped at 2 unfetched chunks (one running,
        # one being fetched), so speculative overshoot past EOS stays
        # bounded. Only at the compaction waterline does the loop drain
        # the pipeline FIRST (a full row about to be retired must not
        # lose its fetched tokens) and give up the overlap.
        # Fetch, emit, retirement, and cancellation sweeps all run on
        # the fetch worker (_fetch_worker); the scheduler loops
        # straight back to admission/dispatch.
        while True:
            # Schedule-exploration seam (analysis/schedule.py): one
            # iteration of the scheduler loop is the protocol step the
            # model checker preempts between.
            sanitizer.sched_point("batcher.schedule")
            pending: list[tuple[list, _Stream]] = []
            with self._work:
                # Idle when there's nothing to admit, dispatch, or
                # interleave — even if tail chunks are still draining
                # through the worker (their tokens emit without scheduler
                # help); the close path below additionally requires the
                # drain to finish.
                if self._idle_locked():
                    # No live row, no queued stream: the pool has no work.
                    with self._spans.span(
                        "pool.wait", self._tid, model=self._model,
                    ) as sp:
                        while self._idle_locked():
                            # Truly idle (the armed work expired/cancelled
                            # away): a gap armed at the last drain must
                            # not span client think time into the next
                            # request's first dispatch.
                            self._idle_at = None
                            # Bounded, so that a wait that straddles a
                            # profiler window's edge is in the trace up
                            # to its last slice (obs/spans.py): at most
                            # this much of it is lost at either edge.
                            self._work.wait(0.05)
                            sp.slice()
                if self._worker_exc is not None:
                    raise self._worker_exc
                if (
                    self._closed
                    and not any(s is not None for s in self._slots)
                    and self._pending_wave is None
                    and self._unfetched == 0
                ):
                    leftovers = self._drain_queue_locked()
                    for _, s in leftovers:
                        s.future.cancel()
                        if s.jentry is not None:
                            s.jentry.close("cancelled")
                    return
                if self._pending_wave is None:
                    pending = list(self._queue)
                    self._queue.clear()
                # else: submissions stay queued until the in-flight wave
                # installs — waves never overlap, and queue growth still
                # breaks the depth gates below so the wave keeps pacing.
            if (
                pending
                and not any(s is not None for s in self._slots)
            ):
                # Idle-pool burst absorption, BEFORE the first admission
                # pass: a burst's submits trickle in from many client
                # threads over tens of ms, and the async-fetch scheduler
                # wakes fast enough to catch only the first arrival —
                # which would admit a 1-candidate wave, skip (and CLEAR)
                # prefix establishment (sharing needs ≥2 candidates), and
                # lose the shared-prefix win for the whole burst
                # (measured: pool_prefix_len 0 at B=256 after the worker
                # split). Pool-idle is the whole gate: a previous burst's
                # tail chunks may still be draining through the worker
                # (their owners are retired, so they don't interact with
                # admission), and nothing useful is decoding, so the
                # bounded pause costs no throughput. Exit requires TWO
                # consecutive quiet 10 ms windows: one window measurably
                # under-collects a large burst (a 256-thread fire split
                # 155+101, and the 101-row wave's padded-size variant
                # cost a fresh ~7 s program compile mid-measurement); a
                # lone request pays ~20 ms.
                with self._spans.span(
                    "pool.absorb", self._tid, model=self._model,
                    queued=len(pending),
                ) as sp, self._work:
                    t_abs = time.monotonic()
                    deadline = t_abs + 0.25
                    seen = -1
                    quiet = 0
                    while (
                        not self._closed
                        and quiet < 2
                        and time.monotonic() < deadline
                    ):
                        n = len(self._queue)
                        quiet = quiet + 1 if n == seen else 0
                        seen = n
                        self._work.wait(timeout=0.01)
                        sp.slice()  # a window's edge costs one 10 ms piece
                    pending += list(self._queue)
                    self._queue.clear()
                    self._stat_add_locked(
                        absorb_s=time.monotonic() - t_abs
                    )
                    self._gap_phase = "absorb"
            if self._pos >= eng.max_seq:
                # Waterline: drain the pipeline before compaction's
                # full-row retires, so no fetched token is lost.
                self._drain_fetches()
                # Compaction breaks steadiness; it runs pipeline-drained,
                # so its booked wall is the host dispatch wall of the
                # roll (nothing else is on the device clock).
                self._mark_nondecode("compact", "compact")
                with self._booked("compact", "pool.compact") as (sp, _):
                    self._compact()
                    sp.set(pos=self._pos)
                if self._pos >= eng.max_seq:
                    # Compaction could not make room (unreachable by
                    # construction — the full-row retire precedes the
                    # move — but a frontier overrun would corrupt rows,
                    # so belt and braces): end every remaining stream.
                    for i, s in enumerate(self._slots):
                        if s is not None:
                            self._retire(i, "length")
            # Admission (outside the lock: prefill can compile/run long).
            # A prompt longer than the current frontier — or whose splice
            # bucket would overrun capacity — waits (``fits``); when the
            # pool is idle the frontier resets to fit the wave. Splices
            # are enqueued behind the in-flight chunk on the device, and a
            # replaced slot's in-flight tokens are dropped by the owner
            # check in _fetch. Multiple admissible streams in one pass
            # share ONE batched prefill (_admit_batch), and the pass
            # re-drains the queue so a burst racing the scheduler lands
            # in the same wave instead of straggling across decode chunks
            # with mostly-empty slots (the measured round-2 serving gap).
            if self._prefix_cache is not None and (
                self._prefix_weight_version != eng.weight_version
            ):
                # A weight swap landed since the prefix was established:
                # its KV belongs to the OLD version. Flips only happen
                # with zero pins, so no resident row is attending it —
                # clear and let the next wave re-establish under the new
                # weights.
                self._clear_prefix()
            if pending and eng.swap_pending():
                # Weight-swap admission gate: a prepared version is
                # parked waiting for the resident set's pins to drain.
                # Admitting now would re-pin the OLD buffer — under
                # sustained load the flip would starve forever — so
                # queued work holds at the queue head while resident
                # streams keep decoding (and retiring) below.
                with self._work:
                    self._queue[:0] = pending
                pending = []
                if not any(s is not None for s in self._slots):
                    # Nothing of ours left to vacate: the flip waits on
                    # pins held elsewhere (single-stream callers, other
                    # pools on this engine). Bounded wait, not hot spin.
                    with self._work:
                        self._work.wait(timeout=0.01)
            while True:
                if self._rows_bucket_enabled and self._rows_cap < self.max_batch:
                    # Admission-driven regrowth: a burst that needs more
                    # slots than the shrunken row bucket offers
                    # re-allocates BEFORE its wave splices (drain first —
                    # see _resize_to).
                    live_n = sum(1 for s in self._slots if s is not None)
                    demand = live_n + sum(
                        1 for _, s in pending
                        if not s.ctx.done() and s.max_new > 0
                    )
                    target = self._rows_target(demand)
                    if target > self._rows_cap:
                        self._drain_fetches()
                        self._mark_nondecode("resize", "compact")
                        self._resize_to(target)
                requeue, admitted = self._execute_admission(
                    self._plan_admission(pending), pending
                )
                if requeue or not admitted:
                    break
                if not any(st is None for st in self._slots):
                    break
                with self._work:
                    if self._closed:
                        break
                    if self._unfetched == 0:
                        # Grace window at a cold start: keep absorbing
                        # the burst while it is still landing (submits
                        # from many client threads trickle in over tens
                        # of ms), so the wave admits as ONE batch
                        # instead of splitting across decode chunks
                        # with mostly-empty slots. Nothing is decoding
                        # yet, so the only cost is a bounded pause
                        # before the first chunk.
                        # The loop exits one 10 ms window after the queue
                        # stops growing, so a lone request pays ~10 ms;
                        # only a still-arriving burst rides the deadline
                        # (B client threads trickle submits over 100+ ms).
                        t_abs = time.monotonic()
                        deadline = t_abs + 0.12
                        seen = -1
                        with self._spans.span(
                            "pool.absorb", self._tid, model=self._model,
                            queued=len(self._queue),
                        ) as sp:
                            while (
                                not self._closed
                                and len(self._queue) != seen
                                and time.monotonic() < deadline
                            ):
                                seen = len(self._queue)
                                self._work.wait(timeout=0.01)
                                sp.slice()
                        self._stat_add_locked(
                            absorb_s=time.monotonic() - t_abs
                        )
                    pending = list(self._queue)
                    self._queue.clear()
                if not pending:
                    break
            resumed: list = []
            # lint-ok pre-check: _plan_preempt drains the nudge under
            # the lock; a racing nudge is simply caught next iteration.
            if self._preempt_enabled and (
                requeue or self._preempt_req  # lint-ok: GS01 racy pre-check
            ):
                # Blocked higher-class work vs resident lower-class
                # streams: preempt at most one victim per blocked
                # stream; the resumed entries queue BEHIND the blocked
                # work so the next admission pass seats the high class
                # into the freed slots first.
                resumed = self._plan_preempt(requeue)
            with self._work:
                if requeue:
                    self._queue[:0] = requeue
                if resumed:
                    self._queue[len(requeue):len(requeue)] = resumed
                qlen0 = len(self._queue)
            if resumed:
                continue  # admit the unblocked work immediately
            if self._pending_wave is not None:
                # Prefill-credit ledger: one LLMC_PREFILL_BUDGET's worth
                # of the pending wave's prefill chunks dispatches here,
                # between the previous decode chunk and the next one —
                # the device interleaves prefill and decode, so resident
                # streams keep emitting while the wave establishes. A
                # pool with nothing live has nothing to overlap: exhaust
                # the session and install immediately.
                self._advance_wave(
                    exhaust=not any(s is not None for s in self._slots),
                )
            if any(s is not None for s in self._slots):
                # Depth gate: wait for pipeline room before dispatching
                # another chunk. Queue growth past the requeued items
                # breaks the wait so a NEW burst admits into free slots
                # before the next chunk is committed — but requeued
                # streams alone (waiting on slots/frontier) must not,
                # or the gate degenerates into a busy spin.
                # close() does NOT break the gate: in-flight streams keep
                # decoding to completion, paced one chunk per fetch like
                # an open pool.
                with self._work:
                    while (
                        self._worker_exc is None
                        and self._unfetched >= 2
                        and len(self._queue) <= qlen0
                    ):
                        self._work.wait(0.1)
                    if self._worker_exc is not None:
                        raise self._worker_exc
                    if self._unfetched >= 2:
                        continue  # new arrivals: admit them first
                # Re-check liveness: the worker may have retired the
                # whole pool while we waited for pipeline room (or
                # between the outer check and here).
                if not any(s is not None for s in self._slots):
                    continue
                # Overshoot gate (tail trim, VERDICT r4 #3): when every
                # live stream's need is covered by already-dispatched
                # work, another chunk is pure dead stepping — the
                # depth-2 pipeline otherwise overshoots one full chunk
                # per pool drain (measured as tail_s ≈ decode_s at small
                # fires). Wait for the in-flight chunks to retire the
                # pool; queue growth breaks the wait so a new burst
                # still admits promptly.
                with self._work:
                    while (
                        self._worker_exc is None
                        and self._unfetched > 0
                        and len(self._queue) <= qlen0
                        and any(s is not None for s in self._slots)
                        and all(
                            s.planned >= s.max_new
                            for s in self._slots if s is not None
                        )
                    ):
                        self._work.wait(0.05)
                    if self._worker_exc is not None:
                        raise self._worker_exc
                live_now = [s for s in self._slots if s is not None]
                if not live_now:
                    continue
                if all(s.planned >= s.max_new for s in live_now):
                    if (
                        self._unfetched > 0  # lint-ok: GS01 monotone read
                        or len(self._queue) > qlen0  # lint-ok: GS01 racy pre-check
                    ):
                        continue  # in-flight chunks or new arrivals
                    # Drained yet still live (owner-dropped tokens —
                    # shouldn't happen): fall through and dispatch so
                    # progress is guaranteed.
                if (
                    self._rows_bucket_enabled
                    and not self._firsts
                    and self._pending_wave is None
                ):
                    # Never shrink with undispatched firsts pending:
                    # their recorded slot indices are not remapped by a
                    # row move, so a relocated stream's prefill-sampled
                    # first token would fail the owner check and vanish.
                    # Nor mid-wave: the pending wave's reserved slot
                    # indices would dangle past a row-capacity change.
                    self._maybe_shrink()
                sampling = next(
                    (s.sampling for s in self._slots if s is not None), None
                )
                if sampling is None:
                    continue  # pool retired between the check and here
                if eng._faults is not None:
                    eng._faults.check("decode")  # injected device loss
                    # engine site (recovery/): `crash` kills the whole
                    # pool mid-decode (pool-fatal, escapes to _run's
                    # cleanup — the supervisor's restart-and-replay
                    # trigger); `wedge` stalls the scheduler in
                    # non-cooperative code, freezing the heartbeat the
                    # watchdog reads.
                    fs = eng._faults.fire("engine", model=eng.cfg.name)
                    if fs is not None:
                        if fs.kind == "crash":
                            from llm_consensus_tpu.faults import InjectedFault

                            raise InjectedFault(
                                f"injected engine crash mid-decode "
                                f"({eng.cfg.name})"
                            )
                        if fs.kind == "wedge":
                            time.sleep(float(fs.param("s", 600.0)))
                    if eng.weight_version > 0:
                        # swap site (flywheel/): `canary_regress` slows
                        # decode ONLY on swapped weights — the latency
                        # regression the canary watcher must catch and
                        # roll back; baseline-version pools stay fast so
                        # the cohort comparison has a clean control.
                        fs = eng._faults.fire(
                            "swap", phase="decode", model=eng.cfg.name,
                            version=eng.weight_version,
                        )
                        if fs is not None and fs.kind == "canary_regress":
                            time.sleep(float(fs.param("s", 0.05)))
                # One decode-chunk dispatch: the host wall of the async
                # enqueue (device time surfaces as fetch arrivals). Live
                # rows are counted here, where the chunk is issued.
                live_starts = [
                    self._row_start_host[i]
                    for i, s in enumerate(self._slots[:self._rows_cap])
                    if s is not None
                ]
                rows_live = len(live_starts)
                pos0 = self._pos
                moe_decode = None
                # ``decode_kv_slots_live`` counts under the window of the
                # model's "*" layers: a uniform model's one window, none
                # where "W" layers carry it (what a full layer sweeps).
                window = eng.cfg.attn_kinds[0][1]
                by_window = {}
                self._mark_dead_rows()
                if self._spec is not None and sampling.temperature == 0.0:
                    # Speculative decode mode: the dispatch becomes a
                    # ROUND GROUP (or a bitmap-maintaining plain window
                    # while the governor probes/locks plain). Greedy
                    # gating is per-template — a sampled-template pool
                    # keeps the classic path below untouched.
                    with self._spans.span(
                        "pool.decode", self._tid, model=self._model,
                        rows_live=rows_live, rows=self._rows_cap,
                    ) as sp, _attrib_tag("spec_verify"):
                        payload, covered, mode = self._dispatch_spec(chunk)
                        slots_live = kv_slots_live(
                            pos0, covered, (self._pos - pos0) // covered,
                            live_starts, window,
                        )
                        sp.set(steps=covered, pos=self._pos, spec=mode,
                               slots_live=slots_live)
                else:
                    n_steps = self._plan_steps(chunk)
                    kv_width = eng._decode_width(self._pos + n_steps)
                    slots_live = kv_slots_live(
                        pos0, n_steps, 1, live_starts, window
                    )
                    by_window = _window_decode(
                        eng.cfg, pos0, n_steps, live_starts)
                    sentinel = self._integrity is not None
                    poison = None
                    if sentinel and eng._faults is not None:
                        # nan_logits@row=N (site ``corrupt``): poison one
                        # row's logits via the traced operand — only
                        # meaningful with the sentinel compiled in.
                        fs = eng._faults.fire(
                            "corrupt", surface="logits",
                            model=eng.cfg.name,
                        )
                        if fs is not None and fs.kind == "nan_logits":
                            poison = jnp.asarray(
                                int(fs.param("row", 0)), jnp.int32
                            )
                    with self._spans.span(
                        "pool.decode", self._tid, model=self._model,
                        steps=n_steps, kv_width=kv_width or 0,
                        rows_live=rows_live, rows=self._rows_cap,
                        pos=self._pos + n_steps, slots_live=slots_live,
                        **_ssm_decode(eng.cfg, n_steps, self._rows_cap),
                    ), _attrib_tag("decode"):
                        out = eng._flash_guard(
                            lambda impl: _decode_chunk(
                                eng.params, eng.cfg, self._token, self._pos,
                                self._cache, self._key, n_steps,
                                sampling.temperature,
                                sampling.top_k, sampling.top_p,
                                row_start=self._row_start,
                                kv_width=kv_width,
                                attn_impl=impl, mesh=eng.mesh,
                                # Shared-prefix merge: participating rows
                                # attend the pool's one prefix KV copy +
                                # their own suffix window (width bucket
                                # above scales with the SUFFIX frontier —
                                # the attention-bytes win).
                                prefix=self._prefix_cache,
                                prefix_len=self._plen if self._prefix_cache
                                is not None else None,
                                prefix_rows=self._prefix_rows
                                if self._prefix_cache is not None else None,
                                w8a8=eng.w8a8,
                                sentinel=sentinel, poison_row=poison,
                                moe_stats=eng.cfg.is_moe,
                            )
                        )
                    if eng.cfg.is_moe:
                        # The chunk's routing sums ride the fetch too.
                        *out, moe_decode = out
                    if sentinel:
                        self._token, toks, self._cache, verdict = out
                        # The verdict rides the fetch with its tokens.
                        payload = ("sentinel", toks, verdict)
                    else:
                        self._token, toks, self._cache = out
                        payload = toks
                    covered, mode = n_steps, None
                    self._pos += n_steps
                # Pure decode interval iff nothing but the previous
                # chunk ran on the device since the last dispatch — no
                # admission prefills (even failed ones), no compaction.
                pure = not self._firsts and not self._nondecode_work
                self._beat = time.monotonic()  # dispatch = progress
                for s in self._slots[:self._rows_cap]:
                    if s is not None:
                        # ``covered`` is the dispatch's GUARANTEED
                        # per-stream advance: exact for classic chunks,
                        # the 1-token-per-round floor for spec groups
                        # (acceptance is data — overshoot past a
                        # stream's need is trimmed by retirement + the
                        # owner checks, bounded by the depth-2 pipeline
                        # like the classic tail).
                        s.planned += covered
                # Owner snapshot sliced to the CURRENT row bucket: the
                # chunk's token matrix has _rows_cap columns.
                t_dispatch = time.monotonic()
                moe = None
                if eng.cfg.is_moe:
                    # (this chunk's sums, its steps x expert layers, those
                    # of them whose experts ran in the kernel: all, if a
                    # step of these rows does; the sums of the prefill
                    # programs dispatched since the last chunk): device
                    # arrays until the fetch.
                    stack = eng.params.get("layers_moe") or eng.params["layers"]
                    layer_steps = covered * eng.cfg.n_expert_layers
                    in_kernel = pairs_kernel_serves(
                        self._rows_cap * eng.cfg.experts_per_token,
                        stack["w_up"], eng.mesh)
                    moe = (moe_decode, layer_steps,
                           layer_steps if in_kernel else 0, eng._moe_bank[:])
                    del eng._moe_bank[:]
                item = (
                    payload, list(self._slots[:self._rows_cap]),
                    self._firsts, pure, t_dispatch, mode, moe,
                )
                self._firsts = []
                self._nondecode_work = False
                with self._work:
                    self._unfetched += 1
                    self._stat_add_locked(
                        decode_chunks=1, decode_steps=covered,
                        decode_row_steps=covered * rows_live,
                        decode_kv_slots_swept=covered * self._rows_cap * (
                            eng._decode_width(self._pos) or eng.max_seq
                        ),
                        decode_kv_slots_live=slots_live,
                        **_ssm_decode(eng.cfg, covered, self._rows_cap),
                        **by_window,
                    )
                    # Host gap closed: the device sat idle from the
                    # drain to this dispatch while the batcher was busy
                    # — attribute the bubble to the scheduler phase that
                    # ran during it.
                    self._close_gap(t_dispatch)
                self._fetch_q.put(item)
