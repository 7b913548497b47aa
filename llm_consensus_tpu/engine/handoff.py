"""Disaggregated prefill/decode serving: cross-mesh KV handoff.

PR 4 made admission prefill *interleave* with decode; this module makes
it *leave the decode chips entirely* (the ROADMAP's "pod-scale
disaggregated serving" item, MPMD-style): a dedicated prefill worker
runs the engine's existing :class:`~llm_consensus_tpu.engine.engine.
AdmissionPrefill` chunk programs to completion on its OWN device
sub-mesh (parallel/mesh.split_roles), then hands the finished prefix KV
to the decode pool's arena — block-granular, resharded through the
decode engine's ``shard_fn`` (the same GSPMD machinery that shards the
judge), published through the paged pool's existing ``_copy_blocks``
scatter. Decode-side admission then degenerates to a radix gather plus
a tiny suffix prefill (the ``pool.covers``-gated install the batcher's
wave planning already implements), so the decode pool's ``prefill``
attribution family drops toward zero and e2e throughput approaches the
pure decode-phase rate.

Design points:

  * **The pool IS the handoff channel.** Nothing new crosses the
    engine/batcher seam: the worker publishes into the decode engine's
    :class:`~llm_consensus_tpu.kv.pool.KVPool` (``source="handoff"``),
    and every existing decode-side reuse path — single-stream restore,
    admission-wave fork, the batcher's shared-prefix establishment and
    radix-consult wave planning — finds the blocks exactly as if a
    local request had retained them. Byte-identity disagg-on/off is
    therefore the pool's own byte-identity contract: blocks hold exact
    cache bytes, and ``jax.device_put`` across meshes is a
    byte-preserving reshard. (The contract is relative to the DECODE
    placement: turning disaggregation on also re-carves the chips, and
    a model whose undisaggregated placement had a different tp degree
    computes float reductions in a different order — that is a
    placement change, the same caveat as any prepare() re-plan, not a
    handoff property. Tests assert identity against the classic path
    on the same decode sub-mesh.)
  * **Bounded, priority-ordered queue.** ``submit`` rejects when
    ``LLMC_DISAGG_DEPTH`` tickets wait (the caller falls back to the
    classic interleaved path immediately) and the worker pops waves in
    priority order (stable within a class — the PR 9 order, preserved
    end to end since the gateway's admission controller already
    dequeues by class). The queue depth feeds the provider's pressure
    signal and the gateway's ``load_score``, so a saturated handoff
    backpressures admission instead of silently queueing.
  * **Per-wave fallback, never correctness.** Any failure inside a wave
    (prefill OOM, a crashed worker — the ``disagg`` fault site's
    ``prefill_worker_crash``) fails only that wave's tickets; their
    submitters proceed down the classic path, whose own prefill is
    always correct. The worker survives to take the next wave.
  * **Staging accounting.** The cross-mesh copy's wall books against
    the ``kv_handoff`` attribution family (obs/attrib) and the staged
    row's bytes register as an ``handoff_staging:<model>`` HBM
    component while resident, so the watermark sentinel sees the
    transfer buffer the decode chips briefly co-host.
"""

from __future__ import annotations

import threading
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from llm_consensus_tpu import integrity
from llm_consensus_tpu.obs.attrib import tag as _attrib_tag
from llm_consensus_tpu.analysis import sanitizer
from llm_consensus_tpu.utils import knobs

DEFAULT_DEPTH = 8
DEFAULT_WAVE_ROWS = 4
DEFAULT_WAIT_S = 30.0


def _pow2_ceil(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


@partial(jax.jit, static_argnames=("span",))
def _extract_row_span(pcache, row, span: int):
    """Row ``row`` of a [k, width] admission-prefill cache, sliced to
    its first ``span`` seq slots — the block-granular staging form the
    handoff transfers (a traced row index keeps one compiled program
    per (span, leaf shapes); ``span`` pow2-buckets like the pool's
    ``_copy_blocks`` k-bucket, so the compile set stays logarithmic)."""
    from llm_consensus_tpu.ops.quant import kv_seq_axis

    def leaf(src):
        ax = kv_seq_axis(src)
        r = jax.lax.dynamic_slice_in_dim(src, row, 1, axis=1)
        return jax.lax.slice_in_dim(r, 0, span, axis=ax)

    return jax.tree.map(leaf, pcache)


class HandoffTicket:
    """One prompt's pending handoff: resolved by the worker wave."""

    __slots__ = ("ids", "priority", "seq", "ok", "truncated", "error", "_done")

    def __init__(self, ids: list, priority: int, seq: int):
        self.ids = ids
        self.priority = priority
        self.seq = seq
        self.ok = False
        self.truncated = False
        self.error: Optional[BaseException] = None
        self._done = sanitizer.make_event("engine.handoff.ticket")

    def resolve(self, ok: bool, truncated: bool = False,
                error: Optional[BaseException] = None) -> None:
        self.ok = ok
        self.truncated = truncated
        self.error = error
        self._done.set()

    def wait(self, timeout: Optional[float]) -> bool:
        return self._done.wait(timeout)


class KVHandoff:
    """Dedicated prefill worker + cross-mesh KV handoff for ONE preset.

    Owns the prefill-only engine (no decode loop, no batcher slots) and
    a bounded priority queue of :class:`HandoffTicket`\\ s; a daemon
    worker drains the queue in waves, runs the admission-prefill chunk
    programs to completion on the prefill mesh, and publishes each
    row's whole-block prefix span into the DECODE engine's KV pool.
    Thread-safe; built by ``TPUProvider._handoff_for``.
    """

    def __init__(self, prefill_engine, decode_engine, *,
                 depth: Optional[int] = None,
                 wave_rows: Optional[int] = None,
                 wait_s: Optional[float] = None,
                 name: str = ""):
        from llm_consensus_tpu.engine.engine import refuse_ssm

        refuse_ssm(decode_engine.cfg, "cross-mesh handoff of a prefilled row")
        pool = getattr(decode_engine, "_kv_pool", None)
        if pool is None:
            raise ValueError(
                "KVHandoff requires the decode engine's paged KV pool "
                "(LLMC_KV_POOL=1): the pool arena is the handoff channel"
            )
        self._pe = prefill_engine
        self._de = decode_engine
        self._pool = pool
        self.depth = depth if depth is not None else max(
            1, knobs.get_int("LLMC_DISAGG_DEPTH", DEFAULT_DEPTH)
        )
        self.wave_rows = wave_rows if wave_rows is not None else max(
            1, knobs.get_int("LLMC_DISAGG_WAVE", DEFAULT_WAVE_ROWS)
        )
        self._wait_s = wait_s if wait_s is not None else knobs.get_float(
            "LLMC_DISAGG_WAIT_S", DEFAULT_WAIT_S
        )
        self._name = name or prefill_engine.cfg.name
        # Queue state below is lock-guarded (static checker: analysis/
        # guarded_state.py; runtime order graph under LLMC_SANITIZE=1).
        self._lock = sanitizer.make_lock("engine.handoff")
        self._work = sanitizer.make_condition("engine.handoff", self._lock)
        self._queue: list[HandoffTicket] = []  # guarded by: _lock
        self._seq = 0  # guarded by: _lock
        self._closed = False  # guarded by: _lock
        self.waves = 0  # guarded by: _lock
        # Lifetime counters: handoff_* measure the cross-mesh transfer
        # (bytes/s is the bench's measured handoff rate), prefill_*
        # the prefill-mesh compute (the per-role utilization gauge's
        # numerator), covered the fast-path skips (prompt already
        # pool-resident — repeat traffic costs the handoff nothing).
        self.stats = {  # guarded by: _lock
            "submitted": 0, "covered": 0, "rejected": 0, "timeouts": 0,
            "fallbacks": 0, "completed": 0, "truncated": 0,
            "handoff_tokens": 0, "handoff_bytes": 0, "handoff_s": 0.0,
            "prefill_tokens": 0, "prefill_s": 0.0, "overlap_polls": 0,
            "overlap_abandons": 0,
        }
        # Fault injection + telemetry: bound once (the standing
        # zero-cost pattern — disabled runs pay a None-check per wave).
        from llm_consensus_tpu import faults as _faults
        from llm_consensus_tpu import obs as _obs

        self._faults = _faults.plan()
        self._obs = _obs.recorder()
        self._attrib = _obs.attrib.ledger()
        # Integrity plane: the cross-mesh transfer is a host-visible
        # byte-crossing seam, so every handed-off block is verified
        # (not sampled) — a mismatch fails only that row's ticket and
        # its submitter prefills classically.
        self._integrity = integrity.plane()
        if self._attrib is not None:
            # The prefill engine's weights are a SECOND resident copy of
            # this preset (the engine itself registered
            # ``weights:<name>``, which the decode engine's identical
            # registration overwrote) — give the duplicate its own
            # component key so the HBM watermark counts both copies.
            try:
                from llm_consensus_tpu.utils.flops import param_count

                wb = {"int8": 1, "int4": 0.5}.get(
                    prefill_engine.quant,
                    jnp.dtype(prefill_engine._dtype).itemsize,
                )
                self._attrib.update_component(
                    f"prefill_weights:{prefill_engine.cfg.name}",
                    int(param_count(prefill_engine.cfg) * wb),
                )
            except Exception:  # noqa: BLE001 — modeling only
                pass
        self._thread = threading.Thread(
            target=self._run, name=f"llmc-handoff-{self._name}", daemon=True
        )
        self._thread.start()

    # -- submit side ---------------------------------------------------------

    def queued(self) -> int:
        with self._lock:
            return len(self._queue)

    def saturation(self) -> float:
        """Queue fullness in [0, 1] — the admission-backpressure signal
        the gateway's load_score and the pressure governor read."""
        with self._lock:
            return min(1.0, len(self._queue) / max(1, self.depth))

    def submit(self, prompt_ids: list, priority: int = 1
               ) -> Optional[HandoffTicket]:
        """Queue one prompt for prefill-mesh establishment; None when
        the prompt is too short for a whole block (nothing to hand off)
        or the bounded queue is full (backpressure: the caller admits
        classically NOW instead of stacking latency here)."""
        bs = self._pool.block_size
        ids = list(prompt_ids)
        if len(ids) < bs:
            return None
        with self._lock:
            self.stats["submitted"] += 1
            if self._closed or len(self._queue) >= self.depth:
                self.stats["rejected"] += 1
                return None
            self._seq += 1
            t = HandoffTicket(ids, int(priority), self._seq)
            if self._pool.covers(ids):
                # Already resident (repeat traffic / a prior wave):
                # the decode-side suffix install needs no new work.
                self.stats["covered"] += 1
                t.resolve(True)
                return t
            self._queue.append(t)
            self._work.notify()
        return t

    def run(self, prompt_ids: list, priority: int = 1, ctx=None
            ) -> "tuple[bool, bool]":
        """Submit + bounded wait: ``(handed_off, truncated)``. A reject,
        timeout, or failed wave returns ``(False, False)`` — the caller
        proceeds down the classic path (reuse lost, never correctness).
        The wait honors the request's own deadline so a handoff stall
        can't eat a client's whole budget."""
        t = self.submit(prompt_ids, priority)
        if t is None:
            return False, False
        timeout = self._wait_s
        if ctx is not None:
            rem = ctx.remaining()
            if rem is not None:
                timeout = min(timeout, max(0.0, rem))
        if not t.wait(timeout):
            with self._lock:
                self.stats["timeouts"] += 1
            return False, False
        return t.ok, t.truncated

    def run_overlapped(self, prompt_ids: list, priority: int = 1, ctx=None,
                       poll_s: float = 0.05) -> "tuple[bool, bool]":
        """Submit + POLLED bounded wait (``LLMC_DISAGG_OVERLAP``, the
        default): same contract as :meth:`run`, but the submitter sleeps
        in short slices instead of one opaque ``Event.wait``. Between
        slices it checks the request context, so a cancelled or expired
        request abandons the ticket within one slice — the classic
        blocking wait sat out the FULL timeout after a cancel, wedging
        the panel worker while sibling streams' SSE flushes queued
        behind it. An abandoned wave still publishes into the pool, so
        the work warms the prefix cache for the next request."""
        t = self.submit(prompt_ids, priority)
        if t is None:
            return False, False
        timeout = self._wait_s
        if ctx is not None:
            rem = ctx.remaining()
            if rem is not None:
                timeout = min(timeout, max(0.0, rem))
        deadline = time.monotonic() + timeout
        while True:
            left = deadline - time.monotonic()
            if left <= 0:
                with self._lock:
                    self.stats["timeouts"] += 1
                return False, False
            if t.wait(min(poll_s, left)):
                return t.ok, t.truncated
            with self._lock:
                self.stats["overlap_polls"] += 1
            if ctx is not None and ctx.done():
                with self._lock:
                    self.stats["overlap_abandons"] += 1
                return False, False

    def close(self) -> None:
        """Stop the worker and fail queued tickets (their submitters
        fall back classically). The daemon thread exits on its own —
        never joined, it may be mid-dispatch on the prefill mesh."""
        with self._work:
            if self._closed:
                return
            self._closed = True
            queued, self._queue = self._queue, []
            self._work.notify_all()
        for t in queued:
            t.resolve(False, error=RuntimeError("handoff closed"))

    # -- worker side ---------------------------------------------------------

    def _run(self) -> None:
        while True:
            # Schedule-exploration seam: one wave drain is the protocol
            # step the model checker preempts between.
            sanitizer.sched_point("handoff.drain")
            with self._work:
                while not self._queue and not self._closed:
                    self._work.wait()
                if self._closed:
                    return
                # Priority-ordered wave pop: stable (class, arrival) —
                # the PR 9 admission order, preserved through the
                # handoff tier.
                self._queue.sort(key=lambda t: (t.priority, t.seq))
                batch = self._queue[:self.wave_rows]
                del self._queue[:len(batch)]
                self.waves += 1
                wave_n = self.waves
            try:
                if self._faults is not None:
                    fs = self._faults.fire(
                        "disagg", wave=wave_n, model=self._pe.cfg.name
                    )
                    if fs is not None:
                        if fs.kind == "handoff_stall":
                            time.sleep(float(fs.param("s", 0.2)))
                        elif fs.kind == "prefill_worker_crash":
                            from llm_consensus_tpu.faults import InjectedFault

                            raise InjectedFault(
                                f"injected prefill worker crash at wave "
                                f"{wave_n} ({self._pe.cfg.name})"
                            )
                self._wave(batch, wave_n)
            except BaseException as exc:  # noqa: BLE001 — per-wave fallback
                with self._lock:
                    self.stats["fallbacks"] += len(batch)
                if self._obs is not None:
                    self._obs.instant(
                        "handoff_fallback", tid="handoff", wave=wave_n,
                        streams=len(batch), error=repr(exc)[:200],
                    )
                for t in batch:
                    t.resolve(False, error=exc)

    def _wave(self, batch: list, wave_n: int) -> None:
        """One wave: admission-prefill the batch's prompts to completion
        on the prefill mesh, then per row extract the whole-block span,
        reshard it onto the decode mesh, and publish into the pool."""
        pe = self._pe
        bs = self._pool.block_size
        t0_obs = self._obs.now() if self._obs is not None else 0
        rows = [list(t.ids) for t in batch]
        t_pf = time.monotonic()
        with _attrib_tag("prefill"):
            session = pe.admission_session(rows)
            session.step(None)  # classic completion — the prefill-only role
            _last_logits, pcache, width = session.finish()
            # The publish below reads the wave cache cross-mesh; the
            # extract is dispatched per row against the SAME buffer, so
            # completion here keeps the wave's wall attributable to the
            # prefill mesh rather than smearing into the transfer.
            jax.block_until_ready(jax.tree.leaves(pcache)[0])
        prefill_s = time.monotonic() - t_pf
        with self._lock:
            self.stats["prefill_tokens"] += sum(len(r) for r in rows)
            self.stats["prefill_s"] += prefill_s
        place = self._decode_place()
        for i, t in enumerate(batch):
            nblk = len(t.ids) // bs
            if nblk < 1:
                t.resolve(False)
                continue
            span = nblk * bs
            span_b = min(width, max(span, _pow2_ceil(span)))
            if span_b % bs:
                # A non-pow2 block size can leave the bucket unaligned;
                # the publish only needs cache_cap >= the block span, so
                # fall back to the full wave bucket.
                span_b = width
            t_x = time.monotonic()
            staging = f"handoff_staging:{self._de.cfg.name}"
            try:
                with _attrib_tag("kv_handoff"):
                    rowcache = _extract_row_span(
                        pcache, pe._place(jnp.asarray(i, jnp.int32)), span_b
                    )
                    staged = place(rowcache)
                    jax.block_until_ready(staged)
                nbytes = sum(
                    leaf.size * leaf.dtype.itemsize
                    for leaf in jax.tree.leaves(staged)
                )
                if self._attrib is not None:
                    # The staged row co-resides on the decode chips until
                    # the publish scatter consumes it: the watermark
                    # sentinel must see the transfer buffer.
                    self._attrib.update_component(staging, nbytes)
                    self._attrib.observe_device(
                        "kv_handoff", time.monotonic() - t_x
                    )
                if self._integrity is not None:
                    # Verify the reshard moved exact bytes: digest each
                    # block span on BOTH sides of the mesh boundary. A
                    # mismatch is a wire/chip corruption — raise the
                    # typed error into the per-row fallback below so
                    # the submitter re-prefills on the decode mesh and
                    # the corrupt blocks never enter the pool.
                    flip = False
                    if self._faults is not None:
                        fs = self._faults.fire(
                            "corrupt", surface="handoff", wave=wave_n
                        )
                        flip = fs is not None and fs.kind == "bit_flip"
                    for b_i in range(span // bs):
                        self._integrity.check("handoff")
                        want = self._pool.block_digest(rowcache, b_i * bs)
                        got = self._pool.block_digest(
                            staged, b_i * bs, flip_bit=flip and b_i == 0
                        )
                        if want != got:
                            self._integrity.failure(
                                "handoff",
                                f"cross-mesh digest mismatch at block "
                                f"{b_i} (wave {wave_n})",
                            )
                            raise integrity.IntegrityError(
                                "handoff",
                                f"block {b_i} corrupted in transfer",
                            )
                wrote, truncated = self._pool.publish(
                    t.ids[:span], staged, source="handoff"
                )
            except BaseException as exc:  # noqa: BLE001 — per-row fallback
                with self._lock:
                    self.stats["fallbacks"] += 1
                t.resolve(False, error=exc)
                continue
            finally:
                if self._attrib is not None:
                    self._attrib.update_component(staging, 0)
            dt = time.monotonic() - t_x
            with self._lock:
                self.stats["completed"] += 1
                self.stats["handoff_tokens"] += span
                self.stats["handoff_bytes"] += nbytes
                self.stats["handoff_s"] += dt
                if truncated:
                    self.stats["truncated"] += 1
            t.resolve(True, truncated=truncated)
        if self._obs is not None:
            self._obs.complete(
                "handoff_wave", t0_obs, tid="handoff", wave=wave_n,
                streams=len(batch), width=width,
            )
            self._obs.count(
                "handoff.tokens", sum((len(t.ids) // bs) * bs for t in batch)
            )

    def _decode_place(self):
        """Reshard a staged cache tree onto the decode engine's leaf
        shardings — the engine's own ``shard_fn`` when it has one (tp
        decode meshes shard the staged blocks exactly like a working
        cache, int8 code+scale stacks included), else a plain transfer
        onto the arena's device."""
        fn = self._de._shard_fn
        if fn is not None:
            return fn
        leaf0 = jax.tree.leaves(self._pool._arena)[0]
        try:
            dev = next(iter(leaf0.devices()))
        except Exception:  # noqa: BLE001 — uncommitted arena: no transfer
            return lambda tree: tree
        return lambda tree: jax.device_put(tree, dev)

    # -- introspection -------------------------------------------------------

    def snapshot(self) -> dict:
        """The /statsz ``disagg`` block entry for this preset."""
        with self._lock:
            out = dict(self.stats)
            out["queued"] = len(self._queue)
            out["waves"] = self.waves
        out["depth"] = self.depth
        out["wave_rows"] = self.wave_rows
        out["prefill_devices"] = (
            self._pe.mesh.devices.size if self._pe.mesh is not None else 1
        )
        out["decode_devices"] = (
            self._de.mesh.devices.size if self._de.mesh is not None else 1
        )
        if out["handoff_s"] > 0:
            out["handoff_bytes_per_s"] = round(
                out["handoff_bytes"] / out["handoff_s"], 1
            )
        out["handoff_s"] = round(out["handoff_s"], 4)
        out["prefill_s"] = round(out["prefill_s"], 4)
        return out


__all__ = ["HandoffTicket", "KVHandoff"]
