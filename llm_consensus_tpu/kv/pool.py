"""Paged KV block pool: one preallocated arena + gather/scatter programs.

The device half of the cross-request KV cache (see the package
docstring). Layout decisions, TPU-first:

  * **One arena per engine**, allocated ONCE at pool construction as an
    ``init_kv_cache(batch=1, max_seq=n_blocks × block_size)`` tree and
    passed through the engine's own ``shard_fn`` — so every leaf keeps
    exactly the per-leaf NamedShardings a working cache has (int8 code
    stacks + seq-minor scale stacks included) and tp engines shard the
    pool transparently (GSPMD partitions the copy programs natively;
    the seq axis blocks live on is never sharded).
  * **One compiled program** (`_copy_blocks`) serves both directions:
    gather (arena → fresh [1, S] cache, the radix-hit fast path) and
    publish (finished cache → arena, donated so the write is in place).
    Block starts are TRACED operands and the block count pow2-buckets
    (padding repeats the last pair — an idempotent self-copy), so the
    compile set is logarithmic in chain length and shared across every
    distinct match.
  * **Bytes, not recompute**: blocks store exact cache bytes at absolute
    positions [0, n) of a left-aligned [1, S] cache — a gather costs
    seq-axis copy bandwidth where the prefill it replaces costs a full
    forward pass, and the gathered prefix is bit-identical to what the
    classic snapshot restore would have produced (the greedy
    byte-identity contract, asserted in tests/test_kv.py).

Concurrency: one pool lock serializes radix walks, slot accounting, and
device DISPATCH (enqueue only — execution overlaps on the device
stream). Host dispatch order is publish-after-gather whenever a slot is
recycled (eviction requires ``refs == 0``, and leases are held across
the gather dispatch), so in-order device streams make slot reuse safe
without any device-side synchronization.
"""

from __future__ import annotations

import time
import warnings
from functools import partial

import jax
import jax.numpy as jnp

from llm_consensus_tpu import integrity
from llm_consensus_tpu.obs.attrib import tag as attrib_tag
from llm_consensus_tpu.analysis import sanitizer
from llm_consensus_tpu.utils import knobs
from llm_consensus_tpu.utils.flops import cache_bytes_per_token


@partial(jax.jit, static_argnames=("k", "bs"), donate_argnames=("dst",))
def _copy_blocks(dst, src, src_starts, dst_starts, k: int, bs: int):
    """Copy ``k`` block-sized seq spans from ``src``'s leaves into
    ``dst``'s (both init_kv_cache trees; traced span starts, so ONE
    program per (k, bs) and leaf shapes). Gather and publish are the
    same program with the roles swapped; padding pairs repeat a real
    pair, which is an idempotent self-overwrite."""
    from llm_consensus_tpu.ops.quant import kv_seq_axis

    def leaf(d, s):
        ax = kv_seq_axis(d)
        for i in range(k):
            blk = jax.lax.dynamic_slice_in_dim(s, src_starts[i], bs, axis=ax)
            d = jax.lax.dynamic_update_slice_in_dim(
                d, blk, dst_starts[i], axis=ax
            )
        return d

    return jax.tree.map(leaf, dst, src)


def _kbucket(k: int) -> int:
    b = 1
    while b < k:
        b *= 2
    return b


class KVPool:
    """Block-granular cross-request KV pool over one engine's cache
    layout. Built via :func:`llm_consensus_tpu.kv.pool_for` (one pool
    per engine — arenas are layout-specific); thread-safe."""

    def __init__(self, cfg, *, dtype, kv_quant, shard_fn, place, max_seq,
                 block_size: int, budget_bytes: float):
        from llm_consensus_tpu.models import init_kv_cache

        self.cfg = cfg
        self.block_size = block_size
        self.max_seq = max_seq
        self._dtype = dtype
        self._kv_quant = kv_quant
        self._place = place
        # Per-token KV bytes across both stacks (codes + scales for int8
        # caches) — the arena sizing unit, also exported for the bench's
        # resident-stream capacity model.
        itemsize = jnp.dtype(dtype).itemsize
        per_tok = cache_bytes_per_token(cfg, 1)
        if kv_quant == "int8":
            self.bytes_per_token = per_tok + 2 * cfg.n_layers * cfg.n_kv_heads * itemsize
        else:
            self.bytes_per_token = per_tok * itemsize
        n_blocks = int(budget_bytes // (block_size * self.bytes_per_token))
        self.n_blocks = max(4, n_blocks)
        arena = init_kv_cache(
            cfg, batch=1, max_seq=self.n_blocks * block_size,
            dtype=dtype, quant=kv_quant,
        )
        if shard_fn is not None:
            arena = shard_fn(arena)
        # One pool lock serializes radix walks, slot accounting, and
        # device dispatch; the guarded-by annotations below are enforced
        # by the static guarded-state checker (analysis/guarded_state.py)
        # and, under LLMC_SANITIZE=1, the named lock joins the runtime
        # lock-order graph (analysis/sanitizer.py).
        self._lock = sanitizer.make_lock("kv.pool")
        self._arena = arena  # guarded by: _lock
        self._free = list(range(self.n_blocks))  # guarded by: _lock
        from llm_consensus_tpu.kv.radix import RadixIndex

        self._radix = RadixIndex(block_size)  # guarded by: _lock
        # Fault injection + telemetry: bound once like every other
        # subsystem, so disabled runs pay a single None-check.
        from llm_consensus_tpu import faults as _faults
        from llm_consensus_tpu import obs as _obs

        self._faults = _faults.plan()
        self._obs = _obs.recorder()
        # Integrity plane (integrity/core.py): stamps a content digest on
        # every published block and verifies a deterministic sample of
        # gathers against it — None when LLMC_INTEGRITY is off, so the
        # hot paths pay one None-check.
        self._integrity = integrity.plane()
        # Chip-time attribution (obs/attrib): gather/publish dispatch
        # walls book as kv_gather/kv_publish; the arena registers as a
        # modeled HBM component; evictions and the pre-truncation
        # pressure event feed the goodput ledger + watermark sentinel.
        self._attrib = _obs.attrib.ledger()
        if self._attrib is not None:
            self._attrib.update_component(
                f"kv_arena:{cfg.name}",
                int(self.n_blocks * block_size * self.bytes_per_token),
            )
        self._stats = {  # guarded by: _lock
            "lookups": 0, "hits": 0, "hit_tokens": 0, "miss_tokens": 0,
            "published_blocks": 0, "evicted_blocks": 0, "exhausted": 0,
            # Disaggregated serving (engine/handoff.py): blocks that
            # arrived via the cross-mesh handoff rather than a local
            # retain — the /statsz ``kv`` block's handoff-traffic view.
            "handoff_blocks": 0,
            # Integrity plane traffic: gathered blocks digest-verified
            # and blocks whose verify failed (subtree dropped, reuse
            # recomputed — see lookup).
            "verified_blocks": 0, "corrupt_blocks": 0,
        }

    @classmethod
    def for_engine(cls, engine) -> "KVPool":
        block = knobs.get_int("LLMC_KV_POOL_BLOCK")
        budget = knobs.get_float("LLMC_KV_POOL_MB") * 1e6
        return cls(
            engine.cfg, dtype=engine._dtype, kv_quant=engine.kv_quant,
            shard_fn=engine._shard_fn, place=engine._place,
            max_seq=engine.max_seq, block_size=max(1, block),
            budget_bytes=budget,
        )

    # -- cache factory -------------------------------------------------------

    def _fresh_cache(self):
        from llm_consensus_tpu.models import init_kv_cache

        cache = init_kv_cache(
            self.cfg, batch=1, max_seq=self.max_seq, dtype=self._dtype,
            quant=self._kv_quant,
        )
        return cache

    # -- integrity (block content digests) -----------------------------------

    def block_digest(self, cache, start: int, flip_bit: bool = False) -> str:
        """Content digest of the block-sized seq span at ``start`` across
        every leaf of ``cache`` — the unit the copy program moves, so a
        digest stamped from the publish source equals a digest of the
        same span read back from the arena or a gathered cache (exact
        bytes, the byte-identity contract doing double duty). Host-side:
        each leaf's span transfers once; only integrity-on paths call
        this. ``flip_bit`` XORs one bit into the first leaf's host copy —
        the ``bit_flip`` fault's injection point, corrupting the
        host-visible copy at the verification boundary."""
        from llm_consensus_tpu.ops.quant import kv_seq_axis

        bs = self.block_size
        crc = 0
        first = True
        for leaf in jax.tree.leaves(cache):
            ax = kv_seq_axis(leaf)
            sl = [slice(None)] * leaf.ndim
            sl[ax] = slice(start, start + bs)
            blk = jax.device_get(leaf[tuple(sl)])
            if first and flip_bit:
                import numpy as _np

                blk = _np.ascontiguousarray(blk).copy()
                blk.view(_np.uint8).reshape(-1)[0] ^= 1
                first = False
            d = integrity.digest_array(blk)
            crc = integrity.crc32_str(d, crc)
        return f"{crc:08x}"

    # -- lookup (radix match + gather) ---------------------------------------

    def lookup(self, ids: list, min_tokens: int, shard_fn=None):
        """(matched tokens, gathered [1, max_seq] cache) — the pool's
        replacement for the engine's snapshot ``_reusable_prefix``.

        The match is capped at ``len(ids) − 1`` (at least one token must
        prefill to produce next-token logits — the classic invariant)
        and floors to a miss below ``min_tokens`` or when the restored
        prefix plus the chunk-rounded tail would overrun ``max_seq`` —
        the caller's ``reuse_ok`` bound, applied HERE so no gather (a
        full [1, max_seq] cache allocation + device copy) is ever
        dispatched for a reuse the engine would then reject. The classic
        snapshot path returns zero-copy so its late gate is free; the
        pool's is not.
        The returned cache holds exact block bytes at [0, n) and zeros
        beyond — the caller masks at n (``_restore_prefix`` /
        ``_fork_prefix``), which also zeroes the matched tail block's
        junk past the match point.
        """
        bs = self.block_size
        with self._lock:
            self._stats["lookups"] += 1
            n, chain = self._radix.match(list(ids))
            n = min(n, len(ids) - 1)
            k = -(-n // bs) if n > 0 else 0
            chunk = max(1, min_tokens)
            tail_rounded = -(-(len(ids) - n) // chunk) * chunk
            if (n < min_tokens or k == 0 or k * bs > self.max_seq
                    or n + tail_rounded > self.max_seq):
                self._stats["miss_tokens"] += len(ids)
                return 0, None
            lease = chain[:k]
            for b in lease:
                b.refs += 1
            self._stats["hits"] += 1
            self._stats["hit_tokens"] += n
            self._stats["miss_tokens"] += len(ids) - n
            # Dispatch INSIDE the lock: slot recycling relies on host
            # dispatch order (gather-before-republish), and publish
            # DONATES the arena — a gather dispatched outside the lock
            # could capture an arena buffer a concurrent publish has
            # already invalidated. The enqueue is async so the lock is
            # held for µs once programs are warm; the first hit in each
            # pow2 k-bucket pays its XLA compile under the lock (once
            # per process, amortized by the persistent XLA cache) —
            # the price of keeping donation + ordering trivially sound.
            try:
                t_g = time.monotonic()
                with attrib_tag("kv_gather"):
                    dst = self._fresh_cache()
                    if shard_fn is not None:
                        dst = shard_fn(dst)
                    kb = _kbucket(k)
                    srcs = [b.slot * bs for b in lease]
                    dsts = [i * bs for i in range(k)]
                    pad = kb - k
                    srcs += [srcs[-1]] * pad
                    dsts += [dsts[-1]] * pad
                    dst = _copy_blocks(
                        dst, self._arena,
                        self._place(jnp.asarray(srcs, jnp.int32)),
                        self._place(jnp.asarray(dsts, jnp.int32)),
                        kb, bs,
                    )
                if self._attrib is not None:
                    self._attrib.observe_device(
                        "kv_gather", time.monotonic() - t_g
                    )
            finally:
                for b in lease:
                    b.refs -= 1
            if self._integrity is not None and self._integrity.sample_hit():
                # Sampled gather verification: re-digest the gathered
                # spans (a host-visible read of what the client is about
                # to reuse) against the publish-time digests. A mismatch
                # drops the whole chain from the index and reports a
                # MISS — the caller re-prefills, so reuse is lost but
                # the stream never decodes over corrupt bytes.
                flip = False
                if self._faults is not None:
                    fs = self._faults.fire(
                        "corrupt", surface="kv", model=self.cfg.name
                    )
                    flip = fs is not None and fs.kind == "bit_flip"
                for i, b in enumerate(lease):
                    if b.digest is None:
                        continue  # published before the plane came up
                    self._integrity.check("kv")
                    self._stats["verified_blocks"] += 1
                    got = self.block_digest(
                        dst, i * bs, flip_bit=flip and i == 0
                    )
                    if got != b.digest:
                        self._integrity.failure(
                            "kv",
                            f"gather digest mismatch at slot {b.slot}",
                        )
                        self._stats["corrupt_blocks"] += 1
                        self._free.extend(self._radix.drop(b))
                        return 0, None
        if self._obs is not None:
            self._obs.count("kv.hit_tokens", n)
        return n, dst

    # -- publish (scatter + radix insert) ------------------------------------

    def publish(self, ids: list, cache, source: str = "local") -> "tuple[int, bool]":
        """Scatter ``ids``'s KV blocks from a finished left-aligned
        [1, S] ``cache`` into the arena and index them — the pool's
        replacement for snapshot retention. Incremental: only blocks the
        radix doesn't already hold are written (a repeated prompt costs
        a host walk and nothing on device). Returns ``(blocks written,
        truncated)`` — ``truncated`` is True when exhaustion dropped the
        tail, so the caller can surface degraded reuse per response
        instead of burying it in a lifetime counter — from EVERY source:
        the cross-mesh handoff path (``source="handoff"``,
        engine/handoff.py) reports exhaustion through the same tuple and
        the same obs instant as a local retain, so a disaggregated
        deployment sees ``kv.truncated`` on the response exactly like
        the classic path does.

        Divergence is copy-on-write by construction: the plan writes
        fresh blocks for any span that extends or forks an existing
        chain, and attached blocks are never rewritten — a concurrent
        reader's gathered bytes cannot change under it. When the free
        list runs dry, LRU-unreferenced blocks evict; if nothing is
        evictable the publish truncates (``pool_exhausted``) — the
        prefix that did fit is still servable.
        """
        from llm_consensus_tpu.ops.quant import kv_seq_axis

        bs = self.block_size
        leaf = jax.tree.leaves(cache)[0]
        cache_cap = leaf.shape[kv_seq_axis(leaf)]
        # Publish only whole in-capacity block spans: the slice of a
        # partial tail block still reads [start, start+bs), which must
        # sit inside the source cache.
        n = min(len(ids), (cache_cap // bs) * bs)
        if n < 1:
            return 0, False
        exhausted_inject = False
        squeeze_limit = None
        if self._faults is not None:
            fs = self._faults.fire("kv", model=self.cfg.name)
            if fs is not None:
                if fs.kind == "pool_exhausted":
                    exhausted_inject = True
                elif fs.kind == "evict_storm":
                    with self._lock:
                        freed = self._radix.evict(self.n_blocks)
                        self._free.extend(freed)
                        self._stats["evicted_blocks"] += len(freed)
                    if self._obs is not None and freed:
                        self._obs.count("kv.evicted_blocks", len(freed))
                    if self._attrib is not None and freed:
                        self._attrib.token_event(
                            "evicted_kv", len(freed) * bs
                        )
            # hbm_squeeze (site ``pressure``, phase=publish): the
            # effective arena shrinks to @frac= of its blocks for this
            # publish — same truncation path as real exhaustion, under a
            # pool that LOOKS healthy, which is the governor's signal.
            fs = self._faults.fire(
                "pressure", phase="publish", model=self.cfg.name
            )
            if fs is not None and fs.kind == "hbm_squeeze":
                squeeze_limit = max(
                    0, int(self.n_blocks * float(fs.param("frac", 0.5)))
                )
        wrote = 0
        evicted = 0
        pressure_info = None  # fired AFTER the lock: a sentinel dump
        # (ring serialize + disk write) must not stall concurrent
        # gathers/publishes exactly when the system is under pressure.
        with self._lock:
            node, _base, writes = self._radix.plan_insert(list(ids[:n]))
            if not writes:
                return 0, False
            slots: list[int] = []
            for _ in writes:
                if exhausted_inject:
                    break
                if squeeze_limit is not None and (
                    # used = non-free blocks; slots already popped this
                    # publish are no longer in the free list, so they
                    # are counted here exactly once.
                    self.n_blocks - len(self._free) >= squeeze_limit
                ):
                    break  # the squeezed arena has no slot to grant
                if not self._free:
                    freed = self._radix.evict(
                        max(1, len(writes) - len(slots))
                    )
                    evicted += len(freed)
                    self._stats["evicted_blocks"] += len(freed)
                    self._free.extend(freed)
                if not self._free:
                    break
                slots.append(self._free.pop())
            if len(slots) < len(writes):
                # Arena exhausted (every block interior or leased, an
                # injected fault, or a squeezed arena): publish the
                # prefix that fits — chains must stay gap-free, so the
                # tail past the last granted slot is dropped, never
                # skipped over.
                if self._attrib is not None:
                    # HBM watermark sentinel — the instant + dump fire
                    # right after this lock releases, before the caller
                    # can observe the truncation it reports.
                    pressure_info = {
                        "wanted": len(writes), "granted": len(slots),
                        "blocks_total": self.n_blocks,
                        "blocks_free": len(self._free),
                    }
                self._stats["exhausted"] += 1
                truncated = True
                if self._obs is not None:
                    self._obs.instant(
                        "kv_pool_exhausted", tid="kv",
                        wanted=len(writes), granted=len(slots),
                        source=source,
                    )
                    self._obs.count("kv.exhausted")
                writes = writes[:len(slots)]
            else:
                truncated = False
            if writes:
                k = len(writes)
                kb = _kbucket(k)
                srcs = [start for start, _ in writes]
                dsts = [slot * bs for slot in slots]
                pad = kb - k
                srcs += [srcs[-1]] * pad
                dsts += [dsts[-1]] * pad
                t_p = time.monotonic()
                with warnings.catch_warnings(), attrib_tag("kv_publish"):
                    # The arena is long-lived and referenced by in-flight
                    # gathers; donation is for the in-place fast path,
                    # and XLA falling back to a copy when a gather still
                    # holds the buffer is correct — just quiet.
                    warnings.filterwarnings(
                        "ignore",
                        message="Some donated buffers were not usable",
                    )
                    self._arena = _copy_blocks(
                        self._arena, cache,
                        self._place(jnp.asarray(srcs, jnp.int32)),
                        self._place(jnp.asarray(dsts, jnp.int32)),
                        kb, bs,
                    )
                if self._attrib is not None:
                    self._attrib.observe_device(
                        "kv_publish", time.monotonic() - t_p
                    )
                # Attach only AFTER the scatter is enqueued. The pool
                # lock already serializes publish against matches;
                # keeping the ordering anyway means no lease can ever
                # cover bytes that are not at least in flight to the
                # arena (in-order device streams do the rest) — an
                # invariant that holds regardless of how this lock is
                # ever split. attach() re-validating the plan is likewise
                # the index guarding itself (under this lock its dedup
                # branch is unreachable; tests drive it directly) —
                # deduped writes hand their slots back.
                attached = self._radix.attach(node, writes, slots)
                used = {b.slot for b in attached}
                for slot in slots:
                    if slot not in used:
                        self._free.append(slot)
                if self._integrity is not None and attached:
                    # Stamp each attached block's content digest from
                    # the publish SOURCE (the finished cache) — the
                    # scatter moves exact bytes, so a later gather of
                    # the same span must reproduce this digest or the
                    # bytes were corrupted in between.
                    starts = {
                        slot: start
                        for (start, _t), slot in zip(writes, slots)
                    }
                    for blk in attached:
                        blk.digest = self.block_digest(
                            cache, starts[blk.slot]
                        )
                wrote = len(attached)
                self._stats["published_blocks"] += wrote
                if source == "handoff":
                    self._stats["handoff_blocks"] += wrote
        if pressure_info is not None:
            self._attrib.hbm_pressure(
                f"kv_pool:{self.cfg.name}", **pressure_info
            )
        if self._obs is not None:
            if wrote:
                self._obs.count("kv.published_blocks", wrote)
            if evicted:
                self._obs.count("kv.evicted_blocks", evicted)
        if self._attrib is not None and evicted:
            # Goodput ledger: tokens whose KV was computed, published,
            # and then dropped — the recompute exposure of eviction.
            self._attrib.token_event("evicted_kv", evicted * bs)
        return wrote, truncated

    def evict_cold(self, target_occupancy: float) -> int:
        """Evict cold (unreferenced, LRU) blocks until arena occupancy
        is at or below ``target_occupancy`` — the pressure governor's
        ``evict`` rung: trade future prefix reuse for admission headroom
        BEFORE anything user-visible degrades. Returns blocks freed
        (possibly fewer than asked when the remainder is leased or
        interior). No device work: eviction only recycles slots."""
        target = min(1.0, max(0.0, float(target_occupancy)))
        with self._lock:
            used = self.n_blocks - len(self._free)
            want = used - int(target * self.n_blocks)
            if want <= 0:
                return 0
            freed = self._radix.evict(want)
            self._free.extend(freed)
            self._stats["evicted_blocks"] += len(freed)
        if self._obs is not None and freed:
            self._obs.count("kv.evicted_blocks", len(freed))
        if self._attrib is not None and freed:
            self._attrib.token_event(
                "evicted_kv", len(freed) * self.block_size
            )
        return len(freed)

    def covers(self, ids: list) -> bool:
        """True when the radix already holds ``ids``'s whole-block span —
        the admission wave's gate before paying the row-0 extraction
        copy (the classic path's ``_prefix_ids != rows[0]`` analog).

        Judged on whole blocks DELIBERATELY: publish CAN store a partial
        tail (single-stream ``_retain_prefix`` does routinely), but for a
        repeat wave the only delta past the covered span is a sub-block
        tail of < block_size tokens — re-extracting the whole row-0 cache
        every wave to capture it costs more than the ≤ block_size−1
        tokens of prefill a future match would save, so such waves skip
        retention and that tail stays unpublished."""
        n = (len(ids) // self.block_size) * self.block_size
        if n < 1:
            return True
        with self._lock:
            return self._radix.covered(list(ids[:n])) >= n

    def match_len(self, ids: list) -> int:
        """Radix-resident prefix length of ``ids`` — a host-only trie
        walk, no lease, no gather. Admission planning consults this to
        size a wave's shared prefix to what the pool can restore nearly
        for free (the establishment prefill then rides the gather)."""
        with self._lock:
            n, _chain = self._radix.match(list(ids))
        return n

    # -- introspection -------------------------------------------------------

    def stats(self) -> dict:
        """Occupancy + traffic counters for /statsz and metrics.json."""
        with self._lock:
            used = self.n_blocks - len(self._free)
            out = dict(self._stats)
        out.update(
            block_size=self.block_size,
            blocks_total=self.n_blocks,
            blocks_used=used,
            occupancy=round(used / max(1, self.n_blocks), 4),
            bytes_per_token=self.bytes_per_token,
        )
        return out
