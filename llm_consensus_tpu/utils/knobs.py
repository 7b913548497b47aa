"""Central ``LLMC_*`` knob registry — the one place an env knob exists.

Thirteen PRs grew ~100 ``LLMC_*`` environment knobs, each parsed ad hoc
at its call site (`os.environ.get(...) or default`, local ``_env_int``
helpers, bespoke strip/compare idioms). Nothing guaranteed a knob was
documented, spelled consistently, or parsed the same way twice — doc
drift was invisible until an operator hit it. This module is the fix:

  * every knob is **declared once** here — name, type, default, owning
    subsystem, one-line doc;
  * call sites read through the typed getters (:func:`get_str`,
    :func:`get_int`, :func:`get_float`, :func:`get_bool`, :func:`raw`),
    which refuse undeclared names — a typo'd knob read raises instead of
    silently returning its default forever;
  * the static analyzer (``python -m llm_consensus_tpu.analysis``,
    checker ``KR``) enforces the routing: a raw ``os.environ`` read of
    an ``LLMC_*`` name anywhere else in the package is a finding, a
    getter call with an undeclared name is a finding, and every declared
    knob must appear in the README / docs knob tables (and vice versa) —
    doc drift fails lint, not an operator.

Parsing contract (shared by every getter): unset or empty/whitespace
value → the declared default; ``get_bool`` reads ``0/false/no/off``
(case-insensitive) as False and anything else as True; ``get_int`` /
``get_float`` fall back to the default on unparsable values instead of
raising mid-serve. Reads happen at call time (nothing is cached here),
so tests that monkeypatch ``os.environ`` keep working unchanged.

Writes are out of scope: the CLI layers that *export* knobs for child
subsystems (``cli/serve.py`` mapping flags onto env) still assign
``os.environ[...]`` directly — the registry governs reads.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional


@dataclass(frozen=True)
class Knob:
    """One declared environment knob."""

    name: str
    kind: str  # "str" | "int" | "float" | "bool"
    default: object
    subsystem: str
    doc: str


REGISTRY: dict[str, Knob] = {}


def _k(name: str, kind: str, default, subsystem: str, doc: str) -> None:
    if name in REGISTRY:
        raise ValueError(f"duplicate knob declaration {name!r}")
    REGISTRY[name] = Knob(name, kind, default, subsystem, doc)


# -- engine ------------------------------------------------------------------
_k("LLMC_FLASH", "str", "auto", "engine",
   "1/0 force the Pallas flash-prefill kernel on/off (default: auto on TPU)")
_k("LLMC_PREFILL_CHUNK", "int", 512, "engine",
   "Chunked-prefill chunk length for long prompts (0 disables); set, also "
   "every chunk's width (unset: the one-row route's is the model's own)")
_k("LLMC_PREFILL_SCAN", "bool", True, "engine",
   "0 disables the scan-form chunked-prefill program")
_k("LLMC_DECODE_KV_MIN", "int", 128, "engine",
   "Decode attention width-bucket floor (0 reads full capacity)")
_k("LLMC_PREFIX_CACHE", "bool", True, "engine",
   "0 disables prefix KV reuse across generates")
_k("LLMC_PREFIX_CACHE_MAX_MB", "float", 2048.0, "engine",
   "Cap on retained prefix-snapshot cache size in MB")
_k("LLMC_QUANT", "str", "", "engine",
   "int8|int4 weight quantization mode")
_k("LLMC_KV_QUANT", "str", "", "engine",
   "int8 KV-cache quantization mode")
_k("LLMC_MAX_SEQ", "int", 0, "engine",
   "Cap every engine's context capacity below the preset's window")
# -- ops ---------------------------------------------------------------------
_k("LLMC_W8A8", "bool", False, "ops",
   "1 quantizes activations per row for int8*int8 MXU matmuls")
_k("LLMC_INT4_IMPL", "str", "auto", "ops",
   "int4 dequant implementation override (auto|nibble)")
_k("LLMC_DECODE_BLOCKS", "str", "", "ops",
   "bbxbk decode-kernel block-shape override for hardware sweeps")
_k("LLMC_DECODE_QSTRUCT", "bool", True, "ops",
   "0 reverts the dense-GQA decode kernel to the per-head matmul form")
_k("LLMC_DECODE_W8A8", "bool", False, "ops",
   "1 enables int8*int8 MXU decode scores (experimental)")
# -- provider ----------------------------------------------------------------
_k("LLMC_CHECKPOINT_DIR", "str", "", "provider",
   "Directory of per-model HF safetensors checkpoints")
_k("LLMC_MAX_BATCH", "int", 0, "provider",
   "Continuous-batching pool slots per preset (0/unset: LLMC_BATCH_STREAMS)")
_k("LLMC_BATCH_STREAMS", "int", 1, "provider",
   "Legacy alias for LLMC_MAX_BATCH (consulted when it is unset)")
_k("LLMC_DRAFT", "str", "", "spec",
   "Speculative decoding draft spec (same syntax as --draft, incl. lookup)")
# -- speculative -------------------------------------------------------------
_k("LLMC_SPEC_K", "int", 4, "spec",
   "Draft-length ceiling per speculative round")
_k("LLMC_SPEC_NGRAM", "int", 3, "spec",
   "Prompt-lookup drafter gram length")
_k("LLMC_SPEC_ADAPT", "bool", True, "spec",
   "0 pins k at the ceiling instead of the acceptance-EMA pow2 ladder")
_k("LLMC_SPEC_GOVERNOR", "bool", True, "spec",
   "0 disables the online drafted-vs-plain A/B governor")
_k("LLMC_SPEC_PROBE", "int", 64, "spec",
   "Tokens per governor probe window")
# -- batcher -----------------------------------------------------------------
_k("LLMC_PREFILL_BUDGET", "int", 0, "batcher",
   "Interleaved admission prefill token budget per decode chunk (0: classic)")
_k("LLMC_POOL_PREFIX", "bool", True, "batcher",
   "0 disables shared-prefix pool serving")
_k("LLMC_POOL_PREFIX_MIN", "int", 192, "batcher",
   "Minimum common-prefix tokens to establish pool sharing")
_k("LLMC_POOL_BUCKET", "bool", True, "batcher",
   "0 disables occupancy row-bucketing of the pool cache")
# -- kv ----------------------------------------------------------------------
_k("LLMC_KV_POOL", "bool", False, "kv",
   "1 replaces the single-slot prefix snapshot with the paged KV pool")
_k("LLMC_KV_POOL_BLOCK", "int", 64, "kv",
   "Pool block size in tokens (radix granule and gather/scatter unit)")
_k("LLMC_KV_POOL_MB", "float", 256.0, "kv",
   "Pool arena budget in MB")
# -- disagg ------------------------------------------------------------------
_k("LLMC_DISAGG", "bool", False, "disagg",
   "1 enables disaggregated prefill/decode serving (serve --disagg)")
_k("LLMC_DISAGG_FRACTION", "float", 0.5, "disagg",
   "Prefill share of each preset's device slice under disaggregation")
_k("LLMC_DISAGG_DEPTH", "int", 8, "disagg",
   "Handoff queue bound; beyond it prompts admit classically")
_k("LLMC_DISAGG_WAVE", "int", 4, "disagg",
   "Max prompts per prefill-worker wave")
_k("LLMC_DISAGG_WAIT_S", "float", 30.0, "disagg",
   "Submitter's bounded wait for its handoff (capped by request deadline)")
_k("LLMC_DISAGG_OVERLAP", "bool", True, "disagg",
   "0 reverts to blocking the submitter on its handoff ticket instead of "
   "polling it between SSE flushes")
# -- parallel ----------------------------------------------------------------
_k("LLMC_MULTIHOST_PLACEMENT", "bool", True, "parallel",
   "0 disables host-aware placement of model slices")
_k("LLMC_ALLGATHER_TIMEOUT", "float", 60.0, "parallel",
   "Deadline cap for one bounded allgather in seconds")
_k("LLMC_DISTRIBUTED", "bool", False, "parallel",
   "1 forces jax.distributed initialization")
_k("LLMC_COORDINATOR", "str", "", "parallel",
   "Multi-host cluster coordinator address (jax.distributed)")
_k("LLMC_NUM_PROCESSES", "int", 0, "parallel",
   "Multi-host cluster process count (jax.distributed)")
_k("LLMC_PROCESS_ID", "int", 0, "parallel",
   "This controller's process id in the multi-host cluster")
# -- runner ------------------------------------------------------------------
_k("LLMC_STALL_GRACE", "float", 5.0, "runner",
   "Grace past the deadline before a stalled panel worker is abandoned")
# -- faults ------------------------------------------------------------------
_k("LLMC_FAULTS", "str", "", "faults",
   "Deterministic fault-injection plan spec (see faults/plan.py grammar)")
_k("LLMC_FAULTS_SEED", "int", 0, "faults",
   "Seed for the fault plan's probabilistic qualifiers")
# -- serve -------------------------------------------------------------------
_k("LLMC_JUDGE_OVERLAP", "bool", False, "serve",
   "1 prefills the judge prompt incrementally as panel answers arrive")
_k("LLMC_CONFIG", "str", "", "cli",
   "Config-file path override (=0 disables config loading)")
_k("LLMC_EVENTS", "str", "", "obs",
   "1 enables the run telemetry recorder (same as --events)")
_k("LLMC_EVENTS_MAX", "int", 200_000, "obs",
   "Bound on recorded telemetry events")
# -- pressure ----------------------------------------------------------------
_k("LLMC_PRESSURE", "bool", True, "pressure",
   "0 disables the pressure governor's overload ladder")
_k("LLMC_PRESSURE_POLL_S", "float", 0.5, "pressure",
   "Governor sample cadence in seconds")
_k("LLMC_PRESSURE_HIGH_WATER", "float", 0.75, "pressure",
   "Hysteresis high-water pressure threshold")
_k("LLMC_PRESSURE_LOW_WATER", "float", 0.35, "pressure",
   "Hysteresis low-water pressure threshold")
_k("LLMC_PRESSURE_UP_PATIENCE", "int", 2, "pressure",
   "Consecutive high samples before the ladder escalates")
_k("LLMC_PRESSURE_DOWN_PATIENCE", "int", 4, "pressure",
   "Consecutive low samples before the ladder relaxes")
_k("LLMC_PRESSURE_EVICT_TARGET", "float", 0.7, "pressure",
   "Cold-KV eviction target occupancy for the evict rung")
_k("LLMC_PRESSURE_JUDGE_FALLBACK", "str", "", "pressure",
   "Brownout judge tier downgrade map (judge=tier,... or one tier)")
_k("LLMC_PRESSURE_BROWNOUT_MAX_NEW", "int", 256, "pressure",
   "Brownout output-token clamp")
_k("LLMC_PRESSURE_SHED_CLASS", "int", 2, "pressure",
   "First priority class the shed rung rejects outright (default low)")
_k("LLMC_PRESSURE_AGE_S", "float", 30.0, "pressure",
   "Admission aging: one class promotion per N seconds queued")
_k("LLMC_PRESSURE_RETRY_SPREAD", "float", 0.5, "pressure",
   "Per-class Retry-After scale step")
_k("LLMC_PRESSURE_DEADLINE_HIGH_S", "float", 15.0, "pressure",
   "Timeout at/below this derives priority high")
_k("LLMC_PRESSURE_DEADLINE_LOW_S", "float", 600.0, "pressure",
   "Timeout at/above this derives priority low")
_k("LLMC_PRESSURE_PREEMPT", "bool", True, "pressure",
   "0 disables priority preemption in the continuous batcher")
# -- fleet -------------------------------------------------------------------
_k("LLMC_FLEET_POLL_S", "float", 2.0, "fleet",
   "Replica health-poll cadence in seconds")
_k("LLMC_FLEET_SUSPECT_AFTER", "int", 1, "fleet",
   "Missed polls before a replica is suspect")
_k("LLMC_FLEET_DEAD_AFTER", "int", 3, "fleet",
   "Missed polls before a replica is dead")
_k("LLMC_FLEET_REVIVE_AFTER", "int", 2, "fleet",
   "Healthy polls before a dead replica revives")
_k("LLMC_FLEET_SATURATION", "float", 0.85, "fleet",
   "load_score at/above which placement overflows")
_k("LLMC_FLEET_SPILLOVER_MIN_TIMEOUT_S", "float", 10.0, "fleet",
   "Minimum request timeout eligible for remote-API spillover")
_k("LLMC_FLEET_SPILLOVER_MAX_PRIORITY", "int", 1, "fleet",
   "Worst priority class eligible for remote-API spillover")
_k("LLMC_FLEET_HEARTBEAT_S", "float", 2.0, "fleet",
   "Gateway announce cadence in seconds")
_k("LLMC_FLEET_ANNOUNCE", "str", "", "fleet",
   "Router URL to announce this gateway to (env form of serve --announce)")
# -- elastic -----------------------------------------------------------------
_k("LLMC_ELASTIC", "bool", False, "elastic",
   "1 starts the elastic controller's tick thread with the router")
_k("LLMC_ELASTIC_TICK_S", "float", 2.0, "elastic",
   "Elastic controller sample cadence in seconds")
_k("LLMC_ELASTIC_HIGH_WATER", "float", 0.8, "elastic",
   "Fleet load at/above which scale-up pressure accumulates")
_k("LLMC_ELASTIC_LOW_WATER", "float", 0.2, "elastic",
   "Fleet load at/below which scale-down pressure accumulates")
_k("LLMC_ELASTIC_UP_PATIENCE", "int", 3, "elastic",
   "Consecutive high samples before the controller scales up")
_k("LLMC_ELASTIC_DOWN_PATIENCE", "int", 6, "elastic",
   "Consecutive idle samples before the controller scales down")
_k("LLMC_ELASTIC_MIN_REPLICAS", "int", 1, "elastic",
   "Floor the controller never scales the serving pool below")
_k("LLMC_ELASTIC_MAX_REPLICAS", "int", 8, "elastic",
   "Ceiling the controller never scales the serving pool above")
_k("LLMC_ELASTIC_MIGRATE_TIMEOUT_S", "float", 10.0, "elastic",
   "Source's bounded wait for the destination to accept one migrated "
   "stream before finishing it locally")
_k("LLMC_ELASTIC_WARM_S", "float", 0.0, "elastic",
   "Seconds a joining gateway stays not-placeable before serving")
# -- flywheel ----------------------------------------------------------------
_k("LLMC_DATA_DIR", "str", "data", "flywheel",
   "Run-dir root the corpus scanner walks (and serving persists into)")
_k("LLMC_DISTILL_LR", "float", 1e-4, "flywheel",
   "Distillation AdamW learning rate")
_k("LLMC_DISTILL_STEPS", "int", 20, "flywheel",
   "Distillation training steps per `llm-consensus distill` invocation")
_k("LLMC_DISTILL_BATCH", "int", 2, "flywheel",
   "Distillation global batch size (split across the dp mesh axis)")
_k("LLMC_DISTILL_SEQ", "int", 128, "flywheel",
   "Distillation example sequence length (pairs are padded/truncated)")
_k("LLMC_DISTILL_TEMP", "float", 2.0, "flywheel",
   "Soft-target KL temperature for teacher-logit distillation")
_k("LLMC_DISTILL_ALPHA", "float", 0.5, "flywheel",
   "Mix weight: alpha*KL(teacher) + (1-alpha)*CE(verdict tokens)")
_k("LLMC_DISTILL_HOLDOUT", "float", 0.2, "flywheel",
   "Holdout fraction of the deduplicated corpus (deterministic split)")
_k("LLMC_DISTILL_CKPT_EVERY", "int", 0, "flywheel",
   "Checkpoint cadence in steps (0: only at the end of the run)")
_k("LLMC_CANARY_FRACTION", "float", 0.0, "flywheel",
   "Router traffic fraction steered to canary-version replicas (0 off)")
_k("LLMC_CANARY_WINDOWS", "int", 3, "flywheel",
   "Consecutive regressing comparisons before the canary rolls back")
_k("LLMC_CANARY_LATENCY_TOL", "float", 1.5, "flywheel",
   "Canary p99 latency ratio vs baseline that counts as regressing")
_k("LLMC_CANARY_MIN_SAMPLES", "int", 4, "flywheel",
   "Minimum samples per version before a canary comparison counts")
_k("LLMC_SWAP_WAIT_S", "float", 30.0, "flywheel",
   "Engine.swap_weights bounded wait for pinned streams to drain when "
   "called with wait=True (0: never wait)")
# -- http --------------------------------------------------------------------
_k("LLMC_HTTP_RETRIES", "int", 2, "http",
   "Remote-provider retry attempts")
_k("LLMC_HTTP_BACKOFF", "float", 0.5, "http",
   "Remote-provider backoff base seconds (doubles per attempt)")
# -- obs ---------------------------------------------------------------------
_k("LLMC_LIVE", "bool", True, "obs",
   "0 disables the continuous metrics plane behind GET /metricsz")
_k("LLMC_LIVE_WINDOW_S", "float", 10.0, "obs",
   "Live-metrics window length in seconds")
_k("LLMC_LIVE_WINDOWS", "int", 30, "obs",
   "Live-metrics recent-window ring depth")
_k("LLMC_SLO_TTFT_P99_S", "float", 0.0, "obs",
   "SLO burn trigger: p99 TTFT threshold (0 disables)")
_k("LLMC_SLO_WINDOWS", "int", 3, "obs",
   "Consecutive burning windows before the SLO dump fires")
_k("LLMC_ATTRIB", "str", "", "obs",
   "0 disables chip-time attribution; unset follows LLMC_LIVE; 1 forces on")
_k("LLMC_ATTRIB_WARMUP_S", "float", 120.0, "obs",
   "Retrace-sentinel warmup window in seconds")
_k("LLMC_ATTRIB_HBM_HIGH", "float", 0.92, "obs",
   "HBM watermark high-water fraction")
_k("LLMC_BLACKBOX", "bool", True, "obs",
   "0 disables the always-on flight recorder")
_k("LLMC_BLACKBOX_EVENTS", "int", 4096, "obs",
   "Flight-recorder span ring capacity")
_k("LLMC_BLACKBOX_DIR", "str", "", "obs",
   "Flight-recorder dump directory (default data/_artifacts/blackbox/)")
_k("LLMC_BLACKBOX_MIN_INTERVAL_S", "float", 30.0, "obs",
   "Minimum seconds between flight-recorder dumps")
_k("LLMC_PROFILE", "bool", True, "obs",
   "0 disables the on-demand deep profiler behind POST /debugz/profile")
_k("LLMC_PROFILE_DIR", "str", "", "obs",
   "Profiler artifact directory (default data/_artifacts/profiles/)")
_k("LLMC_PROFILE_MAX_S", "float", 10.0, "obs",
   "Hard cap on one profiling window's duration in seconds")
_k("LLMC_PROFILE_MIN_INTERVAL_S", "float", 60.0, "obs",
   "Minimum seconds between profiling windows (429 inside the window)")
# -- recovery ----------------------------------------------------------------
_k("LLMC_JOURNAL", "str", "", "recovery",
   "1 enables the per-stream write-ahead journal; =<dir> mirrors to .wal")
_k("LLMC_ENGINE_HEARTBEAT_S", "float", 0.0, "recovery",
   "Supervisor wedge-watchdog heartbeat staleness bound (0 disables)")
_k("LLMC_ENGINE_RESTARTS", "int", 3, "recovery",
   "Replay cap per stream across engine restarts")
# -- integrity ---------------------------------------------------------------
_k("LLMC_INTEGRITY", "bool", False, "integrity",
   "1 enables the end-to-end integrity plane (digests, WAL CRC verify, "
   "finite-logit sentinel, quarantine)")
_k("LLMC_INTEGRITY_SAMPLE", "float", 0.05, "integrity",
   "Fraction of radix-gather KV reads verified against their publish "
   "digests (deterministic every-Nth sampling)")
_k("LLMC_INTEGRITY_QUARANTINE_AFTER", "int", 3, "integrity",
   "Integrity failures on one replica before it walks to the "
   "quarantined lifecycle state (0 keeps detection without quarantine)")
_k("LLMC_INTEGRITY_PROBE_N", "int", 3, "integrity",
   "Consecutive clean probe windows before a quarantined replica "
   "returns to serving")
# -- analysis ----------------------------------------------------------------
_k("LLMC_SANITIZE", "bool", False, "analysis",
   "1 instruments project locks: lock-order cycle + guarded-state "
   "sanitizer (analysis/sanitizer.py)")
_k("LLMC_SCHED", "str", "", "analysis",
   "Deterministic schedule exploration: an integer seeds the cooperative "
   "scheduler's random walk; replay:<token> replays one recorded "
   "interleaving (analysis/schedule.py)")
_k("LLMC_SCHED_PREEMPTS", "int", 4, "analysis",
   "Preemption bound per explored schedule (free context switches at "
   "blocking points are never charged)")
_k("LLMC_SCHED_STEPS", "int", 20000, "analysis",
   "Scheduling-step safety budget per explored schedule")
_k("LLMC_SCHED_RACE", "bool", True, "analysis",
   "0 disables the vector-clock happens-before race detector during "
   "schedule exploration (analysis/race.py)")


_MISSING = object()
_FALSY = ("0", "false", "no", "off")


def _knob(name: str) -> Knob:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"undeclared knob {name!r}: every LLMC_* env read must be "
            "declared in llm_consensus_tpu/utils/knobs.py"
        ) from None


def raw(name: str) -> Optional[str]:
    """The verbatim env value (``None`` when unset). Declared-checked;
    for call sites whose parse really is bespoke (e.g. LLMC_ATTRIB's
    three-state follows-LLMC_LIVE logic)."""
    _knob(name)
    return os.environ.get(name)


def is_set(name: str) -> bool:
    """True when the knob has a non-empty value in the environment."""
    _knob(name)
    return bool((os.environ.get(name) or "").strip())


def get_str(name: str, default=_MISSING) -> str:
    """The stripped string value, or the declared default when unset or
    empty."""
    k = _knob(name)
    if default is _MISSING:
        default = k.default
    v = (os.environ.get(name) or "").strip()
    return v if v else default


def get_bool(name: str, default=_MISSING) -> bool:
    """Unset/empty → default; ``0/false/no/off`` (any case) → False;
    anything else → True."""
    k = _knob(name)
    if default is _MISSING:
        default = k.default
    v = (os.environ.get(name) or "").strip()
    if not v:
        return bool(default)
    return v.lower() not in _FALSY


def get_int(name: str, default=_MISSING) -> Optional[int]:
    """Unset/empty/unparsable → default (declared unless overridden)."""
    k = _knob(name)
    if default is _MISSING:
        default = k.default
    v = (os.environ.get(name) or "").strip()
    if not v:
        return default
    try:
        return int(v)
    except ValueError:
        return default


def get_float(name: str, default=_MISSING) -> Optional[float]:
    """Unset/empty/unparsable → default (declared unless overridden)."""
    k = _knob(name)
    if default is _MISSING:
        default = k.default
    v = (os.environ.get(name) or "").strip()
    if not v:
        return default
    try:
        return float(v)
    except ValueError:
        return default


__all__ = [
    "Knob", "REGISTRY", "raw", "is_set",
    "get_str", "get_bool", "get_int", "get_float",
]
