"""Per-program roofline attribution: WHY is this family slow?

PR 11's chip-time ledger answers *where* device seconds go (family
walls); this module answers *why* each family runs at the rate it does.
At dispatch time every instrumented jitted program captures — once per
``(family, bucket-shape)`` key — the compiler's own static cost model
(``jax.stages.Lowered.cost_analysis()``: FLOPs, bytes accessed, output
bytes), and every subsequent dispatch just bumps counters. Joining the
accumulated static costs with the measured per-family walls the
attribution ledger already books yields live achieved-FLOPs/s,
achieved-bytes/s, arithmetic intensity (FLOPs/byte), and a
compute-vs-memory-bound verdict against the device's balance point
(ridge = peak FLOPs / peak HBM bytes/s where the chip is known,
``LLMC_ROOFLINE_RIDGE`` otherwise) — the machine-checked form of the
"judge decode MFU 0.0075 because decode is bandwidth-bound" diagnosis.

Capture deliberately uses the LOWERED (pre-optimization) cost analysis:

  * ``Lowered.cost_analysis()`` never triggers an XLA backend compile,
    so capture cannot fire the retrace sentinel or pay a second
    multi-second compile — measured: trace+lower only;
  * the unoptimized HLO counts operand bytes arithmetically (operands +
    outputs), which is the roofline convention; the post-fusion
    ``Compiled`` numbers change meaning across backends.

XLA counts a ``while``/``scan`` BODY once regardless of trip count, so
dispatch sites whose program loops on device (the decode chunk's
``lax.scan``, the chunked-prefill ``fori_loop``) pass the host-known
``steps`` multiplier per dispatch; everything else defaults to 1.

Cross-check: engines register their analytic per-token costs
(:func:`note_modeled`, utils/flops — the same model behind the
modeled-MFU gauges), and :meth:`RooflineLedger.snapshot` compares the
cost-analysis FLOPs-per-token against the modeled range per family
(``LLMC_ROOFLINE_TOL``) — the two ledgers cannot silently diverge.

Resolution follows the attrib pattern: ``LLMC_ROOFLINE=0`` disables,
``=1`` forces on, unset follows chip-time attribution (the walls this
module joins against). Hot-path cost when enabled: one dict lookup +
a few counter bumps per *dispatch* (not per token); when disabled, one
module-global None check.
"""

from __future__ import annotations

import threading
from functools import wraps
from typing import Callable, Optional

from llm_consensus_tpu.analysis import sanitizer
from llm_consensus_tpu.utils import knobs

# Fallback balance point (FLOPs per byte) when the device peaks are
# unknown (CPU dev runs): low enough that a batched prefill (hundreds
# of tokens per weight read) lands compute-bound, high enough that a
# small-batch decode chunk (a few FLOPs per weight byte) lands
# memory-bound — the split every real accelerator in utils/flops.py
# also produces (their ridges sit at 140-560).
DEFAULT_RIDGE = 32.0
# Modeled-vs-cost-analysis tolerance: the ratio of XLA-counted to
# analytic FLOPs/token must sit in [1/tol, tol]. The analytic 2·N rule
# and XLA's dot accounting agree to well within 2x; 4.0 leaves room for
# elementwise/softmax traffic on tiny dev configs.
DEFAULT_TOL = 4.0

_SENTINEL_KEY = ()


class RooflineLedger:
    """Process-wide static-cost x measured-wall roofline accounting.

    Thread-safe: one lock serializes counter writes; the one-time cost
    capture per key runs OUTSIDE the lock (tracing + lowering a big
    model takes real time) behind an in-progress marker so concurrent
    first dispatches of one bucket capture once. Telemetry never
    raises: a failed capture is cached as a zero-cost record and the
    family still counts dispatches.
    """

    def __init__(self, ridge: Optional[float] = None,
                 tol: Optional[float] = None):
        if ridge is None:
            ridge = knobs.get_float("LLMC_ROOFLINE_RIDGE", 0.0)
        if tol is None:
            tol = knobs.get_float("LLMC_ROOFLINE_TOL", DEFAULT_TOL)
        # A positive ridge pins the balance point outright (knob or
        # constructor); 0 defers to device peaks with the documented
        # fallback off-accelerator.
        self.ridge_override = ridge if ridge and ridge > 0 else None
        self.fallback_ridge = DEFAULT_RIDGE
        self.tol = max(1.0, tol)
        self._lock = sanitizer.make_lock("obs.roofline")
        # (family, key) -> program record. "raw_*" are the per-dispatch
        # static costs at steps=1; totals accumulate raw x steps.
        self._programs: dict = {}
        self._capturing: set = set()
        # Dispatches that landed while their key's capture was in
        # flight: [dispatches, steps, tokens], merged when it finishes.
        self._deferred: dict = {}
        # Per-family extras the compiler cannot see: cross-mesh
        # device_put transfer bytes (the kv_handoff wall's traffic).
        self._transfer_bytes: dict = {}
        # family -> (min, max) analytic per-token costs registered by
        # engines (utils/flops) — the cross-check's modeled side.
        self._modeled_fpt: dict = {}
        self._modeled_bpt: dict = {}
        self._peaks_resolved = False
        self._peak_flops: Optional[float] = None
        self._peak_bw: Optional[float] = None
        self._n_devices = 1

    # -- capture + dispatch ---------------------------------------------------

    def dispatch(self, family: str, key: tuple, fn, args, kwargs,
                 tokens: int = 0, steps: int = 1) -> None:
        """Book one dispatch of ``fn`` under ``(family, key)``; capture
        its static cost on first sight. Never raises."""
        pkey = (family, key)
        with self._lock:
            rec = self._programs.get(pkey)
            if rec is not None:
                rec["dispatches"] += 1
                rec["steps"] += steps
                rec["tokens"] += tokens
                return
            if pkey in self._capturing:
                # A concurrent first dispatch is lowering this bucket
                # right now; book the counts aside — the capture merges
                # them when it lands.
                d = self._deferred.setdefault(pkey, [0, 0, 0])
                d[0] += 1
                d[1] += steps
                d[2] += tokens
                return
            self._capturing.add(pkey)
        raw = self._capture(fn, args, kwargs)
        with self._lock:
            self._capturing.discard(pkey)
            deferred = self._deferred.pop(pkey, (0, 0, 0))
            rec = self._programs.setdefault(pkey, {
                "dispatches": 0, "steps": 0, "tokens": 0, **raw,
            })
            rec["dispatches"] += 1 + deferred[0]
            rec["steps"] += steps + deferred[1]
            rec["tokens"] += tokens + deferred[2]

    @staticmethod
    def _capture(fn, args, kwargs) -> dict:
        """One program's static costs via the lowered (pre-optimization)
        cost analysis; zeros with source="none" when the backend offers
        nothing."""
        try:
            ca = fn.lower(*args, **kwargs).cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            flops = float(ca.get("flops") or 0.0)
            bytes_ = float(ca.get("bytes accessed") or 0.0)
            out_b = float(ca.get("bytes accessedout{}") or 0.0)
            if flops <= 0.0 and bytes_ <= 0.0:
                return {"raw_flops": 0.0, "raw_bytes": 0.0,
                        "raw_out_bytes": 0.0, "source": "none"}
            return {"raw_flops": flops, "raw_bytes": bytes_,
                    "raw_out_bytes": out_b, "source": "xla"}
        except Exception:  # noqa: BLE001 — telemetry never raises
            return {"raw_flops": 0.0, "raw_bytes": 0.0,
                    "raw_out_bytes": 0.0, "source": "none"}

    def note_transfer(self, family: str, nbytes: float) -> None:
        """Book raw transfer bytes the compiler cannot see (the
        cross-mesh handoff's device_put)."""
        if nbytes <= 0:
            return
        with self._lock:
            self._transfer_bytes[family] = (
                self._transfer_bytes.get(family, 0.0) + float(nbytes)
            )

    def note_modeled(self, family: str, flops_per_token: float,
                     bytes_per_token: Optional[float] = None) -> None:
        """Register an engine's analytic per-token costs for ``family``
        (the modeled-MFU model, utils/flops) — the cross-check baseline.
        Multiple engines widen the accepted range."""
        with self._lock:
            if flops_per_token and flops_per_token > 0:
                lo, hi = self._modeled_fpt.get(
                    family, (flops_per_token, flops_per_token)
                )
                self._modeled_fpt[family] = (
                    min(lo, flops_per_token), max(hi, flops_per_token)
                )
            if bytes_per_token and bytes_per_token > 0:
                lo, hi = self._modeled_bpt.get(
                    family, (bytes_per_token, bytes_per_token)
                )
                self._modeled_bpt[family] = (
                    min(lo, bytes_per_token), max(hi, bytes_per_token)
                )

    # -- device peaks ---------------------------------------------------------

    def _peaks(self) -> "tuple[Optional[float], Optional[float], int]":
        """(peak FLOPs/s, peak HBM bytes/s, device count) per chip from
        the published-spec tables; Nones on a CPU. A TPU the tables do
        not list raises (utils/flops.UnknownDeviceError) — it never
        degrades to the default ridge. Resolved once; jax import stays
        off the dispatch path."""
        if not self._peaks_resolved:
            import jax

            from llm_consensus_tpu.utils import flops as flops_mod

            devices = jax.devices()
            kind = devices[0].device_kind
            peak_f = flops_mod.device_peak_flops(kind)
            peak_b = flops_mod.device_peak_hbm_bw(kind)
            with self._lock:
                self._peak_flops, self._peak_bw = peak_f, peak_b
                self._n_devices = max(1, len(devices))
                self._peaks_resolved = True
        return self._peak_flops, self._peak_bw, self._n_devices

    def ridge(self) -> "tuple[float, str]":
        """(FLOPs-per-byte balance point, its provenance): the chip's
        peak ratio on a TPU, the fallback knob on a CPU."""
        if self.ridge_override is not None:
            return self.ridge_override, "override"
        peak_f, peak_b, _ = self._peaks()
        if peak_f and peak_b:
            return peak_f / peak_b, "device"
        return self.fallback_ridge, "default"

    # -- reading --------------------------------------------------------------

    def activity(self) -> int:
        with self._lock:
            return sum(r["dispatches"] for r in self._programs.values())

    def snapshot(self, device_s: Optional[dict] = None) -> dict:
        """The /statsz ``roofline`` block: per-family static costs
        joined with measured walls, verdicts against the ridge, and the
        modeled-vs-cost-analysis cross-check. ``device_s`` is the attrib
        ledger's per-family wall dict; omitted, it is read from the
        installed ledger."""
        if device_s is None:
            device_s = self._attrib_walls()
        ridge, ridge_source = self.ridge()
        peak_f, peak_b, n_dev = self._peaks()
        with self._lock:
            programs = {
                k: dict(v) for k, v in self._programs.items()
            }
            transfer = dict(self._transfer_bytes)
            modeled_fpt = dict(self._modeled_fpt)
            modeled_bpt = dict(self._modeled_bpt)
        fams: dict = {}
        for (family, key), rec in sorted(
            programs.items(), key=lambda kv: (kv[0][0], str(kv[0][1]))
        ):
            f = fams.setdefault(family, {
                "programs": 0, "dispatches": 0, "tokens": 0,
                "flops": 0.0, "bytes": 0.0, "out_bytes": 0.0,
                "sources": set(),
            })
            f["programs"] += 1
            f["dispatches"] += rec["dispatches"]
            f["tokens"] += rec["tokens"]
            f["flops"] += rec["raw_flops"] * rec["steps"]
            f["bytes"] += rec["raw_bytes"] * rec["steps"]
            f["out_bytes"] += rec["raw_out_bytes"] * rec["steps"]
            f["sources"].add(rec["source"])
        for family, nbytes in transfer.items():
            f = fams.setdefault(family, {
                "programs": 0, "dispatches": 0, "tokens": 0,
                "flops": 0.0, "bytes": 0.0, "out_bytes": 0.0,
                "sources": set(),
            })
            f["bytes"] += nbytes
            f["sources"].add("transfer")
        out_families: dict = {}
        covered_wall = 0.0
        for family, f in fams.items():
            wall = float((device_s or {}).get(family, 0.0))
            if f["dispatches"] > 0 and wall > 0:
                covered_wall += wall
            intensity = f["flops"] / f["bytes"] if f["bytes"] > 0 else None
            verdict = None
            if intensity is not None and (f["flops"] > 0 or f["bytes"] > 0):
                verdict = (
                    "memory_bound" if intensity < ridge else "compute_bound"
                )
            entry = {
                "programs": f["programs"],
                "dispatches": f["dispatches"],
                "tokens": f["tokens"],
                "flops": f["flops"],
                "bytes": f["bytes"],
                "out_bytes": f["out_bytes"],
                "wall_s": round(wall, 4),
                "achieved_flops_per_s": (
                    f["flops"] / wall if wall > 0 else None
                ),
                "achieved_bytes_per_s": (
                    f["bytes"] / wall if wall > 0 else None
                ),
                "intensity": intensity,
                "verdict": verdict,
                "source": "+".join(sorted(f["sources"])) or "none",
            }
            if peak_f and wall > 0:
                entry["mfu_vs_peak"] = f["flops"] / wall / (peak_f * n_dev)
            if peak_b and wall > 0:
                entry["mbu_vs_peak"] = f["bytes"] / wall / (peak_b * n_dev)
            out_families[family] = entry
        total_wall = sum(
            float(v) for v in (device_s or {}).values()
        )
        crosscheck: dict = {}
        for family, (lo, hi) in sorted(modeled_fpt.items()):
            f = fams.get(family)
            if not f or f["tokens"] <= 0 or f["flops"] <= 0:
                continue
            measured = f["flops"] / f["tokens"]
            ratio = measured / hi if measured > hi else (
                measured / lo if measured < lo else 1.0
            )
            entry = {
                "flops_per_token_xla": measured,
                "flops_per_token_modeled": [lo, hi],
                "ratio": round(ratio, 4),
                "ok": (1.0 / self.tol) <= ratio <= self.tol,
            }
            b = modeled_bpt.get(family)
            if b is not None and f["bytes"] > 0:
                entry["bytes_per_token_xla"] = f["bytes"] / f["tokens"]
                entry["bytes_per_token_modeled"] = list(b)
            crosscheck[family] = entry
        return {
            "ridge_flops_per_byte": round(ridge, 4),
            "ridge_source": ridge_source,
            "peak_flops_per_s": peak_f,
            "peak_bytes_per_s": peak_b,
            "n_devices": n_dev,
            "families": {
                k: _round_floats(v) for k, v in sorted(out_families.items())
            },
            "coverage": {
                "covered_wall_s": round(covered_wall, 4),
                "attrib_wall_s": round(total_wall, 4),
                "fraction": (
                    round(covered_wall / total_wall, 4)
                    if total_wall > 0 else None
                ),
            },
            "crosscheck": {
                k: _round_floats(v) for k, v in crosscheck.items()
            },
            "tol": self.tol,
        }

    def prom_families(self, device_s: Optional[dict] = None) -> dict:
        """The ``llmc_roofline_*`` families /metricsz renders. FLOPs /
        bytes / dispatch totals are COUNTERS (monotone, so the router's
        fleet merge sums them exactly like the attrib walls they join
        against); per-replica ratios (intensity, verdicts) deliberately
        stay off this surface — a gauge sum across replicas would be
        nonsense — scrapers derive fleet ratios from the counters, and
        the verdicts live on /statsz."""
        if device_s is None:
            device_s = self._attrib_walls()
        snap = self.snapshot(device_s)
        flops_samples = []
        bytes_samples = []
        disp_samples = []
        tok_samples = []
        for family, f in snap["families"].items():
            flops_samples.append(({"family": family}, f["flops"]))
            bytes_samples.append(({"family": family}, f["bytes"]))
            disp_samples.append(({"family": family}, f["dispatches"]))
            if f["tokens"]:
                tok_samples.append(({"family": family}, f["tokens"]))
        out = {
            "roofline_flops_total": {
                "type": "counter", "samples": flops_samples,
            },
            "roofline_bytes_total": {
                "type": "counter", "samples": bytes_samples,
            },
            "roofline_dispatches_total": {
                "type": "counter", "samples": disp_samples,
            },
            "roofline_tokens_total": {
                "type": "counter", "samples": tok_samples,
            },
            "roofline_ridge_flops_per_byte": {
                "type": "gauge",
                "samples": [
                    ({"source": snap["ridge_source"]},
                     snap["ridge_flops_per_byte"]),
                ],
            },
        }
        return out

    @staticmethod
    def _attrib_walls() -> dict:
        from llm_consensus_tpu.obs import attrib as attrib_mod

        led = attrib_mod.ledger()
        if led is None:
            return {}
        try:
            return led.snapshot()["device_s"]
        except Exception:  # noqa: BLE001
            return {}

    def counter_track(self) -> "list[tuple[str, float]]":
        """(counter name, value) pairs for the exported Perfetto trace's
        roofline counter track (obs/export.py ``ph: "C"`` events)."""
        snap = self.snapshot()
        out = []
        for family, f in snap["families"].items():
            out.append((f"roofline_flops/{family}", f["flops"]))
            out.append((f"roofline_bytes/{family}", f["bytes"]))
        return out


def _round_floats(doc: dict) -> dict:
    out = {}
    for k, v in doc.items():
        if isinstance(v, float):
            out[k] = round(v, 4) if abs(v) < 1e6 else v
        else:
            out[k] = v
    return out


# -- dispatch-site instrumentation -------------------------------------------


def instrument(fn, family: Optional[str] = None,
               key: Optional[Callable] = None,
               tokens: Optional[Callable] = None,
               steps: Optional[Callable] = None):
    """Wrap a jitted ``fn`` so dispatches book into the roofline ledger.

    ``family`` is the fallback program family; the thread's ambient
    attribution tag wins when set (``_copy_blocks`` serves kv_gather AND
    kv_publish, ``_decode_chunk`` serves decode AND draft — the tag at
    the dispatch site is the truth). ``key(args, kwargs)`` returns the
    hashable bucket-shape key (one static-cost capture per distinct
    value); ``tokens(args, kwargs)`` the tokens this dispatch advances
    (cross-check denominators); ``steps(args, kwargs)`` the on-device
    loop trip count XLA's cost analysis counts only once.

    Disabled (ledger None) the wrapper is one None check; the wrapped
    callable is signature- and attribute-transparent (``.lower`` etc.
    delegate to the jitted original).

    What a dispatch books is read before the program runs; the program
    runs; only then is it booked. A program is thus always traced by its
    own call, at one place, and the capture's ``lower`` finds that
    trace. Booked first, whichever pool thread met a ``(family, key)``
    first traced ITS model's program under ``_capture`` and the other
    models' under ``call``; a Pallas kernel's serialized body carries
    the Python frames it was traced under, they are part of the
    persistent compile cache's key, and so each start of a server
    compiled a different handful of its programs anew (found on the
    chip, PR 44: 12, 7 and 2 of 230 programs in the three starts after a
    cold one). ``lower`` reads shapes only, so the arguments the call
    donated still serve it.
    """

    @wraps(fn)
    def call(*args, **kwargs):
        led = ledger()
        if led is None:
            return fn(*args, **kwargs)
        booking = None
        try:
            from llm_consensus_tpu.obs import attrib as attrib_mod

            fam = attrib_mod.current_family() or family or "other"
            k = key(args, kwargs) if key is not None else _SENTINEL_KEY
            n_tok = int(tokens(args, kwargs)) if tokens is not None else 0
            n_steps = int(steps(args, kwargs)) if steps is not None else 1
            booking = (fam, k, n_tok, max(1, n_steps))
        except Exception:  # noqa: BLE001 — telemetry never raises
            pass
        out = fn(*args, **kwargs)
        if booking is not None:
            try:
                fam, k, n_tok, n_steps = booking
                led.dispatch(fam, k, fn, args, kwargs,
                             tokens=n_tok, steps=n_steps)
            except Exception:  # noqa: BLE001
                pass
        return out

    call.__wrapped__ = fn
    for attr in ("lower", "trace", "eval_shape", "clear_cache",
                 "_cache_size"):
        if hasattr(fn, attr):
            setattr(call, attr, getattr(fn, attr))
    return call


def shape_of(x) -> tuple:
    """A cheap hashable bucket key component: the arg's shape, or the
    value itself for plain scalars/statics."""
    shape = getattr(x, "shape", None)
    if shape is not None:
        return tuple(shape)
    return (x,) if isinstance(x, (int, float, bool, str)) else ()


# -- process-wide resolution (the faults/obs binding pattern) -----------------

_lock = sanitizer.make_lock("obs.roofline.registry")
_ledger: Optional[RooflineLedger] = None
_resolved = False
_tls = threading.local()


def ledger() -> Optional[RooflineLedger]:
    """The process-wide roofline ledger, or None when disabled.

    ``LLMC_ROOFLINE=0`` disables; ``=1`` forces on; unset, roofline
    follows chip-time attribution (LLMC_ATTRIB / LLMC_LIVE) — the walls
    it joins against come from that ledger, so the two share one
    serving-observability budget."""
    global _ledger, _resolved
    if not _resolved:
        # Re-entrancy guard: resolving consults attrib.ledger(), and a
        # roofline-instrumented dispatch can occur while attrib itself
        # resolves; the nested call sees disabled rather than deadlock.
        if getattr(_tls, "resolving", False):
            return None
        with _lock:
            if not _resolved:
                _tls.resolving = True
                try:
                    env = knobs.get_str("LLMC_ROOFLINE")
                    if env == "0":
                        enabled = False
                    elif env:
                        enabled = True
                    else:
                        from llm_consensus_tpu.obs import attrib as attrib_mod

                        enabled = attrib_mod.ledger() is not None
                    if enabled:
                        _ledger = RooflineLedger()
                    _resolved = True
                finally:
                    _tls.resolving = False
    return _ledger


def install(led: Optional[RooflineLedger]) -> None:
    """Install ``led`` as the process ledger (tests / CLI flags)."""
    global _ledger, _resolved
    with _lock:
        _ledger = led
        _resolved = True


def reset() -> None:
    """Forget the cached ledger; the next :func:`ledger` re-reads env."""
    global _ledger, _resolved
    with _lock:
        _ledger = None
        _resolved = False


__all__ = [
    "DEFAULT_RIDGE", "DEFAULT_TOL", "RooflineLedger", "install",
    "instrument", "ledger", "note_modeled", "reset", "shape_of",
]


def note_modeled(family: str, flops_per_token: float,
                 bytes_per_token: Optional[float] = None) -> None:
    """Module-level convenience: register modeled per-token costs with
    the installed ledger (no-op when roofline is off)."""
    led = ledger()
    if led is not None:
        led.note_modeled(family, flops_per_token, bytes_per_token)
