"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports. Copied from ``llm_consensus_tpu/utils/flops.py``
(PR 21); source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip). An unknown kind is an
error, never a default: a roofline share against a guessed peak is noise.
"""

from __future__ import annotations

_V5E = {
    "bf16_flops_per_s": 197e12,
    "int8_ops_per_s": 393e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "source": "Google Cloud documentation, TPU v5e",
}
# One v5e chip reports "TPU v5 lite".
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


class UnknownDevice(KeyError):
    pass


def peaks_of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
