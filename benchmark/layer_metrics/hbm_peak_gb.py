"""Device: peak bytes in use on the fullest chip (/statsz device.memory,
which is device.memory_stats()), in GB (1e9 bytes)."""


def peak_bytes(stats: dict):
    mem = (stats.get("device") or {}).get("memory") or {}
    peaks = [m.get("peak_bytes_in_use") for m in mem.values() if m.get("peak_bytes_in_use")]
    return max(peaks) if peaks else None


def read(ctx):
    peak = peak_bytes(ctx["stats_after"])
    return None if peak is None else peak / 1e9
