"""Test configuration.

Tests run on the CPU, asked for by name: a virtual 8-device CPU platform
exercises all sharding / parallelism logic, and the Pallas kernels run
interpreted. (The chip's compiler still checks the kernels at real
widths, without a chip: tests/test_tpu_compile.py.) XLA_FLAGS must be set
before the first backend initialization.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import tempfile

# Persistent XLA compilation cache: the suite's wall time is dominated
# by recompiles of tiny models; caching compiled programs across runs
# cuts repeat invocations ~3× (measured: 21s → 6.6s on a subset).
# Placed from outside the program, as a deployment would
# (JAX_COMPILATION_CACHE_DIR — the tpu provider then sets nothing), in a
# directory of its own so CPU test programs never land in a serving
# cache. Per-user path (shared /tmp on CI boxes), keyed by a host-CPU
# fingerprint as well as uid: XLA:CPU caches AOT executables compiled
# for the build host's exact CPU features, and loading one on a
# different host (container migrated between machines, shared /tmp)
# warns "could lead to execution errors such as SIGILL" — and did: a
# stale cache SEGFAULTED the suite inside
# compilation_cache.get_executable_and_time. A fingerprint change gets
# a fresh dir instead of a crash.
def _cpu_fingerprint() -> str:
    import hashlib

    try:
        with open("/proc/cpuinfo") as f:
            flags = next(
                (ln for ln in f if ln.startswith("flags")), "unknown"
            )
    except OSError:
        flags = "unknown"
    return hashlib.sha256(flags.encode()).hexdigest()[:12]


os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(
        tempfile.gettempdir(),
        f"llmc-test-xla-cache-{os.getuid()}-{_cpu_fingerprint()}",
    ),
)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.3")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# JAX's file cache writes an entry in place (``Path.write_bytes``), and its
# reader takes whatever bytes the file holds: under xdist a worker can read
# an entry another worker is half-way through writing, and a whole run lost
# a worker to "Fatal Python error: Segmentation fault" inside
# compilation_cache.get_executable_and_time (four whole runs of four, PR 48,
# each in another test; every entry read whole afterwards). Write beside the
# entry and rename: a reader sees all of an entry or none of it.
from jax._src import lru_cache as _lru  # noqa: E402

_put_in_place = _lru.LRUCache.put


def _put_whole(self, key: str, val: bytes) -> None:
    if self.eviction_enabled or not key:
        return _put_in_place(self, key, val)
    path = self.path / f"{key}{_lru._CACHE_SUFFIX}"
    if not path.exists():
        beside = path.with_name(f".{path.name}.{os.getpid()}")
        beside.write_bytes(val)
        os.replace(beside, path)


_lru.LRUCache.put = _put_whole


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running (checkpoint/e2e) tests")
    config.addinivalue_line(
        "markers",
        "faults: deterministic fault-injection / degraded-mode tests "
        "(the CI chaos lane runs exactly this marker)",
    )
    config.addinivalue_line(
        "markers",
        "schedules(n): run the test body under n deterministically "
        "explored thread schedules (analysis/schedule.py); a failing "
        "schedule raises with its LLMC_SCHED=replay:<token> repro",
    )


def pytest_sessionstart(session):
    devices = jax.devices()
    assert devices[0].platform == "cpu", f"tests must run on CPU, got {devices}"
    assert len(devices) == 8, f"expected 8 virtual CPU devices, got {len(devices)}"


import pytest as _pytest


@_pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    """``@pytest.mark.schedules(n)``: replace the single call with n
    deterministically explored schedules (seeded ``0..n-1``, rebased by
    ``LLMC_SCHED=<seed>``; ``LLMC_SCHED=replay:<token>`` runs exactly
    one interleaving). Returning True suppresses the default call."""
    m = pyfuncitem.get_closest_marker("schedules")
    if m is None:
        return None
    from llm_consensus_tpu.analysis import schedule

    n = int(m.args[0]) if m.args else 16
    testfn = pyfuncitem.obj
    names = getattr(pyfuncitem, "_fixtureinfo").argnames
    kwargs = {name: pyfuncitem.funcargs[name] for name in names}
    schedule.check(lambda: testfn(**kwargs), schedules=n)
    return True


@_pytest.fixture(autouse=True)
def _no_ambient_config(monkeypatch):
    """Hermetic CLI tests: a developer's ~/.llm-consensus.json must never
    leak into test runs. Config-file tests set LLMC_CONFIG explicitly."""
    monkeypatch.setenv("LLMC_CONFIG", "0")
