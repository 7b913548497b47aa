"""Multi-host distribution: process init + hybrid DCN×ICI meshes.

The reference's "distributed backend" is HTTPS to three vendors
(SURVEY.md §5); scaling here means more TPU hosts. Two pieces:

  * :func:`initialize` — idempotent wrapper over
    ``jax.distributed.initialize``. On Cloud TPU pods the coordinator is
    auto-detected; elsewhere it comes from ``LLMC_COORDINATOR`` /
    ``LLMC_NUM_PROCESSES`` / ``LLMC_PROCESS_ID`` or explicit arguments.
    Single-process runs are a no-op, so the CLI can call it
    unconditionally.
  * :func:`hybrid_mesh` — a mesh whose *outer* axes cross hosts (traffic
    rides DCN: data parallelism, rarely pipeline) and whose *inner* axes
    stay within a host's ICI domain (tensor/sequence/expert parallelism,
    which all-reduce activations every layer and would die on DCN
    latency). Axis names are the framework's standard dp/pp/tp/sp/ep, so
    ``parallel.sharding`` / ``train`` consume the result unchanged — the
    scaling-book recipe: pick the mesh, annotate shardings, let XLA place
    the collectives on the right fabric.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh
from llm_consensus_tpu.utils import knobs


def is_initialized() -> bool:
    """True once ``jax.distributed.initialize`` has run in this process."""
    return jax.distributed.is_initialized()


def _pod_env() -> bool:
    """True in a multi-host TPU pod environment where
    ``jax.distributed.initialize()`` can auto-detect every argument.

    ``TPU_WORKER_HOSTNAMES`` counts only with >1 host — single-host images
    set it to one hostname, and auto-init after the backend exists raises.
    """
    if knobs.get_bool("LLMC_DISTRIBUTED"):
        return True
    if os.environ.get("MEGASCALE_COORDINATOR_ADDRESS") or os.environ.get(
        "CLOUD_TPU_CLUSTER_COORDINATOR_ADDRESS"
    ):
        return True
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    return len([h for h in hosts.split(",") if h.strip()]) > 1


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Join (or skip joining) the multi-host cluster; returns True if joined.

    Resolution order: explicit args > ``LLMC_COORDINATOR`` /
    ``LLMC_NUM_PROCESSES`` / ``LLMC_PROCESS_ID`` env > full auto-detection
    when a TPU-pod environment is present (``MEGASCALE_*``/``TPU_WORKER_*``
    markers, or ``LLMC_DISTRIBUTED=1`` to force the attempt). With no
    configuration and no pod markers, this is a no-op so single-host runs
    never block on a coordinator. Must run before the JAX backend
    initializes (before the first ``jax.devices()``/trace/computation).
    """
    if is_initialized():
        return True
    coordinator_address = (
        coordinator_address or knobs.get_str("LLMC_COORDINATOR") or None
    )
    env_n = knobs.raw("LLMC_NUM_PROCESSES")
    env_id = knobs.raw("LLMC_PROCESS_ID")
    if num_processes is None and env_n:
        num_processes = int(env_n)
    if process_id is None and env_id:
        process_id = int(env_id)
    if coordinator_address is None and num_processes is None:
        if not _pod_env():
            return False  # single-host: nothing to join
        jax.distributed.initialize()  # pod: every argument auto-detects
        return True
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return True


def hybrid_mesh(
    dcn_axes: dict[str, int],
    ici_axes: dict[str, int],
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Mesh with ``dcn_axes`` crossing hosts and ``ici_axes`` within them.

    The DCN axes (outer, slowest-varying) partition devices into
    contiguous per-host granules; ICI axes order within a granule. Granule
    membership comes from each device's ``process_index`` when the
    processes differ (real multi-host), else from contiguous equal splits
    (single-process virtual meshes — tests, the driver's dry run).

    Every collective a sharding induces along an ICI axis then stays
    inside one host's ICI domain; only DCN-axis collectives (e.g. the
    per-step gradient all-reduce over ``dp``) cross hosts.
    """
    devices = list(devices if devices is not None else jax.devices())
    n_granules = 1
    for s in dcn_axes.values():
        n_granules *= s
    per_granule = 1
    for s in ici_axes.values():
        per_granule *= s
    if n_granules * per_granule != len(devices):
        raise ValueError(
            f"mesh {dcn_axes}×{ici_axes} needs {n_granules * per_granule} "
            f"devices, have {len(devices)}"
        )

    from llm_consensus_tpu.parallel.mesh import host_groups

    grouped = host_groups(devices)
    if len(grouped) > 1:
        granules = grouped
        if len(granules) != n_granules or any(
            len(g) != per_granule for g in granules
        ):
            raise ValueError(
                f"DCN axes {dcn_axes} want {n_granules} granules of "
                f"{per_granule}; processes provide "
                f"{[len(g) for g in granules]}"
            )
    else:
        granules = [
            devices[i * per_granule : (i + 1) * per_granule]
            for i in range(n_granules)
        ]

    shape = tuple(dcn_axes.values()) + tuple(ici_axes.values())
    dev_array = np.array(granules).reshape(shape)
    return Mesh(dev_array, tuple(dcn_axes.keys()) + tuple(ici_axes.keys()))
