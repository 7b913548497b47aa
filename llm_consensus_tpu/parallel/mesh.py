"""Topology layer: carve `jax.devices()` into per-model mesh slices.

The reference's "topology" is a map from model name to HTTP endpoint
(/root/reference/cmd/llm-consensus/main.go:49-61). Here topology is
physical: a consensus run owns a set of TPU chips and must place N panel
models plus a judge on them. Each model gets its own `jax.sharding.Mesh`
over a disjoint device slice, so panel decode loops never contend for
chips and XLA collectives for one model ride only that model's slice of
the ICI fabric.

Axis conventions (used across parallel/, train/, and __graft_entry__):
  dp — data (batch) parallelism
  pp — pipeline stages (manual, via parallel.pipeline)
  sp — sequence parallelism: ring attention (parallel.ring) for engine
       prefill, activation sharding in the train step
  tp — tensor parallelism (GSPMD, via parallel.sharding); doubles as the
       expert axis for MoE unless a dedicated ``ep`` axis is present
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh

from llm_consensus_tpu.models.config import ModelConfig
from llm_consensus_tpu.utils import knobs


def make_mesh(
    axis_sizes: dict[str, int],
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a Mesh with the given ``{axis_name: size}`` (insertion order).

    Sizes must multiply to ``len(devices)``; pass ``-1`` for at most one
    axis to infer its size (like numpy reshape).
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    sizes = dict(axis_sizes)
    unknown = [a for a, s in sizes.items() if s == -1]
    if len(unknown) > 1:
        raise ValueError(f"at most one axis may be -1, got {unknown}")
    known = 1
    for a, s in sizes.items():
        if s != -1:
            known *= s
    if unknown:
        if n % known:
            raise ValueError(f"{n} devices not divisible by {known}")
        sizes[unknown[0]] = n // known
    total = 1
    for s in sizes.values():
        total *= s
    if total != n:
        raise ValueError(f"mesh {sizes} needs {total} devices, have {n}")
    import numpy as np

    dev_array = np.array(devices).reshape(tuple(sizes.values()))
    return Mesh(dev_array, tuple(sizes.keys()))


def carve_slices(
    devices: Sequence[jax.Device], sizes: Sequence[int]
) -> list[list[jax.Device]]:
    """Split ``devices`` into consecutive disjoint slices of ``sizes``.

    Consecutive device ids are physically adjacent on TPU slices, so each
    carved slice keeps its collectives on neighboring ICI links.
    """
    if sum(sizes) > len(devices):
        raise ValueError(
            f"requested {sum(sizes)} devices across slices, have {len(devices)}"
        )
    out, i = [], 0
    for s in sizes:
        if s <= 0:
            raise ValueError(f"slice size must be positive, got {s}")
        out.append(list(devices[i : i + s]))
        i += s
    return out


def best_tp(cfg: ModelConfig, n_devices: int) -> int:
    """Largest valid TP degree ≤ n_devices for ``cfg``.

    TP shards attention heads and the MLP hidden dim, so it must divide
    ``n_kv_heads`` (the binding constraint under GQA), ``n_heads`` and
    ``d_ff``. Falls back toward 1, which always works. A latent (MLA)
    model is not sharded yet (its engine refuses a mesh with tp > 1): 1.
    Nor is a state-space model (its scan is not split over heads yet), nor
    any other stack of one-part layers (every leaf whole): 1.
    """
    if cfg.is_latent or cfg.has_state or cfg.layer_kinds:
        return 1
    tp = 1
    d = 1
    while d <= n_devices:
        if (
            cfg.n_kv_heads % d == 0
            and cfg.n_heads % d == 0
            and cfg.d_ff % d == 0
            and n_devices % d == 0
        ):
            tp = d
        d *= 2
    return tp


@dataclass
class ModelPlacement:
    """One model pinned to a device slice with a concrete mesh.

    ``prefill_mesh`` is set only under disaggregated serving
    (:func:`split_roles`): ``mesh`` is then the DECODE role's sub-mesh
    (the resident continuous-batching pool) and ``prefill_mesh`` the
    disjoint slice the dedicated prefill workers run on.
    """

    model: str
    cfg: ModelConfig
    mesh: Mesh
    role: str  # "panel" | "judge"
    prefill_mesh: Optional[Mesh] = None

    @property
    def n_devices(self) -> int:
        return self.mesh.devices.size


@dataclass
class MeshPlan:
    """Placement of a whole consensus run onto the available chips."""

    placements: list[ModelPlacement] = field(default_factory=list)

    def for_model(self, model: str) -> Optional[ModelPlacement]:
        for p in self.placements:
            if p.model == model:
                return p
        return None


def host_groups(devices: Sequence[jax.Device]) -> list[list[jax.Device]]:
    """Group devices by host (``process_index``), hosts in index order.

    Single-process virtual meshes (tests, dry runs) yield one group.
    """
    by_proc: dict[int, list[jax.Device]] = {}
    for d in devices:
        by_proc.setdefault(getattr(d, "process_index", 0), []).append(d)
    return [by_proc[p] for p in sorted(by_proc)]


def _pow2_floor(x: int) -> int:
    p = 1
    while p * 2 <= x:
        p *= 2
    return p


def split_roles(
    cfg: ModelConfig,
    devices: Sequence[jax.Device],
    prefill_fraction: float = 0.5,
) -> tuple[Optional[Mesh], Mesh]:
    """Carve ONE preset's device slice into disjoint (prefill, decode)
    sub-meshes — the role-aware form of the per-model carving above,
    for disaggregated serving (``LLMC_DISAGG``): dedicated prefill
    workers on one sub-mesh hand finished prefix KV to the resident
    decode pool on the other, so admission prefill compute leaves the
    decode chips entirely.

    Both roles get power-of-two slices; the decode role keeps the
    LEADING devices (consecutive ids = adjacent ICI links, and the
    resident pool is the latency-critical half) and its own ``best_tp``,
    while the prefill role MATCHES the decode tp degree whenever its
    slice affords it: KV computed under a different tp degree carries a
    different float-reduction order, and matched degrees keep the
    handed-off bytes bitwise-identical to what the decode engine would
    have computed itself (the byte-identity contract's strong form). A
    prefill share too small to match falls back to its own ``best_tp``
    — the handoff still reshards correctly through the decode engine's
    shard_fn (engine/handoff.py), but low-bit drift between the roles'
    reduction orders is then possible, the same caveat as any placement
    change. A slice too small to split at all (< 2 devices) returns
    ``(None, decode_mesh)`` — the caller falls back to classic
    interleaved admission on the single mesh.
    """
    devices = list(devices)
    n = len(devices)
    if n < 2:
        tp = best_tp(cfg, n)
        return None, make_mesh({"dp": 1, "tp": tp}, devices[:tp])
    f = min(max(float(prefill_fraction), 0.05), 0.9)
    p = _pow2_floor(max(1, int(n * f)))
    if p >= n:
        p = _pow2_floor(n - 1)
    d = _pow2_floor(n - p)
    tp_d = best_tp(cfg, d)
    tp_p = tp_d if tp_d <= p else best_tp(cfg, p)
    decode_mesh = make_mesh({"dp": 1, "tp": tp_d}, devices[:tp_d])
    prefill_mesh = make_mesh(
        {"dp": 1, "tp": tp_p}, devices[n - p:n - p + tp_p]
    )
    return prefill_mesh, decode_mesh


def plan_panel(
    panel: Sequence[tuple[str, ModelConfig]],
    judge: Optional[tuple[str, ModelConfig]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
    judge_fraction: float = 0.5,
    hosts: Optional[Sequence[Sequence[jax.Device]]] = None,
    disagg_fraction: Optional[float] = None,
) -> MeshPlan:
    """Place panel models + judge on disjoint slices of ``devices``.

    Policy (greedy, weight-proportional): the judge — typically the big
    TP-sharded model (BASELINE config[3]: 70B judge + 3×8B panel) — gets
    ``judge_fraction`` of the chips (rounded down to a power of two); the
    rest are split evenly across panel models. Every slice is a power-of-two
    so TP degrees stay MXU/ICI friendly. With fewer devices than models,
    slices are shared round-robin (time-multiplexed by the engine pool).

    **Host-aware placement** (the default whenever ``devices`` spans
    several processes, or an explicit ``hosts`` grouping): every model's
    slice stays WITHIN one host's ICI domain, because TP all-reduces
    activations every layer and would die on DCN latency. The judge
    takes the largest host; panel models round-robin over the other
    hosts, so panel decode loops run on different hosts' chips
    concurrently and DCN carries no per-layer traffic at all — the
    host-level fan-out is task parallelism, exactly like the reference's
    goroutines, just over hosts instead of HTTP connections (SURVEY.md
    §5). Execution matches ownership: each process drives only the
    engines whose slice it can address and results exchange host-side
    (parallel/multicontroller.py, runner/multihost.py).
    ``LLMC_MULTIHOST_PLACEMENT=0`` forces the old single-domain planning
    (debugging only — a cross-host TP mesh is a per-layer DCN all-reduce).
    """
    devices = list(devices if devices is not None else jax.devices())
    if not panel and judge is None:
        return MeshPlan()
    if hosts is not None:
        groups = [list(g) for g in hosts]
        devices = [d for g in groups for d in g]
    elif knobs.get_bool("LLMC_MULTIHOST_PLACEMENT"):
        groups = host_groups(devices)  # single-process: one group
    else:
        groups = [devices]
    if len(groups) > 1:
        return _plan_multihost(
            panel, judge, groups, judge_fraction,
            disagg_fraction=disagg_fraction,
        )

    def placed(name: str, cfg: ModelConfig, slice_devs, role: str):
        """One placement over its device slice — split into prefill and
        decode sub-meshes under disaggregation, one mesh otherwise."""
        if disagg_fraction is not None and len(slice_devs) >= 2:
            pmesh, dmesh = split_roles(cfg, slice_devs, disagg_fraction)
            return ModelPlacement(name, cfg, dmesh, role, prefill_mesh=pmesh)
        tp = best_tp(cfg, len(slice_devs))
        return ModelPlacement(
            name, cfg, make_mesh({"dp": 1, "tp": tp}, slice_devs[:tp]), role
        )

    n = len(devices)
    pow2_floor = _pow2_floor
    plan = MeshPlan()
    remaining = devices
    if judge is not None and n >= 2:
        j = pow2_floor(max(1, int(n * judge_fraction)))
        judge_devs, remaining = remaining[n - j :], remaining[: n - j]
    elif judge is not None:
        judge_devs = devices  # single chip: judge shares it
    else:
        judge_devs = []

    if panel:
        per = max(1, pow2_floor(len(remaining) // len(panel))) if remaining else 1
        pool = remaining if remaining else devices
        taken: set = set()
        for i, (name, cfg) in enumerate(panel):
            start = (i * per) % max(1, len(pool))
            devs = pool[start : start + per]
            if len(devs) < per:  # wrap: share the pool round-robin
                devs = (pool + pool)[start : start + per]
            p = placed(name, cfg, devs, "panel")
            used = [
                d for m in (p.prefill_mesh, p.mesh) if m is not None
                for d in m.devices.flat
            ]
            # One chip holds every model by construction (the one-chip
            # deployment): nothing was mis-planned, nothing to warn of.
            if n > 1 and taken & {d.id for d in used}:
                _warn_wrap_sharing(name, used)
            taken |= {d.id for d in used}
            plan.placements.append(p)

    if judge is not None:
        name, cfg = judge
        plan.placements.append(placed(name, cfg, judge_devs, "judge"))
    return plan


def _warn_wrap_sharing(name: str, devs: Sequence[jax.Device]) -> None:
    """Models outnumber chips: slices time-multiplex. Decode loops on a
    shared slice contend for the chip (the engine pool serializes
    dispatches, so it is correct but slower) — say so instead of letting
    a silently shared placement read as a perf mystery."""
    import warnings

    warnings.warn(
        f"model {name!r} shares chips {sorted(d.id for d in devs)} with "
        "another placement (more models than devices): decode loops will "
        "time-multiplex the slice",
        RuntimeWarning,
        stacklevel=3,
    )


def _plan_multihost(
    panel: Sequence[tuple[str, ModelConfig]],
    judge: Optional[tuple[str, ModelConfig]],
    groups: list[list[jax.Device]],
    judge_fraction: float = 0.5,
    disagg_fraction: Optional[float] = None,
) -> MeshPlan:
    """Host-aware placement, weight-proportional: one ICI domain per
    model slice (see plan_panel's policy note), with hosts and chips
    allotted by PARAMETER COUNT — the biggest model gets the biggest
    host regardless of role (a 70B panel member outranks an 8B judge;
    round 2 always handed the judge the largest host). ``judge_fraction``
    scales the judge's weight (0.5 = neutral, its real size; higher
    biases chips toward the judge the way the single-domain planner's
    fraction does).
    """
    plan = MeshPlan()
    hosts = sorted(groups, key=len, reverse=True)
    jf = min(max(judge_fraction, 0.01), 0.99)
    items: list[tuple[str, ModelConfig, str, float]] = [
        (name, cfg, "panel", float(max(1, cfg.n_params(active_only=True))))
        for name, cfg in panel
    ]
    if judge is not None:
        name, cfg = judge
        items.append((
            name, cfg, "judge",
            float(max(1, cfg.n_params(active_only=True))) * (jf / (1.0 - jf)),
        ))
    # Heaviest model first onto the host where it keeps weight-per-chip
    # lowest — so the biggest model lands on the biggest (least loaded)
    # host and co-tenants balance by size, not by count.
    items.sort(key=lambda it: -it[3])
    loads = [0.0] * len(hosts)
    assigned: list[list[tuple[str, ModelConfig, str, float]]] = [
        [] for _ in hosts
    ]
    for it in items:
        h = min(
            range(len(hosts)),
            key=lambda i: ((loads[i] + it[3]) / len(hosts[i]), i),
        )
        assigned[h].append(it)
        loads[h] += it[3]

    for host, its in zip(hosts, assigned):
        if not its:
            continue
        total = sum(w for *_, w in its)
        start = 0
        for name, cfg, role, w in its:
            # Weight-proportional power-of-two share of this host's chips.
            per = min(
                len(host), max(1, _pow2_floor(int(len(host) * w / total)))
            )
            devs = host[start : start + per]
            if len(devs) < per:  # wrap: share the host round-robin
                devs = (host + host)[start % len(host):][:per]
                _warn_wrap_sharing(name, devs)
            start += per
            if disagg_fraction is not None and len(devs) >= 2:
                # Role split stays WITHIN the host's ICI domain: the KV
                # handoff is a bulk block copy, but the prefill engine's
                # own TP collectives must not cross DCN.
                pmesh, dmesh = split_roles(cfg, devs, disagg_fraction)
                plan.placements.append(
                    ModelPlacement(name, cfg, dmesh, role, prefill_mesh=pmesh)
                )
            else:
                tp = best_tp(cfg, len(devs))
                mesh = make_mesh({"dp": 1, "tp": tp}, devs[:tp])
                plan.placements.append(ModelPlacement(name, cfg, mesh, role))
    return plan
