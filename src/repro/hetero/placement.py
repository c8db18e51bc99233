"""Heterogeneous device placement — branch/region → device assignment.

The paper's runtime targets an accelerator plus a host CPU that absorbs
operator fallbacks (§1).  This module turns an
:class:`~repro.core.plan.ExecutionPlan` into a :class:`PlacementPlan`
assigning every branch a *logical* device:

* branches containing a fused ``delegate`` region (accepted by the §3.1 /
  Appendix B cost model, recorded in ``PartitionReport``) run on an
  accelerator;
* branches with unsupported or control-flow nodes fall back to the host —
  control-flow branches additionally become *dynamic* regions executed by
  ``hetero/dynamic.py`` outside any fused callable;
* remaining supported branches go to the accelerator when their FLOPs clear
  the profile's compute floor ``F > L·R_cpu`` (Appendix B.2 — below it the
  dispatch costs more than the speedup), else they stay on the host, which
  is exactly the paper's "default backend" for undelegated work.

Parallel-group members round-robin across the available accelerator
devices (per-stream placement, cf. Opara in PAPERS.md): position ``p`` of
a §3.3 parallel group lands on logical ``accel:(p mod n_accel)``, so
branch-level parallelism becomes device-level parallelism when more than
one accelerator exists.

Logical devices are resolved to physical ``jax.Device``s by
:func:`resolve_devices`: the host is CPU device 0, and the accelerators
are the default backend's other devices (all of them on a TPU machine;
devices 1..D-1 on a CPU-only one).  Multi-device simulation in CI uses
``XLA_FLAGS=--xla_force_host_platform_device_count=N``; with a single
physical device every logical device aliases it, so placement (and its
byte accounting) still runs everywhere.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax

from ..core.partition import HardwareProfile
from ..core.plan import ExecutionPlan
from ..core.scheduler import Schedule

HOST = "host"
ACCEL = "accel"


@dataclass(frozen=True)
class DeviceAssignment:
    """Logical device of one branch (+ whether it is a dynamic region)."""

    kind: str                 # "accel" | "host"
    index: int                # logical index within the kind
    dynamic: bool = False     # host-side dynamic subgraph (control flow)

    @property
    def key(self) -> tuple:
        return (self.kind, self.index)


@dataclass
class PlacementPlan:
    """Branch id → :class:`DeviceAssignment`, plus the logical topology."""

    assignments: "dict[int, DeviceAssignment]" = field(default_factory=dict)
    n_accel: int = 1
    n_host: int = 1
    profile_name: str = ""

    def device_of(self, branch_id: int) -> tuple:
        return self.assignments[branch_id].key

    def is_dynamic(self, branch_id: int) -> bool:
        return self.assignments[branch_id].dynamic

    def devices_used(self) -> "list[tuple]":
        return sorted({a.key for a in self.assignments.values()})

    def branches_on(self, key: tuple) -> "list[int]":
        return sorted(b for b, a in self.assignments.items() if a.key == key)

    def signature(self) -> tuple:
        """Hashable token folded into :func:`~repro.core.plan.plan_signature`
        so placed plans never share compiled artifacts with unplaced ones."""
        return (self.n_accel, self.n_host, self.profile_name,
                tuple((b, a.kind, a.index, a.dynamic)
                      for b, a in sorted(self.assignments.items())))


def _default_host():
    """The physical device hosting fallbacks: CPU device 0.  On an
    accelerator machine jax.devices() is all GPUs/TPUs and stays the
    accel pool, so the CPU platform must be registered; without it no
    accelerator is quietly made to stand in for the host."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError as e:
        raise RuntimeError(
            "the hetero runtime needs a CPU device as its fallback host; "
            "add 'cpu' to JAX_PLATFORMS") from e


def logical_accel_count(devices=None) -> int:
    """Accelerators the runtime can target.  On the default backend: every
    device that is not the host (on CPU-only/simulated platforms the host
    is device 0, leaving D-1 accels; on real accelerator backends the CPU
    host is a separate platform, so all D devices are accels).  A
    single-device host simulates one accelerator."""
    if devices is not None:
        return max(1, len(devices) - 1)
    devs = jax.devices()
    if _default_host() in devs:
        return max(1, len(devs) - 1)
    return len(devs)


def resolve_devices(placement: PlacementPlan, devices=None) -> "dict[tuple, object]":
    """Logical (kind, index) → physical ``jax.Device``.

    The host is the CPU-platform device (or device 0 of an explicit
    ``devices`` list / a CPU-only backend); the remaining default-backend
    devices form the accelerator pool (logical accels beyond the pool wrap
    around).  With one physical device everything aliases it — placement
    becomes pure simulation.
    """
    if devices is not None:
        devs = list(devices)
        host = devs[0]
        pool = devs[1:] or devs
    else:
        devs = list(jax.devices())
        host = _default_host()
        pool = [d for d in devs if d != host] or devs
    mapping: dict[tuple, object] = {(HOST, i): host
                                    for i in range(placement.n_host)}
    for i in range(placement.n_accel):
        mapping[(ACCEL, i)] = pool[i % len(pool)]
    return mapping


def _assign_branch(plan: ExecutionPlan, bid: int, group_pos: int,
                   n_accel: int, profile: HardwareProfile) -> DeviceAssignment:
    br = plan.branches[bid]
    nodes = [plan.graph.nodes[n] for n in br.nodes]
    dynamic = any(n.is_control_flow() for n in nodes)
    if dynamic or any(not n.supported for n in nodes):
        return DeviceAssignment(HOST, 0, dynamic)
    if br.delegate or br.flops >= profile.derived_flops_floor():
        return DeviceAssignment(ACCEL, group_pos % n_accel)
    return DeviceAssignment(HOST, 0)


def plan_placement(plan: ExecutionPlan,
                   profile: "HardwareProfile | None" = None,
                   n_accel: "int | None" = None,
                   schedule: "Schedule | None" = None) -> PlacementPlan:
    """Deterministic placement of every scheduled branch.

    Walks the §3.3 schedule (sorted layers, groups in order, members in
    order), so two plans with equal signatures always produce identical
    assignments.  ``profile`` defaults to the cost model the plan was
    compiled with; ``n_accel`` to :func:`logical_accel_count`.
    """
    if profile is None:
        cfg = plan.attrs.get("config")
        profile = (cfg.cost_model.profile if cfg is not None
                   else HardwareProfile("permissive", 0.0, 1.0, 1.0, 1.0))
    if n_accel is None:
        n_accel = logical_accel_count()
    sched = schedule if schedule is not None else plan.schedule
    out = PlacementPlan(n_accel=n_accel, profile_name=profile.name)
    for sl in sched.layers:
        for group in sl.parallel_groups:
            for pos, bid in enumerate(group):
                out.assignments[bid] = _assign_branch(
                    plan, bid, pos, n_accel, profile)
        for bid in sl.sequential:
            out.assignments[bid] = _assign_branch(
                plan, bid, 0, n_accel, profile)
    return out
