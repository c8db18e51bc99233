"""Heterogeneous plan execution — the ``parallax-hetero`` runtime.

Drives a placed plan's :class:`~repro.core.compile.CompiledHeteroSchedule`
across the resolved physical devices:

* every segment's inputs are committed to its device with async
  ``jax.device_put`` before dispatch — planned boundary crossings
  (``TransferPlan.crossing_keys``) increment the transfer counters, while
  redundant puts (tensor already resident) are no-ops that only enforce
  the single-device invariant of each fused computation;
* static segments dispatch their jitted callable; dynamic segments run
  host-side through :class:`~repro.hetero.dynamic.DynamicRegionCache`
  (per-subgraph callables, shape-bucketed);
* like the homogeneous executor, dispatches stream asynchronously with
  exactly one host synchronization at the graph outputs
  (``profile=True`` reinstates a barrier after every segment).

Counters: ``last_dispatch_count`` / ``last_sync_count`` mirror
``PlanExecutor``; ``last_device_dispatches`` splits dispatches by logical
device and ``last_transfer_bytes`` / ``last_transfer_count`` account the
boundary traffic actually moved — one copy per (tensor, device), equal to
the static ``TransferPlan.physical_bytes()`` (tests assert this), while
``total_bytes`` is the larger per-consumer staging charge the scheduler
uses.
"""

from __future__ import annotations

import time

import jax

from ..core.compile import compile_hetero_schedule
from ..core.executor import LayerTiming, RunResult
from ..core.plan import ExecutionPlan
from ..runtime.telemetry import Telemetry
from .dynamic import DynamicRegionCache
from .placement import resolve_devices
from .transfer import TransferPlan, plan_transfers


class HeteroExecutor:
    """Executes a heterogenized plan (``plan.placement`` must be set)."""

    def __init__(self, plan: ExecutionPlan, *,
                 use_branch_kernel: bool = True, profile: bool = False,
                 devices=None, telemetry: "Telemetry | None" = None):
        if plan.placement is None:
            raise ValueError("plan has no placement — call "
                             "repro.hetero.heterogenize(plan) first")
        self.plan = plan
        self.profile = profile
        self.device_map = resolve_devices(plan.placement, devices)
        self.compiled = compile_hetero_schedule(
            plan, use_branch_kernel=use_branch_kernel,
            device_map=self.device_map)
        transfers = plan.attrs.get("transfers")
        if not isinstance(transfers, TransferPlan):
            transfers = plan_transfers(plan, plan.placement)
        self.transfers = transfers
        self._crossing = transfers.crossing_keys()
        self.dynamic_cache = DynamicRegionCache(plan.graph)
        # cumulative counters live in the telemetry registry (legacy
        # names below are a read-only façade); the last_* per-run
        # scratch stays plain — it is reset every __call__
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        self._rec = self.telemetry.rec
        m = self.telemetry.metrics
        self._m_dispatches = m.counter("hetero.dispatches")
        self._m_syncs = m.counter("hetero.syncs")
        self._m_transfer_bytes = m.counter("hetero.transfer_bytes")
        self._m_transfers = m.counter("hetero.transfers")
        self._m_per_device: dict = {}      # logical device -> Counter
        self.last_dispatch_count = 0
        self.last_sync_count = 0
        self.last_transfer_bytes = 0
        self.last_transfer_count = 0
        self.last_device_dispatches: dict[tuple, int] = {}

    @property
    def dispatch_count(self) -> int:
        return self._m_dispatches.value

    @property
    def sync_count(self) -> int:
        return self._m_syncs.value

    @property
    def transfer_bytes(self) -> int:
        return self._m_transfer_bytes.value

    @property
    def transfer_count(self) -> int:
        return self._m_transfers.value

    def _device_counter(self, device):
        c = self._m_per_device.get(device)
        if c is None:
            tag = "_".join(str(p) for p in device) \
                if isinstance(device, tuple) else str(device)
            c = self.telemetry.metrics.counter(f"hetero.dispatches.{tag}")
            self._m_per_device[device] = c
        return c

    def stats(self) -> dict:
        """JSON-safe snapshot of the executor's cumulative counters."""
        return self.telemetry.metrics.snapshot()

    def _block(self, arrays) -> None:
        jax.block_until_ready(arrays)
        self.last_sync_count += 1

    def __call__(self, env: "dict[int, object]") -> RunResult:
        graph = self.plan.graph
        tensors = graph.tensors
        self.last_dispatch_count = 0
        self.last_sync_count = 0
        self.last_transfer_bytes = 0
        self.last_transfer_count = 0
        self.last_device_dispatches = {}
        env = dict(env)
        placed: dict[tuple, object] = {}   # (tensor, logical dev) -> array
        timings: list[LayerTiming] = []
        rec = self._rec
        for seg in self.compiled.segments:
            t0 = time.perf_counter()
            dev = self.device_map[seg.device]
            args = []
            for t in seg.in_ids:
                key = (t, seg.device)
                v = placed.get(key)
                if v is None:
                    # Commit to the segment device (async; no-op when the
                    # producer already ran there).  One physical move per
                    # (tensor, device) per run — shared by co-located
                    # consumers, so the counter equals
                    # TransferPlan.physical_bytes().
                    v = jax.device_put(env[t], dev)
                    placed[key] = v
                    if key in self._crossing:
                        self.last_transfer_bytes += tensors[t].nbytes()
                        self.last_transfer_count += 1
                args.append(v)
            if seg.dynamic:
                outs = self.dynamic_cache.run(seg.node_ids, tuple(args))
            else:
                outs = seg.fn(*args)
            self.last_dispatch_count += 1
            self.last_device_dispatches[seg.device] = (
                self.last_device_dispatches.get(seg.device, 0) + 1)
            self._device_counter(seg.device).inc()
            for t, v in zip(seg.out_ids, outs):
                env[t] = v
                # outputs are already resident on the segment device: spare
                # same-device consumers the redundant device_put
                placed[(t, seg.device)] = v
            if self.profile:
                self._block(outs)
            timings.append(LayerTiming(seg.layer_index,
                                       time.perf_counter() - t0, seg.width))
            if rec.enabled:
                rec.span("segment", t0,
                         device=str(seg.device),
                         layer=seg.layer_index,
                         dynamic=bool(seg.dynamic))
        outs = {t: env[t] for t in graph.outputs}
        self._block(list(outs.values()))
        self._m_dispatches.inc(self.last_dispatch_count)
        self._m_syncs.inc(self.last_sync_count)
        self._m_transfer_bytes.inc(self.last_transfer_bytes)
        self._m_transfers.inc(self.last_transfer_count)
        return RunResult(outs, timings)
