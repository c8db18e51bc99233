"""Heterogeneous-execution benchmark: placement, dispatch & transfer counts.

For every zoo graph (plus wide variants), heterogenize the plan with a
permissive profile (zero compute floor — all supported branches are
accelerator-worthy, so the small zoo graphs exercise real splits), run
``parallax-hetero``, and report:

  * per-device dispatch counts (one fused callable per (layer, device)
    segment + one host dispatch per dynamic control-flow region),
  * planned boundary-transfer bytes (per consumer-branch staging charge)
    and the physical bytes the executor actually moved,
  * dynamic-region count and mean latency.

Every run is validated against the reference oracle in-line — the
benchmark doubles as an end-to-end check that placement never changes
numerics.  Under ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
the same script exercises genuine multi-device placement (CI uploads its
output as an artifact); on one device the logical topology is simulated.
"""

from __future__ import annotations

import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "tests"))

import jax  # noqa: E402

from repro.core import (HardwareProfile, ParallaxConfig, PlanExecutor,  # noqa: E402
                        compile_plan)
from graph_zoo import assert_matches_oracle  # noqa: E402
from .common import block_outputs, time_fn  # noqa: E402
from .dispatch import zoo_cases  # noqa: E402  (one zoo, comparable reports)

CFG = ParallaxConfig(budget=1 << 30)
PERMISSIVE = HardwareProfile("permissive", 0.0, 1.0, 1.0, 1.0)


def _fmt_devices(counts: "dict[tuple, int]") -> str:
    return " ".join(f"{kind[0]}{idx}:{n}"
                    for (kind, idx), n in sorted(counts.items()))


def run(iters=5, warmup=2):
    rows = []
    for name, builder in sorted(zoo_cases().items()):
        g, make = builder()
        env = make(np.random.default_rng(0))
        ref = np.asarray(g.execute(dict(env))[g.outputs[0]])
        plan = compile_plan(g, CFG)
        ex = PlanExecutor(plan, mode="parallax-hetero",
                          hetero_profile=PERMISSIVE)
        got = np.asarray(ex(env).outputs[g.outputs[0]])
        # oracle check, every graph
        assert_matches_oracle(ref, got, ex.hetero_stats.gemm_sites)
        transfers = ex.plan.attrs["transfers"]
        assert ex.last_transfer_bytes == transfers.physical_bytes()
        stats = ex.hetero_stats
        _, _, mean = time_fn(lambda: block_outputs(ex(env)),
                             warmup=warmup, iters=iters)
        rows.append({
            "graph": name,
            "devices": dict(ex.last_device_dispatches),
            "dispatches": ex.last_dispatch_count,
            "dynamic": stats.dynamic_regions,
            "planned_bytes": transfers.total_bytes,
            "physical_bytes": transfers.physical_bytes(),
            "edges": transfers.num_edges,
            "mean_ms": mean * 1e3,
        })
    return rows


def main():
    print(f"# parallax-hetero placement & transfer accounting "
          f"({len(jax.devices())} physical device(s))")
    print(f"{'graph':14s} {'disp':>5s} {'dyn':>4s} {'planB':>8s} "
          f"{'physB':>8s} {'edges':>6s} {'mean ms':>8s}  per-device")
    rows = run()
    for r in rows:
        print(f"{r['graph']:14s} {r['dispatches']:5d} {r['dynamic']:4d} "
              f"{r['planned_bytes']:8d} {r['physical_bytes']:8d} "
              f"{r['edges']:6d} {r['mean_ms']:8.2f}  "
              f"{_fmt_devices(r['devices'])}")
    total_phys = sum(r["physical_bytes"] for r in rows)
    total_disp = sum(r["dispatches"] for r in rows)
    dyn = sum(r["dynamic"] for r in rows)
    print(f"\n# totals over the zoo: dispatches={total_disp} "
          f"dynamic-regions={dyn} physical-transfer-bytes={total_phys}")
    assert len(rows) >= 3            # acceptance: >= 3 zoo graphs reported
    assert any(r["dynamic"] > 0 for r in rows)
    return rows


if __name__ == "__main__":
    main()
