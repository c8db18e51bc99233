#!/usr/bin/env python3
"""Compile a cell's programs at their real shapes for a described TPU v5e
(no chip needed, nothing runs) and print ``memory_analysis()`` of each
beside the pool arithmetic.  ``peak_memory_in_bytes`` is what loading
the program needs: the chip refuses a program whose peak passes 15.75
GiB (``Attempting to reserve ... at the bottom of memory``) even where
the compile here passed.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [<workload> ...] [--max-batch B]

Programs: the weight build, the prefill chunk, the single decode step,
the megastep at its longest length, and the reference's gap program at
the cell's ``max_context``.
"""

from __future__ import annotations

import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from bench import harness  # noqa: E402

GIB = 2 ** 30


def rehearse(workload: str, layout, max_batch: "int | None" = None) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.models import build_model
    from repro.runtime.stepper import Stepper

    w = layout.workload(workload)
    cfg = layout.json("configs", w["config"])
    eng = layout.json("traffic", w["traffic"])["engine"]
    if max_batch:
        eng["max_batch"] = max_batch
    ref = layout.module("references", cfg["reference"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    dt = jnp.dtype(cfg["torch_dtype"])
    api = build_model(harness.program_config(cfg))
    build = ref.weight_fn(cfg, dt)
    key = on_chip(jax.eval_shape(lambda: ref.prng_key(0)))
    params = on_chip(jax.eval_shape(build, key))
    B, T, bs = eng["max_batch"], eng["max_context"], eng["block_size"]
    bps = -(-T // bs)
    caches = on_chip(jax.eval_shape(lambda: api.init_paged_caches(
        B, B * bps, bs, dt)))
    w_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    pool = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(caches))
    print(f"{workload} (max_batch {B}): weights {w_bytes / GIB:.3f} GiB; "
          f"KV pool {pool / GIB:.3f} GiB by its shape (on the chip: a "
          f"program's arguments less the weights)", flush=True)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    mask = jax.ShapeDtypeStruct((B,), bool, sharding=chip)
    st = Stepper(api)
    N, C = eng["megastep"], eng["prefill_chunk"]
    progs = {
        "weight build": (jax.jit(build), (key,)),
        "prefill chunk": (st._chunk_paged, (params, caches, i32(B, C), i32(B),
                                            i32(B), i32(B, bps))),
        "decode step": (st._decode_paged, (params, caches, i32(B), i32(B),
                                           mask, i32(B, bps))),
        f"megastep N={N}": (st._mega_paged, (
            params, caches, i32(B), i32(B), mask, i32(B), i32(B, N), i32(B),
            i32(B), i32(B, bps))),
        "reference": (ref.Reference(cfg)._jit, (params, i32(T), i32(T))),
    }
    for name, (fn, args) in progs.items():
        extra = (False,) if name == "reference" else ()
        try:
            compiled = fn.lower(*args, *extra).compile()
        except jax.errors.JaxRuntimeError as e:
            print(f"  {name}: REFUSED: {str(e).splitlines()[0][:300]}",
                  flush=True)
            continue
        m = compiled.memory_analysis()
        print(f"  {name}: arguments {m.argument_size_in_bytes / GIB:.3f}, "
              f"outputs {m.output_size_in_bytes / GIB:.3f}, temp "
              f"{m.temp_size_in_bytes / GIB:.3f}, peak "
              f"{m.peak_memory_in_bytes / GIB:.3f} GiB", flush=True)


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("workloads", nargs="*")
    ap.add_argument("--max-batch", type=int, default=None,
                    help="compile at this max_batch instead of the cell's")
    a = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    layout = harness.Layout()
    for name in a.workloads or [w["name"] for w in
                                layout.bench["workloads"]]:
        rehearse(name, layout, a.max_batch)
    return 0


if __name__ == "__main__":
    sys.exit(main())
