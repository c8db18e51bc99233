"""Serving entry point: batched requests through the §3.3-admitting engine.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-3b \
        --requests 8 --max-new 16 [--engine round|continuous] \
        [--reduced | --full] \
        [--arrival-rate R | --trace-file PATH] [--deadline-s D] \
        [--megastep N] [--host-pool 512M] [--fault-seed S] ...

``--reduced`` (the default, for the CPU) serves the architecture cut to
<= 2 layers and ``d_model`` <= 256 in float32; ``--full`` serves it at
its published width in its own dtype (bfloat16), which needs an
accelerator.  Weights are random, drawn from ``--seed``.  The KV budget
(``--hbm-budget``) defaults to the device's free memory once the
weights are placed.

Every engine knob flag (``--hbm-budget``, ``--max-batch``,
``--megastep``, ``--host-pool``, ``--fault-seed``, ``--max-queue``,
``--prefix-sharing/--no-prefix-sharing``, ...) is **generated** from
:class:`repro.runtime.config.EngineConfig` — run ``--help`` for the
full table.  An omitted flag falls back to its ``PARALLAX_*`` env var,
then the field default (explicit always wins, including falsy values
like ``--host-pool 0``), so the CLI, the env knobs, and the
constructor can never drift apart.

``--engine continuous`` serves through the iteration-level slot-table
engine with cross-request prefix sharing (decoder-only models).  Both
engines keep KV in the physically paged block pool.

**Closed loop** (the default): all requests are submitted up front and
``run()`` drains them — a throughput measurement.  **Open loop**:
``--arrival-rate R`` injects Poisson arrivals at R req/s through the
``submit()``/``step()``/``drain_completions()`` surface on the wall
clock, so queueing is visible; ``--trace-file PATH`` replays a JSONL
arrival trace instead (the format ``runtime/workload.py`` round-trips
via ``save_trace``/``from_trace``; ``benchmarks/openloop.py
--trace-out`` saves one).  Combined with ``--deadline-s`` the run
reports SLO attainment.  Continuous engine only.

``--fault-seed S`` (or env ``PARALLAX_FAULT_SEED``) arms the
fault-injection plane (``runtime/faults.py``) with a deterministic
random schedule and prints the degraded-mode counters afterwards; the
engine itself never consults the env — this entry point resolves the
seed via EngineConfig and hands the engine a built ``FaultPlane``.
"""

from __future__ import annotations

import argparse
import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config
from repro.models import build_model
from repro.runtime.config import EngineConfig
from repro.runtime.engine import (ContinuousEngine, Request,
                                  ServingEngine)
from repro.runtime.faults import FaultPlane
from repro.runtime.telemetry import Telemetry
from repro.runtime.workload import OpenLoopWorkload, percentile, \
    run_open_loop
from repro.utils.compile_cache import enable_compile_cache


def model_config(arch: str, full: bool = False):
    """The architecture's config at its published width (``full``) or
    cut to the CPU-sized ``reduced()`` variant."""
    cfg = get_config(arch)
    return cfg if full else cfg.reduced()


def init_params(api, seed: int):
    """Random weights from ``seed`` in the config's dtype.  Init and cast
    run as one jit, so no float32 copy of the whole tree is ever held on
    the device (stablelm-3b: 11.2 GB in float32, 5.6 GB in bfloat16)."""
    dtype = jnp.dtype(api.cfg.dtype)

    def init(key):
        return jax.tree.map(
            lambda a: a.astype(dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a,
            api.init(key))

    return jax.jit(init)(jax.random.key(seed))


def serve(arch: str, n_requests: int = 8, max_new: int = 16,
          budget_mb: "int | None" = None, prompt_len: int = 12,
          seed: int = 0,
          max_batch: int = 4, engine_mode: str = "round",
          megastep: "int | None" = None,
          fault_seed: "int | None" = None,
          max_queue: "int | None" = None,
          deadline_s: "float | None" = None,
          trace_path: "str | None" = None,
          host_pool: "int | None" = None,
          config: "EngineConfig | None" = None,
          arrival_rate: "float | None" = None,
          trace_file: "str | None" = None,
          full: bool = False, params=None,
          requests: "list[Request] | None" = None):
    """Serve ``n_requests`` random prompts (or the given ``requests``)
    and return their completions by id.

    ``params`` are weights already built by :func:`init_params` for the
    same config (drawn from ``seed`` when omitted).  A ``config`` built
    before the weights are on the device counts their bytes as free KV
    budget: build it after them, or leave it to this function.
    """
    cfg = model_config(arch, full)
    api = build_model(cfg)
    if params is None:
        params = init_params(api, seed)
    tele = Telemetry(trace=trace_path is not None)
    if config is None:
        # legacy keyword surface: a kwarg left at None is unset and
        # falls through EngineConfig's env-then-default resolution
        config = EngineConfig(
            hbm_budget=None if budget_mb is None else budget_mb << 20,
            max_batch=max_batch,
            max_context=(prompt_len + max_new
                         if engine_mode == "continuous" else None),
            **{k: v for k, v in dict(
                megastep=megastep, fault_seed=fault_seed,
                max_queue=max_queue, host_pool=host_pool).items()
               if v is not None})
    open_loop = arrival_rate is not None or trace_file is not None
    if engine_mode != "continuous" and (
            config.fault_seed is not None or max_queue is not None
            or deadline_s is not None or host_pool is not None
            or open_loop):
        raise ValueError("fault plane / backpressure / deadlines / host "
                         "KV tier / open-loop arrivals harden the "
                         "continuous engine only (--engine continuous)")

    workload = None
    if open_loop:
        if trace_file is not None:
            workload = OpenLoopWorkload.from_trace(
                trace_file, vocab_size=cfg.vocab_size, seed=seed,
                deadline_s=deadline_s)
        else:
            workload = OpenLoopWorkload.poisson(
                arrival_rate, n_requests, cfg.vocab_size, seed=seed,
                deadline_s=deadline_s)
        need = max(len(a.request.prompt) + a.request.max_new_tokens
                   for a in workload)
        if config.max_context is None or config.max_context < need:
            print(f"max_context {config.max_context} -> {need} "
                  f"(longest workload request)")
            config = replace(config, max_context=need)
        request_ids = [a.request.id for a in workload]
    else:
        if requests is None:
            rng = np.random.default_rng(seed)
            requests = [Request(
                id=i, prompt=rng.integers(
                    0, cfg.vocab_size,
                    int(rng.integers(4, prompt_len + 1))).astype(np.int32),
                max_new_tokens=max_new, deadline_s=deadline_s)
                for i in range(n_requests)]
        n_requests = len(requests)
        request_ids = [r.id for r in requests]

    faults = None
    if engine_mode == "continuous":
        engine = ContinuousEngine(api, params, config=config,
                                  telemetry=tele)
        if config.fault_seed is not None:
            # the schedule's budget events are absolute post-margin
            # byte values, so derive them from the pool's real budget
            faults = FaultPlane.random(
                config.fault_seed, budget_bytes=engine.kv.budget,
                request_ids=request_ids, max_batch=config.max_batch)
            engine.faults = faults
            print(f"fault plane armed: seed {config.fault_seed}, "
                  f"{len(faults.events)} events")
    else:
        engine = ServingEngine(api, params, config=config,
                               telemetry=tele)

    if open_loop:
        res = run_open_loop(engine, workload)
        done, wall = res.completions, res.wall_s
        n_requests = len(workload)
    else:
        for req in requests:
            engine.submit(req)
        t0 = time.time()
        done = engine.run()
        wall = time.time() - t0
    for rid in sorted(done):
        c = done[rid]
        tag = "" if c.ok else f" [{c.status}: {c.reason}]"
        print(f"req {rid}: {len(c.tokens)} tokens "
              f"(prefill {c.prefill_s*1e3:.1f} ms, "
              f"decode {c.decode_s*1e3:.1f} ms) -> {c.tokens[:8]}..."
              f"{tag}")
    print(f"{len(done)}/{n_requests} requests in {wall:.2f}s; "
          f"peak cache {engine.kv.peak_bytes/2**20:.1f} MiB "
          f"(budget {engine.kv.budget/2**20:.1f} MiB), "
          f"slab reuse hits {engine.kv.reuse_count}")
    if open_loop:
        ok = [c for c in done.values() if c.ok]
        good = sum(len(c.tokens) for c in ok)
        ttfts = [c.ttft_submit_s for c in ok if c.ttft_submit_s > 0]
        depth = max((q for _, q, _ in res.queue_samples), default=0)
        print(f"open loop: offered {workload.offered_rate_rps:.2f} "
              f"req/s over {workload.duration_s:.2f}s, attainment "
              f"{len(ok)}/{n_requests}, goodput "
              f"{good / max(wall, 1e-9):.1f} tok/s, ttft p50 "
              f"{percentile(ttfts, 50)*1e3:.1f} ms / p95 "
              f"{percentile(ttfts, 95)*1e3:.1f} ms, peak queue "
              f"{depth}")
    if engine_mode == "continuous":
        total = sum(len(c.tokens) for c in done.values())
        print(f"iterations {engine.iterations}, dispatches "
              f"{engine.dispatches} ({engine.dispatches/max(total, 1):.2f}"
              f"/tok), megasteps {engine.megasteps} "
              f"({engine.megastep_steps} fused iters, "
              f"N={engine.megastep_n}), "
              f"preemptions {engine.preemptions}")
        print(f"stepper traces {engine.stepper.trace_stats()}")
        if engine.spill_enabled:
            print(f"host tier: {engine.spills} spills / "
                  f"{engine.restores} restores, "
                  f"{engine.prefill_tokens_saved} prefill tokens saved, "
                  f"{engine.reprefill_tokens} re-prefilled, host peak "
                  f"{engine.kv.host_peak_bytes/2**20:.2f} MiB "
                  f"(pool {engine.kv.host_budget/2**20:.2f} MiB), "
                  f"stalls {engine.stalls}")
        if faults is not None or config.max_queue is not None \
                or deadline_s is not None:
            by_status: "dict[str, int]" = {}
            for c in done.values():
                by_status[c.status] = by_status.get(c.status, 0) + 1
            print(f"resolution {by_status}; degraded activations "
                  f"{engine.degraded_activations} (watchdog trips "
                  f"{engine.watchdog_trips}, megastep fallbacks "
                  f"{engine.megastep_fallbacks}, retries "
                  f"{engine.retry_dispatches}, rows failed "
                  f"{engine.rows_failed}), cancellations "
                  f"{engine.cancellations}, rejected {engine.rejected}, "
                  f"budget events {engine.budget_events}")
        engine.assert_quiescent()
    if trace_path is not None:
        trace = tele.save_chrome_trace(trace_path)
        print(f"trace: {len(trace['traceEvents'])} events -> "
              f"{trace_path} (load in Perfetto / chrome://tracing)")
    return done


def main():
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--arch", choices=sorted(ARCHS),
                    default="stablelm-3b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", choices=("round", "continuous"),
                    default="round")
    ap.add_argument("--reduced", action="store_true", default=True,
                    help="serve the CPU-sized cut (the default)")
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="serve the published width in the config's "
                         "dtype (needs an accelerator)")
    ap.add_argument("--arrival-rate", type=float, default=None,
                    metavar="RPS",
                    help="open loop: Poisson arrivals at this req/s "
                         "through submit()/step()/drain_completions() "
                         "on the wall clock (continuous engine)")
    ap.add_argument("--trace-file", default=None, metavar="PATH",
                    help="open loop: replay a JSONL arrival trace "
                         "(see runtime/workload.py)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request wall-clock deadline in seconds")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record structured spans and write a Chrome "
                         "trace-event JSON here (open in Perfetto); "
                         "recording never alters scheduling — streams "
                         "and dispatch counts stay bit-identical")
    EngineConfig.add_cli_args(ap)
    args = ap.parse_args()
    enable_compile_cache()
    full = not args.reduced
    # weights first: the config's default KV budget is the device memory
    # still free once they are placed
    params = init_params(build_model(model_config(args.arch, full)),
                         args.seed)
    overrides = {}
    if args.max_context is None:
        # closed-loop default: prompt + generation exactly fit; the
        # round engine keeps its dynamic per-round bucketing
        overrides["max_context"] = (
            args.prompt_len + args.max_new
            if args.engine == "continuous" else None)
    config = EngineConfig.from_cli_args(args, **overrides)
    serve(args.arch, args.requests, args.max_new,
          prompt_len=args.prompt_len, seed=args.seed,
          engine_mode=args.engine, deadline_s=args.deadline_s,
          trace_path=args.trace, config=config,
          arrival_rate=args.arrival_rate, trace_file=args.trace_file,
          full=full, params=params)


if __name__ == "__main__":
    main()
