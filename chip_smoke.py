#!/usr/bin/env python3
"""Smoke run on the chip: serve stablelm-3b at its published width on one
TPU, and run the Parallax pipeline there.

    python3 chip_smoke.py [--seed S]          # one chip
    python3 chip_smoke.py --four-chips        # hetero pool over 4 chips

Phases, in order, all in this one process:

1. **Device** — exit at once unless JAX's first device is a TPU.
2. **Serve** — ``repro.launch.serve.serve`` with the continuous engine and
   its defaults (paged pool, prefix sharing, megastep 8) answers 8
   requests (prompts of 32-96 tokens, 32 new tokens each) on stablelm-3b
   at full width with random bfloat16 weights drawn from ``--seed``.
   Every stream is then checked against a plain teacher-forced
   ``forward_lm`` + ``lm_logits`` on the same weights (:data:`DELTA_ULPS`).
3. **Parallax pipeline** — ``PlanExecutor`` in ``parallax`` (per-layer and
   whole-plan) and ``parallax-hetero`` mode against ``Graph.execute`` on
   the exported stablelm-3b DAG and the zoo's ``multihead`` graph
   (:data:`PIPE_TOL`); the hetero host must be the CPU and the
   accelerator segments must run on the TPU.

``--four-chips`` runs only the hetero executor, over an accelerator pool
of 4 TPUs with the CPU as host, against the reference, and checks that
every TPU was dispatched to.

The lines before the last report compile seconds, trace counts, serve
wall time, peak device memory and the KV budget: they are a smoke
record, not benchmark numbers.  The last line is one JSON object
``{"ok": true, "device": {...}}``; any failure exits non-zero without it.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = "stablelm-3b"
N_REQUESTS = 8
PROMPT_LEN = (32, 96)
MAX_NEW = 32
MAX_BATCH = 8
MAX_CONTEXT = 128

#: The engine's chosen token must have a reference logit within this many
#: bfloat16 ulps (at the scale of that position's largest logit) of the
#: reference maximum.  Both sides compute in bfloat16 with float32
#: accumulation, but the engine decodes one token at a time through the
#: paged pool while the reference runs the whole sequence at once: they
#: sum in other orders and round activations to bfloat16 at other
#: points, so their logits drift apart by a few ulps over 32 layers, and
#: each side's final rounding to bfloat16 adds up to half an ulp.  A
#: stream built on a wrong cache row or position picks tokens whose
#: reference logit is unrelated to the maximum: the median token's sits
#: some hundred ulps below it.
DELTA_ULPS = 8

#: Pipeline outputs agree with ``Graph.execute`` to this fraction of the
#: output's largest magnitude.  Both run in float32 with every matmul at
#: HIGHEST precision (the TPU's default would take one bfloat16 pass);
#: what is left is the grouped GEMM's K-block accumulation order and the
#: CPU's and the TPU's exp/rsqrt rounding in the last bits.
PIPE_TOL = 1e-4


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def keep_cpu_platform() -> None:
    """The hetero runtime's host is the CPU device: where JAX_PLATFORMS
    names only accelerators, add the CPU to it (before JAX starts).  A
    JAX_PLATFORMS of ``cpu`` alone stays as it is, and the device phase
    then fails."""
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"


class CompileLog:
    """Backend compilations and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def line(self) -> str:
        return (f"{self.compiles} backend compiles, "
                f"{self.compile_s:.1f} s; persistent-cache hits "
                f"{self.cache_hits}")


def device_phase(n_chips: int):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    check(d0.platform == "tpu",
          f"JAX's first device is {d0.platform!r}, not a TPU")
    print(f"device: {d0.device_kind}, {len(devs)} device(s), "
          f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}",
          flush=True)
    check(len(devs) >= n_chips,
          f"{n_chips} TPUs needed, JAX sees {len(devs)}")
    return devs


def make_requests(vocab: int, seed: int):
    import numpy as np

    from repro.runtime.engine import Request
    rng = np.random.default_rng(seed)
    return [Request(id=i, prompt=rng.integers(
        0, vocab, int(rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1))
    ).astype(np.int32), max_new_tokens=MAX_NEW) for i in range(N_REQUESTS)]


def serve_phase(seed: int, log: CompileLog) -> None:
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import init_params, model_config, serve
    from repro.models import build_model
    from repro.runtime.config import EngineConfig

    cfg = model_config(ARCH, full=True)
    api = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(api, seed))
    leaves = jax.tree.leaves(params)
    n_bytes = sum(a.nbytes for a in leaves)
    check(all(a.dtype == jnp.bfloat16 for a in leaves),
          "weights are not all bfloat16")
    print(f"weights: {cfg.num_layers} layers, d_model {cfg.d_model}, "
          f"vocab {cfg.vocab_size}, {sum(a.size for a in leaves)/1e9:.3f} "
          f"B params, {n_bytes/1e9:.3f} GB bf16, built in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)

    # built now, so the default KV budget is what the weights left free
    config = EngineConfig(max_batch=MAX_BATCH, max_context=MAX_CONTEXT)
    print(f"KV budget: {config.hbm_budget/2**30:.3f} GiB free on the "
          f"device, {config.hbm_budget*(1-config.margin)/2**30:.3f} GiB "
          f"after the {config.margin:.0%} margin", flush=True)
    requests = make_requests(cfg.vocab_size, seed)
    c0, s0 = log.compiles, log.compile_s
    t0 = time.perf_counter()
    done = serve(ARCH, engine_mode="continuous", full=True, params=params,
                 config=config, requests=requests, seed=seed)
    wall = time.perf_counter() - t0
    tokens = sum(len(c.tokens) for c in done.values())
    print(f"serve: {tokens} tokens in {wall:.1f} s wall, of which "
          f"{log.compiles - c0} backend compiles took "
          f"{log.compile_s - s0:.1f} s", flush=True)
    check(sorted(done) == [r.id for r in requests], "requests lost")
    for c in done.values():
        check(c.ok and len(c.tokens) == MAX_NEW,
              f"request {c.request_id}: {c.status} ({c.reason}), "
              f"{len(c.tokens)} tokens")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak device memory: "
          f"{stats.get('peak_bytes_in_use', 0)/2**30:.3f} GiB of "
          f"{stats.get('bytes_limit', 0)/2**30:.3f} GiB", flush=True)
    reference_check(cfg, params, requests, done)


def reference_check(cfg, params, requests, done) -> None:
    """Teacher-force the engine's streams through ``forward_lm`` and
    check every generated token against the reference logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.transformer import forward_lm
    from repro.models.vocab import lm_logits

    @jax.jit
    def logits_fn(p, toks):
        hidden, _ = forward_lm(p, cfg, toks, remat=False)
        return lm_logits(p, cfg, hidden).astype(jnp.float32)

    toks = np.zeros((len(requests), MAX_CONTEXT), np.int32)
    for i, r in enumerate(requests):
        seq = np.concatenate([r.prompt, done[r.id].tokens])
        toks[i, :len(seq)] = seq       # causal: the zero tail is unseen
    logits = np.asarray(logits_fn(params, toks))
    short, top_gaps, below_median, exact = [], [], [], 0
    for i, r in enumerate(requests):
        P = len(r.prompt)
        for j, tok in enumerate(done[r.id].tokens):
            row = logits[i, P - 1 + j]
            top2 = np.partition(row, -2)[-2:]
            best = float(top2[1])
            ulp = 2.0 ** (np.floor(np.log2(abs(best))) - 7)
            short.append((best - float(row[tok])) / ulp)
            top_gaps.append(float(top2[1] - top2[0]) / ulp)
            below_median.append((best - float(np.median(row))) / ulp)
            exact += int(row[tok] == best)
    short, gaps = np.asarray(short), np.asarray(top_gaps)
    print(f"reference check: {len(short)} tokens, {exact} are the "
          f"reference argmax, worst shortfall {short.max():.1f} bf16 ulps "
          f"(limit {DELTA_ULPS}); reference top-1/top-2 gap in ulps: "
          f"min {gaps.min():.1f}, median {np.median(gaps):.1f}, "
          f"{int((gaps <= DELTA_ULPS).sum())} within the limit; a median "
          f"token sits {np.median(below_median):.0f} ulps below the top",
          flush=True)
    k = int(short.argmax())
    check(short[k] <= DELTA_ULPS,
          f"request {requests[k // MAX_NEW].id} token {k % MAX_NEW}: the "
          f"engine's choice is {short[k]:.1f} ulps below the reference "
          f"maximum (limit {DELTA_ULPS})")


def pipeline_graphs(seed: int):
    sys.path.insert(0, str(ROOT / "tests"))
    sys.path.insert(0, str(ROOT))
    from benchmarks.common import build_dag
    from graph_zoo import multihead_graph

    # dag_export bakes the weights into the program as numpy constants,
    # so the DAG stays at reduced width; full_flops gives its cost model
    # stablelm-3b's full-width FLOPs
    _, g, make = build_dag(ARCH, mode="reduced", seed=seed,
                           full_flops=True)
    return {f"{ARCH} DAG": (g, make), "multihead": multihead_graph()}


def pipeline_phase(seed: int, four_chips: bool) -> None:
    import jax
    import numpy as np

    from repro.core import (HardwareProfile, ParallaxConfig, PlanExecutor,
                            compile_plan)
    from repro.hetero import ACCEL, HOST, resolve_devices

    perm = HardwareProfile("permissive", 0.0, 1.0, 1.0, 1.0)
    tpus = set(jax.devices())
    accel = jax.devices()[0].platform      # the device phase checked: tpu
    used = set()
    with jax.default_matmul_precision("highest"):
        for name, (g, make) in pipeline_graphs(seed).items():
            env = make(np.random.default_rng(seed))
            ref = np.asarray(g.execute(dict(env))[g.outputs[0]])
            plan = compile_plan(g, ParallaxConfig())
            runs = [("hetero, permissive profile", dict(
                mode="parallax-hetero", hetero_profile=perm))]
            if not four_chips:
                runs = [("per-layer", dict(mode="parallax")),
                        ("whole-plan", dict(mode="parallax",
                                            whole_plan=True)),
                        *runs,
                        ("hetero, plan's own profile",
                         dict(mode="parallax-hetero"))]
            for label, kw in runs:
                ex = PlanExecutor(plan, **kw)
                got = np.asarray(ex(env).outputs[g.outputs[0]])
                err = float(np.abs(got - ref).max() / np.abs(ref).max())
                check(err <= PIPE_TOL, f"{name} {label}: max error "
                      f"{err:.2e} of the output scale (limit {PIPE_TOL})")
                if ex.hetero_stats is None:
                    st = ex.compiled.stats
                    print(f"{name} {label}: error {err:.2e}, "
                          f"batched_groups {st.batched_groups}, "
                          f"gemm_sites {st.gemm_sites}, dispatches "
                          f"{ex.last_dispatch_count}", flush=True)
                    continue
                st = ex.hetero_stats
                dmap = resolve_devices(ex.plan.placement)
                per_dev = {}
                for dev, n in sorted(ex.last_device_dispatches.items()):
                    phys = dmap[dev]
                    want = "cpu" if dev[0] == HOST else accel
                    check(phys.platform == want,
                          f"{name} {label}: {dev} ran on {phys}")
                    per_dev[f"{dev[0]}{dev[1]}->{phys.platform}"
                            f"{phys.id}"] = n
                print(f"{name} {label}: error {err:.2e}, batched_groups "
                      f"{st.batched_groups}, gemm_sites {st.gemm_sites}, "
                      f"dispatches per device {per_dev}", flush=True)
                used |= {dmap[d] for d in ex.last_device_dispatches
                         if d[0] == ACCEL}
    if four_chips:
        # the DAG's head branches hold a host fallback (RoPE), so it is
        # the multihead graph's group that spreads over the pool
        check(used == tpus,
              f"dispatched to {len(used)} of {len(tpus)} TPUs")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the hetero executor over 4 TPUs")
    args = ap.parse_args(argv)

    keep_cpu_platform()
    sys.path.insert(0, str(ROOT / "src"))
    from repro.utils.compile_cache import enable_compile_cache
    devs = device_phase(4 if args.four_chips else 1)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    log = CompileLog()
    t0 = time.perf_counter()
    if not args.four_chips:
        serve_phase(args.seed, log)
    pipeline_phase(args.seed, args.four_chips)
    print(f"total: {time.perf_counter() - t0:.1f} s; {log.line()}",
          flush=True)
    d0 = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
