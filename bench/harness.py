"""The chip benchmark's harness: one cell, one seed, one window.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by its name in ``BENCHMARK.json``:

    bench/configs/<config>.json        sizes, source, reference, limit
    bench/references/<reference>.py    plain reference + weights
    bench/traffic/<traffic>.json       lengths, arrivals, engine sizes
    bench/loops/<loop>.py              generator and clock loop of a loop kind
    bench/metrics/<metric>.py          ``read(ctx)`` of a per-layer metric

A run, in order: the device check (a TPU, as many chips as the cell asks
for, or exit without a result); JAX's compilation cache at
``<checkout>/.jax_cache``; the weights from the seed in one jitted call;
the engine, built after them so its KV budget is what they leave free;
one call of every program the cell's traffic can reach (prefill chunk,
single decode step, megastep lengths 2..N); the window, driven by the
loop kind; then the comparison with the reference once the engine is
freed.  The last line of standard output is one JSON object.

``run(..., rehearsal=True)`` is the CPU path the tests use: no device
check, no compilation cache, and the configuration and traffic cut to
``shrink``'s sizes.  It is not reachable from ``run.py``.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import check, cost, trace  # noqa: E402

#: Share of the window that passes before the profiler starts, and the
#: shortest and longest traced stretch (it ends at a step boundary once
#: a chunk and a decode program have both run, or at the longest).
TRACE_AT, TRACE_MIN_S, TRACE_MAX_S = 0.3, 3.0, 12.0
#: The engine counters whose change over the traced window metrics read.
COUNTERS = ("engine.fused_iterations",)


class Layout:
    """Finds a cell's files by name, under ``roots`` in order."""

    def __init__(self, benchmark=ROOT / "BENCHMARK.json", roots=(BENCH,)):
        self.bench = json.loads(pathlib.Path(benchmark).read_text())
        self.roots = [pathlib.Path(r) for r in roots]

    def find(self, sub: str, name: str, ext: str) -> pathlib.Path:
        for r in self.roots:
            p = r / sub / f"{name}{ext}"
            if p.exists():
                return p
        raise FileNotFoundError(f"no {sub}/{name}{ext} under "
                                f"{[str(r) for r in self.roots]}")

    def json(self, sub: str, name: str) -> dict:
        return json.loads(self.find(sub, name, ".json").read_text())

    def module(self, sub: str, name: str):
        path = self.find(sub, name, ".py")
        key = f"bench_{sub}_{name}".replace("-", "_").replace(".", "_")
        if key in sys.modules:
            return sys.modules[key]
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
        return mod

    def workload(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")

    def metrics(self, section: str, workload: str) -> list[dict]:
        return [m for m in self.bench[section]
                if workload in m.get("workloads", [workload])]


# -- configuration -----------------------------------------------------------

#: configuration-file key -> the program's ModelConfig field
PROGRAM_KEYS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "norm_type": "norm_type",
    "rope_theta": "rope_theta", "sliding_window": "sliding_window",
    "use_qkv_bias": "qkv_bias", "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype", "hidden_act": "act",
}


def program_config(cfg: dict, strict: bool = True):
    """The program's ModelConfig for the configuration file.  ``strict``
    (every chip run) requires the program's registry entry to hold the
    file's sizes already; the rehearsal builds a cut one."""
    from repro.configs import get_config
    base = get_config(cfg["registry"])
    want = {f: type(getattr(base, f))(cfg[k]) for k, f in PROGRAM_KEYS.items()}
    want["head_dim"] = cfg["head_dim"]
    if strict:
        got = {f: getattr(base, f) for f in want}
        got["head_dim"] = base.resolved_head_dim()
        diff = {f: (got[f], v) for f, v in want.items() if got[f] != v}
        if diff:
            raise SystemExit(f"{cfg['name']}: the program's registry differs "
                             f"from the configuration file: {diff}")
    return dataclasses.replace(base, **want)


def shrink(cfg: dict, traffic: dict) -> "tuple[dict, dict]":
    """The rehearsal's sizes: 2 layers, width 256, at most 4 heads (the
    GQA ratio kept), vocab 512; lengths and contexts cut by 16."""
    c = dict(cfg)
    H = min(4, cfg["num_attention_heads"])
    K = max(1, H * cfg["num_key_value_heads"] // cfg["num_attention_heads"])
    c.update(num_hidden_layers=2, hidden_size=256, num_attention_heads=H,
             num_key_value_heads=K, head_dim=256 // H, intermediate_size=512,
             vocab_size=512)
    t = json.loads(json.dumps(traffic))
    for key in ("prompt", "output"):
        spec = t[key]
        for f in ("lo", "hi", "median"):
            if f in spec:
                spec[f] = max(2, spec[f] // 16)
    eng = t["engine"]
    eng["max_context"] = t["prompt"]["hi"] + t["output"]["hi"]
    eng["max_batch"] = min(eng["max_batch"], 4)
    if "clients" in t:
        t["clients"] = eng["max_batch"]
    t["check"] = dict(t["check"], requests=2, min_requests=1)
    return c, t


# -- the client: the harness's side of every call into the engine ------------

@dataclasses.dataclass
class Record:
    prompt: np.ndarray
    max_new: int
    sent: float                 # due (open loop) or sent (closed loop)
    first: "float | None" = None
    last_in_window: "float | None" = None
    n_in_window: int = 0
    ctx_in_window: int = 0      # tokens in its KV cache, last step inside
    tokens: "list[int] | None" = None
    status: "str | None" = None
    done_at: "float | None" = None


class Client:
    """Submits, steps, drains and idles on the engine's behalf, stamping
    every delivery on the host clock from the window's start.  Tokens are
    delivered when ``step`` returns: the live rows' streams, read from the
    engine's slots, and the completions it drained."""

    def __init__(self, engine, seconds: float, annotate, tracer=None):
        self.engine = engine
        self.seconds = seconds
        self.annotate = annotate
        self.tracer = tracer
        self.records: dict[int, Record] = {}
        self.steps: list[dict] = []
        self.lateness: list[float] = []
        self.t0 = time.perf_counter()
        self._fused = engine.telemetry.metrics.counter(
            "engine.fused_iterations")

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def has_work(self) -> bool:
        return self.engine.has_work()

    def submit(self, req: dict, sent: float) -> None:
        from repro.runtime.engine import Request
        t = self.now()
        with self.annotate("client.submit"):
            self.engine.submit(Request(id=req["id"], prompt=req["prompt"],
                                       max_new_tokens=req["max_new"]))
        self.records[req["id"]] = Record(req["prompt"], req["max_new"], sent)
        self.lateness.append(t - sent)

    def idle(self, until: float) -> None:
        wait = until - self.now()
        if wait > 0:
            with self.annotate("client.idle"):
                time.sleep(wait)

    def _deliver(self, rid: int, tokens, t: float, ctx: int) -> None:
        r = self.records[rid]
        n = len(tokens)
        if n and r.first is None:
            r.first = t
        if t <= self.seconds:
            r.ctx_in_window = max(r.ctx_in_window, ctx)
            if n > r.n_in_window:
                r.n_in_window, r.last_in_window = n, t

    def step(self) -> list[int]:
        """One engine step; returns the ids it completed."""
        if self.tracer is not None:
            self.tracer.at_boundary(self.now())
        fused0 = self._fused.value
        t0 = self.now()
        with self.annotate("engine.step"):
            self.engine.step()
        t1 = self.now()
        with self.annotate("client.drain"):
            done = self.engine.drain_completions()
        eng = self.engine
        ctx_end = []
        for s, seq in enumerate(eng.slots):
            if seq is not None:
                ctx_end.append(int(eng.slot_len[s]))
                self._deliver(seq.req.id, seq.gen, t1, ctx_end[-1])
        finished = []
        for c in done:
            r = self.records[c.request_id]
            ctx_end.append(len(r.prompt) + max(len(c.tokens) - 1, 0))
            self._deliver(c.request_id, c.tokens, t1, ctx_end[-1])
            r.tokens, r.status, r.done_at = list(c.tokens), c.status, t1
            if c.ok:
                finished.append(c.request_id)
        self.steps.append({"t0": t0, "t1": t1, "ctx_end": ctx_end,
                           "executed": self._fused.value - fused0})
        return finished


class Tracer:
    """One profiler window inside the measured one, opened and closed at
    step boundaries, so it holds whole steps only."""

    def __init__(self, client_engine, seconds: float, out_dir: pathlib.Path):
        self.engine = client_engine
        self.start_at = TRACE_AT * seconds
        self.out_dir = out_dir
        self.t_a = self.t_b = None
        self.c_a = self.c_b = None
        self.stop_s = 0.0
        self._ev0 = 0

    def _counters(self) -> dict:
        snap = self.engine.telemetry.metrics.snapshot()["counters"]
        return {k: snap.get(k, 0) for k in COUNTERS}

    def at_boundary(self, now: float) -> None:
        import jax
        if self.t_a is None:
            if now >= self.start_at:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                shutil.rmtree(self.out_dir, ignore_errors=True)
                self.c_a = self._counters()
                self._ev0 = len(self.engine.telemetry.events)
                jax.profiler.start_trace(str(self.out_dir),
                                         profiler_options=opts)
                self.t_a = now
            return
        if self.t_b is not None:
            return
        kinds = {e["kind"] for e in self.engine.telemetry.events[self._ev0:]}
        seen = "prefill_chunk" in kinds and kinds & {"megastep", "decode"}
        if (now - self.t_a >= TRACE_MIN_S and seen) \
                or now - self.t_a >= TRACE_MAX_S:
            self.stop(now)

    def stop(self, now: float) -> None:
        import jax
        if self.t_a is not None and self.t_b is None:
            self.t_b = now
            self.c_b = self._counters()
            t = time.perf_counter()
            jax.profiler.stop_trace()
            self.stop_s = time.perf_counter() - t

    def xplane(self) -> "pathlib.Path | None":
        found = sorted(self.out_dir.glob("plugins/profile/*/*.xplane.pb"))
        return found[-1] if found else None


class LayerContext:
    """What a per-layer metric's ``read(ctx)`` may look at."""

    def __init__(self, *, events, trace_, counters, steps, cost_, kind,
                 max_batch, e2e=None):
        self.events = events            # engine telemetry, measured window
        self.e2e = e2e or {}            # the window's end-to-end numbers
        self.trace = trace_             # bench.trace.Trace or None
        self.counters = counters        # engine counter deltas, traced
        self.steps = steps              # harness step records, traced
        self.cost = cost_               # cost.StepCost
        self.kind = kind                # device_kind
        self.max_batch = max_batch

    def peaks(self) -> dict:
        return cost.peaks(self.kind)

    def trace_program_time(self, names) -> "tuple[float, int]":
        return trace.program_time(self.trace, names)

    def step_needs(self):
        """(FLOPs, bytes) of every decode step executed in the traced
        window: each row that ran in an engine step, at the contexts it
        attended over, counted back from its length after the step."""
        for st in self.steps:
            n = st["executed"]
            ends = np.asarray(st["ctx_end"], np.int64)
            for s in range(n):
                yield self.cost.step(np.maximum(ends - n + s + 1, 1))


# -- end-to-end metrics --------------------------------------------------------

def p95(values) -> float:
    """Nearest-rank 95th percentile (``inf`` stays ``inf``)."""
    v = sorted(values)
    return v[max(0, math.ceil(0.95 * len(v)) - 1)] if v else float("nan")


def end_to_end(client: Client, seconds: float) -> "tuple[dict, dict]":
    recs = client.records.values()
    ttft, tpot, cut = [], [], 0
    for r in recs:
        if r.status is not None and r.status != "completed":
            ttft.append(float("inf"))
        elif r.first is not None and r.first <= seconds:
            ttft.append(r.first - r.sent)
        else:
            ttft.append(seconds - r.sent)
            cut += 1
        if r.n_in_window >= 2:
            tpot.append((r.last_in_window - r.first) / (r.n_in_window - 1))
    tokens = sum(r.n_in_window for r in recs)
    written = sum(r.ctx_in_window for r in recs)
    late = sorted(client.lateness) or [0.0]
    info = {"requests": len(client.records), "ttft_samples": len(ttft),
            "tpot_samples": len(tpot), "cut_off": cut,
            "tokens_in_window": tokens, "kv_tokens_in_window": written,
            "completed_in_window": sum(1 for r in recs if r.status ==
                                       "completed" and r.done_at <= seconds),
            "late_p50_s": late[len(late) // 2], "late_max_s": late[-1]}
    return {"output_tok_s": tokens / seconds,
            "total_tok_s": written / seconds,
            "ttft_p95_s": p95(ttft),
            "tpot_p95_ms": p95(tpot) * 1e3}, info


# -- the run -------------------------------------------------------------------

def _annotator(on: bool):
    import contextlib

    import jax
    if on:
        return jax.profiler.TraceAnnotation
    return lambda name: contextlib.nullcontext()


def device_check(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU: JAX's first device is "
                         f"{devs[0].platform!r}; no result")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}; no result")
    return devs


def enable_cache() -> str:
    import jax
    path = ROOT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(path)


class CompileLog:
    """Backend compilations and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def count(self) -> int:
        return self.compiles + self.cache_hits


def check_weights(api, params) -> None:
    """The reference's weight tree must be the one the program serves."""
    import jax
    want = jax.eval_shape(api.init, jax.random.key(0))
    if jax.tree.structure(want) != jax.tree.structure(params):
        raise SystemExit("bench: the reference's weight tree is not the "
                         "program's")
    for w, p in zip(jax.tree.leaves(want), jax.tree.leaves(params)):
        if w.shape != p.shape:
            raise SystemExit(f"bench: weight shape {p.shape} where the "
                             f"program has {w.shape}")


def warm_up(engine, megastep: int) -> None:
    """One call of every program the window can reach, at its shapes:
    the prefill chunk, the single decode step and each megastep length
    2..``megastep``, all with every row inactive."""
    import jax
    st, B = engine.stepper, engine.max_batch
    z = np.zeros(B, np.int32)
    off = np.zeros(B, bool)
    # one call at a time: each returns a whole new KV pool
    jax.block_until_ready(st.prefill_chunk(
        engine.params, engine.caches,
        np.zeros((B, engine.prefill_chunk), np.int32), z, z,
        block_tables=engine.tables))
    jax.block_until_ready(st.decode(engine.params, engine.caches, z, z, off,
                                    block_tables=engine.tables))
    for n in range(2, megastep + 1):
        jax.block_until_ready(st.megastep(
            engine.params, engine.caches, z, z, off, z,
            np.zeros((B, n), np.int32), z, np.full(B, -1, np.int32),
            block_tables=engine.tables))


class Cell:
    """One cell's set-up pieces, reusable across seeds and windows in one
    process (the knee sweep and the calibration share one compile)."""

    def __init__(self, workload: str, layout: "Layout | None" = None,
                 rehearsal: bool = False, log=print):
        self.layout = layout = layout or Layout()
        self.workload = workload
        self.spec = layout.workload(workload)
        self.cfg = layout.json("configs", self.spec["config"])
        self.traffic = layout.json("traffic", self.spec["traffic"])
        if rehearsal:
            self.cfg, self.traffic = shrink(self.cfg, self.traffic)
        else:
            devs = device_check(self.spec["chips"])
            log(f"device: {devs[0].device_kind}, {len(devs)} device(s)")
            log(f"compile cache: {enable_cache()}")
        from repro.models import build_model
        self.compiles = CompileLog()
        self.api = build_model(program_config(self.cfg, strict=not rehearsal))
        self.reference = layout.module("references", self.cfg["reference"])
        self.loop = layout.module("loops", self.traffic["loop"])
        self.eng = self.traffic["engine"]
        self.stepper = None

    def weights(self, seed: int):
        import jax
        import jax.numpy as jnp
        params = jax.block_until_ready(self.reference.init_params(
            self.cfg, seed, jnp.dtype(self.cfg["torch_dtype"])))
        check_weights(self.api, params)
        return params

    def engine(self, params, traced: bool):
        """A fresh engine on ``params``; the first one's programs are
        warmed up, later ones share its stepper (and so its compiles)."""
        from repro.runtime.config import EngineConfig
        from repro.runtime.engine import ContinuousEngine
        from repro.runtime.telemetry import Telemetry
        engine = ContinuousEngine(
            self.api, params, telemetry=Telemetry(trace=traced),
            stepper=self.stepper,
            config=EngineConfig(host_pool=0, prefix_cache=False,
                                fault_seed=None, max_queue=None, **self.eng))
        if self.stepper is None:
            warm_up(engine, self.eng["megastep"])
            self.stepper = engine.stepper
        return engine

    def plan(self, seed: int, seconds: float, **kw) -> list[dict]:
        return self.loop.plan(self.traffic, seed, seconds,
                              self.cfg["vocab_size"], **kw)

    def window(self, engine, reqs, seconds: float, traced: bool):
        """Drive one window; returns (client, tracer, compiles inside)."""
        tracer = Tracer(engine, seconds, ROOT / ".bench_trace") \
            if traced else None
        client = Client(engine, seconds, _annotator(traced), tracer)
        c0 = self.compiles.count()
        self.loop.drive(client, self.traffic, reqs, seconds)
        if tracer is not None:
            tracer.stop(client.now())
        return client, tracer, self.compiles.count() - c0

    def compare(self, params, client, seed: int, control: bool = False):
        """The sample of ``client``'s completed requests against the
        reference; call once the engine is freed."""
        items = check.sample(client.records, client.seconds,
                             self.traffic["check"], seed)
        reading = check.compare(self.reference.Reference(self.cfg), params,
                                items, self.eng["max_context"], control)
        reading["requests"] = len(items)
        return reading


def run(workload: str, seed: int, seconds: float, traced: bool, *,
        layout: "Layout | None" = None, rehearsal: bool = False,
        t_start: "float | None" = None, log=print) -> dict:
    """One run of ``workload``; returns the result object (also printed
    as the last line by ``main``)."""
    import jax
    t_start = time.perf_counter() if t_start is None else t_start
    cell = Cell(workload, layout, rehearsal, log)
    devs = jax.devices()
    params = cell.weights(seed)
    engine = cell.engine(params, traced)
    reqs = cell.plan(seed, seconds)
    setup_s = time.perf_counter() - t_start
    log(f"set-up: {setup_s:.3f} s; {cell.compiles.compiles} backend "
        f"compiles, {cell.compiles.cache_hits} persistent-cache hits")

    client, tracer, in_window = cell.window(engine, reqs, seconds, traced)
    window_end = client.now()
    e2e, info = end_to_end(client, seconds)
    peak = peak_bytes(devs[0], log)
    e2e["peak_hbm_gib"] = peak / 2 ** 30
    e2e["setup_s"] = setup_s
    log(f"window: {seconds} s (loop ended at {window_end:.3f} s); "
        f"compiles inside the window: {in_window}")
    log("requests: " + ", ".join(f"{k} {v}" for k, v in info.items()))
    log("end-to-end: " + ", ".join(f"{k} {v}" for k, v in e2e.items()))

    result = {"correct": False, "attempted": info["requests"],
              "failed": sum(1 for r in client.records.values()
                            if r.status not in (None, "completed")),
              "metrics": {},
              "device": {"platform": devs[0].platform,
                         "kind": devs[0].device_kind, "count": len(devs),
                         "memory_peak_bytes": peak}}
    if traced:
        result["metrics"] = per_layer(cell, engine, client, tracer,
                                      devs[0].device_kind, result, log, e2e)
    else:
        for m in cell.layout.metrics("end_to_end", workload):
            v = e2e[m["name"]]
            result["metrics"][m["name"]] = {
                "value": v if math.isfinite(v) else None, "unit": m["unit"]}

    client.engine = engine = tracer = None
    gc.collect()
    reading = cell.compare(params, client, seed)
    log(f"reference: {reading['requests']} requests, {reading['tokens']} "
        f"served tokens compared, {reading['exact']} the reference's argmax")
    result["correct"], result["checks"] = verdict(
        cell.cfg, cell.traffic, reading)
    return result


def peak_bytes(dev, log) -> int:
    """The chip's peak: buffers (``peak_bytes_in_use``) plus what the
    runtime reserved for the loaded programs' scratch
    (``peak_bytes_reserved``, where the runtime reports it)."""
    stats = dev.memory_stats() or {}
    log("memory: " + ", ".join(f"{k} {v}" for k, v in sorted(stats.items())
                               if "bytes" in k))
    return int(stats.get("peak_bytes_in_use", 0)) + \
        int(stats.get("peak_bytes_reserved", 0))


def verdict(cfg: dict, traffic: dict, reading: dict) -> "tuple[bool, dict]":
    """``correct`` and the numbers compared, each beside its limit: the
    widest gap at most the configuration's ``max_logit_gap``, and at
    least the traffic's ``check.min_requests`` requests compared (the
    sample takes up to ``check.requests``; fewer complete in a window
    whose requests are long)."""
    limit = cfg["check"]["max_logit_gap"]
    need = traffic["check"]["min_requests"]
    checks = {"max_logit_gap": {"value": reading["max_gap"], "limit": limit},
              "requests_compared": {"value": reading["requests"],
                                    "limit": need}}
    ok = (limit is not None and reading["requests"] >= need
          and reading["max_gap"] <= limit)
    return ok, checks


def per_layer(cell, engine, client, tracer, kind, result, log,
              e2e=None) -> dict:
    events = engine.telemetry.events
    t0 = client.t0
    window = [e for e in events if 0 <= e["ts"] - t0 <= client.seconds]
    tr = None
    steps, counters = [], {}
    if tracer.t_b is not None:
        path = tracer.xplane()
        if path is not None:
            tr = trace.load(path, tracer.t_b - tracer.t_a)
        shutil.rmtree(tracer.out_dir, ignore_errors=True)
        steps = [s for s in client.steps
                 if s["t0"] >= tracer.t_a and s["t1"] <= tracer.t_b]
        counters = {k: tracer.c_b[k] - tracer.c_a[k] for k in COUNTERS}
    ctx = LayerContext(events=window, trace_=tr, counters=counters,
                       steps=steps, cost_=cost.StepCost(cell.cfg), kind=kind,
                       max_batch=cell.eng["max_batch"], e2e=e2e)
    out = {}
    for m in cell.layout.metrics("per_layer", cell.workload):
        v = cell.layout.module("metrics", m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    if tr is not None and tr.devices:
        result["device"]["busy_s"] = trace.busy_s(tr)
        result["device"]["window_s"] = tr.window_s
        result["breakdown"] = trace.breakdown(tr)
        log(f"trace: from {tracer.t_a:.3f} s to {tracer.t_b:.3f} s of the "
            f"window (writing it took {tracer.stop_s:.3f} s), "
            f"{tr.window_s:.3f} s window, {len(steps)} steps, "
              f"{sum(len(p) for p in tr.programs)} program runs, "
              f"{sum(len(o) for o in tr.ops)} operations, "
              f"{len(tr.host)} host annotations")
    return out


def emit(result: dict) -> None:
    """The compared numbers beside their limits, last on standard error;
    the result object last on standard output, its checks last in it."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    checks = result.pop("checks")
    result["checks"] = checks
    print(json.dumps(result), flush=True)


def main(argv=None, t_start: "float | None" = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell on "
                                 "the chip this process finds.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    result = run(a.workload, a.seed, a.seconds, bool(a.trace),
                 t_start=t_start,
                 log=lambda s: print(s, file=sys.stderr, flush=True))
    emit(result)
    return 0
