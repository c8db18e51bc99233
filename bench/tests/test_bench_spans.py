"""The program's own spans in a profiler trace: the engine's phases as
``engine.<kind>`` host annotations, recorded on the CPU from a small
engine, and the reduction of engine phases and model scopes
(``bench/spans.py``) on synthetic spans."""

import jax
import numpy as np
import pytest

from bench import cost, spans, trace
from bench.harness import Layout, LayerContext
from repro.configs import get_config
from repro.models import build_model
from repro.runtime import stepper
from repro.runtime.config import EngineConfig
from repro.runtime.engine import ContinuousEngine, Request
from repro.runtime.telemetry import Telemetry

LAYOUT = Layout()
#: the spans a sync or dispatch nests in, inside its iteration
STEP_KINDS = ("megastep", "decode", "prefill_chunk")


@pytest.fixture(scope="module")
def model():
    api = build_model(get_config("stablelm-3b").reduced())
    return api, api.init(jax.random.key(0))


def _profiled_run(model, traced: bool, out) -> "tuple[spans.Spans, list]":
    """A megastep engine serving prompts long enough for the chunked
    prefill, under the profiler, inside the harness's annotation: the
    trace's spans, the engine's events, and the traced programs' name
    stacks."""
    api, params = model
    tele = Telemetry(trace=traced)
    eng = ContinuousEngine(api, params, telemetry=tele, config=EngineConfig(
        hbm_budget=1 << 30, max_batch=2, block_size=4, max_context=40,
        megastep=4, prefill_chunk=4))
    rng = np.random.default_rng(0)
    for i in range(3):
        eng.submit(Request(i, rng.integers(0, api.cfg.vocab_size, 11)
                           .astype(np.int32), max_new_tokens=6))
    jax.profiler.start_trace(str(out))
    try:
        while eng.has_work():
            with jax.profiler.TraceAnnotation("engine.step"):
                eng.step()
    finally:
        jax.profiler.stop_trace()
    path = sorted(out.glob("plugins/profile/*/*.xplane.pb"))[-1]
    return spans.load(path), tele.events, spans.name_stacks(path)


@pytest.fixture(scope="module")
def traced_run(model, tmp_path_factory):
    return _profiled_run(model, True, tmp_path_factory.mktemp("traced"))


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_engine_phases_on_the_profiler_clock(traced_run):
    sp, events, _ = traced_run
    kinds = {k for k, _, _ in sp.host}
    assert {"iteration", "schedule", "plan", "dispatch", "sync",
            "reconcile", "megastep", "prefill_chunk"} <= kinds
    # the profiler holds the very spans the recorder holds
    for kind in kinds:
        assert sum(k == kind for k, _, _ in sp.host) == \
            sum(e["kind"] == kind for e in events), kind
    iters = [h for h in sp.host if h[0] == "iteration"]
    steps = [h for h in sp.host if h[0] in STEP_KINDS]
    for h in sp.host:
        if h[0] in ("sync", "dispatch"):
            assert any(_inside(h, p) for p in steps), h
        if h[0] in STEP_KINDS + ("schedule", "plan", "reconcile"):
            assert any(_inside(h, it) for it in iters), h


def test_untraced_engine_writes_no_engine_annotation(model, tmp_path):
    sp, events, _ = _profiled_run(model, False, tmp_path)
    assert sp.host == [] and events == []


@pytest.mark.parametrize("program", ["jit_run", "jit_run_chunk"])
def test_name_stacks_from_the_metadata_plane(traced_run, program):
    """The HLO the profiler keeps for each traced program names every
    instruction's scope: the model's parts and the sampling tail."""
    _, _, stacks = traced_run
    runs = [k for k in stacks if trace.program_name(k) == program]
    assert runs
    scopes = {spans.scope_of(v) for k in runs for v in stacks[k].values()}
    assert {"embed", "norm", "attention", "mlp", "lm_head", "sample",
            spans.UNSCOPED} <= scopes
    stack = " ".join(v for k in runs for v in stacks[k].values())
    assert "/attention/kv_gather/" in stack


@pytest.mark.parametrize("metric,names", [
    ("decode_step_ms", ("MEGASTEP_PROGRAM", "DECODE_PROGRAM")),
    ("step_roofline", ("MEGASTEP_PROGRAM", "DECODE_PROGRAM")),
    ("step_mfu", ("MEGASTEP_PROGRAM", "DECODE_PROGRAM")),
    ("prefill_chunk_ms", ("CHUNK_PROGRAM",)),
    ("dispatch_gap_ms_p50", ("MEGASTEP_PROGRAM", "DECODE_PROGRAM",
                             "CHUNK_PROGRAM")),
])
def test_metrics_key_on_the_stepper_program_names(metric, names):
    want = tuple(getattr(stepper, n) for n in names)
    assert LAYOUT.module("metrics", metric).PROGRAMS == want
    if metric == "decode_step_ms":
        assert spans.DECODE == want


# -- the reduction on synthetic spans ----------------------------------------

def _trace():
    # one device; the host idles between 2.0 and 2.9
    progs = [("jit_run", 0.0, 1.0), ("jit_run_chunk", 1.1, 1.5),
             ("jit_run", 1.6, 2.0), ("jit_run", 2.9, 3.0)]
    ops = [("fusion.1", 0.0, 0.6), ("fusion.2", 0.6, 1.0),
           ("fusion.3", 1.1, 1.5), ("fusion.1", 1.6, 2.0),
           ("fusion.2", 2.9, 3.0)]
    host = [("engine.step", 0.0, 2.0), ("client.idle", 2.0, 2.9),
            ("engine.step", 2.9, 3.0)]
    return trace.Trace(programs=[progs], ops=[ops], host=host, window_s=4.0)


def _spans():
    host = [("iteration", 0.0, 1.07), ("megastep", 0.0, 1.0),
            ("reconcile", 1.0, 1.07), ("iteration", 1.07, 2.0),
            ("schedule", 1.07, 1.09), ("prefill_chunk", 1.09, 1.54),
            ("plan", 1.54, 1.61)]
    ops = [("jit_run", spans.UNSCOPED, "while.1", 0.0, 1.0),
           ("jit_run", "attention", "fusion.1", 0.0, 0.6),
           ("jit_run", "mlp", "fusion.2", 0.6, 0.9),
           ("jit_run_chunk", "attention", "fusion.3", 1.1, 1.5),
           ("jit_run", "attention", "fusion.1", 1.6, 1.8),
           ("jit_run", spans.UNSCOPED, "copy.5", 1.8, 2.0),
           ("jit_run", "sample", "fusion.2", 2.9, 3.0)]
    return spans.Spans(host=host, ops=[ops])


def _events():
    """The engine's events of two iterations, with their phases."""
    return [
        {"kind": "submit", "ts": 0.0, "request_id": 1},
        {"kind": "admit", "ts": 0.004, "request_id": 1},
        {"kind": "schedule", "ts": 0.0, "dur": 0.002, "iteration": 1},
        {"kind": "dispatch", "ts": 0.01, "dur": 0.003, "iteration": 1},
        {"kind": "sync", "ts": 0.013, "dur": 0.030, "iteration": 1},
        {"kind": "megastep", "ts": 0.01, "dur": 0.033, "iteration": 1,
         "args": {"n": 8, "rows": 3, "executed": 8}},
        {"kind": "iteration", "ts": 0.0, "dur": 0.045, "iteration": 1},
        {"kind": "dispatch", "ts": 0.05, "dur": 0.002, "iteration": 2},
        {"kind": "sync", "ts": 0.052, "dur": 0.010, "iteration": 2},
        {"kind": "decode", "ts": 0.05, "dur": 0.012, "iteration": 2,
         "args": {"rows": 1}},
        {"kind": "iteration", "ts": 0.045, "dur": 0.019, "iteration": 2},
    ]


def _ctx(tr, events):
    cfg = LAYOUT.json("configs", "stablelm-3b")
    steps = [{"t0": 0.0, "t1": 2.0, "executed": 8, "ctx_end": [100, 200]},
             {"t0": 2.9, "t1": 3.0, "executed": 2, "ctx_end": [110]}]
    return LayerContext(events=events, trace_=tr,
                        counters={"engine.fused_iterations": 10},
                        steps=steps, cost_=cost.StepCost(cfg),
                        kind="TPU v5 lite", max_batch=4)


EXISTING = ("queue_wait_ms_p50", "decode_tpot_p95_ms", "batch_occupancy",
            "decode_step_ms", "prefill_chunk_ms", "step_roofline",
            "step_mfu", "device_idle_share", "dispatch_gap_ms_p50")


@pytest.mark.parametrize("metric", EXISTING)
def test_existing_readers_ignore_the_engine_phases(metric):
    events = _events()
    phases = {"schedule", "plan", "dispatch", "sync", "iteration"}
    without = [e for e in events if e["kind"] not in phases]
    read = LAYOUT.module("metrics", metric).read
    assert read(_ctx(_trace(), events)) == read(_ctx(_trace(), without))


def test_engine_host_ms_p50_reader():
    read = LAYOUT.module("metrics", "engine_host_ms_p50").read
    # iterations 45 - 30 = 15 ms and 19 - 10 = 9 ms of host time
    assert read(_ctx(_trace(), _events())) == pytest.approx(12.0)
    # no device trace, or no sync spans (a program without them): nothing
    assert read(_ctx(None, _events())) is None
    assert read(_ctx(_trace(), [e for e in _events()
                                if e["kind"] != "sync"])) is None


def test_idle_gaps_take_the_innermost_engine_phase():
    tr, sp = _trace(), _spans()
    # the iteration covers more than half of each gap too
    assert spans.phase(sp, 1.0, 1.1) == "engine.reconcile"
    assert spans.phase(sp, 1.5, 1.6) == "engine.plan"
    # no phase covers more than half of it
    assert spans.phase(sp, 1.9, 2.9) is None
    assert spans.idle_gaps(tr, sp) == [
        ["client.idle", pytest.approx(0.9)],
        ["engine.reconcile", pytest.approx(0.1)],
        ["engine.plan", pytest.approx(0.1)]]
    # split by the innermost span at each instant: 1.0-1.07 reconcile,
    # 1.07-1.09 schedule, 1.09-1.1 prefill_chunk; 1.5-1.54 prefill_chunk,
    # 1.54-1.6 plan
    assert spans.idle_by_phase(tr, sp) == pytest.approx(
        {"client.idle": 0.9, "engine.reconcile": 0.07, "engine.plan": 0.06,
         "engine.prefill_chunk": 0.05, "engine.schedule": 0.02})


def test_device_scopes_of_the_decode_programs():
    sp = _spans()
    # the loop's own time is what its body leaves uncovered (0.1 s); the
    # chunk program is not a decode program
    assert spans.device_scopes(sp) == pytest.approx(
        {"attention": 0.8, "mlp": 0.3, "unscoped": 0.3, "sample": 0.1})
    assert spans.attention_ms_per_step(sp, 10) == pytest.approx(80.0)
    assert spans.pool_carry_ms_per_step(sp, 10) == pytest.approx(30.0)
    assert spans.scope_ops(sp, spans.UNSCOPED) == [
        ["copy.5", pytest.approx(0.2)], ["while.1", pytest.approx(0.1)]]
    # a program without model scopes, or no steps: nothing
    bare = spans.Spans(ops=[[(p, spans.UNSCOPED, n, s, e)
                             for p, _, n, s, e in sp.ops[0]]])
    assert spans.pool_carry_ms_per_step(bare, 10) is None
    assert spans.attention_ms_per_step(sp, 0) is None


@pytest.mark.parametrize("stack,scope", [
    ("jit(run)/while/body/closed_call/attention/kv_gather/gather",
     "attention"),
    ("jit(run)/while/body/closed_call/mlp/...d,df->...f/dot_general",
     "mlp"),
    ("jit(step)/lm_head/bd,dv->bv/dot_general", "lm_head"),
    ("jit(run)/while/body/sample/argmax", "sample"),
    ("jit(run)/while/body/closed_call/while/body/dynamic_slice",
     spans.UNSCOPED),
    ("", spans.UNSCOPED),
])
def test_scope_of_a_name_stack(stack, scope):
    assert spans.scope_of(stack) == scope
