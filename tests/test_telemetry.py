"""Telemetry plane: metrics registry, span recorder, trace exporters.

Unit tests cover registry semantics (typed create-or-get, counter
monotonicity, gauge high-water, fixed log-bucket histograms), the
recorder's disabled fast path, the Chrome trace validator's rejection
cases, and per-request timelines.  Engine-level tests assert the two
contracts the plane makes: snapshots are *deterministic* (two identical
seeded runs produce identical stats) and every recorded event matches
the fixed span taxonomy.  The hard invariant — tracing ON changes zero
behavior — needs bit-stable greedy streams, so it runs in the pinned
child process (tests/serving_identity_child.py ``--tele``) like every
other stream-identity check.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.runtime.config import EngineConfig
from repro.runtime.engine import ContinuousEngine, Request
from repro.runtime.faults import FaultEvent, FaultPlane
from repro.runtime.kv_cache import BlockKVCache
from repro.runtime.stepper import Stepper
from repro.runtime.telemetry import (DURATION_KINDS, POINT_KINDS,
                                     REQUEST_KINDS, SPAN_KINDS, Counter,
                                     Gauge, Histogram, MetricsRegistry,
                                     SpanRecorder, Telemetry, chrome_trace,
                                     log_buckets, request_timelines,
                                     validate_chrome_trace)

CHILD = os.path.join(os.path.dirname(__file__),
                     "serving_identity_child.py")


# -- metrics registry --------------------------------------------------------

def test_counter_semantics():
    c = Counter("x")
    c.inc()
    c.inc(3)
    c.inc(0)                       # no-op increment is legal
    assert c.value == 4
    with pytest.raises(ValueError, match="negative"):
        c.inc(-1)
    assert c.value == 4            # rejected inc leaves value intact


def test_gauge_high_water():
    g = Gauge("x")
    assert g.value == 0 and g.high_water == 0
    g.set(5)
    g.set(2)
    assert g.value == 2
    assert g.high_water == 5       # high-water survives the drop
    g.set(9)
    assert g.high_water == 9


def test_log_buckets():
    assert log_buckets(1, 8, 2) == (1.0, 2.0, 4.0, 8.0)
    assert log_buckets(1, 5, 2) == (1.0, 2.0, 4.0, 8.0)  # first >= hi
    with pytest.raises(ValueError):
        log_buckets(0, 8)
    with pytest.raises(ValueError):
        log_buckets(1, 8, base=1)


def test_histogram_buckets_and_overflow():
    h = Histogram("x", bounds=(1, 4, 16))
    for v in (1, 2, 4, 5, 16, 17, 1000):
        h.observe(v)
    # bucket i counts v <= bounds[i]; last slot is the overflow
    assert h.counts == [1, 2, 2, 2]
    assert h.count == 7
    assert h.total == sum((1, 2, 4, 5, 16, 17, 1000))
    with pytest.raises(ValueError, match="ascending"):
        Histogram("bad", bounds=(4, 1))
    with pytest.raises(ValueError, match="ascending"):
        Histogram("bad", bounds=())


def test_registry_typed_create_or_get():
    m = MetricsRegistry()
    c = m.counter("a")
    assert m.counter("a") is c          # create-once, return-existing
    m.gauge("b")
    m.histogram("c")
    with pytest.raises(ValueError, match="already registered"):
        m.gauge("a")
    with pytest.raises(ValueError, match="already registered"):
        m.counter("b")
    assert m.names() == ["a", "b", "c"]


def test_registry_snapshot_structure():
    m = MetricsRegistry()
    m.counter("z.count").inc(2)
    m.gauge("a.gauge").set(7)
    m.histogram("m.hist", bounds=(1, 2)).observe(2)
    snap = m.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    assert snap["counters"] == {"z.count": 2}
    assert snap["gauges"] == {"a.gauge": {"value": 7, "high_water": 7}}
    assert snap["histograms"]["m.hist"] == {
        "buckets": [1, 2], "counts": [0, 1, 0], "sum": 2, "count": 1}
    json.dumps(snap)                    # JSON-ready, no numpy leakage
    assert snap == m.snapshot()         # snapshotting is read-only


# -- span recorder -----------------------------------------------------------

def test_recorder_disabled_is_inert():
    rec = SpanRecorder(False)
    assert rec.now() == 0.0             # clock untouched when disabled
    rec.point("submit", request_id=1)
    rec.span("decode", rec.now(), iteration=3)
    assert rec.events == []


class _Annotations:
    """Stands in for ``jax.profiler``'s annotations: records each one's
    name, metadata and entry/exit."""

    def __init__(self):
        self.log = []

    def make(self, name, **meta):
        log = self.log

        class Ann:
            def __enter__(self):
                log.append(("enter", name, meta))

            def __exit__(self, *exc):
                log.append(("exit", name, meta))

        return Ann()


def test_region_records_and_annotates(monkeypatch):
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann.make)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation",
                        lambda name, step_num: ann.make(name,
                                                        step=step_num))
    rec = SpanRecorder(True)
    with rec.region("iteration", iteration=4, active=2) as it:
        with rec.region("sync", iteration=4):
            pass
        it.update(waiting=1)
        it.update(kv_blocks=3)
    sync, iteration = rec.events
    assert sync["kind"] == "sync" and "args" not in sync
    assert iteration == {"kind": "iteration", "ts": iteration["ts"],
                         "dur": iteration["dur"], "iteration": 4,
                         "args": {"active": 2, "waiting": 1,
                                  "kv_blocks": 3}}
    assert iteration["ts"] <= sync["ts"]
    assert sync["ts"] + sync["dur"] <= iteration["ts"] + iteration["dur"]
    # one profiler annotation per span, named engine.<kind>, nested as
    # the spans are, the iteration number as its metadata
    assert ann.log == [("enter", "engine.iteration", {"step": 4}),
                       ("enter", "engine.sync", {"iteration": 4}),
                       ("exit", "engine.sync", {"iteration": 4}),
                       ("exit", "engine.iteration", {"step": 4})]
    # a span left by an exception is recorded all the same
    with pytest.raises(KeyError):
        with rec.region("decode", rows=1):
            raise KeyError("x")
    assert rec.events[-1]["kind"] == "decode"
    assert ann.log[-1] == ("exit", "engine.decode", {})


def test_disabled_region_is_one_shared_noop():
    rec = SpanRecorder(False)
    r = rec.region("sync", iteration=1)
    assert r is rec.region("megastep", iteration=2, n=8)
    with r as span:
        span.update(executed=3, rows=2)
    assert rec.events == []


def test_recorder_event_schema():
    rec = SpanRecorder(True)
    t0 = rec.now()
    assert t0 > 0.0
    rec.span("decode", t0, iteration=2, rows=4)
    rec.point("submit", request_id=7, prompt_len=5)
    rec.point("admit", request_id=7, slot=1, iteration=2)
    span, sub, adm = rec.events
    assert span["kind"] == "decode" and span["ts"] == t0
    assert span["dur"] >= 0.0 and span["iteration"] == 2
    assert span["args"] == {"rows": 4}
    assert "dur" not in sub and sub["request_id"] == 7
    assert adm["slot"] == 1
    # taxonomy partitions cleanly; request-tagged kinds may live on
    # either side (spill/restore are durations — the transfer is timed)
    assert set(SPAN_KINDS) == set(DURATION_KINDS) | set(POINT_KINDS)
    assert REQUEST_KINDS <= set(SPAN_KINDS)
    assert {"spill", "restore"} <= REQUEST_KINDS & set(DURATION_KINDS)


def test_request_timelines_ordering():
    rec = SpanRecorder(True)
    rec.point("submit", request_id=1)
    rec.point("submit", request_id=2)
    rec.point("admit", request_id=1, slot=0)
    rec.span("decode", rec.now(), iteration=1)   # no request_id: dropped
    rec.point("complete", request_id=1, iteration=3)
    tl = request_timelines(rec.events)
    assert sorted(tl) == [1, 2]
    assert [e["kind"] for e in tl[1]] == ["submit", "admit", "complete"]
    assert [e["kind"] for e in tl[2]] == ["submit"]


# -- chrome trace exporter + validator ---------------------------------------

def _traced_lifecycle_events():
    rec = SpanRecorder(True)
    rec.point("submit", request_id=0, prompt_len=4)
    t = rec.now()
    rec.point("admit", request_id=0, slot=2, iteration=1)
    rec.span("prefill_chunk", t, iteration=1, rows=1)
    t = rec.now()
    rec.span("iteration", t, iteration=1, kv_blocks=3, kv_bytes=96,
             active=1, waiting=0)
    rec.point("fault", iteration=1, what="watchdog", where="decode")
    rec.point("preempt", request_id=0, iteration=1, reason="budget")
    rec.point("admit", request_id=0, slot=0, iteration=2)
    rec.point("complete", request_id=0, iteration=3, status="completed",
              reason=None, tokens=2)
    return rec.events


def test_chrome_trace_mapping():
    trace = chrome_trace(_traced_lifecycle_events())
    summary = validate_chrome_trace(
        trace, require_names=("iteration", "prefill_chunk", "kv_pool",
                              "fault", "req 0"))
    by_ph = summary["phases"]
    assert by_ph["b"] == 1 and by_ph["e"] == 1   # one async lifecycle
    assert by_ph["n"] == 3                       # admit x2 + preempt
    assert by_ph["C"] == 1                       # kv_pool sample
    assert by_ph["i"] == 1                       # fault instant
    # admit->preempt and admit->complete each close a slot residency
    # slice, on top of the two duration spans recorded directly
    assert by_ph["X"] == 4
    slot_tracks = [e for e in trace["traceEvents"]
                   if e["ph"] == "X" and e["pid"] == 3]
    assert sorted(e["tid"] for e in slot_tracks) == [0, 2]
    slot_labels = {e["args"]["name"] for e in trace["traceEvents"]
                   if e["ph"] == "M" and e["name"] == "thread_name"
                   and e["pid"] == 3}
    assert slot_labels == {"slot 0", "slot 2"}
    # round-trips through disk
    assert json.dumps(trace)


def test_validate_chrome_trace_rejections(tmp_path):
    ok = {"ph": "X", "name": "a", "pid": 1, "tid": 0, "ts": 0.0,
          "dur": 1.0}
    cases = [
        ({"events": []}, "no traceEvents"),
        ({"traceEvents": []}, "empty"),
        ({"traceEvents": ["nope"]}, "not an object"),
        ({"traceEvents": [dict(ok, ph="Q")]}, "unknown phase"),
        ({"traceEvents": [dict(ok, name="")]}, "missing name"),
        ({"traceEvents": [dict(ok, pid=-1)]}, "bad pid"),
        ({"traceEvents": [dict(ok, ts=-5)]}, "bad ts"),
        ({"traceEvents": [dict(ok, dur=None)]}, "bad dur"),
        ({"traceEvents": [{"ph": "e", "name": "r", "pid": 2, "tid": 0,
                           "ts": 0.0, "cat": "request", "id": "1"}]},
         "async end without begin"),
        ({"traceEvents": [{"ph": "b", "name": "r", "pid": 2, "tid": 0,
                           "ts": 0.0, "cat": "request", "id": "1"}]},
         "unbalanced"),
        ({"traceEvents": [{"ph": "C", "name": "c", "pid": 1, "tid": 0,
                           "ts": 0.0, "args": {"blocks": "many"}}]},
         "numeric args"),
        ({"traceEvents": [ok]}, "absent"),   # require_names miss
    ]
    for trace, match in cases:
        with pytest.raises(ValueError, match=match):
            validate_chrome_trace(trace, require_names=("zebra",)
                                  if match == "absent" else ())
    # validator accepts a path too
    p = tmp_path / "t.json"
    p.write_text(json.dumps({"traceEvents": [ok]}))
    assert validate_chrome_trace(str(p))["events"] == 1
    p.write_text(json.dumps({"traceEvents": []}))
    with pytest.raises(ValueError, match="empty"):
        validate_chrome_trace(str(p))


# -- engine integration ------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    cfg = get_config("stablelm-3b").reduced()
    api = build_model(cfg)
    return cfg, api, api.init(jax.random.key(0))


@pytest.fixture(scope="module")
def shared_stepper(model):
    _, api, _ = model
    return Stepper(api)


def _engine(model, stepper, **kw):
    cfg, api, params = model
    kw.setdefault("hbm_budget_bytes", 1 << 30)
    kw.setdefault("max_batch", 3)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_context", 32)
    kw.setdefault("retry_backoff_s", 0.0)
    return ContinuousEngine(api, params, stepper=stepper, **kw)


def _prompts(cfg, n, plen=5, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
            for _ in range(n)]


def _run(eng, cfg, n=4, max_new=4):
    for i, p in enumerate(_prompts(cfg, n)):
        eng.submit(Request(i, p, max_new_tokens=max_new))
    return eng.run()


def test_snapshot_deterministic_across_runs(model, shared_stepper):
    cfg, _, _ = model
    snaps = []
    for _ in range(2):
        eng = _engine(model, shared_stepper)
        _run(eng, cfg)
        s = eng.stats()
        # the stepper is shared across both engines precisely so traces
        # reuse — its cumulative counters differ by construction
        s.pop("stepper")
        snaps.append(s)
    assert snaps[0] == snaps[1]
    # and the snapshot carries the expected families
    assert snaps[0]["counters"]["engine.requests_resolved"] == 4
    assert "kv.blocks_live" in snaps[0]["gauges"]
    assert snaps[0]["gauges"]["kv.blocks_live"]["high_water"] > 0
    assert snaps[0]["derived"]["degraded_activations"] == 0


def test_engine_span_taxonomy(model, shared_stepper):
    """Every span kind the engine can emit, validated against the fixed
    taxonomy: megastep path (m=8), sync path (m=1), preemption under a
    tight budget, and a fault-plane activation."""
    cfg, api, _ = model
    seen = set()
    runs = []
    # m=8 exercises megastep + reconcile; m=1 exercises decode.
    # prefill_chunk=4 < the pending prompt tokens so the chunked
    # prefill path engages (short tails otherwise ride _decode).
    for m in (1, 8):
        tele = Telemetry(trace=True)
        eng = _engine(model, shared_stepper, megastep=m, telemetry=tele,
                      prefill_chunk=4)
        _run(eng, cfg)
        runs.append((m, tele))
        seen |= {e["kind"] for e in tele.events}
    # preempt + fault + host tier: a mid-run budget shrink below ONE
    # block demotes every active row (spill spans — the host pool is
    # armed), nothing readmits until the scheduled restore (stalled
    # points with the restore's ETA), then restoration re-admits from
    # the host tier (restore spans) and the run finishes
    probe = BlockKVCache(cfg, 0, block_size=4)
    tele = Telemetry(trace=True)
    eng = _engine(model, shared_stepper, megastep=1, telemetry=tele,
                  host_pool=64 * probe.block_bytes,
                  hbm_budget_bytes=int(12 * probe.block_bytes / 0.6) + 1)
    assert eng.spill_enabled
    full = eng.kv.budget
    eng.faults = FaultPlane([
        FaultEvent(3, "budget", budget_bytes=probe.block_bytes),
        FaultEvent(9, "budget", budget_bytes=full),
    ])
    for i, p in enumerate(_prompts(cfg, 3, plen=6)):
        eng.submit(Request(i, p, max_new_tokens=10))
    eng.run()
    seen |= {e["kind"] for e in tele.events}
    kinds_with_faults = {e["kind"] for e in tele.events}
    assert "fault" in kinds_with_faults
    assert "preempt" in kinds_with_faults
    assert "spill" in kinds_with_faults
    assert "restore" in kinds_with_faults
    assert "stalled" in kinds_with_faults
    stalled = [e for e in tele.events if e["kind"] == "stalled"]
    assert all(e["args"]["cause"] == "budget_shrunk" for e in stalled)
    assert all(e["args"]["restore_eta_iteration"] == 9 for e in stalled)
    assert eng.stalls == len(stalled)

    # segment is hetero-only; cache_evict needs prefix_cache=True, and
    # every engine above runs cache-off (test_prefix_cache.py covers it);
    # reset clears per-row SSM state, which an attention-only model has
    # none of (test_granite_hybrid.py covers it)
    expected = set(SPAN_KINDS) - {"segment", "cache_evict", "reset"}
    assert seen == expected
    # schema: every event stamped and shaped per its kind (the fault
    # run rides along so spill/restore/stalled are schema-checked too)
    for _, t in runs + [(1, tele)]:
        for e in t.events:
            assert e["kind"] in SPAN_KINDS
            assert e["ts"] > 0.0
            if e["kind"] in DURATION_KINDS:
                assert e["dur"] >= 0.0
            else:
                assert "dur" not in e
            if e["kind"] in REQUEST_KINDS:
                assert "request_id" in e
    # exporters accept a real engine trace
    for m, t in runs:
        want = ("iteration", "kv_pool",
                "megastep" if m == 8 else "decode")
        validate_chrome_trace(t.chrome_trace(), require_names=want)
        tl = t.timelines()
        assert sorted(tl) == [0, 1, 2, 3]
        for rid, evs in tl.items():
            assert evs[0]["kind"] == "submit"
            assert evs[-1]["kind"] == "complete"


class _NoClock:
    """A ``time`` module whose clock must not be read."""

    def perf_counter(self):
        raise AssertionError("the disabled recorder read the clock")


@pytest.mark.parametrize("m", [1, 8])
def test_untraced_engine_reads_no_clock_and_builds_no_annotation(
        model, shared_stepper, monkeypatch, m):
    """With ``Telemetry(trace=False)`` every phase region of the engine
    (megastep path at m=8, sync decode path at m=1, chunked prefill in
    both) is the shared no-op: the recorder reads no clock and no
    profiler annotation is ever constructed."""
    cfg, _, _ = model

    def boom(*a, **k):
        raise AssertionError("an annotation was constructed")

    import repro.runtime.telemetry as telemetry
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", boom)
    monkeypatch.setattr(telemetry, "time", _NoClock())
    tele = Telemetry(trace=False)
    eng = _engine(model, shared_stepper, megastep=m, telemetry=tele,
                  prefill_chunk=4)
    out = _run(eng, cfg)
    assert all(c.ok for c in out.values())
    assert tele.events == []


def test_fused_iterations_semantics(model, shared_stepper):
    """iterations counts step() calls; fused_iterations counts decode
    iterations actually executed (a megastep advances it by the scan's
    executed length) — the PR-6 gotcha, now first-class counters."""
    cfg, _, _ = model
    e1 = _engine(model, shared_stepper, megastep=1)
    _run(e1, cfg)
    assert e1.megasteps == 0
    assert 0 < e1.fused_iterations <= e1.iterations
    e8 = _engine(model, shared_stepper, megastep=8)
    _run(e8, cfg)
    assert e8.megasteps > 0
    assert e8.megastep_steps > 0
    assert e8.fused_iterations >= e8.megastep_steps
    # fusion means fewer step() calls for the same decoded tokens
    assert e8.iterations < e1.iterations
    assert e8.stats()["counters"]["engine.fused_iterations"] \
        == e8.fused_iterations


@pytest.mark.parametrize("arch,block", [("stablelm-3b", True),
                                        ("granite-4.0-h-small", False)])
def test_block_chunks_count_every_block_dispatch(arch, block):
    """``stepper.block_chunks`` counts the chunk dispatches that ran the
    (B, C) block program: every one of an attention-only model's, none
    of the hybrid's (its Mamba step takes one token); each
    ``prefill_chunk`` span says which program it ran."""
    api = build_model(get_config(arch).reduced())
    cfg = api.cfg
    tele = Telemetry(trace=True)
    eng = ContinuousEngine(api, api.init(jax.random.key(0)),
                           telemetry=tele, config=EngineConfig(
                               max_batch=3, max_context=32, prefill_chunk=4,
                               block_size=4, megastep=4,
                               hbm_budget=1 << 30, host_pool=0,
                               prefix_cache=False, fault_seed=None))
    for i, p in enumerate(_prompts(cfg, 4, plen=11)):
        eng.submit(Request(i, p, max_new_tokens=3))
    eng.run()
    chunks = [e for e in tele.events if e["kind"] == "prefill_chunk"]
    assert chunks
    assert all(e["args"]["block"] is block for e in chunks)
    count = eng.stats()["stepper"]["stepper.block_chunks"]
    assert count == (len(chunks) if block else 0)


# -- tracing invariance (pinned child, like all stream-identity tests) -------

@pytest.fixture(scope="module")
def tele_child_report():
    proc = subprocess.run(
        [sys.executable, CHILD, "--tele", "stablelm-3b"],
        capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, \
        f"tele child failed:\n{proc.stdout}\n{proc.stderr}"
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_tracing_is_behavior_invisible(tele_child_report):
    checks = tele_child_report["stablelm-3b"]
    # *_span_kinds entries are informational lists; everything else is
    # a boolean invariance check that must hold
    failed = {k: v for k, v in checks.items()
              if not k.endswith("_span_kinds") and v is not True}
    assert not failed, f"tele sweep violations: {failed}"
    for key in ("m1_span_kinds", "m8_span_kinds"):
        kinds = checks[key]
        assert kinds and set(kinds) <= set(SPAN_KINDS), (key, kinds)
    assert "megastep" in checks["m8_span_kinds"]
