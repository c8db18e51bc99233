"""The end-to-end arithmetic on synthetic timestamps, and the per-layer
readers on a synthetic trace."""

import math

import numpy as np
import pytest

from bench import cost, harness, trace
from bench.harness import Layout, LayerContext, Record

LAYOUT = Layout()


class _Client:
    def __init__(self, records, lateness=(0.0,)):
        self.records = records
        self.lateness = list(lateness)


def _rec(sent, first=None, last=None, n=0, status=None, done=None):
    r = Record(np.zeros(4, np.int32), 8, sent, first, last, n)
    r.status, r.done_at = status, done
    return r


def test_ttft_counts_from_due_time_and_the_cut_off():
    recs = {0: _rec(1.0, first=3.5, last=4.5, n=3, status="completed",
                    done=4.5),
            1: _rec(8.0),                                # no token yet
            2: _rec(9.0, first=10.5)}        # first token after the end
    e2e, info = harness.end_to_end(_Client(recs), 10.0)
    # TTFTs 2.5, 2.0 (cut at the end) and 1.0 (cut: first at 10.5)
    assert e2e["ttft_p95_s"] == pytest.approx(2.5)
    assert info["cut_off"] == 2
    assert e2e["output_tok_s"] == pytest.approx(3 / 10.0)


def test_failed_request_is_infinite_ttft():
    recs = {i: _rec(0.0, first=1.0, last=2.0, n=3, status="completed",
                    done=2.0) for i in range(19)}
    recs[19] = _rec(0.0, status="failed", done=1.0)
    e2e, _ = harness.end_to_end(_Client(recs), 5.0)
    assert e2e["ttft_p95_s"] == 1.0          # rank 19 of 20 is finite
    recs[18] = _rec(0.0, status="rejected", done=0.0)
    e2e, _ = harness.end_to_end(_Client(recs), 5.0)
    assert math.isinf(e2e["ttft_p95_s"])


def test_tpot_over_requests_with_two_tokens():
    recs = {0: _rec(0.0, first=1.0, last=1.9, n=10),      # 100 ms
            1: _rec(0.0, first=1.0, last=1.0, n=1),       # not counted
            2: _rec(0.0, first=2.0, last=2.2, n=3)}       # 100 ms
    e2e, info = harness.end_to_end(_Client(recs), 5.0)
    assert info["tpot_samples"] == 2
    assert e2e["tpot_p95_ms"] == pytest.approx(100.0)


def test_total_tokens_count_the_kv_written_inside_the_window():
    recs = {0: _rec(0.0, first=1.0, last=1.9, n=10),
            1: _rec(0.0)}                          # still in its prompt
    recs[0].ctx_in_window, recs[1].ctx_in_window = 400, 150
    e2e, info = harness.end_to_end(_Client(recs), 5.0)
    assert info["kv_tokens_in_window"] == 550
    assert e2e["total_tok_s"] == pytest.approx(550 / 5.0)


def test_p95_is_nearest_rank():
    assert harness.p95(range(1, 21)) == 19
    assert harness.p95([5.0]) == 5.0


def _trace():
    # one device: programs and ops; the host idles between 2.0 and 2.9
    progs = [("jit_run", 0.0, 1.0), ("jit_run_chunk", 1.1, 1.5),
             ("jit_run", 1.6, 2.0), ("jit_run", 2.9, 3.0)]
    ops = [("fusion.1", 0.0, 0.6), ("fusion.2", 0.6, 1.0),
           ("fusion.3", 1.1, 1.5), ("fusion.1", 1.6, 2.0),
           ("fusion.2", 2.9, 3.0)]
    host = [("engine.step", 0.0, 2.0), ("client.idle", 2.0, 2.9),
            ("engine.step", 2.9, 3.0)]
    return trace.Trace(programs=[progs], ops=[ops], host=host, window_s=4.0)


def test_trace_reduction():
    tr = _trace()
    assert trace.union_s([(0, 1), (0.5, 2), (3, 4)]) == pytest.approx(3.0)
    assert trace.busy_s(tr) == pytest.approx(1.0 + 0.4 + 0.4 + 0.1)
    assert trace.program_time(tr, ["jit_run"]) == (pytest.approx(1.5), 3)
    assert [round(g, 6) for g in (e - s for s, e in trace.gaps(tr))] == \
        [0.1, 0.1, 0.9]
    assert [round(g, 6) for g in trace.live_gaps(tr)] == [0.1, 0.1]
    assert [round(g, 6) for g in trace.live_gaps(tr, ["jit_run"])] == [0.6]
    bd = trace.breakdown(tr)
    assert bd["device_ops"][0][0] == "fusion.1"
    assert bd["idle_gaps"][0] == ["client.idle", pytest.approx(0.9)]
    assert trace.program_name("jit_run(12345)") == "jit_run"
    assert trace.op_name("%copy.55 = bf16[32,273]{4,3:T(8,128)} copy(x)") \
        == "copy.55 bf16[32,273]"
    # a loop's own time is what its nested body leaves uncovered
    st = trace.self_times([("while", 0.0, 1.0), ("a", 0.1, 0.4),
                           ("b", 0.5, 0.9), ("a", 2.0, 2.5)])
    assert st == pytest.approx({"while": 0.3, "a": 0.8, "b": 0.4})


def _ctx(tr):
    cfg = LAYOUT.json("configs", "stablelm-3b")
    steps = [{"t0": 0.0, "t1": 2.0, "executed": 8,
              "ctx_end": [100, 200]},
             {"t0": 2.9, "t1": 3.0, "executed": 2, "ctx_end": [110]}]
    events = [{"kind": "submit", "ts": 0.0, "request_id": 1},
              {"kind": "admit", "ts": 0.004, "request_id": 1},
              {"kind": "megastep", "ts": 0.1,
               "args": {"rows": 3, "executed": 8}},
              {"kind": "decode", "ts": 0.2, "args": {"rows": 1}}]
    return LayerContext(events=events, trace_=tr,
                        counters={"engine.fused_iterations": 10},
                        steps=steps, cost_=cost.StepCost(cfg),
                        kind="TPU v5 lite", max_batch=4)


def _read(name, ctx):
    return LAYOUT.module("metrics", name).read(ctx)


def test_per_layer_readers_on_a_synthetic_trace():
    ctx = _ctx(_trace())
    assert _read("decode_step_ms", ctx) == pytest.approx(1.5 / 10 * 1e3)
    assert _read("prefill_chunk_ms", ctx) == pytest.approx(400.0)
    assert _read("device_idle_share", ctx) == pytest.approx(
        100 * (1 - 1.9 / 4.0))
    # step programs jit_run / jit_run_chunk: gaps 0.1, 0.1 (0.9 is idle)
    assert _read("dispatch_gap_ms_p50", ctx) == pytest.approx(100.0)
    assert _read("queue_wait_ms_p50", ctx) == pytest.approx(4.0)
    assert _read("batch_occupancy", ctx) == pytest.approx(
        100 * (3 * 8 + 1) / 9 / 4)
    roof = _read("step_roofline", ctx)
    mfu = _read("step_mfu", ctx)
    peak = cost.peaks("TPU v5 lite")
    flops = sum(f for f, _ in ctx.step_needs())
    assert mfu == pytest.approx(100 * flops / (1.5 * peak["bf16_flops_per_s"]))
    assert 0 < mfu < roof < 100


def test_readers_without_a_trace_return_nothing():
    ctx = _ctx(None)
    for name in ("decode_step_ms", "prefill_chunk_ms", "step_roofline",
                 "step_mfu", "device_idle_share", "dispatch_gap_ms_p50"):
        assert _read(name, ctx) is None


def test_step_cost_and_peaks():
    c = cost.StepCost(LAYOUT.json("configs", "stablelm-3b"))
    # 2.795 B parameters, of which the embedding table (50304 x 2560)
    # is looked up, not multiplied
    assert c.matmul_params + 50304 * 2560 == pytest.approx(2.795e9,
                                                           rel=1e-3)
    assert c.kv_token_bytes == 320 * 1024
    f1, b1 = c.step([10])
    f2, b2 = c.step([10, 20])
    assert b2 - b1 == pytest.approx(c.row_bytes + 20 * c.kv_token_bytes)
    assert f2 > 2 * f1 - 1
    with pytest.raises(KeyError):
        cost.peaks("TPU v9 imaginary")
