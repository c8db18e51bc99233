#!/usr/bin/env python3
"""Where a cell's traced stretch goes, by the program's own spans: one
window of the cell with the profiler on, as ``bench/run.py --trace 1``
opens it, reduced by ``bench/spans.py`` beside ``bench/trace.py``.

    python3 bench/span_report.py --workload <cell> --seed <n> --seconds <s> \\
        [--keep <dir>]

Prints one JSON object: the longest idle gaps between programs, each
labelled by the engine phase covering most of it; the idle seconds split
by the innermost phase at each instant; the share of the idle seconds
inside the harness's ``engine.step`` that a phase labels; the decode
programs' device seconds by model scope beside their program time, and
the prefill chunk's by scope; the operations that took most time in the
attention scope and under no scope; and the readings
``engine_host_ms_p50``, ``attention_ms_per_step`` and
``pool_carry_ms_per_step``.  No comparison with the reference: that is
``bench/run.py``'s.  ``--keep`` copies the profiler trace, gzipped,
into a directory.
"""

from __future__ import annotations

import argparse
import gzip
import json
import pathlib
import shutil
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from bench import harness, spans, trace  # noqa: E402


def report(tr: trace.Trace, sp: spans.Spans, events, fused: int) -> dict:
    decode_s, decode_n = trace.program_time(tr, spans.DECODE)
    scopes = spans.device_scopes(sp)
    step_idle = in_phase = 0.0
    for s, e in trace.gaps(tr):
        if trace.host_label(tr, s, e) == "engine.step":
            step_idle += e - s
            if spans.phase(sp, s, e) is not None:
                in_phase += e - s
    kinds: dict = {}
    for kind, s, e in sp.host:
        kinds.setdefault(kind, []).append(e - s)
    return {
        "window_s": tr.window_s, "busy_s": trace.busy_s(tr),
        "fused_iterations": fused,
        "decode_programs": {"seconds": decode_s, "runs": decode_n},
        "device_scopes": scopes,
        "device_scopes_sum_s": sum(scopes.values()),
        "chunk_scopes": spans.device_scopes(sp, ("jit_run_chunk",)),
        "top_ops": {sc: spans.scope_ops(sp, sc)
                    for sc in ("attention", spans.UNSCOPED)},
        "idle_gaps": spans.idle_gaps(tr, sp),
        "idle_gaps_before": trace.breakdown(tr)["idle_gaps"],
        "idle_by_phase": spans.idle_by_phase(tr, sp),
        "idle_in_engine_step_s": step_idle,
        "idle_in_engine_step_named_share": in_phase / step_idle
        if step_idle else None,
        "engine_spans_ms_p50": {k: float(sorted(v)[len(v) // 2]) * 1e3
                                for k, v in sorted(kinds.items())},
        "engine_spans_count": {k: len(v) for k, v in sorted(kinds.items())},
        "engine_host_ms_p50": spans.host_ms_p50(events),
        "attention_ms_per_step": spans.attention_ms_per_step(sp, fused),
        "pool_carry_ms_per_step": spans.pool_carry_ms_per_step(sp, fused),
        "decode_step_ms": decode_s / fused * 1e3 if fused else None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", type=pathlib.Path)
    a = ap.parse_args(argv)
    log = lambda s: print(s, file=sys.stderr, flush=True)  # noqa: E731
    cell = harness.Cell(a.workload, log=log)
    params = cell.weights(a.seed)
    engine = cell.engine(params, traced=True)
    client, tracer, in_window = cell.window(
        engine, cell.plan(a.seed, a.seconds), a.seconds, traced=True)
    path = tracer.xplane()
    if path is None:
        raise SystemExit("span_report: the profiler wrote no trace")
    if a.keep is not None:
        a.keep.mkdir(parents=True, exist_ok=True)
        with open(path, "rb") as f, \
                gzip.open(a.keep / f"{a.workload}.{a.seed}.xplane.pb.gz",
                          "wb") as g:
            shutil.copyfileobj(f, g)
    t0 = client.t0
    events = [e for e in engine.telemetry.events
              if 0 <= e["ts"] - t0 <= client.seconds]
    fused = tracer.c_b["engine.fused_iterations"] \
        - tracer.c_a["engine.fused_iterations"]
    out = {"workload": a.workload, "seed": a.seed,
           "compiles_in_window": in_window,
           **report(trace.load(path, tracer.t_b - tracer.t_a),
                    spans.load(path), events, fused)}
    shutil.rmtree(tracer.out_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
