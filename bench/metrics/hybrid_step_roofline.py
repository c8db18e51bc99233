"""Kernel layer of a hybrid cell: the least time the chip needs for the
decode steps of the traced window, by ``cost_hybrid.HybridStepCost``
(mixers, held experts the rows can reach, each row's SSM state read and
written, attention KV) and the peaks of ``peaks.json``, over the device
time of the decode programs, in %.  Its configuration is the one of the
cell its own ``BENCHMARK.json`` entry lists: a reader is not told which
cell it measures, so the entry may list only one."""

import copy
import json
import pathlib

from bench.cost_hybrid import HybridStepCost

#: jitted decode programs: the megastep's scan and the single step
PROGRAMS = ("jit_run", "jit_step")
ROOT = pathlib.Path(__file__).resolve().parents[2]


def _config(bench: dict) -> dict:
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "hybrid_step_roofline")
    if len(entry["workloads"]) != 1:
        raise ValueError("hybrid_step_roofline counts the configuration "
                         "of the one cell its entry lists, not "
                         f"{entry['workloads']}")
    cell = next(w for w in bench["workloads"]
                if w["name"] == entry["workloads"][0])
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return json.loads((ROOT / conf["file"]).read_text())


def read(ctx):
    if ctx.trace is None:
        return None
    secs, calls = ctx.trace_program_time(PROGRAMS)
    if not calls:
        return None
    hybrid = copy.copy(ctx)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    hybrid.cost = HybridStepCost(_config(bench))
    peak = ctx.peaks()
    least = sum(hybrid.cost.least_s(f, b, peak)
                for f, b in hybrid.step_needs())
    return 100.0 * least / secs if least else None
