#!/usr/bin/env python3
"""Readings that the limit of ``correct`` is set from, on the chip, in one
process: for each seed, the weights and a short window at the cell's own
load, then the widest gap of the program's served tokens below the
reference's best; for the control seeds also the widest gap of the
tokens the fp8 control puts first at the same positions, and the verdict
with the control in the program's place (it has to be false).

    python3 bench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3 --control-seeds 1,2,3

One JSON line per seed, then a summary: the lower reading (the largest
program gap) and the upper one (the smallest control gap).
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

import jax

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    a = ap.parse_args(argv)
    seeds = [int(s) for s in a.seeds.split(",")]
    control = {int(s) for s in a.control_seeds.split(",") if s}

    def log(s):
        print(s, file=sys.stderr, flush=True)

    cell = harness.Cell(a.workload, log=log)
    rows = []
    for seed in seeds:
        params = cell.weights(seed)
        engine = cell.engine(params, traced=False)
        client, _, compiles = cell.window(
            engine, cell.plan(seed, a.seconds), a.seconds, False)
        e2e, info = harness.end_to_end(client, a.seconds)
        peak = harness.peak_bytes(jax.devices()[0], log)
        client.engine = engine = None
        gc.collect()
        reading = cell.compare(params, client, seed, seed in control)
        row = {"seed": seed, **reading, **e2e, "peak_bytes": peak,
               "completed": info["completed_in_window"],
               "compiles_in_window": compiles}
        if "control_max_gap" in reading:
            row["control_correct"] = harness.verdict(
                cell.cfg, cell.traffic,
                dict(reading, max_gap=reading["control_max_gap"]))[0]
        print(json.dumps(row), flush=True)
        rows.append(row)
        params = None
        gc.collect()
    lower = max(r["max_gap"] for r in rows)
    ctrl = [r["control_max_gap"] for r in rows if "control_max_gap" in r]
    print(json.dumps({"workload": a.workload, "lower": lower,
                      "upper": min(ctrl) if ctrl else None,
                      "seeds": len(rows), "control_seeds": len(ctrl)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
