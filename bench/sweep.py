#!/usr/bin/env python3
"""Find an open-loop cell's knee on the chip, in one process: one set-up,
then one window per offered rate on a fresh engine that shares the
compiled programs.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 0.2,0.3,0.4 [--write]

A rate is sustained when, at the end of its window, no more than
``max(2, 5%)`` of its requests still wait for admission (the backlog does
not grow).  The knee is the highest rate sustained with every lower rate
sustained too; ``--write`` puts ``SHARE`` of it into the cell's traffic
file as its ``rate``.  Every leg prints one JSON line.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from bench import harness  # noqa: E402

#: The offered load of an open-loop cell, as a share of its knee.
SHARE = 0.8


def sustained(leg: dict) -> bool:
    return leg["waiting_at_end"] <= max(2, 0.05 * leg["requests"])


def sweep(workload: str, seed: int, seconds: float, rates, log=print):
    cell = harness.Cell(workload, log=log)
    params = cell.weights(seed)
    legs = []
    for rate in rates:
        engine = cell.engine(params, traced=False)
        reqs = cell.plan(seed, seconds, rate=rate)
        client, _, compiles = cell.window(engine, reqs, seconds, False)
        e2e, info = harness.end_to_end(client, seconds)
        leg = {"rate": rate, **e2e, **info,
               "waiting_at_end": len(engine.waiting),
               "active_at_end": engine.num_active,
               "compiles_in_window": compiles}
        leg["sustained"] = sustained(leg)
        print(json.dumps(leg), flush=True)
        legs.append(leg)
        client.engine = engine = None
        gc.collect()
    knee = None
    for leg in legs:
        if not leg["sustained"]:
            break
        knee = leg["rate"]
    return cell, legs, knee


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests/s, "
                         "ascending")
    ap.add_argument("--write", action="store_true",
                    help="write SHARE x knee into the traffic file")
    a = ap.parse_args(argv)
    rates = sorted(float(r) for r in a.rates.split(","))
    cell, legs, knee = sweep(a.workload, a.seed, a.seconds, rates,
                             log=lambda s: print(s, file=sys.stderr,
                                                 flush=True))
    rate = None if knee is None else float(f"{SHARE * knee:.3g}")
    print(json.dumps({"workload": a.workload, "knee": knee, "rate": rate}))
    if a.write and rate is not None:
        path = cell.layout.find("traffic", cell.spec["traffic"], ".json")
        traffic = json.loads(path.read_text())
        traffic["rate"] = rate
        path.write_text(json.dumps(traffic, indent=2) + "\n")
    return 0 if knee is not None else 1


if __name__ == "__main__":
    sys.exit(main())
