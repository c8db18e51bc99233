"""The comparison that decides ``correct`` fails what it must.

The control (the reference in fp8, put in the program's place) reads
well above the program on every seed and turns ``correct`` false where
the program's own readings pass, and each fault the served path can
have, planted under a real run, turns ``correct`` false.  All at the
rehearsal's size on the CPU; the same readings at the cells' own sizes
on the chip are in PERF.md.  Serving has no mean over a batch and one
chip exchanges nothing, so those two faults have no place here.
"""

import gc

import numpy as np
import pytest

from bench import harness
from repro.runtime.stepper import Stepper

WORKLOAD = "h2o-danube-3-4b.decode_long"
SECONDS = 3.0
#: the control must read at least this many times the program's worst
SEPARATION = 3.0
#: the rehearsal's limit over the program's worst reading
ROOM = 2.5


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(WORKLOAD, rehearsal=True, log=lambda s: None)


def _reading(cell, seed, control=False):
    params = cell.weights(seed)
    engine = cell.engine(params, traced=False)
    client, _, _ = cell.window(engine, cell.plan(seed, SECONDS), SECONDS,
                               False)
    client.engine = engine = None
    gc.collect()
    return cell.compare(params, client, seed, control)


def test_control_reads_far_above_the_program(cell):
    rows = [_reading(cell, seed, control=True) for seed in (11, 12, 13)]
    program = max(r["max_gap"] for r in rows)
    control = min(r["control_max_gap"] for r in rows)
    assert control > SEPARATION * max(program, 1e-3), rows


@pytest.fixture(scope="module")
def limit(cell):
    """The rehearsal's limit, set as on the chip but at the rehearsal's
    size: above the program's worst reading over seeds the checks below
    do not use, with room for fresh seeds.  The configuration's own
    limit is for its full size."""
    lower = max(_reading(cell, seed)["max_gap"] for seed in (21, 22, 23, 24))
    return ROOM * max(lower, 1e-3)


def _verdict(cell, reading, limit):
    cfg = dict(cell.cfg, check={"max_logit_gap": limit})
    return harness.verdict(cfg, cell.traffic, reading)


def test_control_in_the_programs_place_is_incorrect(cell, limit):
    for seed in (41, 42, 43):
        reading = _reading(cell, seed, control=True)
        assert _verdict(cell, reading, limit)[0], reading
        ok, checks = _verdict(
            cell, dict(reading, max_gap=reading["control_max_gap"]), limit)
        assert not ok, checks


def _unchanged_state(caches_last: bool):
    """The step's results, with the KV pool it was given in place of the
    one it wrote."""
    def wrap(orig):
        def step(self, params, caches, *args, **kw):
            out = orig(self, params, caches, *args, **kw)
            return (*out[:-1], caches) if caches_last else (caches, *out[1:])
        return step
    return wrap


def _altered_token(orig):
    def megastep(self, *args, **kw):
        toks, act, bad, caches = orig(self, *args, **kw)
        toks = np.asarray(toks).copy()
        toks[0] = (toks[0] + 1) % 7
        return toks, act, bad, caches
    return megastep


FAULTS = {
    # the KV pool never takes the step's writes
    "state_unchanged": {"megastep": _unchanged_state(True),
                        "decode": _unchanged_state(True),
                        "prefill_chunk": _unchanged_state(False)},
    # a token altered where the megastep produces it
    "token_altered": {"megastep": _altered_token},
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_the_run_incorrect(cell, fault, limit, monkeypatch):
    for name, wrap in FAULTS[fault].items():
        monkeypatch.setattr(Stepper, name, wrap(getattr(Stepper, name)))
    reading = _reading(cell, 31)
    ok, checks = _verdict(cell, reading, limit)
    assert reading["requests"] >= 1
    assert not ok, checks
