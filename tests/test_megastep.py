"""Decode-megastep tests: termination fuzz, bulk reserve/release,
re-admission headroom cap, and the PARALLAX_MEGASTEP knob.

Stream-content comparisons (N=8 vs N=1 bit-identity at every
termination offset) run in the synchronous-dispatch child process —
see tests/serving_identity_child.py ``--fuzz`` — because greedy-stream
bits are only stable with async CPU dispatch off.  Everything here that
runs in-process asserts scheduling/bookkeeping invariants that do not
depend on which tokens the model happened to sample.
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
from repro.runtime.kv_cache import BlockKVCache

CHILD = os.path.join(os.path.dirname(__file__),
                     "serving_identity_child.py")


# -- termination fuzz (pinned child process) ---------------------------------

@pytest.fixture(scope="module")
def fuzz_report():
    proc = subprocess.run(
        [sys.executable, CHILD, "--fuzz", "stablelm-3b", "mamba2-370m"],
        capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_termination_fuzz_bit_identical_to_n1(fuzz_report):
    """Rows hitting EOS or max-token at every offset within a megastep
    produce streams bit-identical to the per-iteration engine."""
    for arch, r in fuzz_report.items():
        assert r["cases"] >= 40, (arch, r)
        assert r["identical"], f"{arch}: fused streams diverged from N=1"


def test_termination_fuzz_returns_reserved_blocks(fuzz_report):
    """Reserved-but-unused blocks go back to the pool: the audit engine
    asserts per-iteration that no slot holds blocks beyond its written
    tokens, the pool drains to zero, and the fused engine's high-water
    stays within the bulk-reservation bound of N=1's."""
    for arch, r in fuzz_report.items():
        assert r["drained"], f"{arch}: pool not drained"
        assert r["highwater_bounded"], f"{arch}: reservation high-water "\
            f"exceeded the N-step bound"


# -- re-admission headroom cap (preemption bugfix) ---------------------------

class _HeadroomAudit(ContinuousEngine):
    """Records every megastep planned while a demote-preempted request
    waits, asserting the reservation never consumed the headroom that
    request needs to re-admit (the demote-only contract: a paused
    request resumes the moment its pending cache fits)."""

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        self.megasteps_with_demoted_waiting = 0

    def _plan_megastep(self):
        head = next((q for q in self.waiting if q.preempted), None)
        before = self.kv.headroom
        n, plans = super()._plan_megastep()
        if n >= 2 and head is not None:
            self.megasteps_with_demoted_waiting += 1
            need = self.kv.bytes_for(head.pending_len())
            assert self.kv.headroom >= need \
                or self.kv.headroom == before, (
                    f"megastep reservation ate the demoted request's "
                    f"re-admission headroom: {self.kv.headroom} left, "
                    f"{need} needed, {before} before")
        return n, plans


def test_megastep_respects_preempted_readmission_headroom():
    """Regression: a megastep launched right after demote-only
    preemption must cap N by the post-admission pool state — the paused
    request's re-admission headroom stays fenced off, and every request
    still completes with full-length streams."""
    cfg = get_config("stablelm-3b").reduced()
    api = build_model(cfg)
    params = api.init(jax.random.key(0))
    probe = BlockKVCache(cfg, 1 << 30, block_size=4)
    # room for ~2 growing rows out of 3: growth forces demotions while
    # generations are long enough that fused megasteps keep launching
    budget = int(7 * probe.block_bytes / 0.6) + 1
    rng = np.random.default_rng(3)
    eng = _HeadroomAudit(api, params, hbm_budget_bytes=budget,
                         max_batch=3, block_size=4, max_context=32,
                         megastep=8)
    for i in range(5):
        eng.submit(Request(i, rng.integers(0, cfg.vocab_size, 6)
                           .astype(np.int32), max_new_tokens=10))
    done = eng.run()
    assert sorted(done) == list(range(5))
    assert all(len(c.tokens) == 10 for c in done.values())
    assert eng.preemptions > 0, "workload never preempted"
    assert eng.megasteps_with_demoted_waiting > 0, \
        "no megastep ever planned while a demoted request waited"
    assert eng.kv.in_use == 0
    eng.assert_quiescent()


# -- bulk reserve/release accounting -----------------------------------------

def test_release_to_returns_trailing_blocks():
    cfg = get_config("stablelm-3b").reduced()
    kv = BlockKVCache(cfg, budget_bytes=1 << 30, block_size=4)
    kv.admit(0, 5)                                # 2 blocks
    assert kv.grow(0, 5 + 8)                      # bulk reserve: +2
    assert kv.in_use == 4 * kv.block_bytes
    assert kv.release_to(0, 6) == 2               # keep ceil(6/4) = 2
    assert kv.in_use == 2 * kv.block_bytes
    assert kv.release_to(0, 6) == 0               # idempotent
    kv.free(0)
    assert kv.in_use == 0
    kv.admit(1, 16)                               # reuses all 4 blocks
    assert kv.reuse_count == 4


def test_release_to_property_reserve_release_roundtrip():
    """Hypothesis: any reserve (grow) followed by release_to back to the
    written watermark restores exact block accounting — reservations
    can never leak."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    cfg = get_config("stablelm-3b").reduced()
    kv_budget = 1 << 30

    @settings(max_examples=50, deadline=None)
    @given(st.lists(st.tuples(st.integers(1, 30), st.integers(0, 12),
                              st.integers(0, 12)), min_size=1,
                    max_size=8))
    def run(rows):
        kv = BlockKVCache(cfg, kv_budget, block_size=4)
        for slot, (prompt, reserve, written) in enumerate(rows):
            kv.admit(slot, prompt)
            assert kv.grow(slot, prompt + reserve)
            watermark = min(prompt + written, prompt + reserve)
            kv.release_to(slot, max(watermark, prompt))
            held = len(kv.block_tables[slot])
            assert held == kv.blocks_for(max(watermark, prompt))
        expect = sum(len(t) for t in kv.block_tables.values()) \
            * kv.block_bytes
        assert kv.in_use == expect
        for slot in range(len(rows)):
            kv.free(slot)
        assert kv.in_use == 0

    run()


# -- knob resolution ---------------------------------------------------------

def test_megastep_env_knob(monkeypatch):
    def megastep(**kw):
        return EngineConfig(hbm_budget=1 << 30, **kw).megastep

    monkeypatch.delenv("PARALLAX_MEGASTEP", raising=False)
    assert megastep() == 8                        # default: on, safe N
    assert megastep(megastep=3) == 3              # explicit wins
    monkeypatch.setenv("PARALLAX_MEGASTEP", "4")
    assert megastep() == 4
    assert megastep(megastep=2) == 2              # explicit beats env
    monkeypatch.setenv("PARALLAX_MEGASTEP", "1")
    assert megastep() == 1                        # per-iteration path
    monkeypatch.setenv("PARALLAX_MEGASTEP", "zero")
    with pytest.raises(ValueError, match="PARALLAX_MEGASTEP"):
        megastep()
    monkeypatch.setenv("PARALLAX_MEGASTEP", "0")
    with pytest.raises(ValueError, match=">= 1"):
        megastep()


def test_megastep_one_never_fuses():
    """megastep=1 is the pre-megastep engine: zero fused dispatches and
    length-correct streams (content checked in the identity child)."""
    cfg = get_config("stablelm-3b").reduced()
    api = build_model(cfg)
    params = api.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    eng = ContinuousEngine(api, params, hbm_budget_bytes=1 << 30,
                           max_batch=2, block_size=4, max_context=32,
                           megastep=1)
    for i in range(3):
        eng.submit(Request(i, rng.integers(0, cfg.vocab_size, 5)
                           .astype(np.int32), max_new_tokens=4))
    done = eng.run()
    assert eng.megasteps == 0
    assert all(len(done[i].tokens) == 4 for i in range(3))
    eng.assert_quiescent()


def test_eos_never_sampled_runs_to_max_new():
    """An EOS id outside the vocab can never be sampled: streams run to
    max_new in both engines and the pool drains (the in-carry EOS check
    must not misfire)."""
    cfg = get_config("stablelm-3b").reduced()
    api = build_model(cfg)
    params = api.init(jax.random.key(0))
    rng = np.random.default_rng(1)
    for m in (1, 8):
        eng = ContinuousEngine(api, params, hbm_budget_bytes=1 << 30,
                               max_batch=2, block_size=4,
                               max_context=32, megastep=m)
        for i in range(3):
            eng.submit(Request(i, rng.integers(0, cfg.vocab_size, 5)
                               .astype(np.int32), max_new_tokens=5,
                               eos_id=-5))
        done = eng.run()
        assert all(len(done[i].tokens) == 5 for i in range(3)), m
        assert eng.kv.in_use == 0
        eng.assert_quiescent()
