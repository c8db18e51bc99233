"""Chaos suite: fault-injection plane + graceful degradation.

In-process tests cover the deterministic plane itself and each
degradation mechanism's bookkeeping — statuses, machine-readable
reasons, counters, block reclamation.  None of them compare token bits:
greedy-stream bits are only stable under synchronous dispatch, so the
chaos FUZZ — >= 50 seeded random fault schedules (every fault kind
alone and combined), each replayed at megastep N in {1, 8} against a
fault-free reference — runs in the pinned child process
(tests/serving_identity_child.py ``--chaos``) and asserts the headline
invariants: every submitted id resolves, zero KV blocks leak (the
engine drains to quiescence after every schedule), and unaffected
streams stay bit-identical to the fault-free run.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.runtime.engine import (COMPLETION_STATUSES, ContinuousEngine,
                                  Request, ServingEngine)
from repro.runtime.faults import (FAULT_SEED_ENV, FaultEvent, FaultPlane,
                                  fault_seed_from_env)
from repro.runtime.kv_cache import BlockKVCache

CHILD = os.path.join(os.path.dirname(__file__),
                     "serving_identity_child.py")
#: pinned chaos seeds — CI runs exactly these so a failure reproduces
CHAOS_SEEDS = (0, 1, 2)


# -- fault plane (pure schedule, no engine) ----------------------------------

def test_fault_event_validation():
    with pytest.raises(ValueError, match="iteration"):
        FaultEvent(0, "budget", budget_bytes=1)
    with pytest.raises(ValueError, match="kind"):
        FaultEvent(1, "meteor")
    with pytest.raises(ValueError, match="budget_bytes"):
        FaultEvent(1, "budget")
    with pytest.raises(ValueError, match="budget_bytes"):
        FaultEvent(1, "budget", budget_bytes=-1)
    with pytest.raises(ValueError, match="rows"):
        FaultEvent(1, "poison")
    with pytest.raises(ValueError, match="repeats"):
        FaultEvent(1, "poison", rows=(0,), repeats=0)
    with pytest.raises(ValueError, match="request_id"):
        FaultEvent(1, "cancel")
    with pytest.raises(ValueError, match="phase"):
        FaultEvent(1, "cancel", request_id=1, when="later")
    with pytest.raises(ValueError, match="iteration start"):
        FaultEvent(1, "budget", budget_bytes=1, when="post_reserve")


def test_fault_plane_random_deterministic():
    kw = dict(budget_bytes=1 << 20, request_ids=[1, 2, 3], max_batch=4)
    a = FaultPlane.random(7, **kw)
    assert a.events == FaultPlane.random(7, **kw).events
    assert len(a.events) > 0
    assert a.events != FaultPlane.random(8, **kw).events
    # a finite schedule must never wedge the engine: the LAST budget
    # event restores the full budget
    budgets = [e for e in a.events if e.kind == "budget"]
    assert budgets[-1].budget_bytes == 1 << 20
    assert any(e.budget_bytes < 1 << 20 for e in budgets)  # and it shrank


def test_fault_plane_queries():
    p = FaultPlane([
        FaultEvent(2, "budget", budget_bytes=10),
        FaultEvent(5, "budget", budget_bytes=100),
        FaultEvent(3, "poison", rows=(1,), repeats=2),
        FaultEvent(3, "cancel", request_id=9, when="post_reserve"),
    ])
    assert [e.kind for e in p.events_at(2)] == ["budget"]
    assert p.events_at(3) == []           # the cancel is post_reserve
    assert [e.request_id
            for e in p.events_at(3, when="post_reserve")] == [9]
    assert p.poison_rows(3, 0, 4).tolist() == [False, True, False, False]
    assert p.poison_rows(3, 1, 4) is not None   # repeats=2: 2nd attempt
    assert p.poison_rows(3, 2, 4) is None       # repeats exhausted
    assert p.poison_rows(4, 0, 4) is None       # clean iteration
    assert p.max_future_budget(2) == 100
    assert p.max_future_budget(5) is None
    assert p.poison_armed
    assert not FaultPlane().poison_armed


def test_fault_seed_env_knob(monkeypatch):
    monkeypatch.delenv(FAULT_SEED_ENV, raising=False)
    assert fault_seed_from_env() is None
    monkeypatch.setenv(FAULT_SEED_ENV, "11")
    assert fault_seed_from_env() == 11
    monkeypatch.setenv(FAULT_SEED_ENV, "lots")
    with pytest.raises(ValueError, match=FAULT_SEED_ENV):
        fault_seed_from_env()


# -- engine hardening (in-process: statuses/counters/reclamation) ------------

@pytest.fixture(scope="module")
def model():
    cfg = get_config("stablelm-3b").reduced()
    api = build_model(cfg)
    return cfg, api, api.init(jax.random.key(0))


def _engine(model, **kw):
    cfg, api, params = model
    kw.setdefault("hbm_budget_bytes", 1 << 30)
    kw.setdefault("max_batch", 3)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_context", 32)
    kw.setdefault("retry_backoff_s", 0.0)
    return ContinuousEngine(api, params, **kw)


def _prompts(cfg, n, plen=5, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
            for _ in range(n)]


def test_submit_validation_fails_fast(model):
    cfg, _, _ = model
    eng = _engine(model, max_batch=2, max_context=16)
    ok = np.arange(4, dtype=np.int32)
    with pytest.raises(ValueError, match="1-D"):
        eng.submit(Request(0, ok.reshape(2, 2)))
    with pytest.raises(ValueError, match="empty"):
        eng.submit(Request(0, np.array([], np.int32)))
    with pytest.raises(ValueError, match="integer"):
        eng.submit(Request(0, ok.astype(np.float32)))
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(Request(0, ok, max_new_tokens=-1))
    with pytest.raises(ValueError, match="deadline_s"):
        eng.submit(Request(0, ok, max_new_tokens=2, deadline_s=0.0))
    with pytest.raises(ValueError, match="max_context"):
        eng.submit(Request(0, ok, max_new_tokens=13))
    assert not eng.waiting                # nothing half-admitted


def test_backpressure_rejects_with_reason(model):
    cfg, _, _ = model
    eng = _engine(model, max_queue=2)
    accepted = [eng.submit(Request(i, p, max_new_tokens=2))
                for i, p in enumerate(_prompts(cfg, 5))]
    assert accepted == [True, True, False, False, False]
    assert eng.rejected == 3
    done = eng.run()
    assert sorted(done) == list(range(5))   # rejects resolve too
    for i in (2, 3, 4):
        assert done[i].status == "rejected"
        assert done[i].reason == "queue_full"
        assert done[i].tokens == [] and not done[i].ok
    assert all(done[i].ok and len(done[i].tokens) == 2 for i in (0, 1))
    eng.assert_quiescent()


def test_deadline_expiry_cancels_with_reason(model):
    cfg, _, _ = model
    eng = _engine(model)
    for i, p in enumerate(_prompts(cfg, 4)):
        eng.submit(Request(i, p, max_new_tokens=6, deadline_s=1e-9))
    done = eng.run()
    assert all(done[i].status == "cancelled"
               and done[i].reason == "deadline" for i in range(4))
    assert eng.cancellations == 4
    eng.assert_quiescent()
    # a generous deadline never fires
    eng = _engine(model)
    eng.submit(Request(0, _prompts(cfg, 1)[0], max_new_tokens=3,
                       deadline_s=300.0))
    assert eng.run()[0].ok
    eng.assert_quiescent()


def test_cancel_waiting_and_mid_decode(model):
    cfg, _, _ = model
    eng = _engine(model, max_batch=2)
    for i, p in enumerate(_prompts(cfg, 4)):
        eng.submit(Request(i, p, max_new_tokens=20))
    assert not eng.cancel(99)             # unknown id
    assert eng.cancel(3)                  # still waiting: empty stream
    eng.step()
    eng.step()
    assert eng.cancel(0)                  # mid-decode: blocks reclaimed
    assert not eng.cancel(0)              # already resolved
    done = eng.run()
    assert done[3].status == "cancelled" and done[3].tokens == []
    assert done[0].status == "cancelled"
    assert 0 < len(done[0].tokens) < 20   # partial stream rides along
    assert all(done[i].ok and len(done[i].tokens) == 20 for i in (1, 2))
    assert eng.cancellations == 2
    eng.assert_quiescent()


def test_budget_shrink_restore_degrades_not_dies(model):
    """A mid-run budget shrink below the bytes in use must demote/refuse
    growth — never assert or lose a request — and the scheduled restore
    lets everything complete full-length."""
    cfg, _, _ = model
    probe = BlockKVCache(cfg, 0, block_size=4)
    # Fault schedules key on engine.iterations = step() CALLS, not
    # tokens: at megastep N one step() fuses up to N decode iterations
    # (engine.fused_iterations advances by the scan's executed length),
    # so an iteration-keyed fault would land between whole scans.
    # megastep=1 makes iterations == fused_iterations — one token per
    # step() — so the shrink lands mid-stream and the pool stays
    # infeasible for several iterations.
    eng = _engine(model, megastep=1, hbm_budget_bytes=int(
        (12 * probe.block_bytes + 3 * probe.state_bytes) / 0.6) + 1)
    full = eng.kv.budget
    eng.faults = FaultPlane([
        FaultEvent(3, "budget",
                   budget_bytes=2 * probe.block_bytes
                   + 3 * probe.state_bytes),
        FaultEvent(9, "budget", budget_bytes=full),
    ])
    for i, p in enumerate(_prompts(cfg, 3, plen=6)):
        eng.submit(Request(i, p, max_new_tokens=10))
    done = eng.run()
    assert all(done[i].ok and len(done[i].tokens) == 10
               for i in range(3))
    assert eng.budget_events == 2
    assert eng.kv.budget == full
    eng.assert_quiescent()


def test_budget_shrink_spills_and_restores(model):
    """The same shrink/restore schedule as above, with the host KV tier
    armed: every demotion spills instead of discarding, every
    re-admission restores instead of re-prefilling — zero tokens
    replayed, and the host tier drains to quiescence with the rest."""
    cfg, _, _ = model
    probe = BlockKVCache(cfg, 0, block_size=4)
    eng = _engine(model, megastep=1, hbm_budget_bytes=int(
        (12 * probe.block_bytes + 3 * probe.state_bytes) / 0.6) + 1,
        host_pool=64 * probe.block_bytes)
    assert eng.spill_enabled
    full = eng.kv.budget
    eng.faults = FaultPlane([
        FaultEvent(3, "budget", budget_bytes=2 * probe.block_bytes),
        FaultEvent(9, "budget", budget_bytes=full),
    ])
    for i, p in enumerate(_prompts(cfg, 3, plen=6)):
        eng.submit(Request(i, p, max_new_tokens=10))
    done = eng.run()
    assert all(done[i].ok and len(done[i].tokens) == 10
               for i in range(3))
    assert eng.spills > 0 and eng.restores == eng.spills
    assert eng.reprefill_tokens == 0      # nothing replayed through prefill
    assert eng.prefill_tokens_saved > 0
    assert eng.kv.host_peak_bytes > 0
    assert eng.kv.host_in_use == 0        # tier drained
    eng.assert_quiescent()                # audits the host tier too


def test_budget_shrink_evicts_cache_before_demoting(model):
    """With the persistent prefix cache populated, a mid-run budget
    shrink must reclaim the cold cache tier FIRST: the cached blocks
    absorb the whole shrink and no live request is ever demoted."""
    from repro.runtime.config import EngineConfig
    cfg, api, params = model
    probe = BlockKVCache(cfg, 0, block_size=4)
    eng = ContinuousEngine(api, params, config=EngineConfig(
        hbm_budget=12 * probe.block_bytes, max_batch=3, block_size=4,
        max_context=32, megastep=1, retry_backoff_s=0.0,
        prefix_cache=True))
    assert eng.prefix_cache
    # phase 1: two sequential requests park their prompt blocks in the
    # cache tier (engine drains between them — nothing live holds them)
    for i, p in enumerate(_prompts(cfg, 2, plen=9, seed=3)):
        eng.submit(Request(i, p, max_new_tokens=4))
        assert eng.run()[i].ok
    assert eng.kv.cached_blocks > 0
    eng.assert_quiescent()                # cache-aware drain audit
    # phase 2: live work under a shrink the cache tier alone absorbs
    eng.faults = FaultPlane([FaultEvent(
        eng.iterations + 2, "budget",
        budget_bytes=9 * probe.block_bytes)])
    for i, p in enumerate(_prompts(cfg, 2, plen=6, seed=4)):
        eng.submit(Request(10 + i, p, max_new_tokens=10))
    done = eng.run()
    assert all(done[10 + i].ok and len(done[10 + i].tokens) == 10
               for i in range(2))
    assert eng.kv.prefix_cache_evictions > 0, \
        "shrink never touched the cache tier"
    assert eng.preemptions == 0, \
        "live request demoted while cold cache was evictable"
    assert eng.kv.in_use <= eng.kv.budget
    eng.assert_quiescent()


@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_cache_tier_survives_budget_chaos(model, seed):
    """Random budget fault schedules against a cache-enabled engine on
    a shared-prefix workload: every id resolves, nothing wedges, and
    the drain audit proves zero leaked blocks + consistent cache-tier
    refcounts after the churn (shrinks evict, revivals re-admit)."""
    from repro.runtime.config import EngineConfig
    cfg, api, params = model
    probe = BlockKVCache(cfg, 0, block_size=4)
    eng = ContinuousEngine(api, params, config=EngineConfig(
        hbm_budget=12 * probe.block_bytes, max_batch=3, block_size=4,
        max_context=32, megastep=1, retry_backoff_s=0.0,
        prefix_cache=True))
    eng.faults = FaultPlane.random(
        seed, budget_bytes=eng.kv.budget,
        request_ids=list(range(6)), max_batch=3, kinds=("budget",))
    rng = np.random.default_rng(seed)
    pfx = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    for i in range(6):
        tail = rng.integers(0, cfg.vocab_size,
                            1 + i % 3).astype(np.int32)
        eng.submit(Request(i, np.concatenate([pfx, tail]),
                           max_new_tokens=5))
    done = eng.run(max_iters=2000)
    assert sorted(done) == list(range(6))
    for i in range(6):
        assert done[i].status in COMPLETION_STATUSES
        assert done[i].reason != "max_iters", "engine wedged"
    eng.assert_quiescent()


def test_spill_falls_back_to_demote_when_host_tier_full(model):
    """A host pool too small for even one slot's blocks: preemption
    demote-discards exactly as without the tier — the run still
    completes (via re-prefill) and never wedges on a full tier."""
    cfg, _, _ = model
    probe = BlockKVCache(cfg, 0, block_size=4)
    eng = _engine(model, megastep=1, hbm_budget_bytes=int(
        (12 * probe.block_bytes + 3 * probe.state_bytes) / 0.6) + 1,
        host_pool=1)                      # 1 byte: nothing ever fits
    assert eng.spill_enabled              # armed, but no capacity
    full = eng.kv.budget
    eng.faults = FaultPlane([
        FaultEvent(3, "budget", budget_bytes=2 * probe.block_bytes),
        FaultEvent(9, "budget", budget_bytes=full),
    ])
    for i, p in enumerate(_prompts(cfg, 3, plen=6)):
        eng.submit(Request(i, p, max_new_tokens=10))
    done = eng.run()
    assert all(done[i].ok and len(done[i].tokens) == 10
               for i in range(3))
    assert eng.spills == 0 and eng.restores == 0
    assert eng.reprefill_tokens > 0       # demote path replayed tokens
    eng.assert_quiescent()


def test_stall_iterations_are_visible(model):
    """PR 6 made the engine stall (not raise) through a shrunk budget
    while a restore pends — but the stall was invisible.  Now every
    stalled iteration counts in engine.stalls / stats()."""
    cfg, _, _ = model
    probe = BlockKVCache(cfg, 0, block_size=4)
    eng = _engine(model, megastep=1, hbm_budget_bytes=int(
        (12 * probe.block_bytes + 3 * probe.state_bytes) / 0.6) + 1,
        host_pool=64 * probe.block_bytes)
    full = eng.kv.budget
    eng.faults = FaultPlane([
        FaultEvent(3, "budget", budget_bytes=1),   # below one block
        FaultEvent(9, "budget", budget_bytes=full),
    ])
    for i, p in enumerate(_prompts(cfg, 3, plen=6)):
        eng.submit(Request(i, p, max_new_tokens=10))
    done = eng.run()
    assert all(done[i].ok for i in range(3))
    assert eng.stalls > 0
    assert eng.stats()["counters"]["engine.stalls"] == eng.stalls
    eng.assert_quiescent()


def test_host_pool_env_knob(monkeypatch):
    from repro.runtime.config import HOST_POOL_ENV, EngineConfig

    def host_pool(**kw):
        return EngineConfig(hbm_budget=1 << 30, **kw).host_pool

    monkeypatch.delenv(HOST_POOL_ENV, raising=False)
    assert host_pool() == 0                   # unset: tier disabled
    assert host_pool(host_pool=1 << 20) == 1 << 20  # explicit wins
    monkeypatch.setenv(HOST_POOL_ENV, "512K")
    assert host_pool() == 512 << 10
    assert host_pool(host_pool=0) == 0        # explicit 0 beats env
    monkeypatch.setenv(HOST_POOL_ENV, "lots")
    with pytest.raises(ValueError, match=HOST_POOL_ENV):
        host_pool()
    monkeypatch.setenv(HOST_POOL_ENV, "-4K")
    with pytest.raises(ValueError, match=">= 0"):
        host_pool()


def test_budget_shrink_without_restore_still_raises(model):
    """No scheduled recovery -> permanent infeasibility keeps the
    original MemoryError contract instead of stalling forever."""
    cfg, _, _ = model
    eng = _engine(model)
    eng.faults = FaultPlane([FaultEvent(2, "budget", budget_bytes=0)])
    eng.submit(Request(0, _prompts(cfg, 1, plen=6)[0],
                       max_new_tokens=10))
    with pytest.raises(MemoryError):
        eng.run()


def test_poison_retry_recovers(model):
    """One poisoned dispatch: the watchdog trips, the engine rolls back
    to the pre-dispatch cache snapshot and the N=1 retry completes every
    stream full-length — zero rows failed."""
    cfg, _, _ = model
    eng = _engine(model, megastep=1)
    eng.faults = FaultPlane([FaultEvent(3, "poison", rows=(0, 1, 2))])
    for i, p in enumerate(_prompts(cfg, 3)):
        eng.submit(Request(i, p, max_new_tokens=6))
    done = eng.run()
    assert all(done[i].ok and len(done[i].tokens) == 6 for i in range(3))
    assert eng.watchdog_trips >= 1
    assert eng.retry_dispatches >= 1
    assert eng.rows_failed == 0
    assert eng.stepper.poisoned_traces >= 1   # injected in-trace
    eng.assert_quiescent()


def test_poison_exhaustion_fails_only_affected_rows(model):
    """Persistent poison on ONE row exhausts the bounded retries and
    fails exactly that row (bottom of the ladder); co-batched rows ride
    the same dispatches and still complete full-length."""
    cfg, _, _ = model
    eng = _engine(model, megastep=1)
    eng.faults = FaultPlane([FaultEvent(3, "poison", rows=(1,),
                                        repeats=9)])
    for i, p in enumerate(_prompts(cfg, 3)):
        eng.submit(Request(i, p, max_new_tokens=6))
    done = eng.run()
    failed = [i for i in range(3) if done[i].status == "failed"]
    assert len(failed) == 1
    assert done[failed[0]].reason == "poisoned_logits"
    assert len(done[failed[0]].tokens) < 6    # partial stream returned
    assert all(done[i].ok and len(done[i].tokens) == 6
               for i in range(3) if i not in failed)
    assert eng.rows_failed == 1
    eng.assert_quiescent()


def test_poison_megastep_falls_back_to_sync(model):
    """A poisoned megastep is discarded whole (snapshot restore +
    reservation release) and the iteration re-runs on the N=1 sync
    path — first rung of the degradation ladder."""
    cfg, _, _ = model
    eng = _engine(model, megastep=8)
    eng.faults = FaultPlane([FaultEvent(2, "poison", rows=(0, 1, 2))])
    for i, p in enumerate(_prompts(cfg, 3)):
        eng.submit(Request(i, p, max_new_tokens=8))
    done = eng.run()
    assert all(done[i].ok and len(done[i].tokens) == 8 for i in range(3))
    assert eng.megastep_fallbacks == 1
    assert eng.watchdog_trips >= 1
    assert eng.rows_failed == 0
    eng.assert_quiescent()


def test_nan_params_trip_watchdog_not_streams(model):
    """Genuinely corrupted device results (NaN weights, not injected
    poison) must surface as failed rows with reason 'poisoned_logits' —
    never as silently emitted garbage tokens."""
    cfg, api, params = model
    bad = jax.tree_util.tree_map(
        lambda x: jnp.full_like(x, jnp.nan)
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x,
        params)
    eng = ContinuousEngine(api, bad, hbm_budget_bytes=1 << 30,
                           max_batch=2, block_size=4, max_context=32,
                           retry_backoff_s=0.0)
    eng.submit(Request(0, np.arange(4, dtype=np.int32) + 1,
                       max_new_tokens=4))
    done = eng.run()
    assert done[0].status == "failed"
    assert done[0].reason == "poisoned_logits"
    assert done[0].tokens == []           # poisoned from the first token
    assert eng.watchdog_trips >= 1
    eng.assert_quiescent()


def test_iteration_cap_resolves_every_request(model):
    """run(max_iters) hitting the cap fails still-live requests with a
    machine-readable reason and reclaims their blocks — an explicit
    resolution, never a silent drop."""
    cfg, _, _ = model
    eng = _engine(model, max_batch=2)
    for i, p in enumerate(_prompts(cfg, 4)):
        eng.submit(Request(i, p, max_new_tokens=20))
    done = eng.run(max_iters=2)
    assert sorted(done) == list(range(4))
    assert all(done[i].status == "failed"
               and done[i].reason == "max_iters" for i in range(4))
    eng.assert_quiescent()


def test_round_engine_cap_resolves_queue(model):
    cfg, api, params = model
    eng = ServingEngine(api, params, hbm_budget_bytes=1 << 30,
                        max_batch=2, max_context=32)
    for i, p in enumerate(_prompts(cfg, 2)):
        eng.submit(Request(i, p, max_new_tokens=4))
    done = eng.run(max_rounds=0)
    assert all(done[i].status == "failed"
               and done[i].reason == "max_rounds" for i in range(2))


def test_kv_set_budget_and_quiescence():
    cfg = get_config("stablelm-3b").reduced()
    kv = BlockKVCache(cfg, 1 << 30, block_size=4)
    kv.assert_quiescent()
    full = kv.budget
    kv.admit(0, 8)
    with pytest.raises(AssertionError):
        kv.assert_quiescent()             # live table = leak
    kv.set_budget(kv.in_use // 2)         # below in_use: never evicts
    assert kv.headroom < 0
    assert kv.in_use == 2 * kv.block_bytes
    kv.set_budget(full)
    assert kv.budget == full
    with pytest.raises(ValueError):
        kv.set_budget(-1)
    kv.free(0)
    kv.assert_quiescent()


def test_serve_entry_fault_plane_smoke():
    """launch/serve.py wires the plane + knobs end-to-end (and calls
    assert_quiescent itself)."""
    from repro.launch.serve import serve
    done = serve("stablelm-3b", n_requests=3, max_new=4,
                 engine_mode="continuous", fault_seed=0, max_queue=8)
    assert sorted(done) == [0, 1, 2]
    assert all(c.status in COMPLETION_STATUSES for c in done.values())
    with pytest.raises(ValueError, match="continuous"):
        serve("stablelm-3b", n_requests=1, engine_mode="round",
              fault_seed=0)


# -- chaos fuzz (pinned child process) ---------------------------------------

@pytest.fixture(scope="module")
def chaos_report():
    proc = subprocess.run(
        [sys.executable, CHILD, "--chaos", "stablelm-3b"]
        + [str(s) for s in CHAOS_SEEDS],
        capture_output=True, text=True, timeout=1800)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(
        proc.stdout.strip().splitlines()[-1])["stablelm-3b"]


def test_chaos_fuzz_invariants(chaos_report):
    """>= 50 seeded schedules, each kind alone and combined, each
    replayed at N in {1, 8}: every id resolves, completed streams are
    bit-identical to the fault-free reference, partial streams are
    prefixes, zero blocks leak."""
    assert chaos_report["schedules"] >= 50
    assert chaos_report["runs"] == 2 * chaos_report["schedules"]
    assert chaos_report["ok"], chaos_report["violations"][:5]


def test_chaos_cancel_mid_megastep_identity(chaos_report):
    """Satellite: cancelling a request mid-megastep (both between
    megasteps and post-reserve) leaves surviving rows bit-identical
    across N in {1, 8}; the victim keeps a nonempty strict prefix."""
    assert chaos_report["cancel_survivors_identical"]
    assert chaos_report["cancel_victim_mid_stream"]


def test_chaos_spill_zero_reprefill(chaos_report):
    """Satellite: every budget-bearing chaos schedule replayed with a
    host tier yields bit-identical streams with ZERO re-prefilled
    tokens — preempted work is restored, never recomputed — and the
    deterministic shrink/restore anchor spills, restores, and saves
    prefill tokens at both N in {1, 8}."""
    assert chaos_report["spill_supported"]
    assert chaos_report["spill_schedules"] > 0
    assert chaos_report["spill_runs"] == 2 * chaos_report["spill_schedules"]
    assert chaos_report["spill_ok"], chaos_report["spill_violations"][:5]
    assert chaos_report["spill_total_restores"] > 0
    assert chaos_report["spill_total_spills"] \
        == chaos_report["spill_total_restores"]
    assert chaos_report["spill_anchor_ok"]
