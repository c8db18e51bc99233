"""Tests: continuous-batching engine, block KV cache, incremental admission.

Stream-identity assertions run in a child process that disables
asynchronous CPU dispatch (a backend-init-time option, hence the
separate process) — bitwise comparisons are only meaningful without the
async runtime's heap-layout-dependent result variance; see
tests/serving_identity_child.py.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.arena import _align
from repro.core.scheduler import incremental_select
from repro.models import build_model
from repro.runtime.engine import ContinuousEngine, Request, ServingEngine
from repro.runtime.kv_cache import (BlockKVCache, kv_bytes_per_token,
                                    state_bytes)

CHILD = os.path.join(os.path.dirname(__file__),
                     "serving_identity_child.py")
IDENTITY_ARCHS = ["stablelm-3b", "mamba2-370m", "h2o-danube-3-4b"]


# -- stream identity (pinned child process) ----------------------------------

@pytest.fixture(scope="module")
def identity_report():
    proc = subprocess.run(
        [sys.executable, CHILD] + IDENTITY_ARCHS,
        capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_continuous_streams_bit_identical_to_round(identity_report):
    """Scheduling must be lossless: same tokens out of both engines.
    (The dispatch-count win is asserted on scheduling-relevant workloads
    in test_iteration_level_backfill_beats_rounds_on_dispatches and by
    benchmarks/serving.py — tiny identity workloads can tie.)"""
    for arch in IDENTITY_ARCHS:
        r = identity_report[arch]
        assert r["identical"], f"{arch}: streams diverged"
        assert r["n_tokens"] > 0


def test_paged_cache_bit_identical_to_dense(identity_report):
    """The block size is a pure memory-layout choice: the continuous
    engine must emit the same bits for every block size in the matrix
    (1, non-power-of-two, 16), and the round engine on its contiguous
    table the same bits as the continuous engine, at each size."""
    for arch in IDENTITY_ARCHS:
        r = identity_report[arch]
        assert r["identical"], f"{arch}: round != continuous"
        if r["has_attn"]:
            assert r["block_size_invariant"], \
                f"{arch}: block size changed decoded tokens"


def test_prefix_sharing_lossless_and_engaged(identity_report):
    """Cross-request prefix sharing must not change any stream while
    actually mapping blocks instead of allocating them."""
    for arch in IDENTITY_ARCHS:
        r = identity_report[arch]
        if "sharing_identical" not in r:
            continue                  # hybrid/SSM archs: sharing off
        assert r["sharing_identical"], f"{arch}: sharing changed streams"
        assert r["shared_hits"] > 0, f"{arch}: sharing never engaged"
        assert r["sharing_saved_blocks"] > 0, arch


@pytest.fixture(scope="module")
def cache_report():
    proc = subprocess.run(
        [sys.executable, CHILD, "--cache"] + IDENTITY_ARCHS,
        capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_prefix_cache_lossless_and_engaged(cache_report):
    """Persistent prefix cache: sequential arrivals (live sharing gets
    zero hits) must decode bit-identical streams cache-on vs cache-off
    at megastep N in {1, 8}, while the cache actually skips re-prefill;
    hybrid/SSM archs must gate the cache off entirely."""
    engaged = 0
    for arch in IDENTITY_ARCHS:
        r = cache_report[arch]
        if not r["supported"]:        # hybrid/SSM: state can't outlive
            continue                  # its slot; cache must stay off
        engaged += 1
        assert r["seq_identical"], f"{arch}: cache changed streams"
        assert r["seq_saved_n8"] > 0 and r["seq_saved_n1"] > 0, \
            f"{arch}: cache saved no prefill on sequential arrivals"
        assert r["seq_saved_n8"] == r["seq_saved_n1"], \
            f"{arch}: savings differ across megastep N"
        assert r["seq_hits_n8"] > 0, arch
        assert r["seq_saved_off"] == 0, \
            f"{arch}: cache-off engine reported savings"
    assert engaged > 0, "no arch exercised the prefix cache"


def test_prefix_cache_concurrent_and_eviction_identity(cache_report):
    """Revivals interleaved with live sharing (two concurrent waves)
    and LRU evictions under a tight budget must both leave streams
    bit-identical to cache-off."""
    for arch in IDENTITY_ARCHS:
        r = cache_report[arch]
        if not r["supported"]:
            continue
        assert r["concurrent_identical"], \
            f"{arch}: concurrent revival changed streams"
        assert r["concurrent_hit_blocks"] > 0, \
            f"{arch}: second wave never hit the cache"
        assert r["evict_identical"], \
            f"{arch}: eviction churn changed streams"
        assert r["evictions"] > 0, \
            f"{arch}: tight-budget run never evicted"


def test_single_paged_trace_across_engines(identity_report):
    """Every engine with one pool shape — the round engine and the
    preempting, tight-budget and sharing continuous engines — reuses ONE
    compiled decode + chunk trace (block tables are traced values, not
    shapes)."""
    for arch in IDENTITY_ARCHS:
        assert identity_report[arch]["single_paged_decode_trace"], arch
        assert identity_report[arch]["single_paged_chunk_trace"], arch


def test_preemption_replays_identical_streams(identity_report):
    for arch in IDENTITY_ARCHS:
        r = identity_report[arch]
        assert r["tight_completed"], f"{arch}: requests lost under "\
            f"tight budget"
        assert r["tight_identical"], f"{arch}: preemption changed streams"
        if r["has_attn"]:
            # lazy growth exists only for attention KV; pure-SSM state
            # never grows, so nothing ever needs demoting
            assert r["preemptions"] > 0, arch
        assert r["tight_reuse"] > 0, arch


def test_block_reuse_and_slot_isolation(identity_report):
    for arch in IDENTITY_ARCHS:
        r = identity_report[arch]
        assert r["reuse"] > 0, f"{arch}: no cross-request block reuse"
        assert r["isolation"], f"{arch}: stale slot state leaked"


def test_greedy_decode_deterministic_and_chunk_invariant(identity_report):
    """Same engine config twice -> same streams; prefill chunk width
    (1 vs 4 vs 8) must not change decoded tokens.  (Moved here from
    test_runtime.py: stream comparisons need the child's synchronous
    dispatch — see serving_identity_child.py.)"""
    for arch in IDENTITY_ARCHS:
        assert identity_report[arch]["deterministic"], arch
        assert identity_report[arch]["chunk_invariant"], arch


def test_single_trace_per_step_fn(identity_report):
    """The whole run — mixed prompt lengths, ragged final chunks,
    requests joining/leaving — compiles ONE decode trace and ONE chunk
    trace (the shared stepper served the round and the continuous
    engine on one pool shape)."""
    for arch in IDENTITY_ARCHS:
        assert identity_report[arch]["single_decode_trace"], arch
        assert identity_report[arch]["single_chunk_trace"], arch


def test_megastep_streams_invariant_across_n(identity_report):
    """The decode megastep is a pure dispatch-fusion optimization: the
    continuous engine at N in {1, 4, 8} must emit the same bits, with
    fused dispatches actually used at the default N."""
    for arch in IDENTITY_ARCHS:
        r = identity_report[arch]
        assert r["megastep_invariant"], f"{arch}: megastep changed "\
            f"streams"
        assert r["megasteps_used"] > 0, f"{arch}: default engine never "\
            f"fused"


def test_megastep_eos_terminates_in_carry(identity_report):
    """Per-row EOS flips the active mask inside the scan: streams stop
    exactly at the EOS token and match the per-iteration engine."""
    for arch in IDENTITY_ARCHS:
        r = identity_report[arch]
        assert r["eos_identical"], f"{arch}: EOS diverged N=8 vs N=1"
        assert r["eos_truncated"], f"{arch}: stream not cut at EOS"


def test_megastep_traces_once_per_scan_length(identity_report):
    """Each distinct megastep length compiles exactly once; re-tracing
    an already-seen N would mean the scan signature leaks per-iteration
    values."""
    for arch in IDENTITY_ARCHS:
        assert identity_report[arch]["megastep_no_retrace"], arch


# -- round engine: single-trace regression (satellite) -----------------------

def test_round_engine_prefill_single_trace_across_remainders():
    """Distinct final-chunk remainder widths (prompts 3, 6, 17 with
    chunk 8) must NOT retrace the chunk fn: the last chunk is padded to
    ``prefill_chunk`` and masked per row."""
    cfg = get_config("stablelm-3b").reduced()
    api = build_model(cfg)
    params = api.init(jax.random.key(0))
    eng = ServingEngine(api, params, hbm_budget_bytes=1 << 30,
                        max_batch=2, prefill_chunk=8, max_context=40)
    rng = np.random.default_rng(0)
    for i, plen in enumerate([3, 6, 17, 8]):
        eng.submit(Request(i, rng.integers(0, cfg.vocab_size, plen)
                           .astype(np.int32), max_new_tokens=2))
    done = eng.run()
    assert sorted(done) == [0, 1, 2, 3]
    assert eng.stepper.chunk_traces == 1
    assert eng.stepper.decode_traces == 1


# -- continuous engine scheduling ---------------------------------------------

def _engine(cfg_name="stablelm-3b", **kw):
    cfg = get_config(cfg_name).reduced()
    api = build_model(cfg)
    params = api.init(jax.random.key(0))
    kw.setdefault("hbm_budget_bytes", 1 << 30)
    kw.setdefault("max_batch", 3)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_context", 32)
    return cfg, ContinuousEngine(api, params, **kw)


def test_more_requests_than_slots_all_complete():
    cfg, eng = _engine()
    rng = np.random.default_rng(1)
    for i in range(10):
        eng.submit(Request(i, rng.integers(0, cfg.vocab_size, 5)
                           .astype(np.int32), max_new_tokens=3))
    done = eng.run()
    assert sorted(done) == list(range(10))
    assert all(len(c.tokens) == 3 for c in done.values())
    assert eng.kv.peak_bytes <= eng.kv.budget
    assert eng.kv.in_use == 0                     # everything released
    assert eng.kv.reuse_count > 0                 # slot churn reused blocks
    eng.assert_quiescent()


def test_prefill_only_requests_emit_no_tokens():
    """max_new_tokens=0 is a prefill-only request in BOTH engines: it
    completes with an empty token list (and still releases its blocks)."""
    cfg = get_config("stablelm-3b").reduced()
    api = build_model(cfg)
    params = api.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 5).astype(np.int32)
               for _ in range(2)]
    r_eng = ServingEngine(api, params, hbm_budget_bytes=1 << 30,
                          max_batch=2, max_context=32)
    c_eng = ContinuousEngine(api, params, hbm_budget_bytes=1 << 30,
                             max_batch=2, block_size=4, max_context=32)
    for eng in (r_eng, c_eng):
        eng.submit(Request(0, prompts[0], max_new_tokens=0))
        eng.submit(Request(1, prompts[1], max_new_tokens=3))
        done = eng.run()
        assert done[0].tokens == []
        assert len(done[1].tokens) == 3
    assert c_eng.kv.in_use == 0
    c_eng.assert_quiescent()


def test_request_larger_than_max_context_rejected():
    cfg, eng = _engine(max_context=16)
    with pytest.raises(ValueError):
        eng.submit(Request(0, np.arange(10, dtype=np.int32),
                           max_new_tokens=10))


def test_invalid_submissions_rejected():
    """Empty prompts and duplicate request ids fail fast in BOTH engines
    (admission and completion bookkeeping key on the id)."""
    cfg = get_config("stablelm-3b").reduced()
    api = build_model(cfg)
    params = api.init(jax.random.key(0))
    prompt = np.arange(4, dtype=np.int32)
    for eng in (ServingEngine(api, params, hbm_budget_bytes=1 << 30),
                ContinuousEngine(api, params, hbm_budget_bytes=1 << 30,
                                 max_context=32)):
        with pytest.raises(ValueError):
            eng.submit(Request(0, np.array([], np.int32)))
        eng.submit(Request(0, prompt, max_new_tokens=2))
        with pytest.raises(ValueError):
            eng.submit(Request(0, prompt, max_new_tokens=2))


def test_budget_too_small_for_any_request_raises():
    """BOTH engines surface an unservable request as MemoryError rather
    than silently dropping it from the completion dict."""
    cfg, eng = _engine(hbm_budget_bytes=16)    # a few bytes post-margin
    eng.submit(Request(0, np.arange(6, dtype=np.int32),
                       max_new_tokens=2))
    with pytest.raises(MemoryError):
        eng.run()
    api, params = eng.api, eng.params
    r_eng = ServingEngine(api, params, hbm_budget_bytes=16, max_batch=2)
    r_eng.submit(Request(0, np.arange(6, dtype=np.int32),
                         max_new_tokens=2))
    with pytest.raises(MemoryError):
        r_eng.run()


def test_iteration_level_backfill_beats_rounds_on_dispatches():
    """Long-decode and short-decode requests with EQUAL peak-memory cost
    (plen + max_new identical) land in the same §3.3 round: the round
    engine then burns a decode dispatch per iteration on a mostly-idle
    batch while the long request drains, while the continuous engine
    backfills freed slots immediately — strictly fewer dispatches per
    generated token."""
    cfg = get_config("stablelm-3b").reduced()
    api = build_model(cfg)
    params = api.init(jax.random.key(0))
    rng = np.random.default_rng(2)
    reqs = []
    for i in range(9):
        if i % 3 == 0:           # short prompt, long generation
            plen, new = 4, 18
        else:                    # long prompt, short generation
            plen, new = 18, 4
        reqs.append(Request(i, rng.integers(0, cfg.vocab_size, plen)
                            .astype(np.int32), max_new_tokens=new))
    r_eng = ServingEngine(api, params, hbm_budget_bytes=1 << 30,
                          max_batch=3, max_context=32)
    c_eng = ContinuousEngine(api, params, hbm_budget_bytes=1 << 30,
                             max_batch=3, block_size=4, max_context=32)
    for r in reqs:
        r_eng.submit(Request(r.id, r.prompt, r.max_new_tokens))
        c_eng.submit(Request(r.id, r.prompt, r.max_new_tokens))
    rd, cd = r_eng.run(), c_eng.run()
    r_tok = sum(len(c.tokens) for c in rd.values())
    c_tok = sum(len(c.tokens) for c in cd.values())
    assert r_tok == c_tok == 3 * 18 + 6 * 4
    assert c_eng.dispatches / c_tok < r_eng.dispatches / r_tok
    c_eng.assert_quiescent()


# -- incremental selection (scheduler API) ------------------------------------

def test_incremental_select_charges_live_pool():
    peaks = {1: 10, 2: 20, 3: 30}
    chosen, deferred = incremental_select(peaks, [1, 2, 3], budget=50,
                                          in_use=25)
    assert chosen == [1] and deferred == [2, 3]   # headroom 25: only 10
    chosen, _ = incremental_select(peaks, [1, 2, 3], budget=50, in_use=0)
    assert chosen == [1, 2]
    chosen, deferred = incremental_select(peaks, [1, 2, 3], budget=50,
                                          in_use=60)
    assert chosen == [] and deferred == [1, 2, 3]
    with pytest.raises(ValueError):
        incremental_select(peaks, [1], budget=50, in_use=-1)


# -- block KV cache -----------------------------------------------------------

def test_block_cache_math_and_lifecycle():
    cfg = get_config("stablelm-3b").reduced()
    kv = BlockKVCache(cfg, budget_bytes=1 << 30, block_size=4)
    assert kv.block_bytes == _align(kv_bytes_per_token(cfg) * 4)
    assert kv.blocks_for(0) == 0
    assert kv.blocks_for(1) == 1
    assert kv.blocks_for(4) == 1
    assert kv.blocks_for(5) == 2
    kv.admit(0, 5)
    assert kv.capacity_tokens(0) == 8
    assert kv.in_use == 2 * kv.block_bytes
    assert kv.grow(0, 8)                      # within capacity: no-op
    assert kv.in_use == 2 * kv.block_bytes
    assert kv.grow(0, 9)                      # crosses boundary: +1 block
    assert kv.in_use == 3 * kv.block_bytes
    kv.free(0)
    assert kv.in_use == 0
    kv.admit(1, 12)                           # reuses all three blocks
    assert kv.reuse_count == 3


def test_block_cache_budget_and_ssm_state():
    cfg = get_config("mamba2-370m").reduced()
    kv = BlockKVCache(cfg, budget_bytes=_align(state_bytes(cfg)) * 2,
                      block_size=4)
    assert kv.block_bytes == 0                # no attention layers
    assert kv.bytes_for(1000) == kv.state_bytes
    kv.admit(0, 100)
    kv.admit(1, 100)
    assert kv.grow(0, 10_000)                 # state never grows
    with pytest.raises(MemoryError):
        kv.admit(2, 1)
    kv.free(0)
    kv.admit(2, 1)
    assert kv.reuse_count == 1


def _check_block_cache_ops(cfg, budget, ops):
    """Replay (op, slot, n_tokens) tuples against a BlockKVCache and
    assert the §3.2 pool invariants after every step: never exceed the
    budget, never alias live blocks between slots, account in_use
    exactly, release everything at the end."""
    kv = BlockKVCache(cfg, budget, block_size=4)
    live: "dict[int, int]" = {}               # slot -> token capacity ask
    for op, slot, n in ops:
        if op == 0 and slot not in live:
            try:
                kv.admit(slot, n)
                live[slot] = n
            except MemoryError:
                assert kv.bytes_for(n) > kv.headroom
        elif op == 1 and slot in live:
            if not kv.grow(slot, n):
                extra = kv.blocks_for(n) - len(kv.block_tables[slot])
                assert extra * kv.block_bytes > kv.headroom
        elif op == 2 and slot in live:
            kv.free(slot)
            del live[slot]
        # invariants
        assert kv.in_use <= kv.budget
        assert kv.peak_bytes <= kv.budget
        tables = kv.live_block_ids()
        assert set(tables) == set(live)
        ids = [i for s in tables.values() for i in s]
        assert len(ids) == len(set(ids)), "live blocks aliased"
        expect = sum(len(kv.block_tables[s]) * kv.block_bytes
                     + kv.state_bytes for s in live)
        assert kv.in_use == expect
    for s in list(live):
        kv.free(s)
    assert kv.in_use == 0
    return kv


def _tight_budget(cfg):
    probe = BlockKVCache(cfg, 0, block_size=4)
    return probe.block_bytes * 7 + probe.state_bytes * 4


@pytest.mark.parametrize("arch", ["stablelm-3b", "jamba-v0.1-52b",
                                  "mamba2-370m"])
def test_block_cache_fuzz_invariants(arch):
    """Seeded random admit/grow/free churn (always runs, no hypothesis):
    invariants hold and uniform-size blocks get reused.  jamba covers
    the hybrid case where block and state slabs coexist — they must
    never cross-satisfy each other's pools (budget inflation)."""
    cfg = get_config(arch).reduced()
    rng = np.random.default_rng(0)
    ops = [(int(rng.integers(0, 3)), int(rng.integers(0, 4)),
            int(rng.integers(1, 40))) for _ in range(300)]
    kv = _check_block_cache_ops(cfg, _tight_budget(cfg), ops)
    assert kv.reuse_count > 0                 # churn reused freed blocks


def test_block_cache_property_invariants():
    """Hypothesis sweep of arbitrary admit/grow/free sequences over the
    same invariant checker (importorskip-guarded)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    cfg = get_config("stablelm-3b").reduced()
    budget = _tight_budget(cfg)

    @settings(max_examples=60, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, 2), st.integers(0, 3),
                              st.integers(1, 40)), max_size=40))
    def run(ops):
        _check_block_cache_ops(cfg, budget, ops)

    run()
