"""Child process for serving stream-identity tests (NOT a pytest file).

Bit-identical stream comparisons require ``jax_cpu_enable_async_dispatch``
to be OFF: with asynchronous dispatch, the XLA CPU runtime occasionally
(heap-layout- and load-dependently) produces materially different values
for an identical dispatch, which flips greedy argmaxes and diverges the
streams (observed ~1-in-5 processes under load; 60/60 clean runs with
synchronous dispatch).  The config flag is global, so the comparison
runs in this dedicated child instead of the pytest process — see
runtime/engine.py for the full determinism contract.

Usage: python serving_identity_child.py <arch> [<arch> ...]
       python serving_identity_child.py --fuzz <arch> [<arch> ...]
       python serving_identity_child.py --chaos <arch> [<seed> ...]
       python serving_identity_child.py --tele <arch> [<arch> ...]
       python serving_identity_child.py --cache <arch> [<arch> ...]
Prints one JSON object {arch: {...checks...}} on the last stdout line.

``--fuzz`` runs the megastep termination fuzz instead of the identity
matrix: rows hitting max-token or EOS at EVERY offset within the
megastep must produce streams bit-identical to the per-iteration
(N=1) engine, with every reserved-but-unused block returned to the
pool (see tests/test_megastep.py, which drives this mode).

``--chaos`` runs the fault-injection fuzz (tests/test_chaos.py): for
each seed, random fault schedules (budget shrink/restore, poisoned
dispatches, cancellations — each kind alone and combined) replay at
megastep N in {1, 8} against a fault-free reference, asserting every
submitted id resolves, completed streams stay bit-identical, partial
streams are prefixes, and the engine drains to quiescence every run.
Budget-bearing schedules additionally replay with the host KV tier
armed (spill/restore): the same invariants must hold — quiescence now
audits the host tier too — plus ZERO tokens re-prefilled while the
tier has capacity.

``--cache`` runs the persistent prefix-cache identity sweep
(tests/test_serving.py): the cache's hard contract is that reviving
retained blocks changes ZERO decoded bits — sequential arrivals with a
shared system prompt must decode bit-identical cache-on vs cache-off
at megastep N in {1, 8} while actually skipping re-prefill; a two-wave
concurrent workload (revivals interleaved with live sharing) and a
tight-budget run (LRU evictions mid-workload) must stay identical too.

``--tele`` runs the tracing-invariance sweep (tests/test_telemetry.py):
the telemetry plane's hard contract is that arming the span recorder
changes ZERO behavior — the same workload replayed with tracing ON
must emit bit-identical streams and identical dispatch/iteration
counts at megastep N in {1, 8} and on the round engine, and the
recorded events must export to valid Chrome trace-event JSON.
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# pin the default-megastep engines to the shipped default: the checks
# below assert fused dispatches actually happen (megasteps_used > 0),
# which an ambient PARALLAX_MEGASTEP=1 in a developer's shell would
# otherwise break spuriously; explicit megastep arguments still win
os.environ["PARALLAX_MEGASTEP"] = "8"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

jax.config.update("jax_cpu_enable_async_dispatch", False)

import numpy as np

from repro.configs import get_config
from repro.models import build_model
from repro.runtime.config import EngineConfig
from repro.runtime.engine import (COMPLETION_STATUSES, FREE, PREFILL,
                                  ContinuousEngine, Request,
                                  ServingEngine)
from repro.runtime.faults import FaultEvent, FaultPlane
from repro.runtime.kv_cache import BlockKVCache
from repro.runtime.stepper import Stepper

MAX_CONTEXT = 32
MAX_BATCH = 3
BLOCK = 4


def mixed_requests(cfg, n=7, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(i,
                    rng.integers(0, cfg.vocab_size,
                                 int(rng.integers(3, 14))).astype(np.int32),
                    max_new_tokens=int(rng.integers(2, 9)))
            for i in range(n)]


def round_engine(api, params, block_size, stepper):
    """The round engine at the identity matrix's batch and context."""
    return ServingEngine(api, params, stepper=stepper, config=EngineConfig(
        hbm_budget=1 << 30, max_batch=MAX_BATCH, max_context=MAX_CONTEXT,
        block_size=block_size))


def run_arch(arch: str) -> dict:
    cfg = get_config(arch).reduced()
    api = build_model(cfg)
    params = api.init(jax.random.key(0))
    reqs = mixed_requests(cfg)
    shared = Stepper(api)

    def fresh(r):
        return Request(r.id, r.prompt, r.max_new_tokens, r.eos_id)

    # the round engine on the continuous engine's pool shape (same
    # max_batch, max_context and block_size): both run the same traces
    r_eng = round_engine(api, params, BLOCK, shared)
    c_eng = ContinuousEngine(api, params, hbm_budget_bytes=1 << 30,
                             max_batch=MAX_BATCH, block_size=BLOCK,
                             max_context=MAX_CONTEXT, stepper=shared)
    for r in reqs:
        r_eng.submit(fresh(r))
        c_eng.submit(fresh(r))
    rd, cd = r_eng.run(), c_eng.run()
    c_eng.assert_quiescent()
    n_tokens = sum(len(c.tokens) for c in cd.values())

    out = {
        "identical": all(rd[r.id].tokens == cd[r.id].tokens for r in reqs),
        "n_tokens": n_tokens,
        "round_dispatches": r_eng.dispatches,
        "cont_dispatches": c_eng.dispatches,
        "reuse": c_eng.kv.reuse_count,
        "has_attn": any(cfg.is_attn_layer(i)
                        for i in range(cfg.num_layers)),
        "single_decode_trace": shared.decode_traces == 1,
        "single_chunk_trace": shared.chunk_traces == 1,
    }

    # demote-only preemption under a tight block budget must replay the
    # identical streams (re-prefill of consumed tokens is the same
    # per-token computation)
    uniform = [Request(100 + i, np.asarray(reqs[i].prompt[:8] if
                                           len(reqs[i].prompt) >= 8 else
                                           reqs[i].prompt, np.int32),
                       max_new_tokens=6) for i in range(4)]
    big = ContinuousEngine(api, params, hbm_budget_bytes=1 << 30,
                           max_batch=MAX_BATCH, block_size=BLOCK,
                           max_context=MAX_CONTEXT, stepper=shared)
    tight_budget = int((5 * big.kv.block_bytes
                        + 3 * big.kv.state_bytes) / 0.6) + 1
    tight = ContinuousEngine(api, params, hbm_budget_bytes=tight_budget,
                             max_batch=MAX_BATCH, block_size=BLOCK,
                             max_context=MAX_CONTEXT, stepper=shared)
    for r in uniform:
        big.submit(fresh(r))
        tight.submit(fresh(r))
    bd, td = big.run(), tight.run()
    big.assert_quiescent()
    tight.assert_quiescent()
    out["tight_completed"] = len(td) == len(uniform)
    out["tight_identical"] = all(bd[r.id].tokens == td[r.id].tokens
                                 for r in uniform)
    out["preemptions"] = tight.preemptions
    out["tight_reuse"] = tight.kv.reuse_count

    # slot reuse must be state-isolated: a request served after another
    # tenant used its slot decodes exactly like on a fresh engine
    solo = ContinuousEngine(api, params, hbm_budget_bytes=1 << 30,
                            max_batch=MAX_BATCH, block_size=BLOCK,
                            max_context=MAX_CONTEXT, stepper=shared)
    solo.submit(fresh(reqs[-1]))
    out["isolation"] = solo.run()[reqs[-1].id].tokens \
        == cd[reqs[-1].id].tokens
    solo.assert_quiescent()

    # megastep invariance: the default engines above already ran fused
    # (N=8); N=1 (per-iteration path, exercising the plain decode twin)
    # and N=4 must emit the same bits
    mega_ok = True
    for m in (1, 4):
        eng = ContinuousEngine(api, params, hbm_budget_bytes=1 << 30,
                               max_batch=MAX_BATCH, block_size=BLOCK,
                               max_context=MAX_CONTEXT, stepper=shared,
                               megastep=m)
        for r in reqs:
            eng.submit(fresh(r))
        ed = eng.run()
        eng.assert_quiescent()
        mega_ok &= all(ed[r.id].tokens == cd[r.id].tokens for r in reqs)
    out["megastep_invariant"] = mega_ok
    out["megasteps_used"] = c_eng.megasteps

    # EOS termination inside a megastep: pick a mid-stream token of the
    # longest stream as the EOS id — N=8 must truncate exactly like N=1
    longest = max(reqs, key=lambda r: len(cd[r.id].tokens))
    stream = cd[longest.id].tokens
    eos_tok = stream[len(stream) // 2]
    eos_streams = []
    for m in (1, 8):
        eng = ContinuousEngine(api, params, hbm_budget_bytes=1 << 30,
                               max_batch=MAX_BATCH, block_size=BLOCK,
                               max_context=MAX_CONTEXT, stepper=shared,
                               megastep=m)
        for r in reqs:
            eng.submit(Request(r.id, r.prompt, r.max_new_tokens,
                               eos_id=eos_tok))
        ed = eng.run()
        eng.assert_quiescent()
        eos_streams.append({r.id: ed[r.id].tokens for r in reqs})
    out["eos_identical"] = eos_streams[0] == eos_streams[1]
    out["eos_truncated"] = (
        eos_streams[0][longest.id]
        == stream[:stream.index(eos_tok) + 1])

    # ALL engines above, round and continuous, share one pool shape: ONE
    # decode trace + ONE chunk trace for the whole matrix; the megastep
    # traces once per DISTINCT scan length and never re-traces
    out["single_paged_decode_trace"] = shared.decode_traces == 1
    out["single_paged_chunk_trace"] = shared.chunk_traces == 1
    out["megastep_no_retrace"] = (
        shared.megastep_traces == len(shared.megastep_sizes))

    # prefix sharing (attention-only archs): staggered lifetimes so
    # later admissions overlap live holders of the same prompt prefix —
    # streams must stay bit-identical with sharing on vs off, with
    # physical blocks actually mapped instead of allocated
    if c_eng.prefix_sharing:
        rng = np.random.default_rng(7)
        pfx = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
        spr = [Request(200 + i,
                       np.concatenate([pfx, rng.integers(
                           0, cfg.vocab_size, 1 + i % 3)
                           .astype(np.int32)]),
                       max_new_tokens=3 + (i * 5) % 9)
               for i in range(6)]
        share_on = ContinuousEngine(api, params,
                                    hbm_budget_bytes=1 << 30,
                                    max_batch=MAX_BATCH,
                                    block_size=BLOCK,
                                    max_context=MAX_CONTEXT,
                                    stepper=shared)
        share_off = ContinuousEngine(api, params,
                                     hbm_budget_bytes=1 << 30,
                                     max_batch=MAX_BATCH,
                                     block_size=BLOCK,
                                     max_context=MAX_CONTEXT,
                                     stepper=shared,
                                     prefix_sharing=False)
        for r in spr:
            share_on.submit(fresh(r))
            share_off.submit(fresh(r))
        sd, nd = share_on.run(), share_off.run()
        share_on.assert_quiescent()
        share_off.assert_quiescent()
        out["sharing_identical"] = all(sd[r.id].tokens == nd[r.id].tokens
                                       for r in spr)
        out["shared_hits"] = share_on.kv.shared_block_hits
        out["sharing_saved_blocks"] = (share_off.kv.acquired_blocks
                                       - share_on.kv.acquired_blocks)

    # streams must be invariant to the block size — sweep 1 (token-per-
    # block), a non-power-of-two and 16 (two blocks a row), each on both
    # engines: the round engine's contiguous table must give the
    # continuous engine's streams.  Each size is a new pool shape, so
    # each size's pair of engines brings its own stepper.
    if out["has_attn"]:
        sweeps = []
        for bsz in (1, 3, 16):
            step_b = Stepper(api)
            for eng in (ContinuousEngine(api, params,
                                         hbm_budget_bytes=1 << 30,
                                         max_batch=MAX_BATCH,
                                         block_size=bsz,
                                         max_context=MAX_CONTEXT,
                                         stepper=step_b),
                        round_engine(api, params, bsz, step_b)):
                for r in reqs:
                    eng.submit(fresh(r))
                ed = eng.run()
                if isinstance(eng, ContinuousEngine):
                    eng.assert_quiescent()
                sweeps.append(all(ed[r.id].tokens == cd[r.id].tokens
                                  for r in reqs))
        out["block_size_invariant"] = all(sweeps)

    # greedy decode must be deterministic across engine instances
    again = round_engine(api, params, BLOCK, shared)
    for r in reqs:
        again.submit(fresh(r))
    ad = again.run()
    out["deterministic"] = all(ad[r.id].tokens == rd[r.id].tokens
                               for r in reqs)

    # prefill chunk width must not change decoded tokens (1 = the old
    # token-by-token loop; 8 and 4 cover full + ragged-remainder chunks)
    streams = []
    for chunk in (1, 8, 4):
        eng = ServingEngine(api, params, hbm_budget_bytes=1 << 30,
                            max_batch=2, prefill_chunk=chunk,
                            max_context=MAX_CONTEXT)
        eng.submit(fresh(reqs[0]))
        streams.append(eng.run()[reqs[0].id].tokens)
    out["chunk_invariant"] = streams[0] == streams[1] == streams[2]
    return out


class _AuditEngine(ContinuousEngine):
    """Asserts after every iteration that no slot retains reserved-but-
    unused blocks: a surviving slot's table covers exactly its written
    tokens (or its admitted pending prompt while still prefilling).
    Also asserts no request finishes with prompt tokens unconsumed
    (a megastep must never terminate a still-prefilling row — the
    prefill-only regression streams alone cannot reveal)."""

    def _finish(self, slot):
        assert self.slot_off[slot] == len(self._slot_prompt[slot]), \
            (slot, int(self.slot_off[slot]),
             len(self._slot_prompt[slot]))
        super()._finish(slot)

    def step(self):
        super().step()
        if not self.kv.block_bytes:
            return
        for s in range(self.max_batch):
            if self.slot_phase[s] == FREE:
                continue
            need = int(self.slot_len[s])
            if self.slot_phase[s] == PREFILL:
                need = max(need, len(self._slot_prompt[s]))
            held = len(self.kv.block_tables[s])
            assert held == self.kv.blocks_for(max(need, 1)), \
                (s, held, need)


def run_fuzz(arch: str, seed: int = 0) -> dict:
    """Megastep termination fuzz: seeded random workloads where rows hit
    max-token or EOS at every offset within N — streams must match the
    per-iteration engine bit for bit, reserved-but-unused blocks must
    return to the pool every iteration, and the pool high-water of the
    fused engine may exceed N=1's by at most the bulk reservation bound
    (N-1 extra blocks per slot)."""
    cfg = get_config(arch).reduced()
    api = build_model(cfg)
    params = api.init(jax.random.key(0))
    shared = Stepper(api)
    rng = np.random.default_rng(seed)
    checks = {"cases": 0, "identical": True, "drained": True,
              "highwater_bounded": True}

    def run(reqs, megastep, budget=1 << 30):
        eng = _AuditEngine(api, params, hbm_budget_bytes=budget,
                           max_batch=MAX_BATCH, block_size=BLOCK,
                           max_context=MAX_CONTEXT, stepper=shared,
                           megastep=megastep)
        for r in reqs:
            eng.submit(Request(r.id, r.prompt, r.max_new_tokens,
                               eos_id=r.eos_id))
        done = eng.run()
        eng.assert_quiescent()
        return {r.id: done[r.id].tokens for r in reqs}, eng

    for case in range(8):
        n = int(rng.integers(2, 9))
        # max-token terminations at every offset 1..n+1 within/around
        # one megastep, mixed prompt lengths
        reqs = [Request(i,
                        rng.integers(0, cfg.vocab_size,
                                     int(rng.integers(1, 12)))
                        .astype(np.int32),
                        max_new_tokens=1 + (i + case) % (n + 1))
                for i in range(6)]
        # a prefill-only request whose prompt outlives one megastep:
        # it must NOT terminate before its prompt is fully consumed
        reqs.append(Request(6, rng.integers(0, cfg.vocab_size, n + 3)
                            .astype(np.int32), max_new_tokens=0))
        base, e1 = run(reqs, 1)
        fused, e8 = run(reqs, n)
        checks["cases"] += 1
        checks["identical"] &= base == fused
        checks["drained"] &= e8.kv.in_use == 0
        if e8.kv.block_bytes:
            bound = e1.kv.peak_bytes \
                + MAX_BATCH * (n - 1) * e8.kv.block_bytes
            checks["highwater_bounded"] &= e8.kv.peak_bytes <= bound
        # EOS at every offset of the longest stream
        longest = max(base, key=lambda i: len(base[i]))
        for off, tok in enumerate(base[longest]):
            er = [Request(r.id, r.prompt, r.max_new_tokens,
                          eos_id=int(tok)) for r in reqs]
            b, _ = run(er, 1)
            f, e = run(er, n)
            checks["cases"] += 1
            checks["identical"] &= b == f
            checks["drained"] &= e.kv.in_use == 0
    return checks


# -- chaos: fault-injection fuzz (tests/test_chaos.py) -----------------------

#: each kind alone, then combined — a schedule that only shrinks the
#: budget must degrade differently from one that also poisons dispatches
CHAOS_KIND_CONFIGS = (("budget",), ("poison",), ("cancel",),
                      ("budget", "poison"),
                      ("budget", "poison", "cancel"))
CHAOS_SCHEDULES_PER_CONFIG = 4


def _chaos_violation(reqs, done, ref, eng) -> "str | None":
    """First violated chaos invariant, or None when all hold: every id
    resolves with a valid status, completed streams are bit-identical
    to the fault-free reference, cancelled/failed streams are prefixes
    of it, rejected streams are empty, nothing hit the iteration cap,
    and the engine drained to quiescence."""
    for r in reqs:
        if r.id not in done:
            return f"request {r.id} dropped"
        c = done[r.id]
        if c.status not in COMPLETION_STATUSES:
            return f"request {r.id}: unknown status {c.status!r}"
        if c.reason == "max_iters":
            return f"request {r.id}: engine wedged (max_iters)"
        if c.status == "completed" and c.tokens != ref[r.id]:
            return f"request {r.id}: completed stream diverged"
        if c.status in ("cancelled", "failed") \
                and c.tokens != ref[r.id][:len(c.tokens)]:
            return f"request {r.id}: {c.status} stream not a prefix"
        if c.status == "rejected" and c.tokens:
            return f"request {r.id}: rejected with tokens"
    try:
        eng.assert_quiescent()
    except AssertionError as e:
        return f"not quiescent: {e}"
    return None


def run_chaos(arch: str, seeds) -> dict:
    """Random fault schedules — every kind alone and combined — replay
    at megastep N in {1, 8} against one fault-free reference; the pool
    is tight enough (12 blocks) that budget shrinks actually bite."""
    cfg = get_config(arch).reduced()
    api = build_model(cfg)
    params = api.init(jax.random.key(0))
    shared = Stepper(api)
    rng = np.random.default_rng(0)
    reqs = [Request(i,
                    rng.integers(0, cfg.vocab_size,
                                 int(rng.integers(3, 9))).astype(np.int32),
                    max_new_tokens=int(rng.integers(3, 9)))
            for i in range(6)]
    probe = BlockKVCache(cfg, 0, block_size=BLOCK)
    hbm = int((12 * probe.block_bytes
               + MAX_BATCH * probe.state_bytes) / 0.6) + 1

    def play(megastep, faults, requests, budget=hbm, host_pool=0):
        eng = ContinuousEngine(api, params, hbm_budget_bytes=budget,
                               max_batch=MAX_BATCH, block_size=BLOCK,
                               max_context=MAX_CONTEXT, stepper=shared,
                               megastep=megastep, faults=faults,
                               retry_backoff_s=0.0, host_pool=host_pool)
        for r in requests:
            eng.submit(Request(r.id, r.prompt, r.max_new_tokens))
        return eng.run(max_iters=2000), eng

    ref_done, ref_eng = play(1, None, reqs)
    ref_eng.assert_quiescent()
    ref = {r.id: ref_done[r.id].tokens for r in reqs}
    full_budget = ref_eng.kv.budget

    out = {"schedules": 0, "runs": 0, "violations": []}
    for seed in seeds:
        for ci, kinds in enumerate(CHAOS_KIND_CONFIGS):
            for si in range(CHAOS_SCHEDULES_PER_CONFIG):
                plane = FaultPlane.random(
                    int(seed) * 1000 + ci * 100 + si,
                    budget_bytes=full_budget,
                    request_ids=[r.id for r in reqs],
                    max_batch=MAX_BATCH, kinds=kinds)
                out["schedules"] += 1
                for m in (1, 8):
                    done, eng = play(m, plane, reqs)
                    out["runs"] += 1
                    bad = _chaos_violation(reqs, done, ref, eng)
                    if bad:
                        out["violations"].append(
                            {"seed": int(seed), "kinds": list(kinds),
                             "schedule": si, "megastep": m,
                             "why": bad})
    out["ok"] = not out["violations"]

    # satellite: cancelling a request MID-STREAM — both between
    # megasteps ("start") and right after a megastep bulk-reserved its
    # blocks ("post_reserve") — leaves every surviving row's stream
    # bit-identical across N in {1, 8}; the victim keeps a nonempty
    # strict prefix (proving the cancel landed mid-stream, not before
    # admission or after completion)
    s4 = [Request(50 + i, rng.integers(0, cfg.vocab_size, 6)
                  .astype(np.int32), max_new_tokens=24)
          for i in range(3)]
    victim = s4[0].id

    def play4(megastep, faults):
        done, eng = play(megastep, faults, s4, budget=1 << 30)
        eng.assert_quiescent()
        return done

    ref4_done = play4(1, None)
    ref4 = {r.id: ref4_done[r.id].tokens for r in s4}
    plane_start = FaultPlane([FaultEvent(3, "cancel",
                                         request_id=victim)])
    plane_pr = FaultPlane([FaultEvent(3, "cancel", request_id=victim,
                                      when="post_reserve")])
    cancel_runs = [play4(1, plane_start), play4(8, plane_start),
                   play4(8, plane_pr)]
    out["cancel_survivors_identical"] = all(
        d[r.id].tokens == ref4[r.id]
        for d in cancel_runs for r in s4[1:])
    out["cancel_victim_mid_stream"] = all(
        d[victim].status == "cancelled"
        and 0 < len(d[victim].tokens) < len(ref4[victim])
        and d[victim].tokens == ref4[victim][:len(d[victim].tokens)]
        for d in cancel_runs)

    # satellite: host-tier spill/restore — replay every budget-bearing
    # schedule with the host KV tier armed (64 blocks: ample for this
    # workload, so every preemption can spill).  All the headline chaos
    # invariants must still hold, quiescence now audits the host tier
    # too, and additionally ZERO tokens may be re-prefilled: every
    # budget-shrink preemption spills and every re-admission restores
    # instead of replaying prefill.
    spill_supported = probe.block_bytes > 0 and probe.state_bytes == 0
    out["spill_supported"] = spill_supported
    out["spill_schedules"] = 0
    out["spill_runs"] = 0
    out["spill_violations"] = []
    out["spill_total_spills"] = 0
    out["spill_total_restores"] = 0
    if spill_supported:
        host_pool = 64 * probe.block_bytes
        budget_configs = [k for k in CHAOS_KIND_CONFIGS
                          if "budget" in k]
        for seed in seeds:
            for ci, kinds in enumerate(budget_configs):
                for si in range(CHAOS_SCHEDULES_PER_CONFIG):
                    plane = FaultPlane.random(
                        int(seed) * 1000 + ci * 100 + si,
                        budget_bytes=full_budget,
                        request_ids=[r.id for r in reqs],
                        max_batch=MAX_BATCH, kinds=kinds)
                    out["spill_schedules"] += 1
                    for m in (1, 8):
                        done, eng = play(m, plane, reqs,
                                         host_pool=host_pool)
                        out["spill_runs"] += 1
                        assert eng.spill_enabled
                        bad = _chaos_violation(reqs, done, ref, eng)
                        if bad is None and eng.reprefill_tokens:
                            bad = (f"{eng.reprefill_tokens} tokens "
                                   f"re-prefilled with host capacity")
                        if bad:
                            out["spill_violations"].append(
                                {"seed": int(seed),
                                 "kinds": list(kinds),
                                 "schedule": si, "megastep": m,
                                 "why": bad})
                        out["spill_total_spills"] += eng.spills
                        out["spill_total_restores"] += eng.restores
        # deterministic anchor: a shrink that demotes every slot, then
        # a scheduled restore — at N in {1, 8} the run must actually
        # exercise the spill path (not vacuously pass) and come back
        # bit-identical with zero re-prefill
        shrink_plane = FaultPlane([
            FaultEvent(4, "budget", budget_bytes=2 * probe.block_bytes),
            FaultEvent(10, "budget", budget_bytes=full_budget),
        ])
        anchor_ok = True
        for m in (1, 8):
            done, eng = play(m, shrink_plane, reqs,
                             host_pool=host_pool)
            bad = _chaos_violation(reqs, done, ref, eng)
            if bad or eng.restores == 0 or eng.reprefill_tokens \
                    or eng.prefill_tokens_saved == 0:
                anchor_ok = False
                out["spill_violations"].append(
                    {"anchor": True, "megastep": m,
                     "why": bad or f"restores={eng.restores} "
                     f"reprefill={eng.reprefill_tokens} "
                     f"saved={eng.prefill_tokens_saved}"})
        out["spill_anchor_ok"] = anchor_ok
    out["spill_ok"] = not out["spill_violations"]
    return out


def run_tele(arch: str) -> dict:
    """Tracing-invariance sweep — the telemetry plane's hard contract:
    arming the span recorder changes ZERO behavior.  For megastep N in
    {1, 8} (sync decode path and fused scan) and for the round engine,
    the same workload runs untraced and traced on one shared stepper;
    streams, dispatch counts and iteration counters must come back
    bit-identical, and the recorded events must export to valid Chrome
    trace-event JSON carrying the expected span kinds."""
    from repro.runtime.telemetry import Telemetry, validate_chrome_trace

    cfg = get_config(arch).reduced()
    api = build_model(cfg)
    params = api.init(jax.random.key(0))
    reqs = mixed_requests(cfg)
    shared = Stepper(api)

    def fresh(r):
        return Request(r.id, r.prompt, r.max_new_tokens, r.eos_id)

    def mk_cont(megastep, telemetry=None):
        return ContinuousEngine(api, params, hbm_budget_bytes=1 << 30,
                                max_batch=MAX_BATCH, block_size=BLOCK,
                                max_context=MAX_CONTEXT, stepper=shared,
                                megastep=megastep, telemetry=telemetry)

    out = {}
    for m in (1, 8):
        base = mk_cont(m)
        tele = Telemetry(trace=True)
        traced = mk_cont(m, telemetry=tele)
        for r in reqs:
            base.submit(fresh(r))
            traced.submit(fresh(r))
        bd, td = base.run(), traced.run()
        base.assert_quiescent()
        traced.assert_quiescent()
        out[f"m{m}_identical"] = all(bd[r.id].tokens == td[r.id].tokens
                                     for r in reqs)
        out[f"m{m}_dispatches_equal"] = \
            base.dispatches == traced.dispatches
        out[f"m{m}_iterations_equal"] = (
            base.iterations == traced.iterations
            and base.fused_iterations == traced.fused_iterations)
        require = ("iteration", "kv_pool",
                   "megastep" if m == 8 else "decode")
        try:
            validate_chrome_trace(tele.chrome_trace(),
                                  require_names=require)
            out[f"m{m}_trace_valid"] = True
        except ValueError as e:
            out[f"m{m}_trace_valid"] = False
            out[f"m{m}_trace_error"] = str(e)
        out[f"m{m}_span_kinds"] = sorted(
            {e["kind"] for e in tele.rec.events})

    r_base = ServingEngine(api, params, hbm_budget_bytes=1 << 30,
                           max_batch=MAX_BATCH, max_context=MAX_CONTEXT,
                           stepper=shared)
    r_tele = Telemetry(trace=True)
    r_traced = ServingEngine(api, params, hbm_budget_bytes=1 << 30,
                             max_batch=MAX_BATCH,
                             max_context=MAX_CONTEXT, stepper=shared,
                             telemetry=r_tele)
    for r in reqs:
        r_base.submit(fresh(r))
        r_traced.submit(fresh(r))
    rbd, rtd = r_base.run(), r_traced.run()
    out["round_identical"] = all(rbd[r.id].tokens == rtd[r.id].tokens
                                 for r in reqs)
    out["round_dispatches_equal"] = \
        r_base.dispatches == r_traced.dispatches
    try:
        validate_chrome_trace(r_tele.chrome_trace(),
                              require_names=("prefill_chunk", "decode"))
        out["round_trace_valid"] = True
    except ValueError as e:
        out["round_trace_valid"] = False
        out["round_trace_error"] = str(e)
    return out


def run_cache(arch: str) -> dict:
    """Persistent prefix-cache identity sweep — the cache's hard
    contract: reviving a retained block maps the SAME physical bytes a
    live share would, so enabling the cache changes ZERO decoded bits.

    * sequential arrivals, shared system prompt: each request finishes
      (engine drains) before the next is submitted, so live sharing
      gets zero hits — cache-on must skip the re-prefills yet decode
      bit-identical streams to cache-off, at megastep N in {1, 8}
    * two concurrent waves of the mixed workload: wave 2 revives wave
      1's retained blocks while live sharing operates within each wave
    * a tight-budget sequential run over UNIQUE prompts: the cache
      overflows and LRU-evicts mid-workload; streams stay identical
    """
    cfg = get_config(arch).reduced()
    api = build_model(cfg)
    params = api.init(jax.random.key(0))
    shared = Stepper(api)

    def mk(cache, megastep, budget=1 << 30):
        return ContinuousEngine(api, params, config=EngineConfig(
            hbm_budget=budget, max_batch=MAX_BATCH, block_size=BLOCK,
            max_context=MAX_CONTEXT, megastep=megastep, host_pool=0,
            fault_seed=None, prefix_cache=cache), stepper=shared)

    out = {"supported": mk(True, 8).prefix_cache}
    if not out["supported"]:          # hybrid/SSM archs: cache gated off
        return out

    rng = np.random.default_rng(11)
    pfx = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    seqr = [Request(300 + i,
                    np.concatenate([pfx, rng.integers(
                        0, cfg.vocab_size, 1 + i % 3).astype(np.int32)]),
                    max_new_tokens=3 + (i * 5) % 7)
            for i in range(6)]

    def seq_drive(eng, reqs):
        done = {}
        for r in reqs:
            eng.submit(Request(r.id, r.prompt, r.max_new_tokens))
            done.update(eng.run())
        eng.assert_quiescent()
        return {r.id: done[r.id].tokens for r in reqs}

    streams, engines = {}, {}
    for m in (1, 8):
        for cache in (False, True):
            eng = mk(cache, m)
            streams[(cache, m)] = seq_drive(eng, seqr)
            engines[(cache, m)] = eng
    ref = streams[(False, 1)]
    out["seq_identical"] = all(s == ref for s in streams.values())
    out["seq_saved_n8"] = engines[(True, 8)].prefill_tokens_saved_cache
    out["seq_saved_n1"] = engines[(True, 1)].prefill_tokens_saved_cache
    out["seq_hits_n8"] = engines[(True, 8)].kv.prefix_cache_hits
    out["seq_saved_off"] = \
        engines[(False, 8)].prefill_tokens_saved_cache

    # two concurrent waves: wave 2 resubmits wave 1's prompts under new
    # ids — cache-on revives retained blocks where cache-off re-prefills
    reqs = mixed_requests(cfg)
    waves, hit_blocks = {}, 0
    for cache in (False, True):
        eng = mk(cache, 8)
        for r in reqs:
            eng.submit(Request(r.id, r.prompt, r.max_new_tokens))
        d1 = eng.run()
        for r in reqs:
            eng.submit(Request(100 + r.id, r.prompt,
                               r.max_new_tokens))
        d2 = eng.run()
        eng.assert_quiescent()
        waves[cache] = (
            {r.id: d1[r.id].tokens for r in reqs},
            {100 + r.id: d2[100 + r.id].tokens for r in reqs})
        if cache:
            hit_blocks = eng.kv.prefix_cache_hit_blocks
    out["concurrent_identical"] = waves[True] == waves[False]
    out["concurrent_hit_blocks"] = hit_blocks

    # tight budget + unique prompts: the cache tier overflows and LRU-
    # evicts mid-workload; identity must survive the churn
    probe = BlockKVCache(cfg, 0, block_size=BLOCK)
    tight = int((12 * probe.block_bytes
                 + MAX_BATCH * probe.state_bytes) / 0.6) + 1
    t_on, t_off = mk(True, 8, budget=tight), mk(False, 8, budget=tight)
    out["evict_identical"] = \
        seq_drive(t_on, reqs) == seq_drive(t_off, reqs)
    out["evictions"] = t_on.kv.prefix_cache_evictions
    return out


if __name__ == "__main__":
    args = sys.argv[1:]
    if args and args[0] == "--cache":
        print(json.dumps({arch: run_cache(arch) for arch in args[1:]}))
        sys.exit(0)
    if args and args[0] == "--tele":
        print(json.dumps({arch: run_tele(arch) for arch in args[1:]}))
        sys.exit(0)
    if args and args[0] == "--fuzz":
        print(json.dumps({arch: run_fuzz(arch) for arch in args[1:]}))
    elif args and args[0] == "--chaos":
        seeds = [int(s) for s in args[2:]] or [0]
        print(json.dumps({args[1]: run_chaos(args[1], seeds)}))
    else:
        print(json.dumps({arch: run_arch(arch) for arch in args}))
