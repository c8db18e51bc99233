"""Paged-cache parity + prefix-sharing refcount/copy-on-write properties.

Attention-level parity: the paged decode step (scatter into block pools,
gather through block tables) must agree with the dense per-slot vector
decode step for every block size (1, non-power-of-two, 16) and any
ragged ``cache_len`` / ``active`` pattern.  (Bit-exact *stream* identity
is asserted under synchronous dispatch in tests/test_serving.py via the
identity child; here we fuzz the step function directly.)  Program-level
parity: the stepper's chunk and megastep, whose layer scan carries the
stacked pool, give the tokens of dense caches driven one token a step
through the jitted ``decode_fn``, and their logits within ``TOL``.

Cache-level properties: prefix-shared blocks are refcounted and
immutable — never freed while a holder remains, never writable, and the
sharing cap keeps every admitted slot's write range private.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.runtime.engine import ContinuousEngine, Request
from repro.runtime.kv_cache import BlockKVCache
from repro.runtime.sampling import greedy_serving, megastep_advance
from repro.runtime.stepper import Stepper

TOL = dict(rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------------
# attention-level parity: paged step == dense step
# --------------------------------------------------------------------------

_API_CACHE = {}


def _api(arch):
    if arch not in _API_CACHE:
        cfg = get_config(arch).reduced()
        api = build_model(cfg)
        _API_CACHE[arch] = (api, api.init(jax.random.key(0)))
    return _API_CACHE[arch]


def _run_parity(arch, bs, steps, seed):
    """Drive dense + paged caches through the same masked decode steps
    (random ragged starting lens, random per-step activity) and compare
    logits at every step."""
    api, params = _api(arch)
    cfg = api.cfg
    B, bps = 3, -(-24 // bs)
    max_ctx = bps * bs
    rng = np.random.default_rng(seed)
    dense = api.init_caches(B, max_ctx, jnp.dtype(cfg.dtype))
    P = B * bps
    paged = api.init_paged_caches(B, P, bs, jnp.dtype(cfg.dtype))
    tables = rng.permutation(P).astype(np.int32).reshape(B, bps)

    # ragged starts: replay a shared warmup so both caches hold the
    # same ragged history (rows start at different positions)
    lens = np.zeros(B, np.int32)
    starts = rng.integers(0, 8, B).astype(np.int32)
    for step in range(steps + int(starts.max())):
        toks = rng.integers(0, cfg.vocab_size, B).astype(np.int32)
        warming = lens < starts
        active = np.where(warming, True,
                          rng.random(B) < 0.8) & (lens < max_ctx - 1)
        if not active.any():
            active[0] = lens[0] < max_ctx - 1
        batch = {"tokens": jnp.asarray(toks[:, None]),
                 "cache_len": jnp.asarray(lens),
                 "active": jnp.asarray(active)}
        ld, dense = api.decode_fn(params, dense, batch)
        lp, paged = api.decode_fn(
            params, paged, dict(batch, block_tables=jnp.asarray(tables)))
        np.testing.assert_allclose(
            np.asarray(ld, np.float32)[active],
            np.asarray(lp, np.float32)[active], **TOL)
        lens += active


@pytest.mark.parametrize("arch", ["stablelm-3b", "h2o-danube-3-4b",
                                  "jamba-v0.1-52b"])
@pytest.mark.parametrize("bs", [1, 3, 16])
def test_paged_step_matches_dense_step(arch, bs):
    """Seeded fuzz across block sizes 1 / non-power-of-two / 16 on
    dense-attention, sliding-window and hybrid attn+SSM archs."""
    _run_parity(arch, bs, steps=6, seed=bs)


def test_paged_step_matches_dense_step_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=10, deadline=None)
    @given(bs=st.integers(1, 9), seed=st.integers(0, 100),
           steps=st.integers(1, 5))
    def run(bs, seed, steps):
        _run_parity("stablelm-3b", bs, steps, seed)

    run()


class _DenseSteps:
    """The reference: a dense per-slot cache driven one token a row per
    step through the jitted ``decode_fn``, sampled by the serving
    engines' own greedy and megastep rules."""

    def __init__(self, api, params, B, max_ctx):
        self.params = params
        self.caches = api.init_caches(B, max_ctx, jnp.dtype(api.cfg.dtype))
        self._decode = jax.jit(api.decode_fn)

    def step(self, toks, lens, active):
        logits, self.caches = self._decode(self.params, self.caches, {
            "tokens": jnp.asarray(toks)[:, None],
            "cache_len": jnp.asarray(lens), "active": jnp.asarray(active)})
        return logits

    def chunk(self, toks, lens, n_valid):
        """The chunk's C tokens one step each: (new lens, first token)."""
        first = np.zeros(len(lens), np.int32)
        for i in range(toks.shape[1]):
            logits = self.step(toks[:, i], lens, i < n_valid)
            first = np.where(i == n_valid - 1,
                             np.asarray(greedy_serving(logits)), first)
            lens = lens + (i < n_valid)
        return lens, first

    def megastep(self, last, lens, active, budget, forced, n_forced, eos):
        """N steps with the scan's sampling update: (tokens (N, B),
        executed rows (N, B))."""
        toks_out, act_out = [], []
        for s in range(forced.shape[1]):
            tok_in = np.where(s < n_forced, forced[:, s], last)
            logits = self.step(tok_in, lens, active)
            nxt, nactive, budget = megastep_advance(
                logits, jnp.asarray(last), jnp.asarray(active),
                jnp.asarray(budget), jnp.asarray(n_forced),
                jnp.asarray(eos), s)
            toks_out.append(np.asarray(nxt))
            act_out.append(active)
            lens = lens + active
            last, active = np.asarray(nxt), np.asarray(nactive)
        return np.stack(toks_out), np.stack(act_out)


@pytest.mark.parametrize("arch", ["stablelm-3b", "h2o-danube-3-4b",
                                  "jamba-v0.1-52b"])
def test_paged_programs_match_dense_bit_for_bit(arch):
    """Ragged prompts through the chunked prefill, then three megasteps
    with rows finishing at different steps: every first token, sampled
    token and executed-row mask of the stepper's programs equals that of
    dense caches driven one token a step, and a decode step on the
    caches each leaves gives the same logits within ``TOL``."""
    api, params = _api(arch)
    cfg = api.cfg
    B, bs, bps, C, N = 3, 4, 8, 4, 5
    rng = np.random.default_rng(7)
    tables = rng.permutation(B * bps).astype(np.int32).reshape(B, bps)
    st = Stepper(api)
    ref = _DenseSteps(api, params, B, bps * bs)
    paged = api.init_paged_caches(B, B * bps, bs, jnp.dtype(cfg.dtype))
    prompt_len = np.array([7, 3, 10], np.int32)
    prompts = rng.integers(0, cfg.vocab_size, (B, 12)).astype(np.int32)

    lens = np.zeros(B, np.int32)
    while (lens < prompt_len).any():
        n_valid = np.minimum(prompt_len - lens, C).astype(np.int32)
        toks = np.zeros((B, C), np.int32)
        for b in range(B):
            toks[b, :n_valid[b]] = prompts[b, lens[b]:lens[b] + n_valid[b]]
        new_lens, first = ref.chunk(toks, lens, n_valid)
        paged, new_lens_p, first_p, _ = st.prefill_chunk(
            params, paged, toks, lens, n_valid, block_tables=tables)
        np.testing.assert_array_equal(new_lens, new_lens_p)
        started = n_valid > 0
        np.testing.assert_array_equal(first[started],
                                      np.asarray(first_p)[started])
        lens = new_lens

    toks, active = first, np.ones(B, bool)
    budget = np.array([9, 4, 12], np.int32)
    eos = np.full(B, -1, np.int32)
    forced, n_forced = np.zeros((B, N), np.int32), np.zeros(B, np.int32)
    for _ in range(3):
        out = ref.megastep(toks, lens, active, budget, forced, n_forced,
                           eos)
        out_p = st.megastep(params, paged, toks, lens, active, budget,
                            forced, n_forced, eos, block_tables=tables)
        for a, b in zip(out, out_p[:2]):            # tokens, executed rows
            np.testing.assert_array_equal(a, b)
        paged = out_p[3]
        executed = out[1].sum(0).astype(np.int32)
        lens, toks = lens + executed, out[0][-1]
        budget = budget - executed
        active = budget > 0
    assert not active.any()                 # every row finished

    logits = ref.step(toks, lens, np.ones(B, bool))
    logits_p, _ = jax.jit(api.decode_fn)(params, paged, {
        "tokens": jnp.asarray(toks[:, None]),
        "cache_len": jnp.asarray(lens), "active": jnp.ones(B, bool),
        "block_tables": jnp.asarray(tables)})
    np.testing.assert_allclose(logits, logits_p, **TOL)


# --------------------------------------------------------------------------
# refcount / copy-on-write properties of prefix-shared blocks
# --------------------------------------------------------------------------

def _kv(budget_blocks=64, bs=4):
    cfg = get_config("stablelm-3b").reduced()
    probe = BlockKVCache(cfg, 0, block_size=bs)
    return BlockKVCache(cfg, probe.block_bytes * budget_blocks,
                        block_size=bs), cfg


def _check_sharing_invariants(kv):
    """Pool-wide invariants with sharing in play."""
    live = {}                                 # slab id -> holder count
    for table in kv.block_tables.values():
        for slab in table:
            live[slab.id] = live.get(slab.id, 0) + 1
    # refcounts mirror table references exactly
    assert {i: c for i, c in kv._ref.items()} == live
    # no live block sits in the free pool ("no block freed while shared")
    free_ids = {s.id for s in kv.pool._free}
    assert not (free_ids & set(live)), "live block returned to pool"
    # bytes: every DISTINCT live block charged exactly once
    assert kv.pool.in_use == len(live) * kv.block_bytes
    # every registered hash points at a live slab
    for h, slab in kv._registry.items():
        assert slab.id in live
        assert kv._slab_hash[slab.id] == h


def test_shared_block_never_freed_while_held():
    kv, _ = _kv()
    bs = kv.block_size
    prompt = np.arange(3 * bs + 1, dtype=np.int32)
    m0 = kv.admit(0, len(prompt), tokens=prompt)
    assert m0 == 0                            # nothing published yet
    kv.publish(0, prompt, len(prompt))        # 3 full blocks shareable
    m1 = kv.admit(1, len(prompt), tokens=prompt)
    assert m1 == 3 * bs
    shared_ids = kv.table_ids(1)[:3]
    assert shared_ids == kv.table_ids(0)[:3]  # physically the same
    assert all(kv.refcount(i) == 2 for i in shared_ids)
    _check_sharing_invariants(kv)
    in_use_before = kv.pool.in_use
    kv.free(0)                                # first holder leaves
    _check_sharing_invariants(kv)
    assert all(kv.refcount(i) == 1 for i in shared_ids)
    # only slot 0's PRIVATE tail block was released
    assert kv.pool.in_use == in_use_before - kv.block_bytes
    kv.free(1)                                # last holder leaves
    assert kv.pool.in_use == 0
    assert not kv._registry and not kv._ref


def test_no_write_through_to_shared_blocks():
    kv, _ = _kv()
    bs = kv.block_size
    prompt = np.arange(2 * bs + 2, dtype=np.int32)
    kv.admit(0, len(prompt), tokens=prompt)
    kv.publish(0, prompt, len(prompt))
    matched = kv.admit(1, len(prompt), tokens=prompt)
    assert matched == 2 * bs
    # the sharer's write range starts after its shared prefix: legal
    kv.check_write(1, matched, len(prompt))
    # writing INTO the shared prefix must be rejected (for both holders:
    # slot 0's copy is registered = immutable, slot 1's is shared)
    with pytest.raises(RuntimeError):
        kv.check_write(1, 0, 1)
    with pytest.raises(RuntimeError):
        kv.check_write(0, matched - 1, matched)
    # after the LAST holder of a registered block leaves, fresh blocks
    # at the same position are private again
    kv.free(0)
    kv.free(1)
    kv.admit(2, len(prompt))                  # no tokens: no sharing
    kv.check_write(2, 0, len(prompt))         # fully writable


def test_sharing_cap_keeps_last_prompt_position_private():
    """Even a FULLY published identical prompt shares at most the
    blocks strictly below its last position — the engine must recompute
    that position to produce first-token logits, so its block stays
    writable."""
    kv, _ = _kv()
    bs = kv.block_size
    prompt = np.arange(3 * bs, dtype=np.int32)    # block-aligned prompt
    kv.admit(0, len(prompt), tokens=prompt)
    kv.publish(0, prompt, len(prompt))
    matched = kv.admit(1, len(prompt), tokens=prompt)
    assert matched == 2 * bs                  # NOT all 3 blocks
    kv.check_write(1, matched, len(prompt))   # recompute range writable


def test_sharing_property_fuzz():
    """Random admit/publish/grow/free churn with overlapping prompt
    prefixes: invariants hold at every step and the engine-visible write
    ranges stay private."""
    rng = np.random.default_rng(0)
    kv, _ = _kv(budget_blocks=48)
    bs = kv.block_size
    prefixes = [np.arange(k, k + 40, dtype=np.int32) for k in range(3)]
    live = {}                                 # slot -> (prompt, matched)
    for _ in range(400):
        op = rng.integers(0, 4)
        slot = int(rng.integers(0, 5))
        if op == 0 and slot not in live:
            plen = int(rng.integers(2, 30))
            prompt = prefixes[rng.integers(0, 3)][:plen].copy()
            if rng.random() < 0.3:            # diverge the tail
                prompt[-1] = 999
            try:
                matched = kv.admit(slot, plen, tokens=prompt)
            except MemoryError:
                continue
            assert matched <= plen - 1
            assert matched % bs == 0
            kv.check_write(slot, matched, plen)   # write range private
            live[slot] = [prompt, matched]
        elif op == 1 and slot in live:
            prompt, matched = live[slot]
            filled = int(rng.integers(matched, len(prompt) + 1))
            kv.publish(slot, prompt, filled)
        elif op == 2 and slot in live:
            prompt, _ = live[slot]
            want = len(prompt) + int(rng.integers(0, 10))
            if kv.grow(slot, want):
                kv.check_write(slot, len(prompt), want)
        elif op == 3 and slot in live:
            kv.free(slot)
            del live[slot]
        _check_sharing_invariants(kv)
    for slot in list(live):
        kv.free(slot)
    assert kv.pool.in_use == 0


def test_sharing_property_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=40, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 3),
                              st.integers(2, 24), st.integers(0, 2)),
                    max_size=30))
    def run(ops):
        kv, _ = _kv(budget_blocks=24)
        prefixes = [np.arange(k, k + 30, dtype=np.int32)
                    for k in range(2)]
        live = set()
        for op, slot, n, pick in ops:
            if op == 0 and slot not in live:
                prompt = prefixes[pick % 2][:n]
                try:
                    matched = kv.admit(slot, n, tokens=prompt)
                except MemoryError:
                    continue
                kv.check_write(slot, matched, n)
                live.add(slot)
            elif op == 1 and slot in live:
                kv.publish(slot, prefixes[pick % 2][:n],
                           min(n, kv.capacity_tokens(slot)))
            elif op == 2 and slot in live:
                kv.grow(slot, n)
            elif op == 3 and slot in live:
                kv.free(slot)
                live.discard(slot)
            _check_sharing_invariants(kv)

    run()


# --------------------------------------------------------------------------
# engine-level: sharing reduces physical allocation, pool drains clean
# --------------------------------------------------------------------------

def test_engine_prefix_sharing_reduces_block_allocations():
    cfg = get_config("stablelm-3b").reduced()
    api = build_model(cfg)
    params = api.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    reqs = [Request(i, np.concatenate(
        [prefix, rng.integers(0, cfg.vocab_size, 1 + i % 3)
         .astype(np.int32)]), max_new_tokens=3 + (i * 5) % 9)
        for i in range(8)]

    def run(sharing):
        eng = ContinuousEngine(api, params, hbm_budget_bytes=1 << 30,
                               max_batch=3, block_size=4, max_context=32,
                               prefix_sharing=sharing)
        for r in reqs:
            eng.submit(Request(r.id, r.prompt, r.max_new_tokens))
        done = eng.run()
        assert sorted(done) == list(range(8))
        assert eng.kv.in_use == 0             # everything released
        assert not eng.kv._registry           # registry drained
        return eng

    on, off = run(True), run(False)
    assert on.kv.shared_block_hits > 0
    assert on.kv.acquired_blocks < off.kv.acquired_blocks
    # a shared-prefix workload allocates fewer physical prompt blocks
    # than requests x prompt blocks (the no-sharing lower bound)
    prompt_blocks = sum(-(-len(r.prompt) // 4) for r in reqs)
    assert on.kv.acquired_blocks < prompt_blocks \
        + sum(-(-(r.max_new_tokens) // 4) for r in reqs)
