"""The prefill chunk as one (B, C) block against C one-token steps.

An attention-only model runs a chunk's C tokens a row through the layer
scan at once (``Stepper.block_chunk``): one scatter of the new K/V, one
gather of each layer's table, C causal queries.  The reference is the
same chunk fed one token at a time through the jitted one-token
``decode_fn``, as the scan over tokens did it.  Rows start at different
cache lengths and consume ragged counts, 0 and C among them; at a block
size of 3 every chunk straddles blocks; in
h2o-danube-3-4b's reduced config the sliding window of 16 bites,
dbrx-132b runs its experts on the B * C rows, and qwen2-vl-2b rotates
by M-RoPE positions made (3, B, C) for the block.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.runtime.stepper import Stepper

#: Both sides compute in float32 on the same weights; the block runs
#: each matmul over B * C rows and each softmax over C queries at once,
#: which reorders sums: the logits of the decode step that follows the
#: chunk (|logit| up to ~1.4) differ by at most 6e-7 here, the K/V
#: written by at most 2.4e-6.
TOL = dict(rtol=1e-5, atol=1e-5)

B, C, CTX = 4, 8, 40
#: Row b's cache length before the chunk and the tokens it consumes:
#: full chunks, none, and ragged tails; row 3 runs past position 16.
START = np.array([0, 5, 13, 21], np.int32)
N_VALID = np.array([C, 0, 3, C], np.int32)


def _reference_chunk(api, params, caches, toks, lens, n_valid, tables):
    """C one-token steps of the jitted ``decode_fn``: (caches, lens,
    first token, watchdog) as the token scan computes them."""
    decode = jax.jit(api.decode_fn)
    first = np.zeros(B, np.int32)
    bad = np.zeros(B, bool)
    lens = lens.copy()
    for i in range(C):
        active = i < n_valid
        batch = {"tokens": jnp.asarray(toks[:, i:i + 1]),
                 "cache_len": jnp.asarray(lens),
                 "active": jnp.asarray(active),
                 "block_tables": jnp.asarray(tables)}
        logits, caches = decode(params, caches, batch)
        logits = np.asarray(logits)
        pick = np.asarray(jnp.argmax(jnp.asarray(logits, jnp.bfloat16), -1))
        first = np.where(i == n_valid - 1, pick, first)
        bad |= active & ~np.isfinite(logits).all(-1)
        lens += active
    return caches, lens, first, bad


def _written(lens, n_valid, tables, bs):
    """{(block, offset)} of the pool that the chunk writes."""
    return {(tables[b, t // bs], t % bs) for b in range(B)
            for t in range(lens[b], lens[b] + n_valid[b])}


def _setup(arch, bs):
    api = build_model(get_config(arch).reduced())
    params = api.init(jax.random.key(0))
    cfg = api.cfg
    rng = np.random.default_rng(3)
    bps = -(-CTX // bs)
    caches = api.init_paged_caches(B, B * bps, bs, jnp.dtype(cfg.dtype))
    tables = rng.permutation(B * bps).astype(np.int32).reshape(B, bps)
    # each row's history, one token a step, so rows start apart
    decode = jax.jit(api.decode_fn)
    lens = np.zeros(B, np.int32)
    while (lens < START).any():
        batch = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (B, 1)).astype(np.int32)),
            "cache_len": jnp.asarray(lens),
            "active": jnp.asarray(lens < START),
            "block_tables": jnp.asarray(tables)}
        _, caches = decode(params, caches, batch)
        lens += lens < START
    toks = rng.integers(0, cfg.vocab_size, (B, C)).astype(np.int32)
    return api, params, caches, toks, tables, rng


def _kv_arrays(caches):
    """Every attention pool by (layer group, index, name), each with a
    leading layer axis (a period's stack, or one prefix layer)."""
    out = {}
    for group in ("prefix", "period"):
        for j, c in enumerate(caches[group]):
            for name in ("k_pool", "v_pool"):
                if name in c:
                    a = np.asarray(c[name])
                    out[group, j, name] = a if group == "period" else a[None]
    return out


@pytest.mark.parametrize("bs", [16, 3], ids=["bs16", "bs3"])
@pytest.mark.parametrize("arch", ["stablelm-3b", "h2o-danube-3-4b",
                                  "dbrx-132b", "qwen2-vl-2b"])
def test_block_chunk_matches_one_token_steps(arch, bs):
    """First tokens, lengths and watchdog equal the one-token steps'; the
    decode step after the chunk gives their logits within ``TOL``; the
    chunk's K/V match theirs within ``TOL``; every cache position the
    chunk did not write (the scratch block aside) is untouched, bit for
    bit."""
    api, params, caches, toks, tables, rng = _setup(arch, bs)
    st = Stepper(api)
    assert st.block_chunk
    got, lens, first, bad = st.prefill_chunk(params, caches, toks, START,
                                             N_VALID, block_tables=tables)
    want, ref_lens, ref_first, ref_bad = _reference_chunk(
        api, params, caches, toks, START, N_VALID, tables)
    np.testing.assert_array_equal(lens, ref_lens)
    started = N_VALID > 0
    np.testing.assert_array_equal(np.asarray(first)[started],
                                  ref_first[started])
    np.testing.assert_array_equal(bad, ref_bad)
    assert not np.asarray(bad).any()

    written = _written(START, N_VALID, tables, bs)
    before, after, ref = (_kv_arrays(c) for c in (caches, got, want))
    for key, a in after.items():
        fresh = np.zeros(a.shape[:3], bool)      # (layer, block, offset)
        for i, j in written:
            fresh[:, i, j] = True
        keep = ~fresh
        keep[:, -1] = False                      # the scratch block
        np.testing.assert_array_equal(a[keep], before[key][keep])
        np.testing.assert_allclose(a[fresh], ref[key][fresh], **TOL)

    batch = {"tokens": jnp.asarray(
        rng.integers(0, api.cfg.vocab_size, (B, 1)).astype(np.int32)),
        "cache_len": jnp.asarray(ref_lens), "active": jnp.ones(B, bool),
        "block_tables": jnp.asarray(tables)}
    decode = jax.jit(api.decode_fn)
    np.testing.assert_allclose(decode(params, got, batch)[0],
                               decode(params, want, batch)[0], **TOL)


def test_block_chunk_watchdog_covers_every_valid_token():
    """A token whose embedding is NaN trips the row's watchdog wherever
    it sits among the row's valid tokens, as the one-token steps' check
    of every position's logits does; rows without it stay healthy."""
    api, params, caches, toks, tables, _ = _setup("stablelm-3b", 3)
    poison = 7
    params = dict(params, embed=params["embed"].at[poison].set(jnp.nan))
    toks = np.where(toks == poison, poison + 1, toks)
    toks[0, N_VALID[0] - 1] = poison             # row 0's last valid token
    toks[3, 2] = poison                          # row 3, mid-chunk
    _, _, _, bad = Stepper(api).prefill_chunk(params, caches, toks, START,
                                              N_VALID, block_tables=tables)
    _, _, _, ref_bad = _reference_chunk(api, params, caches, toks, START,
                                        N_VALID, tables)
    np.testing.assert_array_equal(bad, [True, False, False, True])
    np.testing.assert_array_equal(bad, ref_bad)


def test_dense_cache_takes_one_token_a_row():
    """The block chunk runs on the paged pool only: a dense cache given
    C tokens a row raises, as the Mamba step does."""
    api = build_model(get_config("stablelm-3b").reduced())
    params = api.init(jax.random.key(0))
    caches = api.init_caches(B, CTX, jnp.dtype(api.cfg.dtype))
    batch = {"tokens": jnp.zeros((B, C), jnp.int32),
             "cache_len": jnp.asarray(START), "active": jnp.ones((B, C), bool)}
    with pytest.raises(ValueError, match="one token a row"):
        api.decode_fn(params, caches, batch)
