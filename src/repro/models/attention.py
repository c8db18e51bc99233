"""Attention: GQA / MHA / sliding-window, prefill + single-token decode.

Shapes (B = batch, S = query len, T = kv len, H = q heads, K = kv heads,
D = head_dim):

    q: (B, S, H, D)    k, v: (B, T, K, D)

GQA repeats each kv head over ``H // K`` query heads via reshape (no
materialized repeat).  The pure-jnp path here is the reference; the Pallas
flash kernels in ``repro.kernels`` implement the same contract for the
TPU-optimized path and are validated against this module.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .common import apply_mrope, apply_rope, dense_init

NEG_INF = -1e30


def init_attention(key, cfg, d_model=None):
    d = d_model or cfg.d_model
    hd = cfg.resolved_head_dim()
    H, K = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, H * hd)),
        "wk": dense_init(ks[1], (d, K * hd)),
        "wv": dense_init(ks[2], (d, K * hd)),
        "wo": dense_init(ks[3], (H * hd, d)),
    }
    if cfg.qkv_bias:  # Qwen2 family uses QKV bias (arXiv:2407.10671)
        p["bq"] = jnp.zeros((H * hd,), jnp.float32)
        p["bk"] = jnp.zeros((K * hd,), jnp.float32)
        p["bv"] = jnp.zeros((K * hd,), jnp.float32)
    return p


def qkv_project(params, cfg, x, positions=None, positions3=None):
    """x: (B, S, d) -> q (B,S,H,D), k/v (B,S,K,D) with RoPE applied
    (none where ``cfg.nope``)."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim()
    H, K = cfg.num_heads, cfg.num_kv_heads
    dt = x.dtype

    def proj(w, b, nh):
        y = jnp.einsum("bsd,df->bsf", x, w.astype(dt))
        if b is not None:
            y = y + b.astype(dt)
        return y.reshape(B, S, nh, hd)

    q = proj(params["wq"], params.get("bq"), H)
    k = proj(params["wk"], params.get("bk"), K)
    v = proj(params["wv"], params.get("bv"), K)

    if cfg.nope:
        return q, k, v
    if cfg.mrope_sections:
        assert positions3 is not None, "M-RoPE needs 3-stream positions"
        q = apply_mrope(q, positions3, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, positions3, cfg.mrope_sections, cfg.rope_theta)
    elif positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def softmax_scale(cfg):
    """The configured softmax scale, or None for ``1 / sqrt(head_dim)``."""
    return cfg.attn_scale or None


def _gqa_scores(q, k, scale=None):
    """(B,S,H,D) x (B,T,K,D) -> (B,K,G,S,T) with G = H // K, times
    ``scale`` (None: divided by sqrt(D))."""
    B, S, H, D = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, D)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, k)
    return s / np.sqrt(D) if scale is None else s * scale


def _gqa_context(p, v):
    """(B,K,G,S,T) x (B,T,K,D) -> (B,S,H,D)."""
    B, K, G, S, T = p.shape
    D = v.shape[-1]
    ctx = jnp.einsum("bkgst,btkd->bskgd", p, v)
    return ctx.reshape(B, S, K * G, D)


def causal_mask(S: int, T: int, q_offset=0, window: int = 0):
    """(S, T) boolean mask. ``window`` > 0 adds sliding-window locality."""
    qpos = jnp.arange(S)[:, None] + q_offset
    kpos = jnp.arange(T)[None, :]
    m = kpos <= qpos
    if window > 0:
        m &= kpos > qpos - window
    return m


def attend(q, k, v, mask=None, scale=None):
    """Masked softmax attention with GQA grouping; fp32 softmax."""
    s = _gqa_scores(q, k, scale).astype(jnp.float32)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return _gqa_context(p.astype(q.dtype), v)


def attend_chunked(q, k, v, *, causal=True, window=0, q_offset=0,
                   chunk_q=512, chunk_k=1024, scale=None):
    """Exact chunked attention (online softmax over tiles).

    Same contract as :func:`attend` with a causal/window mask, but the
    (S, T) score matrix is never materialized: live memory is one
    (chunk_q, chunk_k) tile per (B, K, G).  This is the pure-JAX analogue
    of the Pallas flash kernel (repro.kernels.flash_attention) and what
    the compiled HLO of the dry-run's --opt mode measures.
    """
    B, S, H, D = q.shape
    _, T, K, _ = k.shape
    G = H // K
    cq = min(chunk_q, S)
    ck = min(chunk_k, T)
    assert S % cq == 0 and T % ck == 0, (S, T, cq, ck)
    nq, nk = S // cq, T // ck
    if scale is None:
        scale = 1.0 / np.sqrt(D)

    qs = jnp.moveaxis(q.reshape(B, nq, cq, K, G, D), 1, 0)
    ks = jnp.moveaxis(k.reshape(B, nk, ck, K, D), 1, 0)
    vs = jnp.moveaxis(v.reshape(B, nk, ck, K, D), 1, 0)

    def outer(_, q_in):
        qc, qi = q_in                                  # (B,cq,K,G,D)
        qf = qc.astype(jnp.float32) * scale
        m0 = jnp.full((B, K, G, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, cq), jnp.float32)
        a0 = jnp.zeros((B, K, G, cq, D), jnp.float32)

        def inner(st, k_in):
            m, l, acc = st
            kc, vc, ki = k_in
            s = jnp.einsum("bqkgd,btkd->bkgqt", qf,
                           kc.astype(jnp.float32))
            qpos = (qi * cq + jnp.arange(cq) + q_offset)[:, None]
            kpos = (ki * ck + jnp.arange(ck))[None, :]
            msk = jnp.ones((cq, ck), bool)
            if causal:
                msk &= kpos <= qpos
            if window > 0:
                msk &= kpos > qpos - window
            s = jnp.where(msk[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + p.sum(-1)
            acc = (acc * alpha[..., None]
                   + jnp.einsum("bkgqt,btkd->bkgqd", p,
                                vc.astype(jnp.float32)))
            return (m_new, l, acc), None

        # checkpoint the tile body: without it autodiff saves every
        # (cq, ck) probability tile — re-materializing the S x S matrix
        # the chunking exists to avoid (flash backward recomputes tiles)
        (m, l, acc), _ = jax.lax.scan(jax.checkpoint(inner), (m0, l0, a0),
                                      (ks, vs, jnp.arange(nk)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]   # (B,K,G,cq,D)
        return None, jnp.moveaxis(out, 3, 1)           # (B,cq,K,G,D)

    _, outs = jax.lax.scan(outer, None, (qs, jnp.arange(nq)))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, S, K * G, D)
    return out.astype(q.dtype)


def self_attention(params, cfg, x, positions=None, positions3=None,
                   causal=True, window: "int | None" = None):
    """Full prefill/training self-attention over x: (B, S, d)."""
    from . import runtime_flags

    S = x.shape[1]
    q, k, v = qkv_project(params, cfg, x, positions, positions3)
    w = cfg.sliding_window if window is None else window
    if (runtime_flags.chunked_attention and causal
            and S >= 2 * runtime_flags.chunk_q
            and S % runtime_flags.chunk_q == 0
            and S % runtime_flags.chunk_k == 0):
        ctx = attend_chunked(q, k, v, causal=True, window=w,
                             chunk_q=runtime_flags.chunk_q,
                             chunk_k=runtime_flags.chunk_k,
                             scale=softmax_scale(cfg))
    else:
        mask = causal_mask(S, S, 0, w) if causal else None
        ctx = attend(q, k, v, mask, softmax_scale(cfg))
    B = x.shape[0]
    out = jnp.einsum("bsf,fd->bsd",
                     ctx.reshape(B, S, -1), params["wo"].astype(x.dtype))
    return out


def cross_attention(params, cfg, x, k, v):
    """Decoder cross-attention: kv precomputed from the encoder."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim()
    H = cfg.num_heads
    q = jnp.einsum("bsd,df->bsf", x, params["wq"].astype(x.dtype))
    if "bq" in params:
        q = q + params["bq"].astype(x.dtype)
    q = q.reshape(B, S, H, hd)
    ctx = attend(q, k, v, mask=None)
    return jnp.einsum("bsf,fd->bsd", ctx.reshape(B, S, -1),
                      params["wo"].astype(x.dtype))


# --------------------------------------------------------------------------
# decode path: single new token against a KV cache
# --------------------------------------------------------------------------

def init_kv_cache(cfg, batch: int, max_len: int, dtype,
                  ring: bool = False):
    """KV cache with per-slot absolute-position bookkeeping.

    ``ring=True`` allocates only ``sliding_window`` slots and wraps — the
    sub-quadratic memory path for SWA architectures on long_500k.  A full
    cache is simply a ring that never wraps, so decode handles both
    uniformly via the ``pos`` array.
    """
    hd = cfg.resolved_head_dim()
    K = cfg.num_kv_heads
    slots = max_len
    if ring:
        assert cfg.sliding_window > 0, "ring cache needs a sliding window"
        slots = min(max_len, cfg.sliding_window)
    shape = (batch, slots, K, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            # absolute position stored in each slot; -1 = empty
            "pos": jnp.full((slots,), -1, jnp.int32)}


def fill_kv_cache(cache, k, v, start: int = 0):
    """Write a prefill segment k/v (B, S, K, D) into the cache at
    ``start`` (absolute positions start..start+S-1; no wrapping — prefill
    must fit the allocated slots)."""
    S = k.shape[1]
    out = dict(cache)
    out["k"] = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), start, axis=1)
    out["v"] = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), start, axis=1)
    out["pos"] = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], jnp.arange(start, start + S, dtype=jnp.int32),
        start, axis=0)
    return out


def init_paged_kv_cache(cfg, num_blocks: int, block_size: int, dtype):
    """Physically paged KV cache: ONE pool of fixed-size blocks per layer.

    Layout ``(num_blocks + 1, block_size, K, D)`` — the trailing row is a
    *scratch block*: block-table entries of unallocated logical blocks
    point at it (gathers read zeros, fully masked) and gated-off writes
    land in it, so the traced step needs no out-of-bounds handling.
    Block ids are handed out by :class:`repro.runtime.kv_cache
    .BlockKVCache` (slab ids double as physical row indices); the same
    ``(B, blocks_per_seq)`` block table indexes every layer's pool.
    """
    hd = cfg.resolved_head_dim()
    K = cfg.num_kv_heads
    shape = (num_blocks + 1, block_size, K, hd)
    return {"k_pool": jnp.zeros(shape, dtype),
            "v_pool": jnp.zeros(shape, dtype)}


def decode_step_attention(params, cfg, x, cache, cache_len,
                          positions3=None, window: int = 0, active=None,
                          block_tables=None, layer=None):
    """Decode step: x (B, S, d), S new tokens a row, against cache k/v
    (B, slots, K, D) or a paged pool.

    ``cache_len`` is the number of tokens already generated/prefilled;
    row b's new tokens have absolute positions ``cache_len[b] + s``.
    S > 1 (a prefill chunk as one block) runs on a paged pool only, and
    takes ``active`` as (B, S), one flag a token; the tokens' K/V are
    written first, and token s attends every position up to its own.
    A dense cache takes one token a row.

    *Scalar* ``cache_len`` (may be traced): every row is at the same
    position; the token is written to slot ``cache_len % slots`` (ring
    semantics via the per-slot ``pos`` array).

    *Vector* ``cache_len`` of shape (B,): each row sits at its own
    position — the continuous-batching serving path.  Row ``b`` writes
    slot ``cache_len[b]`` (non-ring caches only: slot t always holds
    absolute position t, so validity is ``t <= cache_len[b]`` and the
    ``pos`` array is unused).  ``active`` (B,) bool gates the cache
    write per row: inactive rows leave every cache entry untouched, so
    one fixed-shape dispatch can serve a slot table where requests join
    and leave between iterations.  Both vectors double as ``lax.scan``
    carries in the serving runtime's decode megastep, advancing per-row
    inside ONE dispatch — everything below is traced arithmetic on
    them, never host values.

    Because every readable position (``t <= cache_len[b]``, window-
    clipped) is freshly written by the row's own prefill/decode steps
    and everything else is masked to an exact-zero softmax weight, a
    new slot tenant needs NO cache reset on attention-only models —
    the engine skips the reset dispatch unless SSM/conv state exists.

    ``layer`` (traced scalar) marks a paged cache whose pools are the
    layer scan's stacked ``(n_rep, nb + 1, bs, K, D)`` carry: the step
    reads and writes that layer's pool through it (see
    ``_decode_step_attention_paged``).

    Returns ``(out (B,S,d), new_cache)``.
    """
    B, S = x.shape[:2]
    cache_len = jnp.asarray(cache_len, jnp.int32)
    if "k_pool" in cache:
        if cache_len.ndim != 1 or block_tables is None:
            raise ValueError(
                "paged caches require vector cache_len (B,) and a "
                "(B, blocks_per_seq) block table")
        return _decode_step_attention_paged(
            params, cfg, x, cache, cache_len, block_tables, positions3,
            window, active, layer)
    if S > 1:
        raise ValueError("a dense KV cache takes one token a row: "
                         "multi-token steps run on the paged pool")
    slots = cache["k"].shape[1]
    if cache_len.ndim == 1:
        return _decode_step_attention_vec(params, cfg, x, cache, cache_len,
                                          positions3, window, active)
    if active is not None:
        raise ValueError(
            "per-row `active` gating requires vector cache_len (B,): the "
            "scalar path writes every row's cache unconditionally")
    positions = jnp.broadcast_to(cache_len, (B, 1))
    if positions3 is None and cfg.mrope_sections:
        positions3 = jnp.broadcast_to(positions, (3, B, 1))
    q, k_new, v_new = qkv_project(params, cfg, x, positions, positions3)

    slot = cache_len % slots
    with jax.named_scope("kv_write"):
        k = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k_new.astype(cache["k"].dtype), slot, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v_new.astype(cache["v"].dtype), slot, axis=1)
        pos = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], cache_len[None], slot, axis=0)

    valid = (pos >= 0) & (pos <= cache_len)
    w = window or cfg.sliding_window
    if w > 0:
        valid &= pos > cache_len - w
    mask = valid[None, :]                                 # (S=1, T)
    ctx = attend(q, k.astype(q.dtype), v.astype(q.dtype), mask,
                 softmax_scale(cfg))
    out = jnp.einsum("bsf,fd->bsd", ctx.reshape(B, 1, -1),
                     params["wo"].astype(x.dtype))
    return out, {"k": k, "v": v, "pos": pos}


def _token_positions(cache_len, S: int):
    """(B, S) absolute positions of S new tokens a row: ``cache_len[b] +
    s``."""
    positions = cache_len[:, None]
    if S > 1:
        positions = positions + jnp.arange(S, dtype=jnp.int32)
    return positions


def _token_mask(t, positions, window: int):
    """(B, 1, 1, S, T) attention mask of the new tokens at ``positions``
    (B, S) over cache positions ``t`` (1, T): token s sees t <=
    positions[b, s], and t > positions[b, s] - window where ``window`` >
    0."""
    if positions.shape[1] > 1:
        t, positions = t[:, None], positions[:, :, None]  # (1,1,T), (B,S,1)
    valid = t <= positions
    if window > 0:
        valid &= t > positions - window
    if valid.ndim == 2:                                   # S = 1: (B, T)
        return valid[:, None, None, None, :]
    return valid[:, None, None]


def _decode_step_attention_vec(params, cfg, x, cache, cache_len,
                               positions3, window, active):
    """Vector-``cache_len`` decode step, one token a row (see
    decode_step_attention).

    PRECONDITION (uncheckable at trace time — the callers bound it by
    ``max_context``): every ``cache_len[b]`` < slots, i.e. a NON-ring
    cache where slot t holds absolute position t.  A ring cache would
    silently drop writes (``t == cache_len[b]`` never matches once
    positions wrap) and mis-mask stale slots.
    """
    B = x.shape[0]
    slots = cache["k"].shape[1]
    positions = cache_len[:, None]                        # (B, 1)
    if positions3 is None and cfg.mrope_sections:
        positions3 = jnp.broadcast_to(positions, (3, B, 1))
    q, k_new, v_new = qkv_project(params, cfg, x, positions, positions3)

    t = jnp.arange(slots, dtype=jnp.int32)[None, :]       # (1, T)
    with jax.named_scope("kv_write"):
        write = t == positions                            # (B, T)
        if active is not None:
            write &= active[:, None]
        k = jnp.where(write[:, :, None, None],
                      k_new.astype(cache["k"].dtype), cache["k"])
        v = jnp.where(write[:, :, None, None],
                      v_new.astype(cache["v"].dtype), cache["v"])

    mask = _token_mask(t, positions, window or cfg.sliding_window)
    ctx = attend(q, k.astype(q.dtype), v.astype(q.dtype), mask,
                 softmax_scale(cfg))
    out = jnp.einsum("bsf,fd->bsd", ctx.reshape(B, 1, -1),
                     params["wo"].astype(x.dtype))
    return out, {"k": k, "v": v, "pos": cache["pos"]}


def _decode_step_attention_paged(params, cfg, x, cache, cache_len,
                                 block_tables, positions3, window, active,
                                 layer=None):
    """Vector decode step over a physically paged KV pool.

    ``cache`` holds one block pool per layer (``k_pool``/``v_pool``,
    shape ``(nb + 1, bs, K, D)`` — last row is the scratch block);
    ``block_tables`` (B, blocks_per_seq) int32 maps each row's logical
    block index to a physical pool row.  Each new token is scattered
    into the physical block covering its position (gated-off tokens
    write the scratch block instead), all B x S of them in one scatter;
    then K/V are gathered through the table once and attended exactly
    like the dense vector path — the masked-softmax structure is
    identical (garbage in unwritten/scratch positions is masked to an
    exact 0 contribution).

    With ``layer`` given, the pools are every scanned layer's, stacked
    ``(n_rep, nb + 1, bs, K, D)``, and ``layer`` leads every index: the
    tokens land in place in the carried stack and the gather reads only
    the table's blocks, so no layer's pool is sliced out or written
    back whole.

    The engine guarantees a written block is never shared (prefix-shared
    blocks are full, immutable and live strictly below every row's write
    position — see BlockKVCache.check_write).
    """
    B, S = x.shape[:2]
    pool_k, pool_v = cache["k_pool"], cache["v_pool"]
    lead = () if layer is None else (layer,)
    nb_total, bs = pool_k.shape[len(lead)], pool_k.shape[len(lead) + 1]
    scratch = nb_total - 1
    bps = block_tables.shape[1]
    T = bps * bs
    positions = _token_positions(cache_len, S)            # (B, S)
    if positions3 is None and cfg.mrope_sections:
        positions3 = jnp.broadcast_to(positions, (3, B, S))
    q, k_new, v_new = qkv_project(params, cfg, x, positions, positions3)

    # scatter the new tokens into their physical blocks: indexed by row
    # (B,) for one token a row, by token (B, S) for S
    with jax.named_scope("kv_write"):
        pos = cache_len if S == 1 else positions
        lblk = jnp.clip(pos // bs, 0, bps - 1)            # logical block
        if S == 1:
            bids = jnp.take_along_axis(block_tables, lblk[:, None], 1)[:, 0]
        else:
            bids = jnp.take_along_axis(block_tables, lblk, 1)
        offs = pos % bs
        if active is not None:
            bids = jnp.where(active, bids, scratch)
        pool_k = pool_k.at[lead + (bids, offs)].set(
            (k_new[:, 0] if S == 1 else k_new).astype(pool_k.dtype))
        pool_v = pool_v.at[lead + (bids, offs)].set(
            (v_new[:, 0] if S == 1 else v_new).astype(pool_v.dtype))

    # gather the row's cache view through its block table
    with jax.named_scope("kv_gather"):
        k = pool_k[lead + (block_tables,)].reshape(B, T, *pool_k.shape[-2:])
        v = pool_v[lead + (block_tables,)].reshape(B, T, *pool_v.shape[-2:])

    t = jnp.arange(T, dtype=jnp.int32)[None, :]           # (1, T)
    mask = _token_mask(t, positions, window or cfg.sliding_window)
    ctx = attend(q, k.astype(q.dtype), v.astype(q.dtype), mask,
                 softmax_scale(cfg))
    out = jnp.einsum("bsf,fd->bsd", ctx.reshape(B, S, -1),
                     params["wo"].astype(x.dtype))
    return out, {"k_pool": pool_k, "v_pool": pool_v}
