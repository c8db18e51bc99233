"""Paged-attention Pallas kernels: block-table KV pools, read in place.

The serving engine's physically paged KV cache stores every layer's K/V
in ONE pool of fixed-size blocks, ``(num_blocks + 1, block_size, K, D)``
(the trailing row is the scratch block — the target of gated-off writes
and the filler entry of unallocated block-table slots).  Two kernels
operate on the pool **in place** — no gather/scatter through a dense
per-slot staging buffer, so cross-request block reuse and prefix sharing
reach the memory the kernel actually reads:

* :func:`paged_decode_attention` — flash-decode for one query token per
  row: one program per (row, head) *walks the row's block table* as the
  innermost grid dimension, fetching each logical block's physical pool
  row via a scalar-prefetched index map; running max / denominator /
  accumulator persist in VMEM scratch (sequential TPU grid), so HBM
  traffic is one pass over exactly the blocks the table maps.  Per-row
  ``cache_len`` masks the tail (and the sliding window, if any).

* :func:`paged_append` — chunked-prefill KV writes straight into the
  blocks: one program per (row, chunk position) lands the new K/V at
  ``block_tables[b, (lens[b]+c) // bs]`` row ``(lens[b]+c) % bs``; the
  pool buffers are input/output-aliased so everything outside the
  written slots is untouched.  Positions past ``n_valid[b]`` (ragged
  chunk tails, idle rows) are steered to the scratch block.

Shapes: q (B, H, D); pools (nb + 1, bs, K, D); block_tables (B, bpr);
cache_len (B,); out (B, H, D).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


# --------------------------------------------------------------------------
# decode: one query token against the row's block table
# --------------------------------------------------------------------------

def _decode_kernel(tables_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, scale: float, window: int,
                   n_blk: int, block_size: int):
    i = pl.program_id(2)                      # logical block index

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cache_len = len_ref[pl.program_id(0)]     # per-row length (B,)
    pos = i * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1)
    valid = pos <= cache_len                  # slot t holds position t
    if window > 0:
        valid &= pos > cache_len - window

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (1, D)
    k = k_ref[0, :, 0].astype(jnp.float32)               # (bs, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (1, bs)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(-1)
    m_ref[...] = m_new
    v = v_ref[0, :, 0].astype(jnp.float32)               # (bs, D)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)

    @pl.when(i == n_blk - 1)
    def _flush():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, block_tables, cache_len, *,
                           window: int = 0, interpret: bool = False):
    """q (B,H,D) x pools (nb+1,bs,K,D) via block_tables (B,bpr) -> (B,H,D).

    The pools must already hold the token at position ``cache_len[b]``
    (the decode contract shared with ``kernels.decode_attention``);
    ``cache_len`` is a (B,) vector or a scalar broadcast to every row.
    Block-table entries of unallocated logical blocks may point anywhere
    (conventionally the scratch row) — their positions are masked.
    """
    B, H, D = q.shape
    nb1, bs, K, _ = k_pool.shape
    assert H % K == 0, (H, K)
    group = H // K
    bpr = block_tables.shape[1]
    scale = 1.0 / np.sqrt(D)
    q4 = q.reshape(B, H, 1, D)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    cache_len = jnp.asarray(cache_len, jnp.int32)
    assert cache_len.ndim <= 1, cache_len.shape
    cache_len = jnp.broadcast_to(cache_len.reshape(-1), (B,))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, bpr),
        in_specs=[
            pl.BlockSpec((1, 1, 1, D),
                         lambda b, h, i, tbl, lens: (b, h, 0, 0)),
            # walk the row's block table: logical block i of row b lives
            # in physical pool row tbl[b, i]
            pl.BlockSpec((1, bs, 1, D),
                         lambda b, h, i, tbl, lens:
                         (tbl[b, i], 0, h // group, 0)),
            pl.BlockSpec((1, bs, 1, D),
                         lambda b, h, i, tbl, lens:
                         (tbl[b, i], 0, h // group, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, D),
                               lambda b, h, i, tbl, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, window=window,
                          n_blk=bpr, block_size=bs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, cache_len, q4, k_pool, v_pool)
    return out.reshape(B, H, D)


# --------------------------------------------------------------------------
# append: chunked prefill writes straight into blocks
# --------------------------------------------------------------------------

def _append_kernel(tables_ref, len_ref, nv_ref, kp_in, vp_in, kn_ref,
                   vn_ref, k_out, v_out):
    del tables_ref, len_ref, nv_ref, kp_in, vp_in
    # the index map already steered this program at the target (block,
    # row) — or at the scratch block for invalid positions — so the body
    # is a straight store of the new token's K/V
    k_out[0, 0] = kn_ref[0, 0].astype(k_out.dtype)
    v_out[0, 0] = vn_ref[0, 0].astype(v_out.dtype)


def paged_append(k_pool, v_pool, k_new, v_new, block_tables, lens,
                 n_valid, *, interpret: bool = False):
    """Write a prefill chunk's K/V into the physical pools in place.

    k_new/v_new (B, C, K, D): token ``c`` of row ``b`` lands at cache
    position ``lens[b] + c``, i.e. pool row ``tables[b, p // bs]`` slot
    ``p % bs`` — provided ``c < n_valid[b]``; invalid positions (ragged
    chunk tails, rows not prefilling) write the scratch block instead.
    Returns the updated ``(k_pool, v_pool)`` (buffers aliased in place).
    """
    nb1, bs, K, D = k_pool.shape
    B, C, _, _ = k_new.shape
    scratch = nb1 - 1
    bpr = block_tables.shape[1]
    block_tables = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.broadcast_to(jnp.asarray(lens, jnp.int32).reshape(-1), (B,))
    n_valid = jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32)
                               .reshape(-1), (B,))

    def target(b, c, tbl, lens, nv):
        p = lens[b] + c
        ok = c < nv[b]
        blk = jnp.where(ok, jnp.clip(p // bs, 0, bpr - 1), 0)
        bid = jnp.where(ok, tbl[b, blk], scratch)
        off = jnp.where(ok, p % bs, 0)
        return bid, off

    def pool_spec():
        return pl.BlockSpec(
            (1, 1, K, D),
            lambda b, c, tbl, lens, nv: (*target(b, c, tbl, lens, nv),
                                         0, 0))

    def new_spec():
        return pl.BlockSpec((1, 1, K, D),
                            lambda b, c, tbl, lens, nv: (b, c, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, C),
        in_specs=[pool_spec(), pool_spec(), new_spec(), new_spec()],
        out_specs=[pool_spec(), pool_spec()],
    )
    return pl.pallas_call(
        _append_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        # in-place: pools are donated to the outputs (operand indices
        # count the scalar-prefetch args)
        input_output_aliases={3: 0, 4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(block_tables, lens, n_valid, k_pool, v_pool, k_new, v_new)
