"""Blockwise (flash) attention Pallas kernel — causal + sliding window.

Prefill hot spot.  TPU adaptation notes (DESIGN.md §2/§6):
  * q/k blocks are VMEM tiles; block_q x block_k default 128x128 to match
    the MXU systolic array,
  * softmax statistics (running max m, denominator l) and the output
    accumulator live in VMEM scratch and persist across the innermost
    (k-block) grid dimension — the TPU grid is executed sequentially, so
    scratch carry replaces the GPU warp-level reduction of the original
    flash algorithm,
  * GQA is expressed through the kv BlockSpec index_map (``h // group``)
    — kv heads are never materialized per q-head.

Shapes: q (B, H, S, D), k/v (B, K, T, D) with K | H.
Mask: causal with optional sliding window (0 = none) and kv validity
length (for right-padded caches).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int, n_kv: int,
            block_q: int, block_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window

    q = q_ref[0, 0].astype(jnp.float32) * scale
    k = k_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(-1)
    m_ref[...] = m_new
    v = v_ref[0, 0].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)

    @pl.when(ki == n_kv - 1)
    def _flush():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q (B,H,S,D) x k,v (B,K,T,D) -> (B,H,S,D)."""
    B, H, S, D = q.shape
    _, K, T, _ = k.shape
    assert H % K == 0, (H, K)
    group = H // K
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)
    n_kv = T // block_k
    scale = 1.0 / np.sqrt(D)
    grid = (B, H, S // block_q, n_kv)

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal,
                          window=window, n_kv=n_kv, block_q=block_q,
                          block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),       # running max
            pltpu.VMEM((block_q,), jnp.float32),       # denominator
            pltpu.VMEM((block_q, D), jnp.float32),     # output acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
