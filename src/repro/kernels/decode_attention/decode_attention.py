"""Flash-decode Pallas kernel: one query token against a long KV cache.

Decode-shape hot spot (decode_32k / long_500k).  The KV sequence is the
innermost grid dimension; running max / denominator / accumulator persist
in VMEM scratch across KV blocks (sequential TPU grid), so HBM traffic is
exactly one pass over the cache — the memory-roofline optimum for decode.

Validity masking uses the cache's per-slot absolute-position array
(`pos`, -1 = empty — ring-buffer semantics from models/attention.py) and
``cache_len`` — a scalar, or a (B,) vector of per-row lengths for the
continuous-batching slot table, where every batch row sits at its own
sequence position:

    valid = (0 <= pos <= len_b) and (window == 0 or pos > len_b - w)

Shapes: q (B, H, D); k/v (B, K, T, D); pos (T,); out (B, H, D).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale: float, window: int,
            n_kv: int, block_k: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cache_len = len_ref[pl.program_id(0)]     # per-row length (B,)
    pos = pos_ref[...]                                   # (block_k,)
    valid = (pos >= 0) & (pos <= cache_len)
    if window > 0:
        valid &= pos > cache_len - window

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (1, D) block
    k = k_ref[0, 0].astype(jnp.float32)                  # (block_k, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (1, block_k)
    s = jnp.where(valid[None, :], s, NEG_INF)

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


def decode_attention(q, k, v, pos, cache_len, *, window: int = 0,
                     block_k: int = 512, interpret: bool = False):
    """q (B,H,D) x k,v (B,K,T,D), pos (T,) -> (B,H,D).

    ``cache_len`` is a scalar (all rows at the same position) or a (B,)
    vector of per-row lengths — the continuous-batching serving path,
    where every slot of the batch sits at its own sequence position.
    """
    B, H, D = q.shape
    _, K, T, _ = k.shape
    assert H % K == 0
    group = H // K
    block_k = min(block_k, T)
    assert T % block_k == 0, (T, block_k)
    n_kv = T // block_k
    scale = 1.0 / np.sqrt(D)
    q4 = q.reshape(B, H, 1, D)
    cache_len = jnp.asarray(cache_len, jnp.int32)
    assert cache_len.ndim <= 1, cache_len.shape
    cache_len = jnp.broadcast_to(cache_len.reshape(-1), (B,))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, 1, D), lambda b, h, ki, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, lens: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, lens: (b, h // group, ki, 0)),
            pl.BlockSpec((block_k,), lambda b, h, ki, lens: (ki,)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, D),
                               lambda b, h, ki, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window, n_kv=n_kv,
                          block_k=block_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(cache_len, q4, k, v, pos)
    return out.reshape(B, H, D)
