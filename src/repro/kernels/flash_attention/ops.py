"""jit'd wrapper for flash_attention (+ layout adapters for models)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention
from .ref import flash_attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention_op(q, k, v, causal=True, window=0, block_q=128,
                       block_k=128, interpret=False):
    return flash_attention(q, k, v, causal=causal, window=window,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)


def attend_bshd(q, k, v, causal=True, window=0, interpret=False,
                block_q=128, block_k=128):
    """Adapter for the models' (B, S, H, D) layout."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention_op(qt, kt, vt, causal=causal, window=window,
                             block_q=min(block_q, qt.shape[2]),
                             block_k=min(block_k, kt.shape[2]),
                             interpret=interpret)
    return out.transpose(0, 2, 1, 3)


__all__ = ["flash_attention_op", "flash_attention_ref", "attend_bshd"]
