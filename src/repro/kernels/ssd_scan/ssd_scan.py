"""Chunked SSD (Mamba2) Pallas kernel.

One (batch, head) plane per outer grid cell; the chunk index is the
innermost, sequential grid dimension so the running inter-chunk state
(P x N) lives in VMEM scratch — the TPU analogue of Mamba2's
"state-passing" kernel, with the intra-chunk quadratic terms as dense
MXU matmuls (chunk length is the tile knob: multiples of 128 at full
scale; DESIGN.md §6).

Inputs are pre-chunked by ops.py:
    x  (B, H, C, L, P)    dt (B, H, C, L)
    Bm (B, H, C, L, N)    Cm (B, H, C, L, N)    a (H,)  [negative]
Output: y (B, H, C, L, P).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, o_ref, state_ref, *,
            chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)          # (L, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)        # (L,)
    Bm = b_ref[0, 0, 0].astype(jnp.float32)         # (L, N)
    Cm = c_ref[0, 0, 0].astype(jnp.float32)         # (L, N)
    a = a_ref[0]                                    # scalar A_h (negative)

    dA = dt * a                                     # (L,)
    cs = jnp.cumsum(dA)                             # within-chunk cumsum

    # intra-chunk: Y_diag = (C B^T ∘ decay ∘ causal) @ (x * dt)
    seg = cs[:, None] - cs[None, :]                 # (L, L)
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = lj <= li
    decay = jnp.where(causal, jnp.exp(seg), 0.0)
    scores = jnp.dot(Cm, Bm.T, preferred_element_type=jnp.float32)
    xdt = x * dt[:, None]
    y = jnp.dot(scores * decay, xdt,
                preferred_element_type=jnp.float32)

    # inter-chunk: read previous state, then fold this chunk into it
    state = state_ref[...]                          # (P, N)
    decay_in = jnp.exp(cs)[:, None]                 # decay from chunk start
    y += jnp.dot(Cm * decay_in, state.T,
                 preferred_element_type=jnp.float32)

    decay_out = jnp.exp(cs[-1] - cs)[:, None]       # decay to chunk end
    new_state = jnp.dot(xdt.T, Bm * decay_out,
                        preferred_element_type=jnp.float32)  # (P, N)
    state_ref[...] = jnp.exp(cs[-1]) * state + new_state

    o_ref[0, 0, 0] = y.astype(o_ref.dtype)


def ssd_scan(x, dt, Bm, Cm, a, *, interpret: bool = False):
    """x (B,H,C,L,P), dt (B,H,C,L), Bm/Cm (B,H,C,L,N), a (H,) -> y."""
    B, H, C, L, P = x.shape
    N = Bm.shape[-1]
    grid = (B, H, C)

    return pl.pallas_call(
        functools.partial(_kernel, chunk=L),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, L, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, L), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, L, N), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, L, N), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, L, P),
                               lambda b, h, c: (b, h, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, C, L, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, Bm, Cm, a)
