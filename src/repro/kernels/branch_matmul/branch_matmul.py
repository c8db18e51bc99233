"""branch_matmul — grouped multi-branch GEMM Pallas kernel.

THE Parallax technique on the MXU (DESIGN.md §2): K balanced parallel
branches (paper §3.1 — attention heads, MoE experts, parallel subgraph
chains) are executed as ONE kernel launch with the branch index as the
leading grid dimension, instead of K sequential dispatches.  The paper's
β-balance refinement (F_max/F_min <= 1.5) guarantees the padded grid
wastes at most (β-1)/β of the MXU slots.

    x: (G, M, K) · w: (G, K, N) -> (G, M, N)

Grid: (G, M/bm, N/bn, K/bk); the contraction dimension is innermost so
the fp32 VMEM accumulator scratch carries across k-steps and writes out
once per (g, i, j) tile.  Block shapes default to MXU-aligned 128x128
tiles with a 512-wide contraction stripe.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[0], w_ref[0], preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[0, ...] = acc_ref[...].astype(o_ref.dtype)


def branch_matmul(x, w, *, block_m: int = 128, block_n: int = 128,
                  block_k: int = 512, interpret: bool = False):
    """Grouped GEMM: (G, M, K) x (G, K, N) -> (G, M, N)."""
    G, M, K = x.shape
    G2, K2, N = w.shape
    assert G == G2 and K == K2, (x.shape, w.shape)
    block_m = min(block_m, M)
    block_n = min(block_n, N)
    block_k = min(block_k, K)
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0, (
        "branch_matmul requires padded, tile-aligned operands "
        f"({M}x{K}x{N} vs blocks {block_m}/{block_k}/{block_n})")
    n_k = K // block_k
    grid = (G, M // block_m, N // block_n, n_k)

    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_m, block_k),
                         lambda g, i, j, k: (g, i, k)),
            pl.BlockSpec((1, block_k, block_n),
                         lambda g, i, j, k: (g, k, j)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_n),
                               lambda g, i, j, k: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((G, M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, w)
