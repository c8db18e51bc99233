"""jit'd public wrappers for branch_matmul.

``parallel_branches`` is the user-facing Parallax primitive: given K
balanced branch inputs and weights (the §3.1 refinement guarantees
shape-compatibility after padding), run them as one fused grouped GEMM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .branch_matmul import branch_matmul
from .ref import branch_matmul_ref


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "block_k", "interpret"))
def branch_matmul_op(x, w, block_m=128, block_n=128, block_k=512,
                     interpret=False):
    return branch_matmul(x, w, block_m=block_m, block_n=block_n,
                         block_k=block_k, interpret=interpret)


def _pad_to(a, m, axis):
    pad = (-a.shape[axis]) % m
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def parallel_branches(xs, ws, interpret=False, block_m=8, block_n=128,
                      block_k=128):
    """Fuse a list of per-branch (x_i (M_i, K), w_i (K, N)) matmuls.

    Shapes are padded to the max branch size (β-bounded waste) and run
    through one grouped kernel; the unpadded results are returned.
    """
    assert len(xs) == len(ws) and xs
    K = xs[0].shape[1]
    N = ws[0].shape[1]
    m_max = max(x.shape[0] for x in xs)
    m_pad = m_max + (-m_max) % block_m
    x = jnp.stack([_pad_to(x, m_pad, 0) for x in xs])
    w = jnp.stack(list(ws))
    x = _pad_to(x, block_k, 2)
    w = _pad_to(_pad_to(w, block_k, 1), block_n, 2)
    out = branch_matmul_op(x, w, block_m=min(block_m, m_pad),
                           block_n=block_n, block_k=block_k,
                           interpret=interpret)
    return [out[i, :xs[i].shape[0], :N] for i in range(len(xs))]


def grouped_branch_matmul(xs, ws, *, platform: str, **blocks):
    """Placement-aware entry point for the schedule compiler
    (core/compile.py).

    Identical semantics to :func:`parallel_branches`.  ``platform`` is
    that of the device the group was placed on: the compiled Mosaic
    kernel runs on a TPU, the interpreter everywhere else (a group
    placed on the CPU host of a TPU machine included).
    """
    return parallel_branches(xs, ws, interpret=platform != "tpu", **blocks)


__all__ = ["branch_matmul_op", "branch_matmul_ref", "grouped_branch_matmul",
           "parallel_branches"]
