"""MoE expert FFN through the Pallas branch_matmul kernel.

The bridge between the paper's technique and the TPU kernel layer:
routed tokens are bucketed per expert into equal-capacity slots (the
β-balance guarantee of §3.1 — equal-size branches — realized by capacity
padding), and the three expert GEMMs run as grouped ``branch_matmul``
launches with the expert index as the leading grid dimension.

This is the kernel-level realization of ``moe_ragged``; on CPU it runs
in interpret mode and is validated against ``moe_dense`` in
tests/test_kernels_integration.py.  Drop-on-overflow (Switch semantics).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.branch_matmul.ops import branch_matmul_op
from .moe import route
from .mlp import mlp


def moe_branch_matmul(params, cfg, x, *, capacity_factor: float = 2.0,
                      interpret: bool = False, block_m: int = 8,
                      block_n: int = 128, block_k: int = 128):
    """x: (T, d) -> (y (T, d), aux).  Experts as branch-batched GEMMs."""
    m = cfg.moe
    T, d = x.shape
    E, k = m.num_experts, m.num_experts_per_tok
    f = cfg.expert_width()
    w, idx, aux = route(params, cfg, x)

    # capacity bucketing: position of each (token, choice) in its expert
    cap = max(int(T * k * capacity_factor / E), 1)
    cap += (-cap) % block_m                          # tile-align capacity
    flat_e = idx.reshape(-1)                         # (T*k,)
    gates = w.reshape(-1)
    tok = jnp.arange(T * k) // k
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1
    keep = pos < cap

    xb = jnp.zeros((E, cap, d), x.dtype)
    di = jnp.where(keep, flat_e, 0)
    pi = jnp.where(keep, pos, 0)
    xb = xb.at[di, pi].add(jnp.where(keep[:, None], x[tok], 0))

    def pad_k(a, axis):
        padw = (-a.shape[axis]) % block_k
        if padw == 0:
            return a
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, padw)
        return jnp.pad(a, widths)

    dt = x.dtype
    wg = pad_k(params["w_gate"].astype(dt), 1)
    wu = pad_k(params["w_up"].astype(dt), 1)
    wd = pad_k(params["w_down"].astype(dt), 1)
    wg = pad_k(wg, 2)
    wu = pad_k(wu, 2)
    wd = pad_k(wd, 2)
    xbk = pad_k(xb, 2)

    # grouped GEMMs: one kernel launch per projection, expert = grid dim
    g = branch_matmul_op(xbk, wg, block_m=min(block_m, cap),
                         block_n=block_n, block_k=block_k,
                         interpret=interpret)[:, :, :f]
    u = branch_matmul_op(xbk, wu, block_m=min(block_m, cap),
                         block_n=block_n, block_k=block_k,
                         interpret=interpret)[:, :, :f]
    h = jax.nn.silu(g) * u
    y_b = branch_matmul_op(pad_k(h, 2), wd, block_m=min(block_m, cap),
                           block_n=min(block_n, _ceil(d, block_n)),
                           block_k=block_k,
                           interpret=interpret)[:, :, :d]

    contrib = y_b[di, pi] * gates[:, None].astype(dt)
    contrib = jnp.where(keep[:, None], contrib, 0)
    y = jnp.zeros_like(x).at[tok].add(contrib)
    if "shared" in params:
        y = y + mlp(params["shared"], x, "silu")
    return y, aux


def _ceil(n, b):
    return (n + b - 1) // b * b
