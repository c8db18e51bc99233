"""Activation-sharding helpers that degrade gracefully off-mesh.

Models call :func:`maybe_shard` at block boundaries.  Under an active mesh
(``jax.sharding.set_mesh``) this emits ``with_sharding_constraint`` with
any axis names that exist in the mesh; with no mesh (CPU smoke tests) it
is a no-op, so the same model code runs everywhere.
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

# data-parallel axes in priority order; ("pod", "data") on the multi-pod
# mesh, ("data",) on the single-pod mesh.
DP_AXES = ("pod", "data")
MODEL_AXIS = "model"


def active_axes():
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return ()
    return tuple(mesh.axis_names)


def _filter(entry, axes):
    if entry is None:
        return None
    if isinstance(entry, str):
        return entry if entry in axes else None
    sub = tuple(a for a in entry if a in axes)
    if not sub:
        return None
    return sub if len(sub) > 1 else sub[0]


def spec(*entries) -> "P | None":
    axes = active_axes()
    if not axes:
        return None
    return P(*[_filter(e, axes) for e in entries])


def maybe_shard(x, *entries):
    s = spec(*entries)
    if s is None:
        return x
    return jax.lax.with_sharding_constraint(x, s)


def dp():
    """The data-parallel axis group present in the active mesh."""
    axes = active_axes()
    return tuple(a for a in DP_AXES if a in axes)


def shard_batch_seq(x):
    """(B, S, d): batch over data axes, sequence over model (seq-parallel
    residual stream — DESIGN.md §5)."""
    return maybe_shard(x, DP_AXES, MODEL_AXIS, None)


def shard_batch_heads(x):
    """(B, S, H, D): batch over data axes, heads over model."""
    return maybe_shard(x, DP_AXES, None, MODEL_AXIS, None)


def shard_decode(x):
    """(B, 1, d) decode activations: batch over data axes only."""
    return maybe_shard(x, DP_AXES, None, None)


def shard_kv_cache(c, long_context: bool):
    """KV cache (B, T, K, D): heads over model; for long-context
    single-request decode the *sequence* is sharded over data
    (flash-decode style)."""
    if long_context:
        return maybe_shard(c, None, "data", MODEL_AXIS, None)
    return maybe_shard(c, DP_AXES, None, MODEL_AXIS, None)
