"""Output projection helpers (tied / untied vocab heads); the logits are
divided by ``cfg.logits_scaling`` where one is set."""

from __future__ import annotations

import jax.numpy as jnp

from .sharding import maybe_shard, DP_AXES


def _scaled(cfg, logits):
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def lm_logits(params, cfg, hidden):
    """(B, S, d) -> (B, S, V)."""
    dt = hidden.dtype
    if cfg.tie_embeddings:
        w = params["embed"].astype(dt)                # (V, d)
        logits = jnp.einsum("bsd,vd->bsv", hidden, w)
    else:
        logits = jnp.einsum("bsd,dv->bsv", hidden,
                            params["lm_head"].astype(dt))
    return maybe_shard(_scaled(cfg, logits), DP_AXES, None, "model")


def logits_last_token(params, cfg, hidden):
    """(B, S, d) -> (B, V) logits for the final position only."""
    last = hidden[:, -1, :]
    dt = last.dtype
    if cfg.tie_embeddings:
        logits = jnp.einsum("bd,vd->bv", last, params["embed"].astype(dt))
    else:
        logits = jnp.einsum("bd,dv->bv", last,
                            params["lm_head"].astype(dt))
    return maybe_shard(_scaled(cfg, logits), DP_AXES, "model")
