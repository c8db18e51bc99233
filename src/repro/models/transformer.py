"""Decoder-only language model: init / forward / prefill / decode.

Layer structure = unrolled *prefix* + ``lax.scan`` over the repeating
*period* (see blocks.split_pattern).  Scanning keeps HLO size (and compile
time, which matters for the 512-device dry-run) independent of depth;
remat (``jax.checkpoint``) bounds training activation memory to one period
per step.

Parameter pytree:
    embed: (V, d)            final_norm, [lm_head (d, V) unless tied]
    prefix: [block_params, ...]                       (len = prefix_len)
    period: [stacked block_params, ...]               (len = period;
            every leaf has leading dim n_rep = (L - prefix) // period)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import runtime_flags
from .blocks import (apply_block, block_pattern, decode_block,
                     init_block, init_block_cache, init_paged_block_cache,
                     split_pattern)
from .common import embed_init, init_norm, make_norm
from .sharding import maybe_shard, shard_batch_seq, DP_AXES
from .vocab import logits_last_token, lm_logits


def structure(cfg):
    pattern = block_pattern(cfg)
    prefix_len, period = split_pattern(pattern)
    n_rep = (cfg.num_layers - prefix_len) // period
    return pattern, prefix_len, period, n_rep


def init_lm(key, cfg):
    pattern, prefix_len, period, n_rep = structure(cfg)
    ks = jax.random.split(key, 4)
    params = {
        "embed": embed_init(ks[0], (cfg.vocab_size, cfg.d_model)),
        "final_norm": init_norm(ks[1], cfg.d_model, cfg.norm_type),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(ks[2], (cfg.d_model, cfg.vocab_size))

    kb = jax.random.split(ks[3], cfg.num_layers)
    params["prefix"] = [init_block(kb[i], cfg, pattern[i])
                        for i in range(prefix_len)]
    period_params = []
    for j in range(period):
        kind = pattern[prefix_len + j]
        keys = jnp.stack([kb[prefix_len + r * period + j]
                          for r in range(n_rep)])
        period_params.append(
            jax.vmap(lambda k: init_block(k, cfg, kind))(keys))
    params["period"] = period_params
    return params


def _embed(params, cfg, tokens):
    """The token embeddings, times ``cfg.embedding_multiplier`` where one
    is set."""
    x = params["embed"].astype(cfg.dtype)[tokens]
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def embed_tokens(params, cfg, tokens, frontend_embeds=None):
    """tokens: (B, S_txt) int32 -> (B, S, d); frontend embeddings (vision
    patches / audio frames, already projected by the stub frontend) are
    prepended when present."""
    x = _embed(params, cfg, tokens)
    if frontend_embeds is not None:
        x = jnp.concatenate([frontend_embeds.astype(cfg.dtype), x], axis=1)
    return shard_batch_seq(x)


def forward_lm(params, cfg, tokens, frontend_embeds=None, positions3=None,
               moe_impl="ragged", mesh=None, remat=True, window=None):
    """Training / prefill forward.  Returns (hidden (B,S,d), aux_loss)."""
    pattern, prefix_len, period, n_rep = structure(cfg)
    x = embed_tokens(params, cfg, tokens, frontend_embeds)
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]

    aux_total = 0.0
    for i in range(prefix_len):
        x, aux = apply_block(params["prefix"][i], cfg, x, pattern[i],
                             positions, positions3, moe_impl, mesh, window)
        aux_total += aux

    if n_rep:
        kinds = [pattern[prefix_len + j] for j in range(period)]

        def body(carry, layer_params):
            x, aux = carry
            for j in range(period):
                x, a = apply_block(layer_params[j], cfg, x, kinds[j],
                                   positions, positions3, moe_impl, mesh,
                                   window)
                aux = aux + a
            return (x, aux), None

        if remat:
            body = jax.checkpoint(body)
        (x, aux_total), _ = jax.lax.scan(
            body, (x, jnp.float32(aux_total)), tuple(params["period"]),
            **runtime_flags.scan_kwargs())

    norm = make_norm(cfg.norm_type, cfg.rms_norm_eps)
    return norm(params["final_norm"], x), aux_total


def lm_loss(params, cfg, tokens, labels, frontend_embeds=None,
            positions3=None, moe_impl="ragged", mesh=None):
    """Mean cross-entropy (+ MoE aux).  labels = -1 entries are masked."""
    hidden, aux = forward_lm(params, cfg, tokens, frontend_embeds,
                             positions3, moe_impl, mesh, remat=True)
    if frontend_embeds is not None:        # frontend tokens carry no loss
        hidden = hidden[:, frontend_embeds.shape[1]:, :]
    logits = lm_logits(params, cfg, hidden)
    mask = labels >= 0
    safe = jnp.maximum(labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1)
    return loss + aux, {"ce": loss, "aux": aux}


# --------------------------------------------------------------------------
# serving: prefill + single-token decode over layered caches
# --------------------------------------------------------------------------

def init_caches(cfg, batch, max_len, dtype, ring=False):
    pattern, prefix_len, period, n_rep = structure(cfg)
    caches = {"prefix": [init_block_cache(cfg, pattern[i], batch, max_len,
                                          dtype, ring)
                         for i in range(prefix_len)]}
    stacked = []
    for j in range(period):
        kind = pattern[prefix_len + j]
        c = init_block_cache(cfg, kind, batch, max_len, dtype, ring)
        stacked.append(jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_rep,) + a.shape), c))
    caches["period"] = stacked
    return caches


def init_paged_caches(cfg, batch, num_blocks, block_size, dtype):
    """Paged-cache counterpart of :func:`init_caches`: every attention
    layer gets ONE physical ``(num_blocks + 1, block_size, K, D)`` block
    pool (shared across slot-table rows via block tables); SSM state
    keeps its per-row layout."""
    pattern, prefix_len, period, n_rep = structure(cfg)
    caches = {"prefix": [init_paged_block_cache(cfg, pattern[i], batch,
                                                num_blocks, block_size,
                                                dtype)
                         for i in range(prefix_len)]}
    stacked = []
    for j in range(period):
        kind = pattern[prefix_len + j]
        c = init_paged_block_cache(cfg, kind, batch, num_blocks,
                                   block_size, dtype)
        stacked.append(jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_rep,) + a.shape), c))
    caches["period"] = stacked
    return caches


def prefill_lm(params, cfg, tokens, frontend_embeds=None, positions3=None,
               moe_impl="ragged", mesh=None, window=None):
    """Prefill: full forward returning last-token logits only (the full
    (B, S, V) logits tensor is never materialized — serving-path memory
    discipline).  KV caches for subsequent decode are built by the engine
    via ``fill_kv_cache``; the dry-run lowers this entry point."""
    hidden, _ = forward_lm(params, cfg, tokens, frontend_embeds, positions3,
                           moe_impl, mesh, remat=False, window=window)
    return logits_last_token(params, cfg, hidden)


def decode_lm(params, cfg, caches, tokens, cache_len, positions3=None,
              moe_impl="ragged", mesh=None, active=None,
              block_tables=None, logits_at=None):
    """One decode step.  tokens: (B, S) -> (logits (B, V), new caches).

    ``cache_len`` may be a scalar (all rows at the same position) or a
    (B,) vector (continuous batching: per-slot positions); ``active``
    (B,) bool gates cache writes per row — see models/attention.py.
    ``block_tables`` (B, blocks_per_seq) must be passed when ``caches``
    were built by :func:`init_paged_caches` (one table routes every
    layer's pool).

    S > 1 runs a block of S tokens a row, at positions ``cache_len[b] +
    s``, through every layer at once (attention-only models on paged
    caches: the Mamba step and a dense cache take one token): ``active``
    is then (B, S), one flag a token, and the logits returned are row
    b's at position ``logits_at[b]`` (default S - 1).  A row whose final
    hidden state is not finite at any of its active positions gets NaN
    logits, so a watchdog over the returned logits covers every token of
    the block.

    Each paged attention pool of the period rides in the layer scan's
    carry, stacked ``(n_rep, nb + 1, bs, K, D)`` as the cache holds it,
    and the scan hands the block its layer index: the new token is
    written in place and K/V are gathered through the table, so a step
    neither slices a layer's pool out nor writes one back.  Every other
    cache (dense per-slot KV, SSM/conv state) is scanned as ``xs`` and
    ``ys``.

    The parts run under ``jax.named_scope``s (``embed``, each block's
    in ``decode_block``, ``norm``, ``lm_head``); the layer scan itself
    is left outside every scope, so its per-layer slices of the stacked
    weights (and of scanned caches) carry no model scope.
    """
    pattern, prefix_len, period, n_rep = structure(cfg)
    B, S = tokens.shape
    if active is not None and S == 1:
        active = active.reshape(B)           # one token a row: (B,)
    with jax.named_scope("embed"):
        x = _embed(params, cfg, tokens)                # (B, S, d)

    new_prefix = []
    for i in range(prefix_len):
        x, c = decode_block(params["prefix"][i], cfg, x,
                            caches["prefix"][i], pattern[i], cache_len,
                            positions3, moe_impl, mesh, active,
                            block_tables)
        new_prefix.append(c)

    new_period = caches["period"]
    if n_rep:
        kinds = [pattern[prefix_len + j] for j in range(period)]
        carried = ["k_pool" in c for c in caches["period"]]
        pools = tuple(c if k else None
                      for c, k in zip(caches["period"], carried))
        scanned = tuple(None if k else c
                        for c, k in zip(caches["period"], carried))

        def body(carry, xs):
            x, pools = carry
            layer_params, layer_caches, layer = xs
            pools, new_caches = list(pools), []
            for j in range(period):
                cache = pools[j] if carried[j] else layer_caches[j]
                x, c = decode_block(layer_params[j], cfg, x, cache,
                                    kinds[j], cache_len, positions3,
                                    moe_impl, mesh, active, block_tables,
                                    layer if carried[j] else None)
                if carried[j]:
                    pools[j] = c
                new_caches.append(None if carried[j] else c)
            return (x, tuple(pools)), tuple(new_caches)

        (x, pools), ys = jax.lax.scan(
            body, (x, pools),
            (tuple(params["period"]), scanned, jnp.arange(n_rep)),
            **runtime_flags.scan_kwargs())
        new_period = [p if k else y
                      for p, y, k in zip(pools, ys, carried)]

    norm = make_norm(cfg.norm_type, cfg.rms_norm_eps)
    with jax.named_scope("norm"):
        hidden = norm(params["final_norm"], x)
    if S > 1:
        if logits_at is None:
            logits_at = jnp.full((B,), S - 1, jnp.int32)
        finite = jnp.isfinite(hidden).all(-1)
        if active is not None:
            finite |= ~active
        healthy = finite.all(-1)
        hidden = jnp.take_along_axis(hidden, logits_at[:, None, None], 1)
    with jax.named_scope("lm_head"):
        logits = logits_last_token(params, cfg, hidden)
    if S > 1:
        logits = jnp.where(healthy[:, None], logits, jnp.nan)
    return logits, {"prefix": new_prefix, "period": new_period}
