"""Decoder blocks: attention / Mamba mixer + dense-MLP / MoE channel mix.

A block's *kind* is ``(mixer, channel)`` with mixer in {"attn", "mamba"}
and channel in {"dense", "moe", "none"}.  ``block_pattern`` derives the
per-layer kind list from a ModelConfig (hybrid interleave + MoE frequency),
and ``split_pattern`` factors it into (prefix, period) so the transformer
can scan over repeated structure while unrolling irregular prefixes
(e.g. a dense first layer before the MoE stack).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .attention import (decode_step_attention, init_attention,
                        self_attention)
from .common import init_norm, make_norm
from .mlp import init_mlp, mlp
from .moe import init_moe, moe_dense, moe_ep, moe_ragged
from .sharding import shard_batch_seq, shard_decode
from .ssm import init_mamba, init_mamba_cache, mamba_block, mamba_decode_step


def block_pattern(cfg):
    """[(mixer, channel)] for each of cfg.num_layers blocks."""
    out = []
    for i in range(cfg.num_layers):
        mixer = "attn" if cfg.is_attn_layer(i) else "mamba"
        if cfg.is_moe_layer(i):
            channel = "moe"
        elif cfg.d_ff > 0:
            channel = "dense"
        else:
            channel = "none"                      # mamba2: mixer-only blocks
        out.append((mixer, channel))
    return out


def split_pattern(pattern):
    """Factor ``pattern`` into (prefix_len, period) with minimal scan HLO:
    the suffix pattern[prefix:] repeats with ``period``; prefix layers are
    unrolled.  Greedy: smallest (prefix, period) lexicographically."""
    n = len(pattern)
    for prefix in range(0, min(n, 4) + 1):
        m = n - prefix
        if m == 0:
            return prefix, 1
        for period in range(1, min(m, 16) + 1):
            if m % period:
                continue
            if all(pattern[prefix + i] == pattern[prefix + i % period]
                   for i in range(m)):
                return prefix, period
    return n, 1                                    # fully unrolled fallback


def init_block(key, cfg, kind):
    mixer, channel = kind
    ks = jax.random.split(key, 4)
    p = {"norm1": init_norm(ks[0], cfg.d_model, cfg.norm_type)}
    if mixer == "attn":
        p["attn"] = init_attention(ks[1], cfg)
    else:
        p["mamba"] = init_mamba(ks[1], cfg)
    if channel != "none":
        p["norm2"] = init_norm(ks[2], cfg.d_model, cfg.norm_type)
        if channel == "moe":
            p["moe"] = init_moe(ks[3], cfg)
        else:
            p["mlp"] = init_mlp(ks[3], cfg.d_model, cfg.d_ff, cfg.act)
    return p


def _residual(cfg, x, y):
    """``x + y``, the branch ``y`` scaled by ``cfg.residual_multiplier``
    where one is set."""
    if cfg.residual_multiplier != 1.0:
        y = y * cfg.residual_multiplier
    return x + y


def _channel_mix(params, cfg, x, kind, moe_impl, mesh):
    """The block's channel mix and its residual add, named ``norm`` and
    ``mlp`` (dense or MoE) in the program's name stack."""
    channel = kind[1]
    if channel == "none":
        return x, 0.0
    norm = make_norm(cfg.norm_type, cfg.rms_norm_eps)
    with jax.named_scope("norm"):
        h = norm(params["norm2"], x)
    with jax.named_scope("mlp"):
        if channel == "dense":
            return _residual(cfg, x, mlp(params["mlp"], h, cfg.act)), 0.0
        B, S, d = h.shape
        flat = h.reshape(B * S, d)
        if moe_impl == "dense":
            y, aux = moe_dense(params["moe"], cfg, flat)
        elif moe_impl == "ep" and mesh is not None:
            y, aux = moe_ep(params["moe"], cfg, flat, mesh)
        else:
            y, aux = moe_ragged(params["moe"], cfg, flat)
        return _residual(cfg, x, y.reshape(B, S, d)), aux


def apply_block(params, cfg, x, kind, positions=None, positions3=None,
                moe_impl="ragged", mesh=None, window=None):
    """Full-sequence (train / prefill) block.  x: (B, S, d)."""
    mixer, _ = kind
    norm = make_norm(cfg.norm_type, cfg.rms_norm_eps)
    with jax.named_scope("norm"):
        h = norm(params["norm1"], x)
    if mixer == "attn":
        with jax.named_scope("attention"):
            x = _residual(cfg, x, self_attention(
                params["attn"], cfg, h, positions, positions3, causal=True,
                window=window))
    else:
        with jax.named_scope("ssm"):
            x = _residual(cfg, x, mamba_block(params["mamba"], cfg, h))
    x = shard_batch_seq(x)
    x, aux = _channel_mix(params, cfg, x, kind, moe_impl, mesh)
    return shard_batch_seq(x), aux


def init_block_cache(cfg, kind, batch, max_len, dtype, ring=False):
    from .attention import init_kv_cache
    mixer, _ = kind
    if mixer == "attn":
        return init_kv_cache(cfg, batch, max_len, dtype, ring=ring)
    return init_mamba_cache(cfg, batch, dtype)


def init_paged_block_cache(cfg, kind, batch, num_blocks, block_size,
                           dtype):
    """Paged variant of init_block_cache: attention layers get a
    physical block pool (no batch axis — rows are shared across the slot
    table via block tables); SSM state stays per-row."""
    from .attention import init_paged_kv_cache
    mixer, _ = kind
    if mixer == "attn":
        return init_paged_kv_cache(cfg, num_blocks, block_size, dtype)
    return init_mamba_cache(cfg, batch, dtype)


def decode_block(params, cfg, x, cache, kind, cache_len,
                 positions3=None, moe_impl="ragged", mesh=None,
                 active=None, block_tables=None, layer=None):
    """Decode block.  x: (B, S, d), S new tokens a row at positions
    ``cache_len[b] + s``; the Mamba mixer and a dense KV cache take one
    (S = 1).

    ``active`` (B,) bool — (B, S) for S > 1 — gates per-row (per-token)
    cache updates (continuous batching: inactive slot-table rows must
    not mutate their caches).  The norms and the channel mix run on all
    B * S rows.
    ``block_tables`` (B, blocks_per_seq) routes paged attention caches
    (see attention.init_paged_kv_cache); ignored by dense caches.
    ``layer`` indexes a paged cache whose pools are stacked over the
    scanned layers (see ``attention.decode_step_attention``).

    ``cache_len`` and ``active`` are *scan carries* in the serving
    runtime: the decode megastep threads them through ``lax.scan`` with
    per-row values advancing every fused iteration, so both must be
    consumed as traced arrays (vector per-row positions, no host
    round-trips) — which also guarantees a row flipping inactive
    mid-megastep freezes BOTH its attention KV writes (masked inside
    ``decode_step_attention``) and its SSM/conv state (the
    ``jnp.where`` below).

    Each part and its residual add runs under a ``jax.named_scope``
    (``norm``, ``attention`` or ``ssm``, then ``norm`` and ``mlp``; an
    MoE's ``mlp`` holds ``router``, ``experts`` and ``shared_expert``),
    so every operation of the served step names the part it belongs to
    in its op metadata.
    """
    mixer, _ = kind
    norm = make_norm(cfg.norm_type, cfg.rms_norm_eps)
    with jax.named_scope("norm"):
        h = norm(params["norm1"], x)
    if mixer == "attn":
        with jax.named_scope("attention"):
            y, cache = decode_step_attention(params["attn"], cfg, h, cache,
                                             cache_len, positions3,
                                             active=active,
                                             block_tables=block_tables,
                                             layer=layer)
            x = _residual(cfg, x, y)
    else:
        if x.shape[1] > 1:
            raise ValueError("the Mamba decode step consumes one token a "
                             "row")
        with jax.named_scope("ssm"):
            y, new_cache = mamba_decode_step(params["mamba"], cfg, h,
                                             cache)
            if active is not None:
                cache = jax.tree.map(
                    lambda n, o: jnp.where(
                        active.reshape((-1,) + (1,) * (n.ndim - 1)), n,
                        o),
                    new_cache, cache)
            else:
                cache = new_cache
            x = _residual(cfg, x, y)
    x = shard_decode(x)
    x, _aux = _channel_mix(params, cfg, x, kind, moe_impl, mesh)
    return shard_decode(x), cache
