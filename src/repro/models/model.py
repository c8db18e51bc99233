"""Model registry: ModelConfig -> uniform init / loss / prefill / decode API.

Every assigned architecture (dense, MoE, SSM, hybrid, VLM, audio enc-dec)
is exposed through the same five entry points so the launcher, dry-run,
serving engine and benchmarks never special-case architectures:

    api = build_model(cfg)
    params = api.init(key)
    loss, metrics = api.loss_fn(params, batch)            # train
    logits = api.prefill_fn(params, batch)                # prefill
    caches = api.init_caches(batch_size, max_len, dtype, ring=...)
    logits, caches = api.decode_fn(params, caches, batch) # decode step

``api.input_specs(shape)`` returns jax.ShapeDtypeStruct stand-ins for the
batch of a given InputShape (the dry-run contract), and
``api.batch_pspecs(shape)`` the matching PartitionSpecs.

Frontend stubs (the one allowed carve-out): audio frame embeddings and
vision patch embeddings enter as precomputed (B, S_front, d) inputs.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from . import encdec, transformer
from .sharding import DP_AXES


def _dp(mesh_axes=None):
    return DP_AXES


@dataclass
class ModelAPI:
    cfg: Any
    init: Callable
    loss_fn: Callable              # (params, batch) -> (loss, metrics)
    prefill_fn: Callable           # (params, batch) -> (B, V) logits
    decode_fn: Callable            # (params, caches, batch) -> (logits, caches)
    init_caches: Callable          # (batch, max_len, dtype, ring) -> caches
    input_specs: Callable          # (InputShape) -> dict[str, ShapeDtypeStruct]
    batch_pspecs: Callable         # (InputShape) -> dict[str, PartitionSpec]
    # (batch, num_blocks, block_size, dtype) -> physically paged caches;
    # None for families without a paged decode path (encoder-decoder)
    init_paged_caches: "Callable | None" = None

    def decode_supported(self) -> bool:
        return True

    def paged_supported(self) -> bool:
        return self.init_paged_caches is not None


def _moe_impl_for(cfg, distributed: bool):
    """Single-shard: every held expert on every token (``moe_dense``)
    where a chip holds no more experts than a token is routed to, or a
    smoke config's four, else the ragged grouped GEMM.  With so few held
    experts a decode batch reaches every one of them, so both paths read
    the same expert weights and the dense one skips the sort, gather and
    scatter: on a TPU v5e, granite-4.0-h-small (9 held, top-10) at a
    batch of 64 decodes a step in 17.6 ms dense against 20.3 ragged."""
    m = cfg.moe
    if m.num_experts == 0:
        return "ragged"
    if not distributed:
        dense = cfg.held_experts() <= max(4, m.num_experts_per_tok)
        return "dense" if dense else "ragged"
    if cfg.held_experts() < m.num_experts:
        raise ValueError(f"{cfg.name}: holds a share of the experts; "
                         "expert parallelism over a mesh holds them all")
    return "ep"


def build_model(cfg, distributed: bool = False, mesh=None,
                long_context: bool = False) -> ModelAPI:
    if cfg.is_encoder_decoder:
        return _build_encdec(cfg)
    return _build_decoder_lm(cfg, distributed, mesh, long_context)


# --------------------------------------------------------------------------
# decoder-only family (dense / moe / ssm / hybrid / vlm)
# --------------------------------------------------------------------------

def _build_decoder_lm(cfg, distributed, mesh, long_context):
    moe_impl = _moe_impl_for(cfg, distributed)
    is_vlm = cfg.frontend == "vision_patches"
    n_front = cfg.num_frontend_tokens if is_vlm else 0
    idt = jnp.int32

    def init(key):
        return transformer.init_lm(key, cfg)

    def loss_fn(params, batch):
        return transformer.lm_loss(
            params, cfg, batch["tokens"], batch["labels"],
            batch.get("frontend_embeds"), batch.get("positions3"),
            moe_impl=moe_impl, mesh=mesh)

    def prefill_fn(params, batch):
        return transformer.prefill_lm(
            params, cfg, batch["tokens"], batch.get("frontend_embeds"),
            batch.get("positions3"), moe_impl=moe_impl, mesh=mesh)

    def decode_fn(params, caches, batch):
        return transformer.decode_lm(
            params, cfg, caches, batch["tokens"], batch["cache_len"],
            batch.get("positions3"), moe_impl=moe_impl, mesh=mesh,
            active=batch.get("active"),
            block_tables=batch.get("block_tables"),
            logits_at=batch.get("logits_at"))

    def init_caches(batch, max_len, dtype, ring=False):
        return transformer.init_caches(cfg, batch, max_len, dtype, ring)

    def init_paged_caches(batch, num_blocks, block_size, dtype):
        return transformer.init_paged_caches(cfg, batch, num_blocks,
                                             block_size, dtype)

    def input_specs(shape):
        B, S = shape.global_batch, shape.seq_len
        sds = jax.ShapeDtypeStruct
        if shape.kind == "train":
            sp = {"tokens": sds((B, S), idt), "labels": sds((B, S), idt)}
            if is_vlm:
                sp["tokens"] = sds((B, S - n_front), idt)
                sp["labels"] = sds((B, S - n_front), idt)
                sp["frontend_embeds"] = sds((B, n_front, cfg.d_model),
                                            jnp.dtype(cfg.dtype))
                sp["positions3"] = sds((3, B, S), idt)
            return sp
        if shape.kind == "prefill":
            sp = {"tokens": sds((B, S), idt)}
            if is_vlm:
                sp["tokens"] = sds((B, S - n_front), idt)
                sp["frontend_embeds"] = sds((B, n_front, cfg.d_model),
                                            jnp.dtype(cfg.dtype))
                sp["positions3"] = sds((3, B, S), idt)
            return sp
        # decode: one token against a seq_len cache
        sp = {"tokens": sds((B, 1), idt),
              "cache_len": sds((), idt)}
        if is_vlm:
            sp["positions3"] = sds((3, B, 1), idt)
        return sp

    def batch_pspecs(shape):
        dp = DP_AXES
        if shape.kind == "train":
            sp = {"tokens": P(dp, None), "labels": P(dp, None)}
            if is_vlm:
                sp["frontend_embeds"] = P(dp, None, None)
                sp["positions3"] = P(None, dp, None)
            return sp
        if shape.kind == "prefill":
            sp = {"tokens": P(dp, None)}
            if is_vlm:
                sp["frontend_embeds"] = P(dp, None, None)
                sp["positions3"] = P(None, dp, None)
            return sp
        sp = {"tokens": P(dp, None) if shape.global_batch > 1 else P(None,
                                                                     None),
              "cache_len": P()}
        if is_vlm:
            sp["positions3"] = P(None, dp, None) \
                if shape.global_batch > 1 else P(None, None, None)
        return sp

    return ModelAPI(cfg, init, loss_fn, prefill_fn, decode_fn,
                    init_caches, input_specs, batch_pspecs,
                    init_paged_caches=init_paged_caches)


# --------------------------------------------------------------------------
# encoder-decoder family (whisper)
# --------------------------------------------------------------------------

def _build_encdec(cfg):
    idt = jnp.int32
    ddt = jnp.dtype(cfg.dtype)
    dec_len = 448                       # whisper decoder context

    def init(key):
        return encdec.init_encdec(key, cfg)

    def loss_fn(params, batch):
        return encdec.encdec_loss(params, cfg, batch["frames"],
                                  batch["tokens"], batch["labels"])

    def prefill_fn(params, batch):
        # serving prefill = encoder + first decoder token
        caches = encdec.init_dec_caches(
            cfg, batch["frames"].shape[0], dec_len, ddt)
        _, caches = encdec.prefill_encdec(params, cfg, batch["frames"],
                                          caches)
        logits, _ = encdec.decode_step_encdec(
            params, cfg, caches, batch["tokens"][:, :1],
            jnp.asarray(0, jnp.int32))
        return logits

    def decode_fn(params, caches, batch):
        return encdec.decode_step_encdec(params, cfg, caches,
                                         batch["tokens"],
                                         batch["cache_len"],
                                         active=batch.get("active"))

    def init_caches(batch, max_len, dtype, ring=False):
        del ring
        return encdec.init_dec_caches(cfg, batch, max_len, dtype)

    def input_specs(shape):
        B, S = shape.global_batch, shape.seq_len
        sds = jax.ShapeDtypeStruct
        if shape.kind == "train":
            # encoder carries the assigned seq_len (frame embeddings from
            # the stub frontend); decoder uses Whisper's native context.
            return {"frames": sds((B, S, cfg.d_model), ddt),
                    "tokens": sds((B, dec_len), idt),
                    "labels": sds((B, dec_len), idt)}
        if shape.kind == "prefill":
            return {"frames": sds((B, S, cfg.d_model), ddt),
                    "tokens": sds((B, 1), idt)}
        return {"tokens": sds((B, 1), idt), "cache_len": sds((), idt)}

    def batch_pspecs(shape):
        dp = DP_AXES
        if shape.kind == "train":
            return {"frames": P(dp, None, None), "tokens": P(dp, None),
                    "labels": P(dp, None)}
        if shape.kind == "prefill":
            return {"frames": P(dp, None, None), "tokens": P(dp, None)}
        return {"tokens": P(dp, None), "cache_len": P()}

    return ModelAPI(cfg, init, loss_fn, prefill_fn, decode_fn,
                    init_caches, input_specs, batch_pspecs)


# --------------------------------------------------------------------------
# frontend stubs (smoke tests / examples need concrete inputs)
# --------------------------------------------------------------------------

def stub_vision_frontend(key, cfg, batch, total_seq):
    """Vision-patch embeddings + M-RoPE 3-stream positions (Qwen2-VL).

    Text tokens use equal (t, h, w) position ids continuing after the
    vision grid — a faithful simplification of dynamic-resolution M-RoPE.
    """
    n = cfg.num_frontend_tokens
    emb = jax.random.normal(key, (batch, n, cfg.d_model),
                            jnp.dtype(cfg.dtype)) * 0.02
    side = max(1, int(np.sqrt(n)))
    t = np.zeros(n, np.int32)
    h = (np.arange(n) // side).astype(np.int32)
    w = (np.arange(n) % side).astype(np.int32)
    text = np.arange(total_seq - n, dtype=np.int32) + h.max() + 1
    pos3 = np.stack([np.concatenate([t, text]),
                     np.concatenate([h, text]),
                     np.concatenate([w, text])])
    pos3 = np.broadcast_to(pos3[:, None, :], (3, batch, total_seq))
    return emb, jnp.asarray(pos3)


def stub_audio_frontend(key, cfg, batch, n_frames):
    """Mel+conv frontend stub: precomputed frame embeddings."""
    return jax.random.normal(key, (batch, n_frames, cfg.d_model),
                             jnp.dtype(cfg.dtype)) * 0.02
