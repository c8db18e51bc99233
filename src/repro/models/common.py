"""Shared model primitives: norms, activations, RoPE / M-RoPE, init.

Pure-JAX (no flax): parameters are nested dicts of jnp arrays; every
function is ``fn(params, x, ...) -> y`` and jit/pjit-friendly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def cast(x, dtype):
    return x.astype(dtype) if x.dtype != jnp.dtype(dtype) else x


# --------------------------------------------------------------------------
# initializers
# --------------------------------------------------------------------------

def dense_init(key, shape, in_axis=-2, dtype=jnp.float32):
    """LeCun-normal in fp32 (master weights); cast at use time."""
    fan_in = shape[in_axis] if len(shape) > 1 else shape[0]
    return (jax.random.normal(key, shape) / np.sqrt(fan_in)).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


# --------------------------------------------------------------------------
# norms / activations
# --------------------------------------------------------------------------

def rms_norm(scale, x, eps=1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(dt)


def layer_norm(params, x, eps=1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)
            + params["bias"].astype(jnp.float32)).astype(dt)


def make_norm(norm_type: str, eps: float = 1e-6):
    """The norm of ``norm_type``; ``eps`` is RMSNorm's (LayerNorm keeps
    its own 1e-5)."""
    if norm_type == "rmsnorm":
        return lambda p, x: rms_norm(p["scale"], x, eps)
    if norm_type == "layernorm":
        return layer_norm
    raise ValueError(norm_type)


def init_norm(key, d, norm_type):
    del key
    if norm_type == "rmsnorm":
        return {"scale": jnp.ones((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def activation(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "relu": jax.nn.relu}[name]


# --------------------------------------------------------------------------
# RoPE and M-RoPE
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2,
                                      dtype=np.float32) / head_dim))


def apply_rope(x, positions, theta=1e4):
    """x: (..., S, H, head_dim); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    inv = jnp.asarray(rope_freqs(hd, theta))
    ang = positions[..., :, None, None].astype(jnp.float32) * inv  # (...,S,1,hd/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, sections, theta=1e4):
    """Multimodal RoPE (Qwen2-VL, arXiv:2409.12191).

    ``positions3``: (3, ..., S) — temporal / height / width position ids
    (all equal for text tokens).  ``sections`` split the *rotary half* of
    head_dim among the three streams, e.g. (16, 24, 24) for head_dim 128.
    """
    hd = x.shape[-1]
    half = hd // 2
    assert sum(sections) == half, (sections, hd)
    inv = jnp.asarray(rope_freqs(hd, theta))          # (half,)
    # choose which position stream drives each frequency band
    sect_id = np.concatenate([np.full((s,), i)
                              for i, s in enumerate(sections)])
    angles = []
    for i in range(3):
        ang_i = positions3[i][..., :, None, None].astype(jnp.float32) * inv
        angles.append(ang_i)
    ang = jnp.where(sect_id == 0, angles[0],
                    jnp.where(sect_id == 1, angles[1], angles[2]))
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(length: int, dim: int):
    """Whisper-style fixed sinusoidal embeddings."""
    pos = np.arange(length)[:, None]
    i = np.arange(dim // 2)[None, :]
    angle = pos / np.power(10000.0, 2 * i / dim)
    emb = np.concatenate([np.sin(angle), np.cos(angle)], axis=-1)
    return jnp.asarray(emb, jnp.float32)
