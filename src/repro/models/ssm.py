"""Mamba2 blocks via SSD — state-space duality (arXiv:2405.21060).

The SSD layer computes, per head h with state size N and head dim P:

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * B_t x_t^T      (N x P state)
    y_t = C_t^T h_t + D x_t

The *chunked* algorithm splits the sequence into chunks of length L and
evaluates intra-chunk terms with dense matmuls (MXU-friendly — this is the
TPU adaptation: chunk sizes are multiples of the 128 MXU tile at full
scale) plus an inter-chunk scan over per-chunk states.  A sequential-scan
reference (`ssd_scan_ref`) validates it, and `repro.kernels.ssd_scan`
implements the chunk kernel in Pallas.

Block layout follows Mamba2: in_proj -> [z | xBC | dt], causal conv1d on
xBC, SSD, gated RMSNorm, out_proj.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .common import dense_init, rms_norm


# --------------------------------------------------------------------------
# SSD core
# --------------------------------------------------------------------------

def segsum(x):
    """Stable 'segment sum' producing pairwise decay exponents.

    x: (..., L).  Returns (..., L, L) with out[i, j] = sum_{j < k <= i} x_k
    for j <= i, -inf above the diagonal.
    """
    L = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = np.tril(np.ones((L, L), bool), 0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_chunked(x, dt, A, B, C, chunk: int, initial_state=None):
    """Chunked SSD scan.

    x:  (b, S, H, P)   dt: (b, S, H)    A: (H,) negative
    B, C: (b, S, G, N) with G groups broadcast over H // G heads.
    Returns (y (b,S,H,P), final_state (b,H,P,N)).
    """
    b, S, H, Pd = x.shape
    G, N = B.shape[2], B.shape[3]
    assert S % chunk == 0, f"seq {S} not divisible by chunk {chunk}"
    nc = S // chunk
    rep = H // G

    # broadcast groups to heads
    Bh = jnp.repeat(B, rep, axis=2)                   # (b,S,H,N)
    Ch = jnp.repeat(C, rep, axis=2)

    def r(t, last):  # reshape into chunks
        return t.reshape((b, nc, chunk) + last)

    xc = r(x, (H, Pd))
    dtc = r(dt, (H,))
    Bc = r(Bh, (H, N))
    Cc = r(Ch, (H, N))

    dA = dtc * A[None, None, None, :]                 # (b,nc,L,H)
    dA = jnp.moveaxis(dA, -1, 2)                      # (b,nc,H,L)
    dA_cs = jnp.cumsum(dA, axis=-1)                   # within-chunk cumsum

    # 1) intra-chunk (diagonal blocks): Y_diag = (C B^T ∘ decay) (x*dt)
    Ldec = jnp.exp(segsum(dA))                        # (b,nc,H,L,L)
    scores = jnp.einsum("bclhn,bcshn->bchls", Cc, Bc)  # (b,nc,H,L,S=L)
    gated = scores * Ldec
    xdt = xc * dtc[..., None]                          # (b,nc,L,H,P)
    y_diag = jnp.einsum("bchls,bcshp->bclhp", gated, xdt)

    # 2) chunk states: decay-to-end weighted outer products
    decay_end = jnp.exp(dA_cs[..., -1:] - dA_cs)      # (b,nc,H,L)
    states = jnp.einsum("bclhn,bchl,bclhp->bchpn",
                        Bc, decay_end, xdt)           # (b,nc,H,P,N)

    # 3) inter-chunk recurrence over chunk states
    chunk_decay = jnp.exp(dA_cs[..., -1])             # (b,nc,H)
    if initial_state is None:
        initial_state = jnp.zeros((b, H, Pd, N), x.dtype)

    def step(carry, inp):
        s_prev = carry
        s_new, dec = inp                               # (b,H,P,N), (b,H)
        s = s_new + dec[..., None, None] * s_prev
        return s, s_prev                               # emit state *before*

    (final, prev_states) = jax.lax.scan(
        step,
        initial_state,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)      # (b,nc,H,P,N)

    # 4) off-diagonal contribution: read previous state into the chunk
    state_decay = jnp.exp(dA_cs)                       # decay from chunk start
    y_off = jnp.einsum("bclhn,bchl,bchpn->bclhp",
                       Cc, state_decay, prev_states)

    y = (y_diag + y_off).reshape(b, S, H, Pd)
    return y, final


def ssd_scan_ref(x, dt, A, B, C, initial_state=None):
    """Sequential-recurrence oracle (O(S) steps, exact)."""
    b, S, H, Pd = x.shape
    G, N = B.shape[2], B.shape[3]
    rep = H // G
    Bh = jnp.repeat(B, rep, axis=2)
    Ch = jnp.repeat(C, rep, axis=2)
    if initial_state is None:
        initial_state = jnp.zeros((b, H, Pd, N), x.dtype)

    def step(h, inp):
        xt, dtt, Bt, Ct = inp                          # (b,H,P),(b,H),(b,H,N)
        decay = jnp.exp(dtt * A[None, :])              # (b,H)
        upd = jnp.einsum("bhn,bhp->bhpn", Bt, xt * dtt[..., None])
        h = decay[..., None, None] * h + upd
        y = jnp.einsum("bhn,bhpn->bhp", Ct, h)
        return h, y

    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(Bh, 1, 0), jnp.moveaxis(Ch, 1, 0))
    final, ys = jax.lax.scan(step, initial_state, xs)
    return jnp.moveaxis(ys, 0, 1), final


def ssd_decode_step(state, x, dt, A, B, C):
    """Single-token recurrent update (decode path).

    state: (b,H,P,N); x: (b,H,P); dt: (b,H); B, C: (b,G,N).
    Returns (y (b,H,P), new_state).
    """
    G = B.shape[1]
    H = x.shape[1]
    rep = H // G
    Bh = jnp.repeat(B, rep, axis=1)
    Ch = jnp.repeat(C, rep, axis=1)
    decay = jnp.exp(dt * A[None, :])
    upd = jnp.einsum("bhn,bhp->bhpn", Bh, x * dt[..., None])
    state = decay[..., None, None] * state + upd
    y = jnp.einsum("bhn,bhpn->bhp", Ch, state)
    return y, state


# --------------------------------------------------------------------------
# Mamba2 block
# --------------------------------------------------------------------------

def _dims(cfg):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    nheads = d_inner // s.head_dim
    conv_dim = d_inner + 2 * s.n_groups * s.d_state
    return d_inner, nheads, conv_dim


def init_mamba(key, cfg):
    s = cfg.ssm
    d = cfg.d_model
    d_inner, nheads, conv_dim = _dims(cfg)
    ks = jax.random.split(key, 5)
    return {
        "in_proj": dense_init(
            ks[0], (d, 2 * d_inner + 2 * s.n_groups * s.d_state + nheads)),
        "conv_w": dense_init(ks[1], (s.conv_width, conv_dim)) * 0.1,
        "conv_b": jnp.zeros((conv_dim,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nheads)),
        "D": jnp.ones((nheads,), jnp.float32),
        "dt_bias": jnp.zeros((nheads,), jnp.float32),
        "norm_scale": jnp.ones((d_inner,), jnp.float32),
        "out_proj": dense_init(ks[2], (d_inner, d)),
    }


def _split_proj(cfg, zxbcdt):
    s = cfg.ssm
    d_inner, nheads, _ = _dims(cfg)
    gN = s.n_groups * s.d_state
    z, xBC, dt = jnp.split(
        zxbcdt, [d_inner, 2 * d_inner + 2 * gN], axis=-1)
    return z, xBC, dt


def _causal_conv(xBC, w, b):
    """Depthwise causal conv1d over (b, S, C)."""
    Kw = w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (Kw - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xBC.shape[1], :] * w[i][None, None, :]
              for i in range(Kw))
    return jax.nn.silu(out + b[None, None, :])


def mamba_block(params, cfg, x, use_chunked=True):
    """Full-sequence Mamba2 block.  x: (b, S, d) -> (b, S, d)."""
    s = cfg.ssm
    b, S, d = x.shape
    d_inner, nheads, conv_dim = _dims(cfg)
    dt_p = x.dtype

    zxbcdt = jnp.einsum("bsd,df->bsf", x, params["in_proj"].astype(dt_p))
    z, xBC, dt = _split_proj(cfg, zxbcdt)
    xBC = _causal_conv(xBC, params["conv_w"].astype(dt_p),
                       params["conv_b"].astype(dt_p))
    gN = s.n_groups * s.d_state
    xs, B, C = jnp.split(xBC, [d_inner, d_inner + gN], axis=-1)
    xs = xs.reshape(b, S, nheads, s.head_dim)
    B = B.reshape(b, S, s.n_groups, s.d_state)
    C = C.reshape(b, S, s.n_groups, s.d_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt_bias"][None, None, :])
    A = -jnp.exp(params["A_log"].astype(jnp.float32))

    from . import runtime_flags
    if runtime_flags.shard_ssm_heads:
        # §Perf O6: heads over the model axis; the SSD scan is sequential
        # over seq, so without this the full-seq fp32 tensors replicate.
        from .sharding import DP_AXES, maybe_shard
        xs = maybe_shard(xs, DP_AXES, None, "model", None)
        dt = maybe_shard(dt, DP_AXES, None, "model")

    fn = ssd_chunked if use_chunked else ssd_scan_ref
    kw = {"chunk": s.chunk} if use_chunked else {}
    y, _ = fn(xs.astype(jnp.float32), dt, A,
              B.astype(jnp.float32), C.astype(jnp.float32), **kw)
    y = y + params["D"][None, None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(b, S, d_inner).astype(dt_p)
    # gated RMSNorm (Mamba2): norm(y * silu(z))
    y = rms_norm(params["norm_scale"], y * jax.nn.silu(z),
                 cfg.rms_norm_eps)
    return jnp.einsum("bsf,fd->bsd", y, params["out_proj"].astype(dt_p))


def init_mamba_cache(cfg, batch: int, dtype):
    s = cfg.ssm
    d_inner, nheads, conv_dim = _dims(cfg)
    return {
        "state": jnp.zeros((batch, nheads, s.head_dim, s.d_state),
                           jnp.float32),
        "conv": jnp.zeros((batch, s.conv_width - 1, conv_dim), dtype),
    }


def mamba_decode_step(params, cfg, x, cache):
    """Single-token decode.  x: (b, 1, d) -> (y (b,1,d), new_cache).

    Scan-carry contract (serving): this step runs not only as its own
    dispatch but as the body of the prefill-chunk scan AND the decode
    megastep (``runtime.stepper``), with ``cache`` a ``lax.scan`` carry
    — so it must stay a pure function of traced values (no host reads,
    no python-int shapes derived from the state).  Row gating lives in
    the caller (``blocks.decode_block`` masks the state update by
    ``active``), which is what lets a megastep's finished rows stop
    mutating their SSM state mid-scan.
    """
    s = cfg.ssm
    b = x.shape[0]
    d_inner, nheads, conv_dim = _dims(cfg)
    dt_p = x.dtype

    zxbcdt = jnp.einsum("bsd,df->bsf", x, params["in_proj"].astype(dt_p))
    z, xBC, dt = _split_proj(cfg, zxbcdt)                 # (b,1,*)
    # rolling conv window
    win = jnp.concatenate([cache["conv"], xBC], axis=1)   # (b,Kw,conv)
    w = params["conv_w"].astype(dt_p)
    out = (win * w[None, :, :]).sum(axis=1, keepdims=True)
    xBC = jax.nn.silu(out + params["conv_b"].astype(dt_p)[None, None, :])
    new_conv = win[:, 1:, :]

    gN = s.n_groups * s.d_state
    xs, B, C = jnp.split(xBC[:, 0], [d_inner, d_inner + gN], axis=-1)
    xs = xs.reshape(b, nheads, s.head_dim)
    B = B.reshape(b, s.n_groups, s.d_state)
    C = C.reshape(b, s.n_groups, s.d_state)
    dtv = jax.nn.softplus(dt[:, 0].astype(jnp.float32)
                          + params["dt_bias"][None, :])
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    y, state = ssd_decode_step(cache["state"], xs.astype(jnp.float32),
                               dtv, A, B.astype(jnp.float32),
                               C.astype(jnp.float32))
    y = y + params["D"][None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(b, 1, d_inner).astype(dt_p)
    y = rms_norm(params["norm_scale"], y * jax.nn.silu(z),
                 cfg.rms_norm_eps)
    y = jnp.einsum("bsf,fd->bsd", y, params["out_proj"].astype(dt_p))
    return y, {"state": state, "conv": new_conv}
