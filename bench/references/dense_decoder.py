"""Plain reference for a dense decoder-only transformer, and its weights.

It imports nothing of the system under test.  What it knows of the
program is the layout of the weight tree the program serves from, which
:func:`init_params` builds from the seed and the harness checks against
the program's own tree before anything runs:

    embed (V, d)   lm_head (d, V)   final_norm {scale[, bias]}
    prefix []      period [{norm1, attn {wq wk wv wo}, norm2,
                            mlp {w_gate w_up w_down}}]   (leading axis L)

The forward pass follows the configuration file, not the program: pre-norm
blocks (LayerNorm with bias, or RMSNorm), rotate-half RoPE on the first
``partial_rotary_factor`` of each head, causal softmax attention with
grouped KV heads and an optional sliding window, SwiGLU, a final norm and
an untied output head.  It runs one sequence at a time, layer by layer
under ``lax.scan``, in float32 with every matrix product at ``highest``
precision, on the weights as served (bfloat16, upcast exactly).

``quant="fp8"`` is the control: every matrix (attention, MLP, output
head) rounded to float8 e4m3 with one scale per output column, the step a
later change that stores weights in fp8 would take.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FP8_MAX = 448.0          # largest finite float8_e4m3fn


def prng_key(seed: int):
    """A key that keeps all 64 bits of ``seed``: ``jax.random.key``
    alone keeps the low 32."""
    seed = int(seed) % 2 ** 64
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    return dict(L=cfg["num_hidden_layers"], d=d, H=H,
                K=cfg["num_key_value_heads"],
                D=cfg.get("head_dim") or d // H,
                F=cfg["intermediate_size"], V=cfg["vocab_size"])


def _norm_eps(cfg: dict) -> float:
    return float(cfg.get("norm_eps", cfg.get("rms_norm_eps")))


def init_params(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """The weights from ``seed``, built on the default device in one
    jitted call, in ``dtype``."""
    return jax.jit(weight_fn(cfg, dtype))(prng_key(seed))


def weight_fn(cfg: dict, dtype):
    """key -> weight tree (see the module docstring)."""
    s = sizes(cfg)
    L, d, H, K, D, F, V = (s[k] for k in "LdHKDFV")
    layernorm = cfg["norm_type"] == "layernorm"

    def build(key):
        keys = iter(jax.random.split(key, 16))

        def normal(shape, std):
            return (jax.random.normal(next(keys), shape, F32) * std
                    ).astype(dtype)

        def norm(shape):
            p = {"scale": (1.0 + 0.1 * jax.random.normal(
                next(keys), shape, F32)).astype(dtype)}
            if layernorm:
                p["bias"] = normal(shape, 0.1)
            return p

        block = {
            "norm1": norm((L, d)),
            "attn": {"wq": normal((L, d, H * D), d ** -0.5),
                     "wk": normal((L, d, K * D), d ** -0.5),
                     "wv": normal((L, d, K * D), d ** -0.5),
                     "wo": normal((L, H * D, d), (H * D) ** -0.5)},
            "norm2": norm((L, d)),
            "mlp": {"w_gate": normal((L, d, F), d ** -0.5),
                    "w_up": normal((L, d, F), d ** -0.5),
                    "w_down": normal((L, F, d), F ** -0.5)},
        }
        return {"embed": normal((V, d), 0.02),
                "final_norm": norm((d,)),
                "lm_head": normal((d, V), 0.02),
                "prefix": [], "period": [block]}

    return build


def _matrix(w, quant):
    w = w.astype(F32)
    if quant is None:
        return w
    if quant != "fp8":
        raise ValueError(f"unknown control precision {quant!r}")
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _norm(cfg, p, x):
    eps = _norm_eps(cfg)
    if cfg["norm_type"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = jnp.square(x - mu).mean(-1, keepdims=True)
        return ((x - mu) * jax.lax.rsqrt(var + eps) * p["scale"].astype(F32)
                + p["bias"].astype(F32))
    if cfg["norm_type"] == "rmsnorm":
        var = jnp.square(x).mean(-1, keepdims=True)
        return x * jax.lax.rsqrt(var + eps) * p["scale"].astype(F32)
    raise ValueError(cfg["norm_type"])


def _rope(x, pos, cfg):
    """Rotate-half RoPE on the first ``partial_rotary_factor`` of the
    head dimension.  x: (T, heads, D)."""
    D = x.shape[-1]
    rot = int(D * float(cfg.get("partial_rotary_factor", 1.0)))
    inv = 1.0 / (float(cfg["rope_theta"])
                 ** (np.arange(0, rot, 2, dtype=np.float64) / rot))
    ang = pos[:, None, None].astype(F32) * jnp.asarray(inv, F32)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    xr, rest = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., :rot // 2], xr[..., rot // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _logits(cfg, params, tokens, quant):
    """tokens (T,) -> logits (T, V), float32."""
    s = sizes(cfg)
    H, K, D = s["H"], s["K"], s["D"]
    T = tokens.shape[0]
    G = H // K
    pos = jnp.arange(T)
    mask = pos[None, :] <= pos[:, None]
    if cfg.get("sliding_window"):
        mask &= pos[None, :] > pos[:, None] - int(cfg["sliding_window"])
    act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[cfg["hidden_act"]]

    def layer(x, p):
        h = _norm(cfg, p["norm1"], x)
        a = p["attn"]
        q = (h @ _matrix(a["wq"], quant)).reshape(T, H, D)
        k = (h @ _matrix(a["wk"], quant)).reshape(T, K, D)
        v = (h @ _matrix(a["wv"], quant)).reshape(T, K, D)
        q, k = _rope(q, pos, cfg), _rope(k, pos, cfg)
        sc = jnp.einsum("tkgd,skd->kgts", q.reshape(T, K, G, D), k) / np.sqrt(D)
        sc = jnp.where(mask, sc, -jnp.inf)
        ctx = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(sc, axis=-1), v)
        x = x + ctx.reshape(T, H * D) @ _matrix(a["wo"], quant)
        h = _norm(cfg, p["norm2"], x)
        m = p["mlp"]
        y = act(h @ _matrix(m["w_gate"], quant)) * (h @ _matrix(m["w_up"], quant))
        return x + y @ _matrix(m["w_down"], quant), None

    x = params["embed"][tokens].astype(F32)
    x, _ = jax.lax.scan(layer, x, params["period"][0])
    x = _norm(cfg, params["final_norm"], x)
    return x @ _matrix(params["lm_head"], quant)


class Reference:
    """Gap readings for one configuration.  :meth:`gaps` runs one padded
    sequence and returns, at each position ``t`` whose ``target[t] >= 0``,
    how far the reference's logit of ``target[t]`` lies below its best;
    with ``control=True`` it also returns the same gap for the token the
    fp8 control puts first at ``t``.  Each sequence length compiles once."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self._jit = jax.jit(self._gaps, static_argnums=(3,))

    def _gaps(self, params, tokens, target, control):
        with jax.default_matmul_precision("highest"):
            ref = _logits(self.cfg, params, tokens, None)
            best = ref.max(-1)
            served = jnp.take_along_axis(
                ref, jnp.maximum(target, 0)[:, None], axis=1)[:, 0]
            gap = jnp.where(target >= 0, best - served, 0.0)
            if not control:
                return gap, None
            low = jnp.argmax(_logits(self.cfg, params, tokens, "fp8"), -1)
            chosen = jnp.take_along_axis(ref, low[:, None], axis=1)[:, 0]
            return gap, jnp.where(target >= 0, best - chosen, 0.0)

    def gaps(self, params, tokens, target, control: bool = False):
        gap, ctrl = self._jit(params, jnp.asarray(tokens, jnp.int32),
                               jnp.asarray(target, jnp.int32), control)
        return np.asarray(gap), None if ctrl is None else np.asarray(ctrl)
