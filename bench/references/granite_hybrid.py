"""Plain reference for the Granite 4.0-H hybrid (Hugging Face
``granitemoehybrid``), and its weights.

It imports nothing of the system under test.  What it knows of the
program is the layout of the weight tree the program serves from, which
:func:`init_params` builds from the seed and the harness checks against
the program's own tree before anything runs:

    embed (V, d), tied as the output head      final_norm {scale}
    prefix [block, ...]   period [block, ...]  (period blocks stacked
                                                over their repeats)
    block: norm1 {scale}, mamba {in_proj conv_w conv_b A_log D dt_bias
           norm_scale out_proj} or attn {wq wk wv wo}, norm2 {scale},
           moe {router, w_gate w_up w_down (held experts), shared
           {w_gate w_up w_down}}

The prefix and period are the program's split of its layer pattern: the
fewest leading layers kept apart (at most 4), then the shortest period
(at most 16) the rest repeats with.

Every size comes from the configuration file's keys: the layer kinds are
``layer_types[:num_hidden_layers]``; Mamba-2 has ``mamba_expand *
hidden_size`` inner channels in heads of ``mamba_d_head``; the experts
are ``intermediate_size`` wide, the shared one
``shared_intermediate_size``; the router scores ``router_experts``, of
which this chip holds the first ``num_local_experts``.
The forward pass, one sequence at a time, in float32 with every matrix
product at ``highest`` precision, on the weights as served (bfloat16,
upcast exactly):

    x = embedding_multiplier * E[tok]
    per layer:  h = x + r * mixer(RMSNorm(x))
                x = h + r * (MoE(RMSNorm(h)) + shared(RMSNorm(h)))
    logits = RMSNorm(x) E^T / logits_scaling

with r the ``residual_multiplier``.  Mamba-2: ``[z | xBC | dt] =
in_proj(u)``, a causal depthwise conv with bias then SiLU on xBC, ``dt =
softplus(dt + dt_bias)``, ``A = -exp(A_log)``, the recurrence ``S_t =
exp(dt A) S_{t-1} + dt x_t B_t^T``, ``y = C_t S_t + D x``, run token by
token; then RMSNorm over all inner channels of ``y * silu(z)`` and
out_proj.  Attention: grouped KV heads, no position embedding, softmax
of ``q k^T * attention_multiplier``, causal.  MoE: router logits over
every expert, the top ``num_experts_per_tok``, their gates a softmax over
those; the held experts' SwiGLU outputs weighted by their gates (an
absent expert's part is left out, with no stand-in).  RMSNorm at
``rms_norm_eps`` everywhere.

``quant="fp8"`` is the control: every matrix (projections, router,
experts, shared expert, output head; not the depthwise conv) rounded to
float8 e4m3 with one scale per output column, the step a later change
that stores weights in fp8 would take.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.references.dense_decoder import prng_key

F32 = jnp.float32
FP8_MAX = 448.0          # largest finite float8_e4m3fn
#: The embedding's std.  It is tied as the output head and enters x12:
#: at 0.02 a position's own token outscores every other by ~2 logits (of
#: 0.08 spread), so the best token never changes on any rounding and
#: neither the program nor an fp8 control can read off it; at 0.002 the
#: largest logits are the layers' work, within ~0.001 of each other.
EMBED_STD = 0.002


def sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    inner = cfg["mamba_expand"] * d
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    return dict(L=cfg["num_hidden_layers"], d=d, H=H,
                K=cfg["num_key_value_heads"],
                D=cfg.get("head_dim") or d // H, V=cfg["vocab_size"],
                kinds=list(cfg["layer_types"][:cfg["num_hidden_layers"]]),
                E=cfg["num_local_experts"], E_all=cfg["router_experts"],
                k=cfg["num_experts_per_tok"], F=cfg["intermediate_size"],
                Fs=cfg["shared_intermediate_size"], inner=inner,
                P=cfg["mamba_d_head"], Hm=inner // cfg["mamba_d_head"],
                G=G, N=N, W=cfg["mamba_d_conv"], conv=inner + 2 * G * N)


def layout(kinds: list) -> "tuple[int, int]":
    """(prefix, period) of the weight tree for the layer kinds."""
    n = len(kinds)
    for prefix in range(min(n, 4) + 1):
        m = n - prefix
        if m == 0:
            return prefix, 1
        for period in range(1, min(m, 16) + 1):
            if m % period == 0 and all(
                    kinds[prefix + i] == kinds[prefix + i % period]
                    for i in range(m)):
                return prefix, period
    return n, 1


def init_params(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """The weights from ``seed``, built on the default device in one
    jitted call, in ``dtype``."""
    return jax.jit(weight_fn(cfg, dtype))(prng_key(seed))


def weight_fn(cfg: dict, dtype):
    """key -> weight tree (see the module docstring).  Matrices are
    N(0, 1/fan_in), the embedding N(0, EMBED_STD^2), norm scales 1 +
    N(0, 0.1^2); Mamba-2 as it is initialised: ``A = -U[1, 16]``, ``dt``
    at ``softplus(dt_bias)`` log-uniform in [0.001, 0.1], ``D`` near 1."""
    s = sizes(cfg)
    d, V, E, F, Fs = s["d"], s["V"], s["E"], s["F"], s["Fs"]
    H, K, D = s["H"], s["K"], s["D"]
    inner, Hm, conv = s["inner"], s["Hm"], s["conv"]
    prefix, period = layout(s["kinds"])
    reps = (s["L"] - prefix) // period

    def block(key, kind):
        keys = iter(jax.random.split(key, 24))

        def normal(shape, std, mean=0.0):
            return (mean + jax.random.normal(next(keys), shape, F32) * std
                    ).astype(dtype)

        def uniform(shape, lo, hi):
            return jax.random.uniform(next(keys), shape, F32, lo, hi)

        p = {"norm1": {"scale": normal((d,), 0.1, 1.0)}}
        if kind == "mamba":
            dt = jnp.exp(uniform((Hm,), np.log(1e-3), np.log(1e-1)))
            p["mamba"] = {
                "in_proj": normal((d, 2 * inner + 2 * s["G"] * s["N"] + Hm),
                                  d ** -0.5),
                "conv_w": normal((s["W"], conv), s["W"] ** -0.5),
                "conv_b": normal((conv,), 0.1),
                "A_log": jnp.log(uniform((Hm,), 1.0, 16.0)).astype(dtype),
                "D": normal((Hm,), 0.1, 1.0),
                "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
                "norm_scale": normal((inner,), 0.1, 1.0),
                "out_proj": normal((inner, d), inner ** -0.5)}
        else:
            p["attn"] = {"wq": normal((d, H * D), d ** -0.5),
                         "wk": normal((d, K * D), d ** -0.5),
                         "wv": normal((d, K * D), d ** -0.5),
                         "wo": normal((H * D, d), (H * D) ** -0.5)}
        p["norm2"] = {"scale": normal((d,), 0.1, 1.0)}
        p["moe"] = {"router": normal((d, s["E_all"]), d ** -0.5),
                    "w_gate": normal((E, d, F), d ** -0.5),
                    "w_up": normal((E, d, F), d ** -0.5),
                    "w_down": normal((E, F, d), F ** -0.5),
                    "shared": {"w_gate": normal((d, Fs), d ** -0.5),
                               "w_up": normal((d, Fs), d ** -0.5),
                               "w_down": normal((Fs, d), Fs ** -0.5)}}
        return p

    def build(key):
        k_embed, k_norm, k_layers = jax.random.split(key, 3)
        kl = jax.random.split(k_layers, s["L"])
        kinds = s["kinds"]
        return {
            "embed": (jax.random.normal(k_embed, (V, d), F32) * EMBED_STD
                      ).astype(dtype),
            "final_norm": {"scale": (1.0 + 0.1 * jax.random.normal(
                k_norm, (d,), F32)).astype(dtype)},
            "prefix": [block(kl[i], kinds[i]) for i in range(prefix)],
            "period": [jax.vmap(lambda k, kind=kinds[prefix + j]:
                                block(k, kind))(
                kl[prefix + j::period][:reps]) for j in range(period)],
        }

    return build


def _layers(params, kinds):
    """Each layer's weights, in order."""
    prefix, period = layout(kinds)
    out = list(params["prefix"])
    for i in range(len(kinds) - prefix):
        r, j = divmod(i, period)
        out.append(jax.tree.map(lambda a, r=r: a[r], params["period"][j]))
    return out


def _matrix(w, quant):
    """``w`` (..., in, out) in float32; the control rounds it to fp8 with
    one scale per output column."""
    w = w.astype(F32)
    if quant is None:
        return w
    if quant != "fp8":
        raise ValueError(f"unknown control precision {quant!r}")
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _rms(x, scale, eps):
    var = jnp.square(x).mean(-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _mamba(cfg, s, p, u, quant):
    """Mamba-2 over one sequence u (T, d), token by token."""
    T = u.shape[0]
    inner, Hm, P, G, N = s["inner"], s["Hm"], s["P"], s["G"], s["N"]
    zxbcdt = u @ _matrix(p["in_proj"], quant)
    z, xBC, dt = jnp.split(zxbcdt, [inner, inner + s["conv"]], axis=-1)
    w = p["conv_w"].astype(F32)
    pad = jnp.concatenate([jnp.zeros((s["W"] - 1, s["conv"]), F32), xBC])
    xBC = jax.nn.silu(sum(pad[i:i + T] * w[i] for i in range(s["W"]))
                      + p["conv_b"].astype(F32))
    xs, B, C = jnp.split(xBC, [inner, inner + G * N], axis=-1)
    xs = xs.reshape(T, Hm, P)
    head_group = np.arange(Hm) // (Hm // G)
    B = B.reshape(T, G, N)[:, head_group]                 # (T, Hm, N)
    C = C.reshape(T, G, N)[:, head_group]
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))   # (T, Hm)
    A = -jnp.exp(p["A_log"].astype(F32))

    def step(S, inp):
        x_t, dt_t, B_t, C_t = inp
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, C_t)

    _, y = jax.lax.scan(step, jnp.zeros((Hm, P, N), F32), (xs, dt, B, C))
    y = y + p["D"].astype(F32)[:, None] * xs
    y = _rms(y.reshape(T, inner) * jax.nn.silu(z), p["norm_scale"],
             cfg["rms_norm_eps"])
    return y @ _matrix(p["out_proj"], quant)


def _attention(cfg, s, p, h, quant):
    """Causal GQA with no position embedding over h (T, d)."""
    T = h.shape[0]
    H, K, D = s["H"], s["K"], s["D"]
    q = (h @ _matrix(p["wq"], quant)).reshape(T, K, H // K, D)
    k = (h @ _matrix(p["wk"], quant)).reshape(T, K, D)
    v = (h @ _matrix(p["wv"], quant)).reshape(T, K, D)
    sc = jnp.einsum("tkgd,skd->kgts", q, k) * cfg["attention_multiplier"]
    pos = jnp.arange(T)
    sc = jnp.where(pos[None, :] <= pos[:, None], sc, -jnp.inf)
    ctx = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(sc, axis=-1), v)
    return ctx.reshape(T, H * D) @ _matrix(p["wo"], quant)


def _swiglu(p, h, quant):
    return (jax.nn.silu(h @ _matrix(p["w_gate"], quant))
            * (h @ _matrix(p["w_up"], quant))) @ _matrix(p["w_down"], quant)


def _moe(s, p, h, quant):
    """This chip's experts' part of the routed layer, plus the shared
    expert, over h (T, d)."""
    logits = h @ _matrix(p["router"], quant)              # (T, E_all)
    top, idx = jax.lax.top_k(logits, s["k"])
    gates = jax.nn.softmax(top, axis=-1)                  # (T, k)
    held = jax.nn.one_hot(idx, s["E"], dtype=F32)
    g = jnp.einsum("tk,tke->te", gates, held)             # (T, E)
    act = (jax.nn.silu(jnp.einsum("td,edf->tef", h,
                                  _matrix(p["w_gate"], quant)))
           * jnp.einsum("td,edf->tef", h, _matrix(p["w_up"], quant)))
    routed = jnp.einsum("tef,efd->td", act * g[:, :, None],
                        _matrix(p["w_down"], quant))
    return routed + _swiglu(p["shared"], h, quant)


def _logits(cfg, params, tokens, quant):
    """tokens (T,) -> logits (T, V), float32."""
    s = sizes(cfg)
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    x = params["embed"][tokens].astype(F32) * cfg["embedding_multiplier"]
    for kind, p in zip(s["kinds"], _layers(params, s["kinds"])):
        h = _rms(x, p["norm1"]["scale"], eps)
        if kind == "mamba":
            y = _mamba(cfg, s, p["mamba"], h, quant)
        else:
            y = _attention(cfg, s, p["attn"], h, quant)
        x = x + r * y
        x = x + r * _moe(s, p["moe"], _rms(x, p["norm2"]["scale"], eps),
                         quant)
    x = _rms(x, params["final_norm"]["scale"], eps)
    return (x @ _matrix(params["embed"].T, quant)) / cfg["logits_scaling"]


class Reference:
    """Gap readings for one configuration.  :meth:`gaps` runs one padded
    sequence and returns, at each position ``t`` whose ``target[t] >= 0``,
    how far the reference's logit of ``target[t]`` lies below its best;
    with ``control=True`` it also returns the same gap for the token the
    fp8 control puts first at ``t``.  Each sequence length compiles once."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self._jit = jax.jit(self._gaps, static_argnums=(3,))

    def logits(self, params, tokens, quant=None):
        """The reference's logits (T, V) of one sequence."""
        with jax.default_matmul_precision("highest"):
            return _logits(self.cfg, params, jnp.asarray(tokens, jnp.int32),
                           quant)

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
