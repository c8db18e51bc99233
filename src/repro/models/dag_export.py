"""Model -> Parallax DAG exporter.

Builds a ``repro.core.graph.Graph`` for any ModelConfig at a given
(batch, seq), with executable node fns closing over real parameters —
so the paper's pipeline (partition / branch / arena / schedule) and the
PlanExecutor latency benchmarks run against the *actual* architectures,
not toy graphs.

Granularity mirrors what a mobile-framework graph looks like after
conversion (the paper's "Pre" graphs): per-head attention chains,
per-expert MoE chains, elementwise/norm nodes, dynamic control-flow ops
(router top-k, dynamic gathers) marked unsupported -> CPU fallback.

Fallback/delegate mix: matmul/conv ops are delegate-eligible; routing
top-k, dynamic-shape ops and sampling are ``control_flow`` (unsupported),
exactly the operator classes that trigger fallbacks in §1 of the paper.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import GraphBuilder, TensorSpec, matmul_flops
from repro.core.flops import attention_flops, elementwise_flops
from .common import rms_norm


def _np(x):
    return np.asarray(x, np.float32)


def export_decoder_graph(cfg, params, batch: int, seq: int,
                         flops_cfg=None):
    """Decoder-only LM -> (graph, make_inputs).

    ``params`` must come from ``transformer.init_lm(key, cfg)`` on the
    same (typically reduced) config.  The graph covers embed -> blocks
    (attention heads / experts as parallel branches) -> final norm ->
    lm_head.

    ``flops_cfg``: when the graph is built from a width-shrunk
    ``structural()`` config, pass the FULL config here — node FLOP
    metadata (which drives the §3.1 delegation cost model and balance
    refinement) is then computed at full-model scale while the
    executable fns keep the small weights.  Topology (node/branch/layer
    counts) is width-invariant, so Table 7 statistics are exact.
    """
    from .blocks import block_pattern
    from .transformer import structure

    fc = flops_cfg or cfg
    pattern, prefix_len, period, n_rep = structure(cfg)
    b = GraphBuilder()
    d = cfg.d_model
    hd = cfg.resolved_head_dim()
    H, K = cfg.num_heads, cfg.num_kv_heads
    S, B = seq, batch
    f32 = "float32"

    tokens = b.input((B, S), "int32", name="tokens")
    embed_t = b.param((cfg.vocab_size, d), name="embed")

    def block_params(i):
        if i < prefix_len:
            return params["prefix"][i]
        j = (i - prefix_len) % period
        r = (i - prefix_len) // period
        return jax.tree.map(lambda a: a[r], params["period"][j])

    x = b.op("embed", "misc", [tokens, embed_t], [TensorSpec((B, S, d))],
             flops=0.0, fn=lambda t, e: e[t])

    positions = jnp.arange(S)[None, :]

    for i in range(cfg.num_layers):
        kind = pattern[i]
        bp = block_params(i)
        x = _export_block(b, cfg, bp, x, kind, i, B, S, positions, fc)

    fn_scale = params["final_norm"]["scale"]
    x = b.op("final_norm", "elementwise", [x], [TensorSpec((B, S, d))],
             flops=elementwise_flops(B * S * fc.d_model),
             fn=lambda h, s=fn_scale: rms_norm(s, h)
             if cfg.norm_type == "rmsnorm" else _layernorm(
                 params["final_norm"], h))
    head_flops = matmul_flops(S, fc.vocab_size, fc.d_model, B)
    if cfg.tie_embeddings:
        logits = b.op("lm_head", "matmul", [x, embed_t],
                      [TensorSpec((B, S, cfg.vocab_size))],
                      flops=head_flops,
                      fn=lambda h, e: jnp.einsum("bsd,vd->bsv", h, e))
    else:
        head_t = b.param((d, cfg.vocab_size), name="lm_head")
        logits = b.op("lm_head", "matmul", [x, head_t],
                      [TensorSpec((B, S, cfg.vocab_size))],
                      flops=head_flops,
                      fn=lambda h, w: jnp.einsum("bsd,dv->bsv", h, w))
    b.mark_output(logits)
    g = b.build()

    def make_inputs(rng):
        env = {tokens: rng.integers(0, cfg.vocab_size, (B, S)).astype(
            np.int32)}
        env[embed_t] = _np(params["embed"])
        if not cfg.tie_embeddings:
            env[head_t] = _np(params["lm_head"])
        return env

    return g, make_inputs


def _layernorm(p, h):
    from .common import layer_norm
    return layer_norm(p, h)


def _norm_node(b, cfg, np_, x, name, B, S):
    d = cfg.d_model
    if cfg.norm_type == "rmsnorm":
        sc = np_["scale"]
        fn = lambda h, s=sc: rms_norm(s, h)
    else:
        pp = np_
        fn = lambda h, p=pp: _layernorm(p, h)
    return b.op(name, "elementwise", [x], [TensorSpec((B, S, d))],
                flops=elementwise_flops(B * S * d), fn=fn)


def _export_block(b, cfg, bp, x, kind, layer_i, B, S, positions, fc=None):
    fc = fc or cfg
    mixer, channel = kind
    d = cfg.d_model
    dF = fc.d_model
    h_in = _norm_node(b, cfg, bp["norm1"], x, f"L{layer_i}.norm1", B, S)

    if mixer == "attn":
        y = _export_attention(b, cfg, bp["attn"], h_in, layer_i, B, S,
                              positions, fc)
    else:
        y = _export_mamba(b, cfg, bp["mamba"], h_in, layer_i, B, S, fc)

    x = b.op(f"L{layer_i}.residual1", "elementwise", [x, y],
             [TensorSpec((B, S, d))], flops=elementwise_flops(B * S * dF),
             fn=lambda a, c: a + c)

    if channel == "none":
        return x
    h2 = _norm_node(b, cfg, bp["norm2"], x, f"L{layer_i}.norm2", B, S)
    if channel == "dense":
        y2 = _export_mlp(b, cfg, bp["mlp"], h2, layer_i, B, S, fc)
    else:
        y2 = _export_moe(b, cfg, bp["moe"], h2, layer_i, B, S, fc)
    return b.op(f"L{layer_i}.residual2", "elementwise", [x, y2],
                [TensorSpec((B, S, d))],
                flops=elementwise_flops(B * S * dF), fn=lambda a, c: a + c)


def _export_attention(b, cfg, ap, h, layer_i, B, S, positions, fc=None):
    """Per-KV-group 4-node chains:

        qkv proj (matmul) -> RoPE (control_flow, CPU fallback) ->
        attention core (elementwise) -> out proj (matmul)

    A GQA group (one kv head + its query heads) is the natural branch
    unit — chains clear the paper's N > 2 floor and are β-balanced by
    construction.  RoPE's data-dependent position gather is the
    realistic per-layer *unsupported* op (dynamic-shape class, paper §1)
    that fragments delegate regions inside every attention layer."""
    from .common import apply_rope

    fc = fc or cfg
    d = cfg.d_model
    hd = cfg.resolved_head_dim()
    hdF = fc.resolved_head_dim()
    dF = fc.d_model
    H, K = cfg.num_heads, cfg.num_kv_heads
    G = H // K
    window = cfg.sliding_window
    outs = []
    wq = _np(ap["wq"]).reshape(d, H, hd)
    wk = _np(ap["wk"]).reshape(d, K, hd)
    wv = _np(ap["wv"]).reshape(d, K, hd)
    wo = _np(ap["wo"]).reshape(H, hd, d)
    for g in range(K):
        wq_g = jnp.asarray(wq[:, g * G:(g + 1) * G, :].reshape(d, G * hd))
        wk_g = jnp.asarray(wk[:, g, :])
        wv_g = jnp.asarray(wv[:, g, :])
        wo_g = jnp.asarray(wo[g * G:(g + 1) * G].reshape(G * hd, d))

        def qkv_fn(hh, wq_=wq_g, wk_=wk_g, wv_=wv_g):
            q = jnp.einsum("bsd,df->bsf", hh, wq_)
            k = jnp.einsum("bsd,df->bsf", hh, wk_)
            v = jnp.einsum("bsd,df->bsf", hh, wv_)
            return jnp.concatenate([q, k, v], axis=-1)

        qkv = b.op(f"L{layer_i}.g{g}.qkv", "matmul", [h],
                   [TensorSpec((B, S, (G + 2) * hd))],
                   flops=matmul_flops(S, (G + 2) * hdF, dF, B),
                   fn=qkv_fn)

        def rope_fn(qkv_, G_=G):
            q, k, v = jnp.split(qkv_, [G_ * hd, (G_ + 1) * hd], axis=-1)
            q = apply_rope(q.reshape(B, S, G_, hd), positions,
                           cfg.rope_theta).reshape(B, S, G_ * hd)
            k = apply_rope(k.reshape(B, S, 1, hd), positions,
                           cfg.rope_theta).reshape(B, S, hd)
            return jnp.concatenate([q, k, v], axis=-1)

        roped = b.op(f"L{layer_i}.g{g}.rope", "elementwise", [qkv],
                     [TensorSpec((B, S, (G + 2) * hd))],
                     flops=elementwise_flops(B * S * (G + 1) * hdF),
                     supported=False, fn=rope_fn)

        def attn_fn(qkv_, G_=G):
            q, k, v = jnp.split(qkv_, [G_ * hd, (G_ + 1) * hd], axis=-1)
            q = q.reshape(B, S, G_, hd)
            s = jnp.einsum("bsgd,btd->bgst", q, k) / np.sqrt(hd)
            qpos = jnp.arange(S)[:, None]
            kpos = jnp.arange(S)[None, :]
            mask = kpos <= qpos
            if window:
                mask &= kpos > qpos - window
            s = jnp.where(mask[None, None], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bgst,btd->bsgd", p, v).reshape(
                B, S, G_ * hd)

        core = b.op(f"L{layer_i}.g{g}.attn", "elementwise", [roped],
                    [TensorSpec((B, S, G * hd))],
                    flops=attention_flops(B, S, S, G, hdF),
                    fn=attn_fn)
        out = b.op(f"L{layer_i}.g{g}.out", "matmul", [core],
                   [TensorSpec((B, S, d))],
                   flops=matmul_flops(S, dF, G * hdF, B),
                   fn=lambda c, wo_=wo_g: jnp.einsum("bsf,fd->bsd", c,
                                                     wo_))
        outs.append(out)
    return b.op(f"L{layer_i}.head_merge", "elementwise", outs,
                [TensorSpec((B, S, cfg.d_model))],
                flops=elementwise_flops(B * S * dF * len(outs)),
                fn=lambda *hs: sum(hs))


def _export_mlp(b, cfg, mp, h, layer_i, B, S, fc=None):
    fc = fc or cfg
    d, ff = cfg.d_model, cfg.d_ff
    dF, ffF = fc.d_model, fc.d_ff
    if "w_gate" in mp:
        wg, wu, wd = (jnp.asarray(_np(mp[k]))
                      for k in ("w_gate", "w_up", "w_down"))
        gate = b.op(f"L{layer_i}.mlp.gate", "matmul", [h],
                    [TensorSpec((B, S, ff))],
                    flops=matmul_flops(S, ffF, dF, B),
                    fn=lambda x, w=wg: jax.nn.silu(
                        jnp.einsum("bsd,df->bsf", x, w)))
        up = b.op(f"L{layer_i}.mlp.up", "matmul", [h],
                  [TensorSpec((B, S, ff))],
                  flops=matmul_flops(S, ffF, dF, B),
                  fn=lambda x, w=wu: jnp.einsum("bsd,df->bsf", x, w))
        mul = b.op(f"L{layer_i}.mlp.mul", "elementwise", [gate, up],
                   [TensorSpec((B, S, ff))],
                   flops=elementwise_flops(B * S * ffF),
                   fn=lambda a, c: a * c)
        return b.op(f"L{layer_i}.mlp.down", "matmul", [mul],
                    [TensorSpec((B, S, d))],
                    flops=matmul_flops(S, dF, ffF, B),
                    fn=lambda x, w=wd: jnp.einsum("bsf,fd->bsd", x, w))
    wu, wd = jnp.asarray(_np(mp["w_up"])), jnp.asarray(_np(mp["w_down"]))
    bu, bd = jnp.asarray(_np(mp["b_up"])), jnp.asarray(_np(mp["b_down"]))
    up = b.op(f"L{layer_i}.mlp.up", "matmul", [h],
              [TensorSpec((B, S, ff))], flops=matmul_flops(S, ffF, dF, B),
              fn=lambda x, w=wu, bb=bu: jax.nn.gelu(
                  jnp.einsum("bsd,df->bsf", x, w) + bb))
    return b.op(f"L{layer_i}.mlp.down", "matmul", [up],
                [TensorSpec((B, S, d))], flops=matmul_flops(S, dF, ffF, B),
                fn=lambda x, w=wd, bb=bd: jnp.einsum("bsf,fd->bsd", x, w)
                + bb)


def _export_moe(b, cfg, mp, h, layer_i, B, S, fc=None):
    """Router (dynamic -> fallback) + per-expert 3-node branches.

    The router's top-k is a control_flow op (unsupported: data-dependent
    dispatch); each expert is a delegate-eligible chain — exactly the
    heterogeneous mix Parallax targets."""
    fc = fc or cfg
    m = cfg.moe
    d, ff = cfg.d_model, cfg.expert_width()
    dF, ffF = fc.d_model, fc.expert_width()
    E, k = m.num_experts, m.num_experts_per_tok
    router_w = jnp.asarray(_np(mp["router"]))

    gates = b.op(
        f"L{layer_i}.router", "control_flow", [h],
        [TensorSpec((B, S, E))], flops=matmul_flops(S, E, dF, B),
        supported=False,
        fn=lambda x, w=router_w: _topk_gates(x, w, k))

    # per-expert FLOPs at the *routed share* of tokens (k/E of them),
    # matching how a runtime graph sees expert workloads.  gate+up are one
    # fused node (attrs N=2 — converters fuse the SwiGLU pair) so each
    # expert stays a clean Sequential chain of original-op count 3.
    share = max(k / E, 1e-3)
    outs = []
    for e in range(E):
        wg = jnp.asarray(_np(mp["w_gate"][e]))
        wu = jnp.asarray(_np(mp["w_up"][e]))
        wd = jnp.asarray(_np(mp["w_down"][e]))
        g1 = b.op(f"L{layer_i}.e{e}.gateup", "matmul", [h],
                  [TensorSpec((B, S, ff))],
                  flops=2 * matmul_flops(S, ffF, dF, B) * share,
                  fn=lambda x, w=wg, w2=wu: jax.nn.silu(
                      jnp.einsum("bsd,df->bsf", x, w))
                  * jnp.einsum("bsd,df->bsf", x, w2),
                  N=2)
        dn = b.op(f"L{layer_i}.e{e}.down", "matmul", [g1],
                  [TensorSpec((B, S, d))],
                  flops=matmul_flops(S, dF, ffF, B) * share,
                  fn=lambda a, w=wd: jnp.einsum("bsf,fd->bsd", a, w))
        outs.append(dn)

    def combine(gates_, *expert_outs):
        y = jnp.zeros_like(expert_outs[0])
        for e, eo in enumerate(expert_outs):
            y = y + gates_[..., e:e + 1] * eo
        return y

    return b.op(f"L{layer_i}.moe_combine", "elementwise",
                [gates] + outs, [TensorSpec((B, S, d))],
                flops=elementwise_flops(B * S * dF * E), fn=combine)


def _topk_gates(x, w, k):
    logits = jnp.einsum("bsd,de->bse", x, w)
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, k)
    vals = vals / jnp.clip(vals.sum(-1, keepdims=True), 1e-9)
    gates = jnp.zeros_like(probs)
    bidx = jnp.arange(x.shape[0])[:, None, None]
    sidx = jnp.arange(x.shape[1])[None, :, None]
    return gates.at[bidx, sidx, idx].add(vals)


def _export_mamba(b, cfg, mp, h, layer_i, B, S, fc=None):
    """Mamba2 mixer as a 4-node sequential chain; the selective scan is a
    control_flow (dynamic recurrence) op -> CPU fallback, matching the
    paper's 'unsupported kernel' class."""
    from .ssm import _dims, _split_proj, _causal_conv, ssd_chunked

    fc = fc or cfg
    d = cfg.d_model
    s = cfg.ssm
    d_inner, nheads, conv_dim = _dims(cfg)
    d_innerF, nheadsF, conv_dimF = _dims(fc)
    proj_w = jnp.asarray(_np(mp["in_proj"]))
    conv_w = jnp.asarray(_np(mp["conv_w"]))
    conv_b = jnp.asarray(_np(mp["conv_b"]))
    out_w = jnp.asarray(_np(mp["out_proj"]))
    F = proj_w.shape[1]

    FF = 2 * d_innerF + 2 * fc.ssm.n_groups * fc.ssm.d_state + nheadsF
    zx = b.op(f"L{layer_i}.in_proj", "matmul", [h],
              [TensorSpec((B, S, F))],
              flops=matmul_flops(S, FF, fc.d_model, B),
              fn=lambda x, w=proj_w: jnp.einsum("bsd,df->bsf", x, w))
    cv = b.op(f"L{layer_i}.conv", "conv", [zx],
              [TensorSpec((B, S, F))],
              flops=B * S * conv_dimF * fc.ssm.conv_width * 2,
              fn=lambda zxbcdt: _conv_part(cfg, zxbcdt, conv_w, conv_b))

    def scan_fn(zx_conv, mp_=mp):
        z, xBC, dt = _split_proj(cfg, zx_conv)
        gN = s.n_groups * s.d_state
        xs, Bm, Cm = jnp.split(xBC, [d_inner, d_inner + gN], axis=-1)
        xs = xs.reshape(B, S, nheads, s.head_dim)
        Bm = Bm.reshape(B, S, s.n_groups, s.d_state)
        Cm = Cm.reshape(B, S, s.n_groups, s.d_state)
        dtv = jax.nn.softplus(dt + jnp.asarray(_np(mp_["dt_bias"])))
        A = -jnp.exp(jnp.asarray(_np(mp_["A_log"])))
        chunk = s.chunk if S % s.chunk == 0 else S
        y, _ = ssd_chunked(xs, dtv, A, Bm, Cm, chunk=chunk)
        y = y + jnp.asarray(_np(mp_["D"]))[None, None, :, None] * xs
        y = y.reshape(B, S, d_inner)
        return rms_norm(jnp.asarray(_np(mp_["norm_scale"])),
                        y * jax.nn.silu(z))

    from repro.core.flops import ssd_scan_flops
    sc = b.op(f"L{layer_i}.ssd_scan", "elementwise", [cv],
              [TensorSpec((B, S, d_inner))],
              flops=ssd_scan_flops(B, S, nheadsF, fc.ssm.head_dim,
                                   fc.ssm.d_state),
              supported=False, fn=scan_fn)
    return b.op(f"L{layer_i}.out_proj", "matmul", [sc],
                [TensorSpec((B, S, d))],
                flops=matmul_flops(S, fc.d_model, d_innerF, B),
                fn=lambda y, w=out_w: jnp.einsum("bsf,fd->bsd", y, w))


def _conv_part(cfg, zxbcdt, conv_w, conv_b):
    from .ssm import _split_proj, _causal_conv
    z, xBC, dt = _split_proj(cfg, zxbcdt)
    xBC = _causal_conv(xBC, conv_w, conv_b)
    return jnp.concatenate([z, xBC, dt], axis=-1)


def export_graph(cfg, params, batch: int, seq: int, flops_cfg=None):
    """Dispatch by family.  Encoder-decoder exports the encoder side
    (the paper's Whisper evaluation profiles encoder layers)."""
    if cfg.is_encoder_decoder:
        return export_encoder_graph(cfg, params, batch, seq, flops_cfg)
    return export_decoder_graph(cfg, params, batch, seq, flops_cfg)


def export_encoder_graph(cfg, params, batch: int, seq: int,
                         flops_cfg=None):
    """Whisper encoder -> DAG (per-head branches, layernorm, GELU MLP)."""
    from .common import sinusoidal_positions

    fc = flops_cfg or cfg
    b = GraphBuilder()
    d = cfg.d_model
    dF = fc.d_model
    B, S = batch, seq
    frames = b.input((B, S, d), name="frames")
    pos = sinusoidal_positions(S, d)

    x = b.op("pos_embed", "elementwise", [frames],
             [TensorSpec((B, S, d))], flops=elementwise_flops(B * S * dF),
             fn=lambda f: f + pos[None])
    positions = jnp.arange(S)[None, :]
    for i in range(cfg.encoder_layers):
        bp = jax.tree.map(lambda a: a[i], params["encoder"])
        h = _norm_node(b, cfg, bp["norm1"], x, f"E{i}.norm1", B, S)
        y = _export_attention(b, cfg, bp["attn"], h, f"E{i}", B, S,
                              positions, fc)
        x = b.op(f"E{i}.res1", "elementwise", [x, y],
                 [TensorSpec((B, S, d))],
                 flops=elementwise_flops(B * S * dF),
                 fn=lambda a, c: a + c)
        h2 = _norm_node(b, cfg, bp["norm2"], x, f"E{i}.norm2", B, S)
        y2 = _export_mlp(b, cfg, bp["mlp"], h2, f"E{i}", B, S, fc)
        x = b.op(f"E{i}.res2", "elementwise", [x, y2],
                 [TensorSpec((B, S, d))],
                 flops=elementwise_flops(B * S * dF),
                 fn=lambda a, c: a + c)
    x = _norm_node(b, cfg, params["enc_final"], x, "enc_final", B, S)
    b.mark_output(x)
    g = b.build()

    def make_inputs(rng):
        return {frames: rng.standard_normal((B, S, d)).astype(np.float32)
                * 0.1}

    return g, make_inputs
