"""Mixture-of-Experts: routing, dropless ragged compute, expert parallelism.

Three execution paths share one parameter layout:

* ``moe_dense`` — every held expert processes every token, gate-weighted
  combine.  O(E) FLOPs: for tiny smoke configs (E <= 4) and for a chip
  that holds no more experts than a token is routed to.
* ``moe_ragged`` — single-shard *dropless* compute: token copies sorted by
  expert id, grouped GEMM via ``jax.lax.ragged_dot``.  This is the direct
  Parallax realization: the E experts are the balanced parallel branches
  (§3.1) and the grouped GEMM is the branch-batched kernel (DESIGN.md §2);
  ``repro.kernels.branch_matmul`` is the Pallas version of this contraction.
* ``moe_ep`` — explicit expert parallelism under ``shard_map``: experts
  sharded over the ``model`` mesh axis, capacity-based dispatch with
  ``all_to_all`` exchange (drop-on-overflow, standard Switch semantics).

Parameters:
    router: (d, E)
    w_gate / w_up: (E, d, f)    w_down: (E, f, d)
    shared expert (optional): plain SwiGLU MLP always active (Kimi K2).

A config may hold a share of the routed experts (the first ``MoEConfig.
num_local_experts``): the router still scores all of them, the expert weights are the held ones' only, and the
single-shard paths compute the share's part of the layer, with no
exchange and no stand-in for the rest.  The parts run under the
``router``, ``experts`` and ``shared_expert`` named scopes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import dense_init
from .mlp import init_mlp, mlp


def init_moe(key, cfg):
    """The router scores every expert; the expert weights are the held
    ones' only (``cfg.held_experts()``, all unless the config holds a
    share)."""
    m = cfg.moe
    d, f, E = cfg.d_model, cfg.expert_width(), cfg.held_experts()
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, m.num_experts)),
        "w_gate": dense_init(ks[1], (E, d, f), in_axis=-2),
        "w_up": dense_init(ks[2], (E, d, f), in_axis=-2),
        "w_down": dense_init(ks[3], (E, f, d), in_axis=-2),
    }
    if m.num_shared_experts:
        p["shared"] = init_mlp(ks[4], d, cfg.shared_expert_width(), "silu")
    return p


def route(params, cfg, x):
    """Top-k routing.  x: (T, d) -> (weights (T,k), idx (T,k), aux_loss)."""
    m = cfg.moe
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, m.num_experts_per_tok)
    w = w / jnp.clip(w.sum(-1, keepdims=True), 1e-9)     # renormalize
    # Switch-style load-balance auxiliary loss: E * Σ_e f_e · p̄_e
    E = m.num_experts
    f_e = jnp.mean(jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1), axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f_e * p_e) * m.aux_loss_weight
    return w.astype(x.dtype), idx, aux


def _expert_ffn_ragged(params, xs, group_sizes, dtype):
    """Grouped SwiGLU over expert-contiguous rows (dropless grouped GEMM)."""
    g = jax.lax.ragged_dot(xs, params["w_gate"].astype(dtype), group_sizes)
    u = jax.lax.ragged_dot(xs, params["w_up"].astype(dtype), group_sizes)
    h = jax.nn.silu(g) * u
    return jax.lax.ragged_dot(h, params["w_down"].astype(dtype), group_sizes)


def _shared_expert(params, x, y):
    if "shared" not in params:
        return y
    with jax.named_scope("shared_expert"):
        return y + mlp(params["shared"], x, "silu")


def moe_ragged(params, cfg, x):
    """Dropless single-shard MoE.  x: (T, d) -> (y (T, d), aux).

    Where the config holds a share of the experts, the assignments to
    absent experts sort last, past every held group, and give nothing:
    ``y`` is this share's part of the layer (plus the shared expert)."""
    m = cfg.moe
    T, d = x.shape
    k = m.num_experts_per_tok
    E = cfg.held_experts()
    with jax.named_scope("router"):
        w, idx, aux = route(params, cfg, x)

    share = E < m.num_experts
    with jax.named_scope("experts"):
        flat_e = idx.reshape(-1)                          # (T*k,)
        if share:            # absent experts' choices: group E, last
            flat_e = jnp.minimum(flat_e, E)
        order = jnp.argsort(flat_e)
        token_of = order // k                             # source token
        xs = x[token_of]                                  # (T*k, d) sorted
        group_sizes = (jnp.bincount(flat_e, length=E + 1)[:E] if share
                       else jnp.bincount(flat_e, length=E))
        ys = _expert_ffn_ragged(params, xs, group_sizes, x.dtype)
        # un-sort and gate-weighted combine
        contrib = ys * w.reshape(-1)[order][:, None]
        if share:            # ragged_dot leaves rows past the groups open
            contrib = jnp.where((flat_e[order] < E)[:, None], contrib, 0)
        y = jnp.zeros_like(x).at[token_of].add(contrib)
    return _shared_expert(params, x, y), aux


def moe_dense(params, cfg, x):
    """Every held expert on every token, gate-weighted combine (the
    smoke-test oracle, and the served path where a chip holds no more
    experts than a token is routed to).  x: (T, d).  Absent experts of
    a share get no gate."""
    m = cfg.moe
    E = cfg.held_experts()
    with jax.named_scope("router"):
        w, idx, aux = route(params, cfg, x)
    dt = x.dtype
    with jax.named_scope("experts"):
        g = jnp.einsum("td,edf->tef", x, params["w_gate"].astype(dt))
        u = jnp.einsum("td,edf->tef", x, params["w_up"].astype(dt))
        h = jax.nn.silu(g) * u
        ys = jnp.einsum("tef,efd->ted", h, params["w_down"].astype(dt))
        if E < m.num_experts:
            hot = jax.nn.one_hot(idx, E, dtype=dt)
            gates = jnp.einsum("tk,tke->te", w, hot)
        else:
            gates = jnp.zeros((x.shape[0], E), dt)
            gates = gates.at[jnp.arange(x.shape[0])[:, None], idx].add(w)
        y = jnp.einsum("ted,te->td", ys, gates)
    return _shared_expert(params, x, y), aux


# --------------------------------------------------------------------------
# Expert parallelism (shard_map over the `model` axis)
# --------------------------------------------------------------------------

def moe_ep(params, cfg, x, mesh, axis: str = "model"):
    """Expert-parallel MoE dispatcher.  x: (T, d) global tokens.

    Two regimes (both shard experts over ``axis``):

    * **a2a** (train/prefill, many tokens): tokens are split over every
      mesh axis and travel to their expert's shard via capacity-based
      ``all_to_all`` — Switch-style, minimal redundant compute.
    * **replicated** (decode, few tokens): tokens are replicated over the
      expert axis; each shard computes only its local experts' share and
      the outputs ``psum`` over ``axis`` — no dispatch buffers, dropless,
      and communication is one (T, d) psum, which for T=O(batch) is far
      cheaper than a2a buffers.

    The regime is chosen by token divisibility, mirroring how serving
    systems switch dispatch strategy between prefill and decode.
    """
    n_shards = mesh.shape[axis]
    data_axes = tuple(a for a in mesh.axis_names if a != axis)
    n_data = 1
    for a in data_axes:
        n_data *= mesh.shape[a]
    T = x.shape[0]
    if T % (n_shards * n_data) == 0 and T // (n_shards * n_data) >= 8:
        return _moe_ep_a2a(params, cfg, x, mesh, axis)
    return _moe_ep_replicated(params, cfg, x, mesh, axis)


def _moe_ep_replicated(params, cfg, x, mesh, axis: str = "model"):
    """Decode-regime EP with 2-D expert sharding (§Perf O2').

    Tokens (a decode step has only O(batch) of them) are replicated over
    the whole mesh; expert weights stay fully sharded at rest — expert
    dim over ``axis`` ('model'), FFN hidden dim over the data axes — so
    NO weight ever moves.  Every device computes its experts' share of
    its FFN slice; partial outputs psum over both axes: the only
    communication is two (T, d)-sized reductions per layer.  Dropless.
    """
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    n_shards = mesh.shape[axis]
    E_local = m.num_experts // n_shards
    assert E_local * n_shards == m.num_experts
    data_axes = tuple(a for a in mesh.axis_names if a != axis)
    n_data = 1
    for a in data_axes:
        n_data *= mesh.shape[a]
    f_sharded = data_axes and cfg.expert_width() % n_data == 0

    def local_moe(router, w_gate, w_up, w_down, x_loc):
        # w_gate/w_up: (E_local, d, f_loc); w_down: (E_local, f_loc, d)
        T_loc, d = x_loc.shape
        k = m.num_experts_per_tok
        w, idx, aux = route({"router": router}, cfg, x_loc)
        shard = jax.lax.axis_index(axis)
        flat_e = idx.reshape(-1)
        gates = w.reshape(-1)
        tok = jnp.arange(T_loc * k) // k
        mine = (flat_e // E_local) == shard
        e_loc = jnp.where(mine, flat_e % E_local, E_local)  # overflow grp
        order = jnp.argsort(e_loc)
        keep_sorted = mine[order]
        xs = jnp.where(keep_sorted[:, None], x_loc[tok[order]], 0)
        gs = jnp.bincount(e_loc, length=E_local + 1)[:E_local]
        ep = {"w_gate": w_gate, "w_up": w_up, "w_down": w_down}
        ys_sorted = _expert_ffn_ragged(ep, xs, gs, x_loc.dtype)
        ys_sorted = jnp.where(keep_sorted[:, None], ys_sorted, 0)
        ys = jnp.zeros_like(ys_sorted).at[order].set(ys_sorted)
        y = jnp.zeros_like(x_loc).at[tok].add(
            ys * gates[:, None].astype(x_loc.dtype))
        # partial over f (data axes) + masked over experts (model axis)
        y = jax.lax.psum(y, axis)
        if f_sharded:
            y = jax.lax.psum(y, data_axes)
        return y, aux

    f_entry = (data_axes if len(data_axes) > 1 else data_axes[0]) \
        if f_sharded else None
    y, aux = jax.shard_map(
        local_moe, mesh=mesh,
        in_specs=(P(None, None), P(axis, None, f_entry),
                  P(axis, None, f_entry), P(axis, f_entry, None),
                  P(None, None)),
        out_specs=(P(None, None), P()),
        check_vma=False,
    )(params["router"], params["w_gate"], params["w_up"],
      params["w_down"], x)
    if "shared" in params:
        y = y + mlp(params["shared"], x, "silu")
    return y, aux


def _moe_ep_a2a(params, cfg, x, mesh, axis: str = "model"):
    """Train/prefill-regime EP: capacity-based all_to_all dispatch.

    Must be called *inside* jit with ``mesh`` the active mesh.  Experts are
    sharded over ``axis``; tokens travel via capacity-based all_to_all.
    Dropped tokens (over capacity) contribute zero — Switch semantics.
    Returns (y, aux) with y sharded like x.
    """
    shard_map = jax.shard_map

    m = cfg.moe
    n_shards = mesh.shape[axis]
    E_local = m.num_experts // n_shards
    assert E_local * n_shards == m.num_experts, \
        f"{m.num_experts} experts not divisible by {axis}={n_shards}"

    data_axes = tuple(a for a in mesh.axis_names if a != axis)

    def local_moe(router, w_gate, w_up, w_down, x_loc):
        # x_loc: (T_loc, d) — this shard's tokens (replicated over `axis`
        # would double-count; instead tokens are *split* over `axis` too).
        T_loc, d = x_loc.shape
        k = m.num_experts_per_tok
        lp = {"router": router}
        w, idx, aux = route(lp, cfg, x_loc)               # (T_loc, k)
        kcap = int(max(1, T_loc * k * m.capacity_factor // n_shards))

        # --- build per-destination-shard send buffers ---------------------
        flat_e = idx.reshape(-1)                          # (T_loc*k,)
        dest = flat_e // E_local                          # shard owning e
        e_loc = flat_e % E_local
        gates = w.reshape(-1)
        tok = jnp.arange(T_loc * k) // k

        # position of each assignment within its destination's buffer
        onehot = jax.nn.one_hot(dest, n_shards, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) * onehot         # 1-based
        pos_in_dest = pos.sum(-1) - 1                     # (T_loc*k,)
        keep = pos_in_dest < kcap

        send_x = jnp.zeros((n_shards, kcap, d), x_loc.dtype)
        send_meta = jnp.full((n_shards, kcap, 2), -1.0, jnp.float32)
        di = jnp.where(keep, dest, 0)
        pi = jnp.where(keep, pos_in_dest, 0)
        send_x = send_x.at[di, pi].add(
            jnp.where(keep[:, None], x_loc[tok], 0))
        send_meta = send_meta.at[di, pi].set(
            jnp.where(keep[:, None],
                      jnp.stack([e_loc.astype(jnp.float32),
                                 gates.astype(jnp.float32)], -1),
                      -1.0))

        # --- exchange: shard i sends row j to shard j ----------------------
        recv_x = jax.lax.all_to_all(send_x, axis, 0, 0, tiled=False)
        recv_meta = jax.lax.all_to_all(send_meta, axis, 0, 0, tiled=False)
        rx = recv_x.reshape(n_shards * kcap, d)
        re = recv_meta.reshape(-1, 2)[:, 0].astype(jnp.int32)
        valid = re >= 0
        re = jnp.where(valid, re, E_local)                # overflow bucket

        # --- local grouped expert FFN (sorted + ragged_dot) ----------------
        order = jnp.argsort(re)
        xs = rx[order]
        gs = jnp.bincount(re, length=E_local + 1)[:E_local]
        ep = {"w_gate": w_gate, "w_up": w_up, "w_down": w_down}
        ys_sorted = _expert_ffn_ragged(ep, xs, gs, x_loc.dtype)
        ys = jnp.zeros_like(ys_sorted).at[order].set(ys_sorted)
        ys = jnp.where(valid[:, None], ys, 0)
        ys = ys.reshape(n_shards, kcap, d)

        # --- return to source shards and combine ---------------------------
        back = jax.lax.all_to_all(ys, axis, 0, 0, tiled=False)
        y = jnp.zeros_like(x_loc)
        contrib = back[di, pi] * gates[:, None].astype(x_loc.dtype)
        contrib = jnp.where(keep[:, None], contrib, 0)
        y = y.at[tok].add(contrib)
        aux = jax.lax.pmean(aux, data_axes) if data_axes else aux
        aux = jax.lax.pmean(aux, axis)
        return y, aux

    tok_spec = P((*data_axes, axis))                      # tokens split all axes
    out = shard_map(
        local_moe, mesh=mesh,
        in_specs=(P(None, None), P(axis, None, None), P(axis, None, None),
                  P(axis, None, None), tok_spec),
        out_specs=(tok_spec, P()),
        check_vma=False,
    )(params["router"], params["w_gate"], params["w_up"],
      params["w_down"], x)
    y, aux = out
    if "shared" in params:
        y = y + mlp(params["shared"], x, "silu")
    return y, aux
