"""Hand-built graphs used by core unit tests and benchmarks.

Each builder returns ``(graph, make_inputs)`` where ``make_inputs(rng)``
produces a tensor-id -> array environment covering graph inputs + params.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import GraphBuilder, TensorSpec, matmul_flops


def assert_matches_oracle(ref, got, gemm_sites: int):
    """Bit for bit, except where a group ran through the grouped
    ``branch_matmul`` GEMM (``gemm_sites`` > 0): its f32 accumulator adds
    the K-blocks up in another order than ``jnp.dot``, which moves
    results by about 5e-7 absolute and 5e-5 relative."""
    if gemm_sites:
        np.testing.assert_allclose(ref, got, rtol=2e-5, atol=2e-6)
    else:
        np.testing.assert_array_equal(ref, got)


def _mm_spec(m, n):
    return TensorSpec((m, n), "float32")


def chain_graph(depth=5, dim=8):
    """input -> matmul x depth -> output: one branch, no parallelism."""
    b = GraphBuilder()
    x = b.input((dim, dim), name="x")
    ws = []
    cur = x
    for i in range(depth):
        w = b.param((dim, dim), name=f"w{i}")
        ws.append(w)
        cur = b.op(f"mm{i}", "matmul", [cur, w], [_mm_spec(dim, dim)],
                   flops=matmul_flops(dim, dim, dim),
                   fn=lambda a, w: jnp.dot(a, w))
    b.mark_output(cur)
    g = b.build()

    def make_inputs(rng):
        env = {x: rng.standard_normal((dim, dim), dtype=np.float32)}
        for w in ws:
            env[w] = rng.standard_normal((dim, dim), dtype=np.float32)
        return env

    return g, make_inputs


def diamond_graph(dim=8, branch_len=3, width=2):
    """splitter -> `width` parallel chains of len `branch_len` -> merger."""
    b = GraphBuilder()
    x = b.input((dim, dim), name="x")
    params = []
    split = b.op("split", "elementwise", [x], [_mm_spec(dim, dim)],
                 flops=dim * dim, fn=lambda a: a * 2.0)
    tails = []
    for w_i in range(width):
        cur = split
        for d in range(branch_len):
            w = b.param((dim, dim), name=f"w{w_i}_{d}")
            params.append(w)
            cur = b.op(f"br{w_i}_mm{d}", "matmul", [cur, w],
                       [_mm_spec(dim, dim)],
                       flops=matmul_flops(dim, dim, dim),
                       fn=lambda a, w: jnp.tanh(jnp.dot(a, w)))
        tails.append(cur)
    merged = b.op("merge", "elementwise", tails, [_mm_spec(dim, dim)],
                  flops=dim * dim * width,
                  fn=lambda *ts: sum(ts))
    b.mark_output(merged)
    g = b.build()

    def make_inputs(rng):
        env = {x: rng.standard_normal((dim, dim), dtype=np.float32)}
        for p in params:
            env[p] = (rng.standard_normal((dim, dim), dtype=np.float32)
                      * 0.3)
        return env

    return g, make_inputs


def heterogeneous_graph(dim=16):
    """Mixed supported/unsupported ops: two big matmul regions separated by
    a control-flow (fallback) op, plus a small misc tail — exercises the
    delegate cost model and fallback handling."""
    b = GraphBuilder()
    x = b.input((dim, dim), name="x")
    params = []

    def mm_chain(cur, count, tag):
        for i in range(count):
            w = b.param((dim, dim), name=f"{tag}_w{i}")
            params.append(w)
            cur = b.op(f"{tag}_mm{i}", "matmul", [cur, w],
                       [_mm_spec(dim, dim)],
                       flops=2e9,  # force F over the delegation floor
                       fn=lambda a, w: jnp.dot(a, w) * 0.1)
        return cur

    r1 = mm_chain(x, 4, "regA")
    # dynamic control-flow op: unsupported -> CPU fallback
    cf = b.op("dyn_if", "control_flow", [r1], [_mm_spec(dim, dim)],
              flops=0.0, supported=False,
              fn=lambda a: jnp.where(a.sum() > 0, a, -a))
    r2 = mm_chain(cf, 4, "regB")
    # second fallback then a *small* supported region: rejected by the cost
    # model (N=2 < 3, F << 1e9) -> stays on CPU ("trims small segments")
    cf2 = b.op("dyn_while", "control_flow", [r2], [_mm_spec(dim, dim)],
               flops=0.0, supported=False,
               fn=lambda a: jnp.where(a.mean() > 0, a, a * 0.5))
    wsmall = b.param((dim, dim), name="w_small")
    params.append(wsmall)
    tiny = b.op("tiny_mm", "matmul", [cf2, wsmall], [_mm_spec(dim, dim)],
                flops=matmul_flops(dim, dim, dim),
                fn=lambda a, w: jnp.dot(a, w))
    small = b.op("reshape", "misc", [tiny], [TensorSpec((dim * dim,),
                                                        "float32")],
                 flops=0.0, fn=lambda a: a.reshape(-1))
    b.mark_output(small)
    g = b.build()

    def make_inputs(rng):
        env = {x: rng.standard_normal((dim, dim), dtype=np.float32)}
        for p in params:
            env[p] = rng.standard_normal((dim, dim), dtype=np.float32) * 0.2
        return env

    return g, make_inputs


def multihead_graph(dim=16, heads=4, seq=8):
    """Transformer-attention shaped: shared input -> per-head chains
    (qkv proj -> attention core -> per-head out proj) -> residual merge.
    The canonical source of branch parallelism Parallax exploits; each
    head branch has N=3 nodes so it clears the paper's N>2 floor."""
    b = GraphBuilder()
    x = b.input((seq, dim), name="x")
    params = []
    head_dim = dim // heads
    outs = []

    def attn_core(qkv):
        q, k, v = jnp.split(qkv, 3, axis=-1)
        s = jnp.dot(q, k.T) / np.sqrt(head_dim)
        p = jnp.exp(s - s.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        return jnp.dot(p, v)

    for h in range(heads):
        w_qkv = b.param((dim, 3 * head_dim), name=f"wqkv{h}")
        w_o = b.param((head_dim, dim), name=f"wo{h}")
        params += [w_qkv, w_o]
        qkv = b.op(f"h{h}_qkv", "matmul", [x, w_qkv],
                   [TensorSpec((seq, 3 * head_dim))],
                   flops=matmul_flops(seq, 3 * head_dim, dim),
                   fn=lambda a, w: jnp.dot(a, w))
        core = b.op(f"h{h}_attn", "elementwise", [qkv],
                    [TensorSpec((seq, head_dim))],
                    flops=2 * matmul_flops(seq, seq, head_dim),
                    fn=attn_core)
        o = b.op(f"h{h}_proj", "matmul", [core, w_o],
                 [TensorSpec((seq, dim))],
                 flops=matmul_flops(seq, dim, head_dim),
                 fn=lambda a, w: jnp.dot(a, w))
        outs.append(o)
    y = b.op("head_merge", "elementwise", outs, [TensorSpec((seq, dim))],
             flops=seq * dim * heads, fn=lambda *hs: sum(hs))
    b.mark_output(y)
    g = b.build()

    def make_inputs(rng):
        env = {x: rng.standard_normal((seq, dim), dtype=np.float32)}
        for p in params:
            env[p] = rng.standard_normal(
                tuple(g.tensors[p].spec.static_shape),
                dtype=np.float32) * 0.3
        return env

    return g, make_inputs


def cond_graph(dim=8, branch_len=3, width=2, tail_len=3):
    """Parallel matmul branches feeding a ``lax.cond``-gated fallback.

    The control-flow node picks its executed branch at runtime (§3.4:
    forced Split-Merge, unsupported -> host fallback), then a supported
    matmul tail resumes — an accel -> host -> accel round trip for the
    heterogeneous runtime."""
    b = GraphBuilder()
    x = b.input((dim, dim), name="x")
    params = []
    split = b.op("split", "elementwise", [x], [_mm_spec(dim, dim)],
                 flops=dim * dim, fn=lambda a: a * 0.5 + 0.1)
    tails = []
    for w_i in range(width):
        cur = split
        for d in range(branch_len):
            w = b.param((dim, dim), name=f"cw{w_i}_{d}")
            params.append(w)
            cur = b.op(f"c{w_i}_mm{d}", "matmul", [cur, w],
                       [_mm_spec(dim, dim)],
                       flops=matmul_flops(dim, dim, dim),
                       fn=lambda a, w: jnp.tanh(jnp.dot(a, w)))
        tails.append(cur)
    merged = b.op("merge", "elementwise", tails, [_mm_spec(dim, dim)],
                  flops=dim * dim * width, fn=lambda *ts: sum(ts))
    gate = b.op("cond_gate", "control_flow", [merged], [_mm_spec(dim, dim)],
                flops=0.0, supported=False,
                fn=lambda a: jax.lax.cond(a.sum() > 0,
                                          lambda t: t * 1.5 + 1.0,
                                          lambda t: -t * 0.5, a))
    cur = gate
    for d in range(tail_len):
        w = b.param((dim, dim), name=f"ct_{d}")
        params.append(w)
        cur = b.op(f"tail_mm{d}", "matmul", [cur, w], [_mm_spec(dim, dim)],
                   flops=matmul_flops(dim, dim, dim),
                   fn=lambda a, w: jnp.dot(a, w) * 0.1)
    b.mark_output(cur)
    g = b.build()

    def make_inputs(rng):
        env = {x: rng.standard_normal((dim, dim), dtype=np.float32)}
        for p in params:
            env[p] = rng.standard_normal((dim, dim), dtype=np.float32) * 0.3
        return env

    return g, make_inputs


def while_graph(dim=8, depth=3, max_iters=6):
    """Matmul chain -> bounded ``lax.while_loop`` fallback -> matmul chain.

    The loop's trip count is data-dependent but bounded by ``max_iters``
    (§3.2 dynamic-shape discipline applied to control flow): classified
    Split-Merge, executed as a host-side dynamic region."""
    b = GraphBuilder()
    x = b.input((dim, dim), name="x")
    params = []

    def mm_chain(cur, tag):
        for i in range(depth):
            w = b.param((dim, dim), name=f"{tag}_w{i}")
            params.append(w)
            cur = b.op(f"{tag}_mm{i}", "matmul", [cur, w],
                       [_mm_spec(dim, dim)],
                       flops=matmul_flops(dim, dim, dim),
                       fn=lambda a, w: jnp.dot(a, w) * 0.2)
        return cur

    head = mm_chain(x, "pre")

    def bounded_while(a, _n=max_iters):
        def cond(s):
            return (s[0] < _n) & (jnp.abs(s[1]).sum() > 1e-3)

        def body(s):
            return (s[0] + 1, s[1] * 0.5 + 0.01)

        return jax.lax.while_loop(cond, body, (0, a))[1]

    loop = b.op("bounded_while", "control_flow", [head],
                [_mm_spec(dim, dim)], flops=0.0, supported=False,
                fn=bounded_while)
    tail = mm_chain(loop, "post")
    b.mark_output(tail)
    g = b.build()

    def make_inputs(rng):
        env = {x: rng.standard_normal((dim, dim), dtype=np.float32)}
        for p in params:
            env[p] = rng.standard_normal((dim, dim), dtype=np.float32) * 0.4
        return env

    return g, make_inputs


ALL_ZOO = {
    "chain": chain_graph,
    "cond": cond_graph,
    "diamond": diamond_graph,
    "heterogeneous": heterogeneous_graph,
    "multihead": multihead_graph,
    "while": while_graph,
}
