"""Tests for the schedule compiler (core/compile.py) and the fused
execution paths of PlanExecutor."""

import numpy as np
import pytest

from repro.core import (ParallaxConfig, PlanExecutor, clear_compile_cache,
                        compile_plan, compile_schedule, gemm_positions,
                        plan_signature)
from graph_zoo import (ALL_ZOO, assert_matches_oracle, diamond_graph,
                       multihead_graph)

CFG = ParallaxConfig(budget=1 << 30)


def _ref(graph, env):
    return np.asarray(graph.execute(dict(env))[graph.outputs[0]])


# -- numerics: fused executions vs. the oracle, bit-for-bit ------------------

@pytest.mark.parametrize("name", sorted(ALL_ZOO))
@pytest.mark.parametrize("whole_plan", [False, True],
                         ids=["per-layer", "whole-plan"])
def test_fused_matches_oracle_bit_for_bit(name, whole_plan):
    g, make = ALL_ZOO[name]()
    env = make(np.random.default_rng(42))
    ref = _ref(g, env)
    plan = compile_plan(g, CFG)
    ex = PlanExecutor(plan, mode="parallax", whole_plan=whole_plan)
    got = np.asarray(ex(env).outputs[plan.graph.outputs[0]])
    assert_matches_oracle(ref, got, ex.compiled.stats.gemm_sites)


def test_fused_matches_oracle_without_branch_kernel():
    g, make = ALL_ZOO["multihead"]()
    env = make(np.random.default_rng(1))
    plan = compile_plan(g, CFG)
    ex = PlanExecutor(plan, mode="parallax", use_branch_kernel=False)
    got = np.asarray(ex(env).outputs[plan.graph.outputs[0]])
    np.testing.assert_array_equal(_ref(g, env), got)


# -- homogeneous-group batching ---------------------------------------------

def test_multihead_routes_through_branch_matmul():
    """The head branches of the multihead zoo graph are a balanced group of
    pure-dot chains: qkv and out-proj positions must lower to the grouped
    branch_matmul GEMM."""
    g, make = multihead_graph()
    plan = compile_plan(g, CFG)
    compiled = compile_schedule(plan)
    assert compiled.use_branch_kernel
    assert compiled.stats.batched_groups >= 1
    assert compiled.stats.gemm_sites >= 2
    # and the batched execution still matches the oracle
    env = make(np.random.default_rng(5))
    ex = PlanExecutor(plan, mode="parallax")
    got = np.asarray(ex(env).outputs[g.outputs[0]])
    np.testing.assert_allclose(_ref(g, env), got, rtol=2e-5, atol=2e-6)


def test_epilogue_matmuls_are_not_batched():
    """diamond branches compute tanh(dot) — op_class 'matmul' but NOT a pure
    dot, so jaxpr-based purity detection must reject them."""
    g, _ = diamond_graph()
    plan = compile_plan(g, CFG)
    assert compile_schedule(plan).stats.batched_groups == 0
    for sl in plan.schedule.layers:
        for group in sl.parallel_groups:
            assert gemm_positions(plan, group) == ()


# -- compile cache -----------------------------------------------------------

def test_compile_cache_shares_callables_across_executors():
    g, _ = ALL_ZOO["diamond"]()
    plan = compile_plan(g, CFG)
    ex1 = PlanExecutor(plan, mode="parallax")
    ex2 = PlanExecutor(plan, mode="parallax")
    assert ex1.compiled is ex2.compiled
    # a fresh plan over the same graph has the same signature -> same artifact
    plan2 = compile_plan(g, CFG)
    assert plan_signature(plan2) == plan_signature(plan)
    assert compile_schedule(plan2) is ex1.compiled
    # different lowering options are distinct cache entries
    assert compile_schedule(plan, whole_plan=True) is not ex1.compiled


def test_cache_never_shared_across_graph_objects():
    """Two structurally identical graphs whose fns close over *different*
    weights have equal signatures (fingerprints reduce arrays to metadata)
    — the per-graph cache scope must still keep their compiled callables
    apart, or one graph's weights get baked into the other's results."""
    import jax.numpy as jnp
    from repro.core import GraphBuilder, TensorSpec

    def build(weight):
        w = jnp.full((4, 4), weight, jnp.float32)
        b = GraphBuilder()
        x = b.input((4, 4), name="x")
        y = b.op("mm", "matmul", [x], [TensorSpec((4, 4))],
                 fn=lambda a, _w=w: jnp.dot(a, _w))
        b.mark_output(y)
        return b.build()

    g1, g2 = build(1.0), build(2.0)
    p1, p2 = compile_plan(g1, CFG), compile_plan(g2, CFG)
    assert plan_signature(p1) == plan_signature(p2)
    assert compile_schedule(p1) is not compile_schedule(p2)
    env = {g1.inputs[0]: np.ones((4, 4), np.float32)}
    out1 = np.asarray(PlanExecutor(p1)(env).outputs[g1.outputs[0]])
    out2 = np.asarray(PlanExecutor(p2)(env).outputs[g2.outputs[0]])
    np.testing.assert_array_equal(out1, np.full((4, 4), 4.0))
    np.testing.assert_array_equal(out2, np.full((4, 4), 8.0))


def test_fingerprint_distinguishes_referenced_names():
    """exp vs log differ only in co_names (bytecode stores name indices) —
    the fingerprint must still tell them apart."""
    import jax.numpy as jnp
    from repro.core import fn_fingerprint
    f = lambda a: jnp.exp(a)       # noqa: E731
    g = lambda a: jnp.log(a)       # noqa: E731
    assert fn_fingerprint(f) != fn_fingerprint(g)


def test_clear_compile_cache_forces_recompile():
    g, _ = ALL_ZOO["chain"]()
    plan = compile_plan(g, CFG)
    first = compile_schedule(plan)
    clear_compile_cache()
    assert compile_schedule(plan) is not first


# -- dispatch & synchronization accounting -----------------------------------

@pytest.mark.parametrize("name", sorted(ALL_ZOO))
def test_single_host_sync_per_run(name):
    g, make = ALL_ZOO[name]()
    env = make(np.random.default_rng(0))
    plan = compile_plan(g, CFG)
    for kw in [dict(), dict(whole_plan=True), dict(fused=False)]:
        ex = PlanExecutor(plan, mode="parallax", **kw)
        ex(env)
        assert ex.last_sync_count == 1, kw


def test_profile_mode_reinstates_layer_barriers():
    g, make = ALL_ZOO["diamond"]()
    env = make(np.random.default_rng(0))
    plan = compile_plan(g, CFG)
    ex = PlanExecutor(plan, mode="parallax", profile=True)
    ex(env)
    assert ex.last_sync_count == len(plan.schedule.layers) + 1


def test_dispatch_counts_per_strategy():
    g, make = diamond_graph(width=8)      # wider than max_parallel=6
    env = make(np.random.default_rng(0))
    plan = compile_plan(g, CFG)
    n_layers = len(plan.schedule.layers)
    n_units = sum(len(sl.parallel_groups) + len(sl.sequential)
                  for sl in plan.schedule.layers)
    assert n_units > n_layers             # the cap split a layer into units

    fused = PlanExecutor(plan, mode="parallax")
    fused(env)
    assert fused.last_dispatch_count == n_layers

    whole = PlanExecutor(plan, mode="parallax", whole_plan=True)
    whole(env)
    assert whole.last_dispatch_count == 1

    interp = PlanExecutor(plan, mode="parallax", fused=False)
    interp(env)
    assert interp.last_dispatch_count == n_units
    assert whole.last_dispatch_count < fused.last_dispatch_count \
        < interp.last_dispatch_count


def test_donation_argnums_mark_dead_intermediates():
    """Chain graph: each layer's activation input dies at that layer, so it
    must be recorded as donatable; params / graph inputs never are."""
    g, _ = ALL_ZOO["chain"]()
    plan = compile_plan(g, CFG)
    compiled = compile_schedule(plan)
    caller_owned = set(g.inputs) | set(g.params)
    for cl in compiled.layers:
        for i in cl.donate_argnums:
            assert cl.in_ids[i] not in caller_owned
            assert cl.in_ids[i] not in g.outputs


def test_runresult_timings_cover_every_layer():
    g, make = ALL_ZOO["multihead"]()
    env = make(np.random.default_rng(0))
    plan = compile_plan(g, CFG)
    res = PlanExecutor(plan, mode="parallax")(env)
    assert len(res.layer_timings) == len(plan.schedule.layers)
    assert max(t.width for t in res.layer_timings) >= 2
