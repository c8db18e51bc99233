"""Tests for the heterogeneous placement & fallback dispatch runtime
(src/repro/hetero/): placement determinism, transfer accounting, dynamic
region execution, and oracle equality of the ``parallax-hetero`` mode."""

import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.core import (HardwareProfile, ParallaxConfig, PlanExecutor,
                        compile_hetero_schedule, compile_plan, greedy_select,
                        plan_signature, region_boundary_tensors)
from repro.hetero import (ACCEL, HOST, DynamicRegionCache, HeteroExecutor,
                          heterogenize, plan_placement, plan_transfers,
                          shape_bucket)
from graph_zoo import (ALL_ZOO, assert_matches_oracle, cond_graph,
                       diamond_graph, multihead_graph)

CFG = ParallaxConfig(budget=1 << 30)
# Zero compute floor: every supported branch is accelerator-worthy, so the
# tiny zoo graphs exercise real placement splits.
PERM = HardwareProfile("permissive", 0.0, 1.0, 1.0, 1.0)


def _ref(graph, env):
    return np.asarray(graph.execute(dict(env))[graph.outputs[0]])


# -- oracle equality ---------------------------------------------------------

@pytest.mark.parametrize("name", sorted(ALL_ZOO))
def test_hetero_matches_oracle_bit_for_bit(name):
    g, make = ALL_ZOO[name]()
    env = make(np.random.default_rng(42))
    ref = _ref(g, env)
    plan = compile_plan(g, CFG)
    ex = PlanExecutor(plan, mode="parallax-hetero", hetero_profile=PERM)
    got = np.asarray(ex(env).outputs[g.outputs[0]])
    assert_matches_oracle(ref, got, ex.hetero_stats.gemm_sites)
    # single host sync; observed boundary traffic equals the plan's
    # physical accounting (one move per (tensor, device))
    assert ex.last_sync_count == 1
    transfers = ex.plan.attrs["transfers"]
    assert ex.last_transfer_bytes == transfers.physical_bytes()
    assert sum(ex.last_device_dispatches.values()) == ex.last_dispatch_count


@pytest.mark.parametrize("name", ["heterogeneous", "cond", "while"])
def test_hetero_matches_oracle_default_profile(name):
    """With the plan's own (mobile-SoC) cost model only delegates clear the
    compute floor — fallbacks and small compute stay host-side — and the
    result must still be exact."""
    g, make = ALL_ZOO[name]()
    env = make(np.random.default_rng(1))
    ref = _ref(g, env)
    ex = PlanExecutor(compile_plan(g, CFG), mode="parallax-hetero")
    got = np.asarray(ex(env).outputs[g.outputs[0]])
    assert_matches_oracle(ref, got, ex.hetero_stats.gemm_sites)
    assert (HOST, 0) in ex.last_device_dispatches


def test_hetero_multidevice_subprocess_bit_for_bit():
    """Acceptance: with >= 2 simulated devices
    (``--xla_force_host_platform_device_count``) the hetero executor stays
    bit-for-bit against the oracle across the full zoo (within the
    grouped GEMM's tolerance where a group is batched).  Run in a fresh
    interpreter because the flag must precede jax initialization."""
    root = pathlib.Path(__file__).resolve().parents[1]
    script = (
        "import sys, numpy as np\n"
        f"sys.path.insert(0, {str(root / 'tests')!r})\n"
        "import jax\n"
        "assert len(jax.devices()) == 2, jax.devices()\n"
        "from repro.core import (ParallaxConfig, PlanExecutor, compile_plan,\n"
        "                        HardwareProfile)\n"
        "from graph_zoo import ALL_ZOO, assert_matches_oracle\n"
        "perm = HardwareProfile('permissive', 0.0, 1.0, 1.0, 1.0)\n"
        "cfg = ParallaxConfig(budget=1 << 30)\n"
        "for name, builder in sorted(ALL_ZOO.items()):\n"
        "    g, make = builder()\n"
        "    env = make(np.random.default_rng(42))\n"
        "    ref = np.asarray(g.execute(dict(env))[g.outputs[0]])\n"
        "    ex = PlanExecutor(compile_plan(g, cfg), mode='parallax-hetero',\n"
        "                      hetero_profile=perm)\n"
        "    got = np.asarray(ex(env).outputs[g.outputs[0]])\n"
        "    assert_matches_oracle(ref, got, ex.hetero_stats.gemm_sites)\n"
        "    assert ex.plan.placement.n_accel == 1\n"
        "print('multidevice-ok')\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = (str(root / "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr
    assert "multidevice-ok" in out.stdout


# -- placement ---------------------------------------------------------------

def test_placement_deterministic_for_equal_signatures():
    g, _ = ALL_ZOO["multihead"]()
    p1, p2 = compile_plan(g, CFG), compile_plan(g, CFG)
    assert plan_signature(p1) == plan_signature(p2)
    a1 = plan_placement(p1, PERM, n_accel=2)
    a2 = plan_placement(p2, PERM, n_accel=2)
    assert a1.signature() == a2.signature()
    assert a1.assignments == a2.assignments
    h1, h2 = heterogenize(p1, PERM), heterogenize(p2, PERM)
    assert h1.placement.signature() == h2.placement.signature()
    assert plan_signature(h1) == plan_signature(h2)


def test_placed_plan_signature_differs_from_unplaced():
    g, _ = ALL_ZOO["diamond"]()
    plan = compile_plan(g, CFG)
    hetero = heterogenize(plan, PERM)
    assert plan_signature(hetero) != plan_signature(plan)
    assert plan.placement is None          # input plan not mutated


def test_control_flow_branches_are_host_dynamic():
    for name in ("cond", "while"):
        g, _ = ALL_ZOO[name]()
        plan = compile_plan(g, CFG)
        placement = plan_placement(plan, PERM)
        dyn = [b for b, a in placement.assignments.items() if a.dynamic]
        assert dyn, name
        for bid in dyn:
            assert placement.assignments[bid].kind == HOST
            assert any(plan.graph.nodes[n].is_control_flow()
                       for n in plan.branches[bid].nodes)


def test_delegates_go_to_accelerator():
    g, _ = ALL_ZOO["heterogeneous"]()
    plan = compile_plan(g, CFG)
    placement = plan_placement(plan)       # plan's own (mobile) profile
    for bid, br in plan.branches.items():
        if br.delegate:
            assert placement.assignments[bid].kind == ACCEL


def test_round_robin_spreads_parallel_groups():
    g, _ = multihead_graph(heads=4)
    plan = compile_plan(g, CFG)
    placement = plan_placement(plan, PERM, n_accel=2)
    group = next(grp for sl in plan.schedule.layers
                 for grp in sl.parallel_groups if len(grp) >= 4)
    indices = [placement.assignments[b].index for b in group]
    assert indices == [0, 1, 0, 1]
    assert {(ACCEL, 0), (ACCEL, 1)} <= set(placement.devices_used())


# -- transfers ---------------------------------------------------------------

def test_transfer_bytes_match_region_boundary():
    """Per-branch incoming bytes must equal the ∂S accounting: non-param
    in-boundary tensors (region_boundary_tensors) whose producer sits on a
    different logical device."""
    g, _ = cond_graph()
    plan = compile_plan(g, CFG)
    placement = plan_placement(plan, PERM, n_accel=2)
    tp = plan_transfers(plan, placement)
    owner = {n: b.id for b in plan.branches.values() for n in b.nodes}
    params = set(g.params)
    for bid, br in plan.branches.items():
        in_t, _ = region_boundary_tensors(g, set(br.nodes))
        expect = 0
        for t in in_t:
            if t in params:
                continue
            prod = g.producer_of(t)
            src = (placement.device_of(owner[prod]) if prod is not None
                   else (HOST, 0))
            if src != placement.device_of(bid):
                expect += g.tensors[t].nbytes()
        assert tp.bytes_in.get(bid, 0) == expect, bid
    assert tp.total_bytes == sum(tp.bytes_in.values())
    assert tp.physical_bytes() <= tp.total_bytes
    assert tp.num_edges == len(tp.edges)


def test_transfer_plan_layers_and_seconds():
    g, _ = ALL_ZOO["while"]()
    plan = compile_plan(g, CFG)
    tp = plan_transfers(plan, plan_placement(plan, PERM))
    assert sum(tp.bytes_at_layer().values()) == tp.total_bytes
    assert tp.seconds(PERM) == pytest.approx(tp.total_bytes / 1.0)


def test_greedy_select_charges_extra_mems():
    mems = {0: 10, 1: 10, 2: 10}
    chosen, deferred = greedy_select(mems, [0, 1, 2], budget=30)
    assert chosen == [0, 1, 2]
    # branch 2's staged transfers push it over the budget
    chosen, deferred = greedy_select(mems, [0, 1, 2], budget=30,
                                     extra_mems={2: 15})
    assert chosen == [0, 1]
    assert deferred == [2]


def test_transfer_charge_defers_parallel_execution():
    """End-to-end §3.3 feedback: a budget that admits both diamond branches
    by compute peak alone no longer admits them once cross-device staging
    bytes are charged — heterogenize serializes the layer."""
    g, _ = diamond_graph()
    probe = compile_plan(g, CFG)
    group = next(grp for sl in probe.schedule.layers
                 for grp in sl.parallel_groups)
    exact = sum(probe.branches[b].peak_memory for b in group)
    plan = compile_plan(g, ParallaxConfig(budget=exact))
    assert plan.schedule.max_width() >= 2       # fits without the charge
    hetero = heterogenize(plan, PERM, n_accel=2)
    assert hetero.attrs["transfers"].total_bytes > 0
    assert hetero.schedule.max_width() == 1     # deferred under the charge
    uncharged = heterogenize(plan, PERM, n_accel=2, charge_transfers=False)
    assert uncharged.schedule.max_width() >= 2


@pytest.mark.parametrize("name", sorted(ALL_ZOO))
def test_final_schedule_fits_final_transfer_charges(name):
    """The demote-only repair loop's guarantee: every admitted parallel
    group fits the budget under the charges of the placement that
    actually runs (not a stale first-pass estimate)."""
    g, _ = ALL_ZOO[name]()
    probe = compile_plan(g, CFG)
    groups = [grp for sl in probe.schedule.layers
              for grp in sl.parallel_groups]
    budgets = [1 << 30]
    if groups:   # also stress a budget right at the compute-peak boundary
        budgets.append(min(sum(probe.branches[b].peak_memory for b in grp)
                           for grp in groups))
    for budget in budgets:
        plan = compile_plan(g, ParallaxConfig(budget=budget))
        hetero = heterogenize(plan, PERM, n_accel=2)
        charges = hetero.attrs["transfers"].bytes_in
        for sl in hetero.schedule.layers:
            for grp in sl.parallel_groups:
                total = sum(hetero.branches[b].peak_memory
                            + charges.get(b, 0) for b in grp)
                assert total <= hetero.schedule.budget, (budget, grp)
        # no branch lost or duplicated by the repair loop
        scheduled = sorted(b for sl in hetero.schedule.layers
                           for b in sl.all_branches())
        assert scheduled == sorted(hetero.branches)


# -- compiled segments -------------------------------------------------------

def test_hetero_segments_split_by_device():
    g, _ = ALL_ZOO["cond"]()
    hetero = heterogenize(compile_plan(g, CFG), PERM)
    compiled = compile_hetero_schedule(hetero)
    assert compiled.stats.dynamic_regions == 1
    devices = {s.device for s in compiled.segments}
    assert (HOST, 0) in devices and (ACCEL, 0) in devices
    assert compiled.stats.segments == len(compiled.segments)
    assert compiled.dispatches_per_run() == len(compiled.segments)
    dyn = [s for s in compiled.segments if s.dynamic]
    assert dyn[0].fn is None and dyn[0].node_ids


def test_hetero_compile_cache_shared_across_executors():
    g, _ = ALL_ZOO["diamond"]()
    plan = compile_plan(g, CFG)
    ex1 = PlanExecutor(plan, mode="parallax-hetero", hetero_profile=PERM)
    ex2 = PlanExecutor(plan, mode="parallax-hetero", hetero_profile=PERM)
    assert ex1._hetero.compiled is ex2._hetero.compiled


def test_hetero_host_must_be_a_cpu_device(monkeypatch):
    """Without a CPU platform no accelerator is made to stand in for
    the fallback host."""
    import jax
    from repro.hetero import resolve_devices
    placement = plan_placement(compile_plan(ALL_ZOO["cond"]()[0], CFG), PERM)
    real = jax.devices

    def no_cpu(backend=None):
        if backend == "cpu":
            raise RuntimeError("Unknown backend cpu")
        return real(backend)

    monkeypatch.setattr(jax, "devices", no_cpu)
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS"):
        resolve_devices(placement)


def test_hetero_executor_requires_placement():
    g, _ = ALL_ZOO["chain"]()
    plan = compile_plan(g, CFG)
    with pytest.raises(ValueError, match="placement"):
        HeteroExecutor(plan)
    with pytest.raises(ValueError, match="placement"):
        compile_hetero_schedule(plan)


def test_hetero_rejects_parallax_only_knobs():
    g, _ = ALL_ZOO["chain"]()
    plan = compile_plan(g, CFG)
    for kw in (dict(whole_plan=True), dict(fused=False), dict(donate=True)):
        with pytest.raises(ValueError, match="parallax-only"):
            PlanExecutor(plan, mode="parallax-hetero", **kw)
    ex = PlanExecutor(plan, mode="parallax-hetero", hetero_profile=PERM)
    assert ex.hetero_stats is not None
    assert ex.hetero_stats.segments >= 1
    assert PlanExecutor(plan, mode="parallax").hetero_stats is None


# -- dynamic regions ---------------------------------------------------------

def test_dynamic_cache_reuses_compilation():
    g, make = ALL_ZOO["while"]()
    env = make(np.random.default_rng(3))
    full = g.execute(dict(env))
    node = next(n for n in g.nodes.values() if n.is_control_flow())
    cache = DynamicRegionCache(g)
    args = tuple(full[t] for t in node.inputs)
    out1 = cache.run((node.id,), args)
    out2 = cache.run((node.id,), args)
    assert cache.compile_count == 1
    assert cache.hit_count == 1
    assert cache.trace_count == 1          # jit traced exactly once
    np.testing.assert_array_equal(np.asarray(out1[0]),
                                  np.asarray(full[node.outputs[0]]))
    np.testing.assert_array_equal(np.asarray(out1[0]), np.asarray(out2[0]))


def test_dynamic_cache_shape_buckets():
    assert shape_bucket((5, 8), "pow2") == (8, 8)
    assert shape_bucket((1, 3), "pow2") == (1, 4)
    assert shape_bucket((5, 8), "exact") == (5, 8)
    with pytest.raises(ValueError):
        shape_bucket((2,), "nope")


def test_dynamic_cache_pow2_bucket_shares_compilations():
    """A pad-safe elementwise fallback region: pow2 bucketing serves all
    shapes in a bucket from one compilation; exact mode compiles each."""
    import jax.numpy as jnp
    from repro.core import GraphBuilder, TensorSpec

    b = GraphBuilder()
    x = b.input((8, 8), name="x")
    y = b.op("relu_gate", "control_flow", [x], [TensorSpec((8, 8))],
             supported=False, fn=lambda a: jnp.where(a > 0, a, a * 0.1))
    b.mark_output(y)
    g = b.build()
    node_id = g.producer_of(y)

    rng = np.random.default_rng(0)
    shapes = [(5, 8), (7, 8), (8, 8)]
    exact = DynamicRegionCache(g, bucket="exact")
    pow2 = DynamicRegionCache(g, bucket="pow2")
    for s in shapes:
        a = rng.standard_normal(s).astype(np.float32)
        want = np.where(a > 0, a, a * 0.1)
        for cache in (exact, pow2):
            got = np.asarray(cache.run((node_id,), (a,))[0])
            assert got.shape == s
            np.testing.assert_allclose(got, want, rtol=1e-6)
    assert exact.compile_count == 3
    assert pow2.compile_count == 1
    assert pow2.trace_count == 1


def test_dynamic_cache_eager_fallback_for_untraceable_fn():
    """Data-dependent Python control flow cannot trace: the entry demotes
    to eager host execution — the literal CPU fallback — and still
    computes the right answer."""
    import jax.numpy as jnp
    from repro.core import GraphBuilder, TensorSpec

    def untraceable(a):
        if float(jnp.sum(a)) > 0:      # Python bool of a traced value
            return a + 1.0
        return a - 1.0

    b = GraphBuilder()
    x = b.input((4, 4), name="x")
    y = b.op("py_if", "control_flow", [x], [TensorSpec((4, 4))],
             supported=False, fn=untraceable)
    b.mark_output(y)
    g = b.build()
    node_id = g.producer_of(y)

    cache = DynamicRegionCache(g)
    a = np.ones((4, 4), np.float32)
    got = np.asarray(cache.run((node_id,), (a,))[0])
    np.testing.assert_array_equal(got, a + 1.0)
    assert cache.eager_fallbacks == 1
    got2 = np.asarray(cache.run((node_id,), (-a,))[0])   # same shape bucket
    np.testing.assert_array_equal(got2, -a - 1.0)
    assert cache.eager_fallbacks == 1      # demoted once, stays eager


def test_dynamic_cache_eager_fallback_for_numpy_fn():
    """An np-implemented fallback op (TracerArrayConversionError, not a
    bool conversion) must also demote to eager host execution — the
    canonical unsupported-operator scenario."""
    from repro.core import GraphBuilder, TensorSpec

    b = GraphBuilder()
    x = b.input((4, 4), name="x")
    y = b.op("np_op", "control_flow", [x], [TensorSpec((4, 4))],
             supported=False, fn=lambda a: np.tanh(np.asarray(a)))
    b.mark_output(y)
    g = b.build()

    cache = DynamicRegionCache(g)
    a = np.random.default_rng(0).standard_normal((4, 4)).astype(np.float32)
    got = np.asarray(cache.run((g.producer_of(y),), (a,))[0])
    np.testing.assert_allclose(got, np.tanh(a), rtol=1e-6)
    assert cache.eager_fallbacks == 1
