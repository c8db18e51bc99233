"""Unit tests: balance refinement (§3.1) + greedy scheduling (§3.3)."""

import pytest

from repro.core import (Branch, LayerGroups, balance_ratio, compile_plan,
                        greedy_select, group_layer, memory_budget,
                        ParallaxConfig, query_available_memory,
                        schedule_layers)
from graph_zoo import diamond_graph, multihead_graph


def _mk_branches(flops_list, n_ops=3):
    return {i: Branch(i, list(range(n_ops)), n_ops=n_ops, flops=f)
            for i, f in enumerate(flops_list)}


def test_group_layer_balanced():
    brs = _mk_branches([100.0, 110.0, 95.0, 105.0])
    out = group_layer(brs, [0, 1, 2, 3], beta=1.5)
    assert out.parallel_groups == [[0, 1, 2, 3]]
    assert out.sequential == []
    assert balance_ratio(brs, out.parallel_groups[0]) <= 1.5


def test_group_layer_imbalanced_splits():
    # 1000 vs 100: ratio 10 > beta -> cannot share a group
    brs = _mk_branches([1000.0, 1000.0, 100.0, 100.0])
    out = group_layer(brs, [0, 1, 2, 3], beta=1.5)
    assert sorted(map(tuple, out.parallel_groups)) == [(0, 1), (2, 3)]


def test_group_layer_min_ops_floor():
    # N must exceed 2 (paper: N > 2)
    brs = _mk_branches([100.0, 100.0], n_ops=2)
    out = group_layer(brs, [0, 1], beta=1.5)
    assert out.parallel_groups == []
    assert out.sequential == [0, 1]


def test_group_layer_delegate_exempt_from_floor():
    brs = _mk_branches([100.0, 100.0], n_ops=1)
    for b in brs.values():
        b.delegate = True
    out = group_layer(brs, [0, 1], beta=1.5)
    assert out.parallel_groups == [[0, 1]]


def test_greedy_select_max_cardinality():
    mems = {0: 10, 1: 20, 2: 30, 3: 100}
    chosen, deferred = greedy_select(mems, [0, 1, 2, 3], budget=60)
    assert chosen == [0, 1, 2]
    assert deferred == [3]


def test_greedy_select_respects_budget_and_cap():
    mems = {i: 10 for i in range(10)}
    chosen, _ = greedy_select(mems, list(range(10)), budget=1000,
                              max_parallel=4)
    assert len(chosen) == 4
    chosen, _ = greedy_select(mems, list(range(10)), budget=25,
                              max_parallel=8)
    assert len(chosen) == 2


def test_memory_budget_margin():
    assert memory_budget(available=100, margin=0.4) == 60
    with pytest.raises(ValueError):
        memory_budget(available=100, margin=1.5)


def test_memory_budget_env_override(monkeypatch):
    """PARALLAX_MEM_BUDGET pins the queried memory (with K/M/G suffixes) —
    no silent fallback when the operator set an explicit budget."""
    monkeypatch.setenv("PARALLAX_MEM_BUDGET", "1000")
    assert query_available_memory() == 1000
    assert memory_budget(margin=0.4) == 600
    monkeypatch.setenv("PARALLAX_MEM_BUDGET", "4G")
    assert query_available_memory() == 4 << 30
    monkeypatch.setenv("PARALLAX_MEM_BUDGET", "512M")
    assert query_available_memory() == 512 << 20
    monkeypatch.setenv("PARALLAX_MEM_BUDGET", "not-a-size")
    with pytest.raises(ValueError, match="PARALLAX_MEM_BUDGET"):
        query_available_memory()
    for bad in ("0", "-8G"):         # non-positive would silently serialize
        monkeypatch.setenv("PARALLAX_MEM_BUDGET", bad)
        with pytest.raises(ValueError, match="positive"):
            query_available_memory()
    monkeypatch.delenv("PARALLAX_MEM_BUDGET")
    assert query_available_memory() > 0    # the CPU's MemAvailable


def test_memory_query_reads_accelerator_memory_stats(monkeypatch):
    """Off the CPU the budget is the device's free HBM, and a device
    that reports no limit raises instead of a guessed size."""
    import jax

    class FakeChip:
        platform = "tpu"

        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    monkeypatch.delenv("PARALLAX_MEM_BUDGET", raising=False)
    chip = FakeChip({"bytes_limit": 16 << 30, "bytes_in_use": 6 << 30})
    monkeypatch.setattr(jax, "devices", lambda *a: [chip])
    assert query_available_memory() == 10 << 30
    chip.stats = None
    with pytest.raises(RuntimeError, match="PARALLAX_MEM_BUDGET"):
        query_available_memory()


def test_schedule_layers_extra_mems_defer():
    """Transfer surcharges flow through schedule_layers into deferral."""
    peak = {0: 50, 1: 50}
    groups = [LayerGroups(parallel_groups=[[0, 1]])]
    assert schedule_layers(groups, peak, budget=100).max_width() == 2
    charged = schedule_layers(groups, peak, budget=100,
                              extra_mems={1: 10})
    assert charged.max_width() == 1
    assert sorted(charged.layers[0].all_branches()) == [0, 1]


def test_schedule_never_exceeds_budget():
    brs = _mk_branches([100.0] * 6)
    peak = {i: 50 for i in brs}
    groups = [LayerGroups(parallel_groups=[[0, 1, 2, 3, 4, 5]])]
    sched = schedule_layers(groups, peak, budget=120)
    for sl in sched.layers:
        for g in sl.parallel_groups:
            assert sum(peak[b] for b in g) <= 120
        # unscheduled branches run sequentially, none dropped
        assert sorted(sl.all_branches()) == [0, 1, 2, 3, 4, 5]


def test_schedule_parallel_when_budget_allows():
    brs = _mk_branches([100.0] * 4)
    peak = {i: 10 for i in brs}
    groups = [LayerGroups(parallel_groups=[[0, 1, 2, 3]])]
    sched = schedule_layers(groups, peak, budget=1 << 30)
    assert sched.layers[0].parallel_groups == [[0, 1, 2, 3]]
    assert sched.max_width() == 4


def test_compile_plan_end_to_end_structures():
    g, _ = multihead_graph(heads=4)
    plan = compile_plan(g, ParallaxConfig(budget=1 << 30))
    # every branch scheduled exactly once
    scheduled = sorted(b for sl in plan.schedule.layers
                       for b in sl.all_branches())
    assert scheduled == sorted(plan.branches.keys())
    # parallelism exposed and admitted
    assert plan.schedule.max_width() >= 2
    # arena accounting invariants
    assert plan.scheduled_parallel_peak() <= plan.schedule.budget
    assert plan.pooled_arena_peak() <= plan.sum_arena_sizes()


def test_compile_plan_tight_budget_serializes():
    g, _ = diamond_graph(branch_len=3, width=2)
    plan = compile_plan(g, ParallaxConfig(budget=1))  # nothing fits
    assert plan.schedule.max_width() == 1
