"""The hybrid step's least bytes and FLOPs, counted by hand at the
granite-4.0-h-small cut (one period of 10 layers, 9 held experts of 72),
and tied to the reference's weight tree and the runtime's state bytes."""

import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

from bench.cost_hybrid import HybridStepCost
from bench.references import granite_hybrid

CFG = json.loads((pathlib.Path(__file__).resolve().parents[1]
                  / "configs/granite-4.0-h-small.json").read_text())

# by hand, d 4096, bfloat16:
MAMBA = 4096 * (2 * 8192 + 2 * 128 + 128) + 8192 * 4096     # 102,236,160
ATTN = 4096 * (32 + 2 * 8) * 128 + 32 * 128 * 4096          # 41,943,040
ROUTER_SHARED = 4096 * 72 + 3 * 4096 * 1536                 # 19,169,280
EMBED = 100352 * 4096                                       # 411,041,792
EXPERT = 10 * 3 * 4096 * 768          # one held expert, all 10 layers
SMALL = 9 * (4 * 8448 + 8448 + 3 * 128 + 8192) + 21 * 4096  # conv, A, D,
#                                     dt_bias, gated norm, 21 RMSNorms
STATE = 9 * (4 * 128 * 64 * 128 + 2 * 3 * 8448)             # 38,204,928


@pytest.fixture(scope="module")
def cost():
    return HybridStepCost(CFG)


def test_counts_by_hand(cost):
    rows = 9 * MAMBA + ATTN + 10 * ROUTER_SHARED + EMBED
    assert cost.row_params == rows == 1_564_803_072
    assert cost.weight_bytes == 2 * (rows + SMALL)
    assert cost.expert_bytes == 2 * EXPERT
    assert cost.state_bytes == STATE            # 36.4 MiB a row
    assert cost.kv_token_bytes == 2 * 2 * 8 * 128  # one attention layer
    assert cost.row_flops == 2 * rows + 2 * EXPERT * 10 * 9 / 72 + 9 * (
        6 * 128 * 64 * 128 + 2 * 4 * 8448)


def test_step_at_a_batch_of_64(cost):
    flops, nbytes = cost.step([1024] * 64)
    # 3.13 GB of weights, 9 experts of 189 MB, 64 rows' state read and
    # written (4.89 GB), 64 rows of 1024 tokens of KV (268 MB)
    assert nbytes == (2 * (9 * MAMBA + ATTN + 10 * ROUTER_SHARED + EMBED
                           + SMALL) + 9 * 2 * EXPERT
                      + 64 * (2 * 4096 + 2 * STATE) + 4096 * 64 * 1024)
    assert nbytes == pytest.approx(9.989e9, rel=1e-3)
    assert flops == 64 * cost.row_flops + 4 * 32 * 128 * 64 * 1024


def test_one_row_reaches_no_more_experts_than_it_picks():
    cfg = dict(CFG, num_experts_per_tok=2)
    one = HybridStepCost(cfg)
    assert one.step([5])[1] - one.step([4])[1] == one.kv_token_bytes
    assert one.step([5])[1] == (one.weight_bytes + 2 * one.expert_bytes
                                + one.row_bytes + 2 * one.state_bytes
                                + 5 * one.kv_token_bytes)


def test_held_weights_are_the_reference_tree(cost):
    tree = jax.eval_shape(granite_hybrid.weight_fn(CFG, jnp.bfloat16),
                          jax.eval_shape(lambda: granite_hybrid.prng_key(0)))
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))
    assert cost.weight_bytes + 9 * cost.expert_bytes == held


def test_state_is_what_the_engine_charges_a_row(cost):
    from repro.configs import get_config
    from repro.runtime.kv_cache import kv_bytes_per_token, state_bytes
    cfg = get_config("granite-4.0-h-small")
    assert state_bytes(cfg) == cost.state_bytes
    assert kv_bytes_per_token(cfg) == cost.kv_token_bytes


def test_roofline_reads_the_one_cell_its_entry_lists():
    """The reader is not told which cell it measures: it takes the
    configuration of the one cell its entry lists, and refuses an entry
    that lists more."""
    from bench.metrics import hybrid_step_roofline as metric
    bench = json.loads((metric.ROOT / "BENCHMARK.json").read_text())
    assert metric._config(bench) == CFG
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "hybrid_step_roofline")
    entry["workloads"] = entry["workloads"] + ["stablelm-3b.prompt_heavy"]
    with pytest.raises(ValueError, match="one cell"):
        metric._config(bench)
