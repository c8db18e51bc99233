"""Compiles for a described TPU v5e chip: nothing runs, no chip is needed.

The TPU's compiler is installed with JAX, so it can refuse here what
interpret mode accepts: a tile that is not aligned, a kernel that wants
more fast memory than it may use, a step that does not fit the chip's
HBM.  These tests compile the serving path's programs at stablelm-3b's
and h2o-danube-3-4b's published widths (cut to 2 layers) and the grouped
GEMM at the shapes the schedule compiler gives it.  The topology is described inside a fixture,
never at import: only one process at a time may load the TPU's library,
and every test worker imports this file.
"""

from __future__ import annotations

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.branch_matmul.ops import grouped_branch_matmul
from repro.models import build_model
from repro.models.transformer import structure
from repro.runtime.stepper import Stepper
from repro.utils.hlo import loop_body_ops

#: What XLA lets one program use of a v5e chip's 16 GiB of HBM.
V5E_HBM = 15.75 * 2 ** 30

# the serving smoke's shapes (chip_smoke.py)
MAX_BATCH, MAX_CONTEXT, BLOCK, MEGASTEP, CHUNK = 8, 128, 16, 8, 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _hbm_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


# -- grouped GEMM ------------------------------------------------------------

GEMM_SHAPES = {
    # the zoo's multihead graph: 4 heads, seq 8, qkv projection 16 -> 12
    "multihead": (4, 8, 16, 12),
    # stablelm-3b's heads at full width: 32 heads, 128 tokens,
    # d_model 2560 -> head_dim 80
    "stablelm-3b heads": (32, 128, 2560, 80),
}


# float32 at HIGHEST is what chip_smoke.py's pipeline check runs
@pytest.mark.parametrize("dtype,precision", [
    ("float32", "default"), ("float32", "highest"),
    ("bfloat16", "default")])
@pytest.mark.parametrize("shape", sorted(GEMM_SHAPES))
def test_grouped_branch_matmul_compiles_for_v5e(one_chip, shape, dtype,
                                                precision):
    G, M, K, N = GEMM_SHAPES[shape]
    xs = [jax.ShapeDtypeStruct((M, K), dtype, sharding=one_chip)] * G
    ws = [jax.ShapeDtypeStruct((K, N), dtype, sharding=one_chip)] * G
    fn = jax.jit(lambda xs, ws: grouped_branch_matmul(xs, ws,
                                                      platform="tpu"))
    with jax.default_matmul_precision(precision):
        compiled = fn.lower(xs, ws).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _hbm_bytes(compiled) < V5E_HBM


# -- serving steps -----------------------------------------------------------

def _serving_shapes(arch, one_chip):
    """(stepper, params, caches, arg factories) for ``arch`` at full
    width, 2 layers, bf16 weights, on the smoke's paged pool."""
    cfg = dataclasses.replace(get_config(arch), num_layers=2)
    api = build_model(cfg)
    dt = jnp.dtype(cfg.dtype)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(lambda k: jax.tree.map(
        lambda a: a.astype(dt), api.init(k)), jax.random.key(0)))
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree.leaves(params))
    bps = MAX_CONTEXT // BLOCK
    caches = on_chip(jax.eval_shape(lambda: api.init_paged_caches(
        MAX_BATCH, MAX_BATCH * bps, BLOCK, dt)))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    B = MAX_BATCH
    mask = jax.ShapeDtypeStruct((B,), bool, sharding=one_chip)
    args = {
        "decode": lambda st: (st._decode_paged, i32(B), i32(B), mask,
                              i32(B, bps)),
        "megastep": lambda st: (st._mega_paged, i32(B), i32(B), mask,
                                i32(B), i32(B, MEGASTEP), i32(B), i32(B),
                                i32(B, bps)),
        "prefill_chunk": lambda st: (st._chunk_paged, i32(B, CHUNK), i32(B),
                                     i32(B), i32(B, bps)),
    }
    return Stepper(api), params, caches, args


@pytest.fixture(scope="module")
def serving_shapes(one_chip):
    return _serving_shapes("stablelm-3b", one_chip)


@pytest.mark.parametrize("step", ["decode", "megastep", "prefill_chunk"])
def test_serving_step_fits_v5e(serving_shapes, step):
    stepper, params, caches, args = serving_shapes
    fn, *rest = args[step](stepper)
    compiled = fn.lower(params, caches, *rest).compile()
    weights = sum(np.prod(a.shape) * a.dtype.itemsize
                  for a in jax.tree.leaves(params))
    assert compiled.memory_analysis().argument_size_in_bytes > weights
    assert _hbm_bytes(compiled) < V5E_HBM


#: ``memory_analysis().temp_size_in_bytes`` of the paged programs at the
#: shapes above when the layer scan took each layer's pool as ``xs`` and
#: gave it back as ``ys`` (compiled for the described v5e with jax 0.9.0
#: and libtpu 0.0.34): the ceiling for the carried pool.
XS_POOL_TEMP_BYTES = {
    ("stablelm-3b", "megastep"): 105_769_984,
    ("stablelm-3b", "prefill_chunk"): 105_506_816,
    ("h2o-danube-3-4b", "megastep"): 1_981_440,
    ("h2o-danube-3-4b", "prefill_chunk"): 1_782_784,
}


_COMPILED = {}


def _compiled(one_chip, arch, step):
    """(compiled program, caches, config) of ``step`` for ``arch``,
    compiled once for the file's tests."""
    if (arch, step) not in _COMPILED:
        stepper, params, caches, args = _serving_shapes(arch, one_chip)
        fn, *rest = args[step](stepper)
        _COMPILED[arch, step] = (fn.lower(params, caches, *rest).compile(),
                                 caches, stepper.cfg)
    return _COMPILED[arch, step]


@pytest.mark.parametrize("step", ["megastep", "prefill_chunk"])
@pytest.mark.parametrize("arch", ["stablelm-3b", "h2o-danube-3-4b"])
def test_layer_scan_moves_no_whole_pool(one_chip, arch, step):
    """The stacked KV pool rides in the layer scan's carry: no loop body
    copies, slices or writes back the pool or one layer's pool; each
    step writes its token in place and gathers through the table."""
    compiled, caches, _ = _compiled(one_chip, arch, step)
    pool = caches["period"][0]["k_pool"].shape
    layer_pool = (1,) + pool[1:]
    moved = [(op, dims) for op, dims in loop_body_ops(compiled.as_text())
             if op in ("copy", "dynamic-slice", "dynamic-update-slice")
             and dims in (pool, pool[1:], layer_pool)]
    assert not moved, moved
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= XS_POOL_TEMP_BYTES[arch, step]


def _loops(hlo_text):
    """[(trip count, carried array shapes)] of every ``while`` of a
    compiled program: the count is the constant its condition compares
    the induction variable with."""
    out = []
    for m in re.finditer(r"= \((?P<carry>.*)\) while\(.*?condition="
                         r"%?(?P<cond>[\w.\-]+)", hlo_text):
        body = re.search(rf"^%?{re.escape(m['cond'])} .*?^}}", hlo_text,
                         re.M | re.S)[0]
        trips = int(re.search(r"constant\((\d+)\)", body)[1])
        shapes = [tuple(int(d) for d in dims.split(",") if d)
                  for dims in re.findall(r"\[([0-9,]*)\]", m["carry"])]
        out.append((trips, shapes))
    return out


@pytest.mark.parametrize("arch", ["stablelm-3b", "h2o-danube-3-4b"])
def test_chunk_runs_one_block_through_the_layer_scan(one_chip, arch):
    """An attention-only model's chunk program holds one loop, the layer
    scan, run ``n_rep`` times with the whole (B, C, d) block in its
    carry: no loop runs over the chunk's tokens."""
    compiled, _, cfg = _compiled(one_chip, arch, "prefill_chunk")
    (trips, carry), = _loops(compiled.as_text())
    assert trips == structure(cfg)[3]             # n_rep
    assert (MAX_BATCH, CHUNK, cfg.d_model) in carry
