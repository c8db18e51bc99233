"""The served step programs' names and model scopes, read from their
compiled HLO at a tiny size: each program is named by its stepper
constant, every operation of the model's parts carries its
``jax.named_scope`` in its op metadata, and the layer scan stays under
no model scope.  What a profiler reduction counts as ``unscoped`` is
then the scan's per-layer slices of the stacked weights (and the copy
of the pool a program makes once, on entry): the KV pool rides in the
scan's carry, so no loop body copies, slices or writes it back."""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.runtime import stepper as stepper_mod
from repro.runtime.stepper import Stepper
from repro.utils.hlo import loop_body_ops

#: the model's top-level scopes, and the attention scope's two parts
SCOPES = ("embed", "norm", "attention", "mlp", "lm_head", "sample")
B, BLOCK, BPS, N, C = 2, 4, 4, 3, 4


@pytest.fixture(scope="module")
def model():
    """The reduced model and its paged caches."""
    api = build_model(get_config("stablelm-3b").reduced())
    return api, api.init_paged_caches(B, B * BPS, BLOCK, jnp.float32)


@pytest.fixture(scope="module")
def compiled(model):
    """HLO text of the paged decode step, megastep and prefill chunk."""
    api, caches = model
    params = api.init(jax.random.key(0))
    st = Stepper(api)
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    off = jnp.zeros((B,), bool)
    progs = {
        "DECODE_PROGRAM": (st._decode_paged, (i32(B), i32(B), off,
                                              i32(B, BPS))),
        "MEGASTEP_PROGRAM": (st._mega_paged, (i32(B), i32(B), off, i32(B),
                                              i32(B, N), i32(B), i32(B),
                                              i32(B, BPS))),
        "CHUNK_PROGRAM": (st._chunk_paged, (i32(B, C), i32(B), i32(B),
                                            i32(B, BPS))),
    }
    return {k: fn.lower(params, caches, *args).compile().as_text()
            for k, (fn, args) in progs.items()}


def _ops(text):
    """(opcode, op_name) of every instruction carrying metadata."""
    out = []
    for line in text.splitlines():
        m = re.search(r"= \S+ ([\w\-]+)\(.*op_name=\"([^\"]*)\"", line)
        if m:
            out.append(m.groups())
    return out


def _scopes(op_name):
    return set(op_name.split("/")) & set(SCOPES)


@pytest.mark.parametrize("const", ["DECODE_PROGRAM", "MEGASTEP_PROGRAM",
                                   "CHUNK_PROGRAM"])
def test_program_names_are_the_stepper_constants(compiled, const):
    name = getattr(stepper_mod, const)
    assert compiled[const].startswith(f"HloModule {name},")


@pytest.mark.parametrize("const", ["DECODE_PROGRAM", "MEGASTEP_PROGRAM",
                                   "CHUNK_PROGRAM"])
def test_step_ops_carry_the_model_scopes(compiled, const):
    ops = _ops(compiled[const])
    seen = set().union(*(_scopes(n) for _, n in ops))
    assert seen == set(SCOPES)
    names = " ".join(n for _, n in ops)
    assert "/attention/kv_write/" in names
    assert "/attention/kv_gather/" in names
    dots = [n for op, n in ops if op in ("dot", "convolution")]
    assert dots and all(_scopes(n) for n in dots), dots
    # the layer scan is left outside every model scope: its per-layer
    # slices of the stacked weights carry none
    scan = [n for op, n in ops if op == "dynamic-slice"
            and n.endswith("/while/body/dynamic_slice")]
    assert scan and not any(_scopes(n) for n in scan), scan


@pytest.mark.parametrize("const", ["DECODE_PROGRAM", "MEGASTEP_PROGRAM",
                                   "CHUNK_PROGRAM"])
def test_layer_scan_moves_no_whole_pool(compiled, model, const):
    """No loop body copies, slices or writes back the stacked pool or
    one layer's pool: the token is written in place in the carry."""
    pool = model[1]["period"][0]["k_pool"].shape
    moved = [(op, dims) for op, dims in loop_body_ops(compiled[const])
             if op in ("copy", "dynamic-slice", "dynamic-update-slice")
             and dims in (pool, pool[1:], (1,) + pool[1:])]
    assert not moved, moved
