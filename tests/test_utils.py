"""Tests: sharding rules, HLO parsers, roofline math, chunked attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.utils.hlo import (bf16_convert_artifact_bytes, collective_bytes,
                             collective_counts, loop_body_ops)
from repro.utils.roofline import (HBM_BW, ICI_BW, PEAK_FLOPS,
                                  model_flops_estimate, roofline)
from repro.utils.sharding import spec_for
from jax.sharding import PartitionSpec as P


# -- hlo parsing -------------------------------------------------------------

HLO_SAMPLE = """
ENTRY %main {
  %ag = bf16[16,1024]{1,0} all-gather(%x), replica_groups=...
  %ar = f32[256]{0} all-reduce(%y), to_apply=%sum
  %rs = bf16[8,512]{1,0} reduce-scatter(%z)
  %a2a = (f32[8,2]{1,0}, f32[8,2]{1,0}) all-to-all(%p, %q)
  %cp = bf16[4]{0} collective-permute(%w)
  %dot = f32[8,8]{1,0} dot(%a, %b)
}
"""


def test_collective_bytes_per_type():
    cb = collective_bytes(HLO_SAMPLE)
    assert cb["all-gather"] == 16 * 1024 * 2
    assert cb["all-reduce"] == 256 * 4
    assert cb["reduce-scatter"] == 8 * 512 * 2
    assert cb["all-to-all"] == 2 * 8 * 2 * 4      # tuple: both shapes
    assert cb["collective-permute"] == 4 * 2
    assert cb["total"] == sum(v for k, v in cb.items() if k != "total")


def test_collective_counts():
    cc = collective_counts(HLO_SAMPLE)
    assert cc == {"all-gather": 1, "all-reduce": 1, "reduce-scatter": 1,
                  "all-to-all": 1, "collective-permute": 1}


def test_convert_artifact_wrapped_dedup():
    hlo = """
  %wrapped_convert.1 = f32[100000000]{0} fusion(%p), kind=kLoop, calls=%c1
  %convert.9 = f32[100000000]{0} convert(%pp)
"""
    # wrapped fusions present -> only those counted (inner dupes skipped)
    assert bf16_convert_artifact_bytes(hlo, min_bytes=1) == 400000000


# -- roofline ---------------------------------------------------------------

def test_loop_body_ops_reads_only_loop_bodies():
    """What a scan's body runs is listed, fused or not; what runs once
    outside the loop is not."""
    def f(x, y):
        z = jax.lax.scan(lambda c, _: (c * 3.0, None), x, None,
                         length=4)[0]
        return z, y[::2]

    text = jax.jit(f).lower(jnp.ones((4, 8)), jnp.ones((6, 5))) \
        .compile().as_text()
    ops = loop_body_ops(text)
    assert ("multiply", (4, 8)) in ops
    assert not any(dims == (3, 5) for _, dims in ops)
    assert loop_body_ops(HLO_SAMPLE) == []


def test_roofline_terms_and_dominant():
    rl = roofline(flops_per_device=197e12, bytes_per_device=819e9,
                  collective_bytes_per_device=25e9, chips=256,
                  model_flops=197e12 * 256 * 0.5)
    np.testing.assert_allclose(rl.compute_s, 1.0)
    np.testing.assert_allclose(rl.memory_s, 1.0)
    np.testing.assert_allclose(rl.collective_s, 0.5)
    assert rl.dominant in ("compute", "memory")
    np.testing.assert_allclose(rl.useful_flops_ratio, 0.5)


def test_model_flops_estimate_kinds():
    from repro.configs import get_config, INPUT_SHAPES
    cfg = get_config("qwen2-72b")
    tr = model_flops_estimate(cfg, INPUT_SHAPES["train_4k"])
    pf = model_flops_estimate(cfg, INPUT_SHAPES["prefill_32k"])
    dc = model_flops_estimate(cfg, INPUT_SHAPES["decode_32k"])
    assert tr == pytest.approx(6 * cfg.active_param_count() * 256 * 4096)
    assert pf == pytest.approx(2 * cfg.active_param_count() * 32 * 32768)
    assert dc == pytest.approx(2 * cfg.active_param_count() * 128)


def test_moe_active_params_much_smaller():
    from repro.configs import get_config
    kimi = get_config("kimi-k2-1t-a32b")
    assert kimi.param_count() > 0.9e12
    assert kimi.active_param_count() < 0.05 * kimi.param_count()


# -- sharding rules -----------------------------------------------------------

class _FakeLeaf:
    def __init__(self, shape):
        self.shape = shape
        self.ndim = len(shape)


class _Key:
    def __init__(self, k):
        self.key = k


def _spec(path_names, shape, axes=("data", "model"),
          sizes={"data": 16, "model": 16}):
    path = tuple(_Key(k) for k in path_names)
    return spec_for(path, _FakeLeaf(shape), axes, sizes)


def test_param_rules_basic():
    assert _spec(("attn", "wq"), (1024, 2048)) == P("data", "model")
    assert _spec(("attn", "wo"), (2048, 1024)) == P("model", "data")
    # stacked leading dim padded with None
    assert _spec(("period", "attn", "wq"), (8, 1024, 2048)) == \
        P(None, "data", "model")


def test_param_rules_divisibility_fallback():
    # vocab 51865 not divisible by 16 -> replicated on that dim
    s = _spec(("embed",), (51865, 384))
    assert s == P(None, "data")
    # d=384/16 ok
    s2 = _spec(("embed",), (51200, 384))
    assert s2 == P("model", "data")


def test_moe_expert_rule_needs_moe_path():
    moe = _spec(("moe", "w_gate"), (16, 1024, 512))
    assert moe == P("model", "data", None)
    dense_stacked = _spec(("mlp", "w_gate"), (16, 1024, 512))
    assert dense_stacked == P(None, "data", "model")


def test_unknown_params_replicated():
    assert _spec(("whatever",), (7, 9)) == P()


# -- chunked attention vs reference ------------------------------------------

@pytest.mark.parametrize("window", [0, 24])
def test_attend_chunked_exact(window):
    from repro.models.attention import attend, attend_chunked, causal_mask
    B, S, H, K, D = 2, 64, 4, 2, 16
    q = jax.random.normal(jax.random.key(0), (B, S, H, D))
    k = jax.random.normal(jax.random.key(1), (B, S, K, D))
    v = jax.random.normal(jax.random.key(2), (B, S, K, D))
    ref = attend(q, k, v, causal_mask(S, S, 0, window))
    got = attend_chunked(q, k, v, causal=True, window=window,
                         chunk_q=16, chunk_k=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_chunked_flag_preserves_model_forward():
    from repro.configs import get_config
    from repro.models import build_model
    from repro.models import runtime_flags
    cfg = get_config("stablelm-3b").reduced()
    api = build_model(cfg)
    params = api.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (1, 32), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    base, _ = api.loss_fn(params, batch)
    try:
        runtime_flags.chunked_attention = True
        runtime_flags.chunk_q, runtime_flags.chunk_k = 8, 16
        chunked, _ = api.loss_fn(params, batch)
    finally:
        runtime_flags.chunked_attention = False
        runtime_flags.chunk_q, runtime_flags.chunk_k = 512, 1024
    np.testing.assert_allclose(float(base), float(chunked), rtol=1e-5)
