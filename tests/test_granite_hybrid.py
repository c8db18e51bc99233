"""granite-4.0-h-small's mechanisms at a small size on the CPU.

The served path (``build_model`` -> ``ContinuousEngine``: paged pool,
chunked prefill, megastep decode, SSM state beside KV) against the
plain float32 reference ``bench/references/granite_hybrid.py``, compared
on logits at every position the engine computed; a chip's share of the
experts against the uncut layer; NoPE, the softmax scale and each muP
scalar on their own; the dense configurations' step programs and
streams unchanged by the new fields; and the per-row state reset the
engine dispatches and records.
"""

import dataclasses
import hashlib
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.references import granite_hybrid as ref
from repro.configs import get_config
from repro.models import build_model
from repro.models.attention import attend, qkv_project, self_attention
from repro.models.blocks import apply_block
from repro.models.common import rms_norm
from repro.models.mlp import mlp
from repro.models.moe import init_moe, moe_dense, moe_ragged
from repro.models.transformer import embed_tokens
from repro.models.vocab import logits_last_token
from repro.runtime.config import EngineConfig
from repro.runtime.engine import ContinuousEngine, Request
from repro.runtime.sampling import greedy_serving
from repro.runtime.stepper import RESET_PROGRAM, Stepper
from repro.runtime.telemetry import Telemetry

ROOT = pathlib.Path(__file__).resolve().parent.parent
FILE = json.loads(
    (ROOT / "bench/configs/granite-4.0-h-small.json").read_text())
GRANITE = get_config("granite-4.0-h-small")


def small(held: int = 4):
    """The small size: 4 layers (attention at index 1), width 128, GQA
    4/2 heads of 32, 16 routed experts of width 64, top-4, the first
    ``held`` of them held, a shared expert of width 96, Mamba-2 with
    16 heads of 16 and d_state 16; float32.  Returns the program's
    config and the configuration file the reference reads."""
    cfg = dataclasses.replace(
        GRANITE, num_layers=4, d_model=128, num_heads=4, num_kv_heads=2,
        head_dim=32, d_ff=64, vocab_size=256, attn_period=4, attn_offset=1,
        moe=dataclasses.replace(GRANITE.moe, num_experts=16,
                                num_experts_per_tok=4, shared_d_ff=96,
                                num_local_experts=held),
        ssm=dataclasses.replace(GRANITE.ssm, d_state=16, head_dim=16,
                                chunk=8),
        dtype="float32")
    file = dict(FILE, num_hidden_layers=4, hidden_size=128,
                num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                intermediate_size=64, shared_intermediate_size=96,
                vocab_size=256, router_experts=16, num_experts_per_tok=4,
                num_local_experts=held,
                mamba_d_state=16, mamba_d_head=16, torch_dtype="float32",
                layer_types=["mamba", "attention", "mamba", "mamba"])
    return cfg, file


def _tapped(api, seen: dict):
    """``api`` whose decode function also hands every active row's
    logits to the host, keyed by (row, position): the engine's own
    programs, with a callback added."""
    def decode_fn(params, caches, batch):
        logits, caches = api.decode_fn(params, caches, batch)

        def record(lg, pos, act):
            for b in np.flatnonzero(act):
                seen[(int(b), int(pos[b]))] = np.asarray(lg[b])

        jax.debug.callback(record, logits, batch["cache_len"],
                           batch["active"])
        return logits, caches
    return dataclasses.replace(api, decode_fn=decode_fn)


def _engine(api, params, telemetry=None):
    return ContinuousEngine(api, params, telemetry=telemetry, config=EngineConfig(
        max_batch=3, max_context=48, prefill_chunk=4, block_size=4,
        megastep=4, hbm_budget=1 << 30, host_pool=0, prefix_cache=False,
        fault_seed=None))


#: Both sides compute in float32 (the reference at ``highest``); what
#: differs is summation order (a chunk scan of one token at a time and a
#: Mamba-2 step against whole-sequence products and a sequential
#: recurrence; the router's softmax over all experts renormalised over
#: the top four against a softmax over the four).  That moves the last
#: few bits: logits here lie within +-0.006 (divided by 16), the best
#: two ~3e-4 apart, and the widest difference seen is 7e-9.  Dropping the residual or embedding
#: multiplier, the softmax scale or NoPE, or gating an absent expert
#: each fails this comparison.
LOGIT_ATOL = 1e-6


@pytest.mark.parametrize("held", [4, 6],
                         ids=["dense-experts", "ragged-experts"])
def test_engine_matches_reference_on_logits(held):
    """Paged chunked prefill and megastep decode of three requests: the
    logits at every prompt and generated position equal the reference's
    full forward over the same tokens, and each served token is the
    engine's greedy pick and, within bfloat16 rounding, the reference's
    best.  Four held experts of top-4 take the all-held
    path, six the ragged grouped GEMM."""
    cfg, file = small(held)
    params = ref.init_params(file, 3, jnp.float32)
    seen = {}
    api = _tapped(build_model(cfg), seen)
    eng = _engine(api, params)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (7, 10, 13)]
    for i, p in enumerate(prompts):
        eng.submit(Request(id=i, prompt=p, max_new_tokens=12))
    eng.step()
    slot_of = {s.req.id: b for b, s in enumerate(eng.slots) if s}
    eng.run()
    assert eng.stepper.megastep_sizes, "no megastep ran"
    reference = ref.Reference(file)
    for i, p in enumerate(prompts):
        served = np.asarray(eng.completed[i].tokens, np.int32)
        assert len(served) == 12
        seq = np.concatenate([p, served])
        want = np.asarray(reference.logits(params, seq))
        got = np.stack([seen[(slot_of[i], t)] for t in range(len(seq) - 1)])
        np.testing.assert_allclose(got, want[:-1], rtol=0, atol=LOGIT_ATOL)
        # the engine's greedy rule picks over its logits rounded to
        # bfloat16 (runtime/sampling.py): each served token is that pick
        # of the logits it computed, and lies within one bfloat16 step of
        # the best (plus LOGIT_ATOL) by the reference
        np.testing.assert_array_equal(
            served, greedy_serving(jnp.asarray(got[len(p) - 1:])))
        ref_at = want[len(p) - 1:-1]
        best = ref_at.max(-1)
        bf16_step = 2.0 ** (np.floor(np.log2(np.abs(best))) - 7)
        np.testing.assert_array_less(
            best - ref_at[np.arange(len(served)), served],
            bf16_step + LOGIT_ATOL)


@pytest.mark.parametrize("impl", [moe_dense, moe_ragged],
                         ids=["dense", "ragged"])
def test_expert_shares_sum_to_the_uncut_layer(impl):
    """Four chips' shares of 16 experts (4 each), each computing its own
    experts' part plus the shared expert: their sum, with the shared
    expert counted once, is the layer that holds all 16.  A chip holds
    the first four experts of its own numbering: chip c's router lists
    the layer's experts from 4c on, so its first four are experts
    4c..4c+3 of the uncut layer."""
    cfg, _ = small()
    whole = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, num_local_experts=0))
    p = init_moe(jax.random.key(1), whole)
    x = jax.random.normal(jax.random.key(2), (24, cfg.d_model))
    uncut, _ = impl(p, whole, x)
    total = -3 * mlp(p["shared"], x, "silu")
    for first in range(0, 16, 4):
        order = (np.arange(16) + first) % 16
        held = {k: (v[first:first + 4] if k.startswith("w_") else v)
                for k, v in p.items()}
        held["router"] = p["router"][:, order]
        y, _ = impl(held, cfg, x)
        total = total + y
    np.testing.assert_allclose(total, uncut, rtol=1e-5, atol=1e-5)
    # a share alone is not the layer: the held experts carry real weight
    assert float(jnp.abs(y - uncut).max()) > 1e-2


def test_nope_leaves_q_and_k_unrotated():
    cfg, _ = small()
    params = build_model(cfg).init(jax.random.key(0))["period"][1]["attn"]
    params = jax.tree.map(lambda a: a[0], params)
    x = jax.random.normal(jax.random.key(4), (1, 5, cfg.d_model))
    far = jnp.arange(5)[None] + 100
    q0, k0, v0 = qkv_project(params, cfg, x, jnp.arange(5)[None])
    q1, k1, _ = qkv_project(params, cfg, x, far)
    np.testing.assert_array_equal(q0, q1)
    np.testing.assert_array_equal(k0, k1)
    np.testing.assert_allclose(
        q0, jnp.einsum("bsd,df->bsf", x, params["wq"]).reshape(q0.shape),
        rtol=1e-6, atol=1e-6)
    roped = dataclasses.replace(cfg, nope=False)
    assert not np.allclose(qkv_project(params, roped, x, far)[0], q0)


def test_softmax_scale_replaces_one_over_sqrt_d():
    cfg, _ = small()
    params = build_model(cfg).init(jax.random.key(0))["period"][1]["attn"]
    params = jax.tree.map(lambda a: a[0], params)
    x = jax.random.normal(jax.random.key(5), (2, 6, cfg.d_model))
    D = cfg.resolved_head_dim()
    # the default scale, 1/sqrt(D), stated explicitly gives the default
    default = dataclasses.replace(cfg, attn_scale=0.0)
    stated = dataclasses.replace(cfg, attn_scale=float(D) ** -0.5)
    np.testing.assert_allclose(self_attention(params, stated, x),
                               self_attention(params, default, x),
                               rtol=1e-5, atol=1e-6)
    # Granite's 1/128 is the default with q scaled by sqrt(D) / 128
    q, k, v = qkv_project(params, cfg, x)
    np.testing.assert_allclose(
        attend(q, k, v, scale=cfg.attn_scale),
        attend(q * (np.sqrt(D) * cfg.attn_scale), k, v),
        rtol=1e-5, atol=1e-6)


def test_embedding_multiplier_scales_the_embeddings():
    cfg, _ = small()
    params = {"embed": jax.random.normal(jax.random.key(6),
                                         (cfg.vocab_size, cfg.d_model))}
    toks = jnp.array([[3, 1, 4]])
    one = dataclasses.replace(cfg, embedding_multiplier=1.0)
    np.testing.assert_allclose(embed_tokens(params, cfg, toks),
                               12.0 * embed_tokens(params, one, toks),
                               rtol=1e-6)


def test_logits_are_divided_by_logits_scaling():
    cfg, _ = small()
    params = {"embed": jax.random.normal(jax.random.key(7),
                                         (cfg.vocab_size, cfg.d_model))}
    hidden = jax.random.normal(jax.random.key(8), (2, 3, cfg.d_model))
    one = dataclasses.replace(cfg, logits_scaling=1.0)
    np.testing.assert_allclose(logits_last_token(params, cfg, hidden),
                               logits_last_token(params, one, hidden) / 16,
                               rtol=1e-6)


def test_residual_multiplier_scales_both_branches():
    """An attention-and-MoE block: x + r * attention(RMSNorm(x)), then
    + r * (MoE + shared)(RMSNorm(.)), with r = 0.22, from the parts."""
    cfg, _ = small(held=16)
    params = build_model(cfg).init(jax.random.key(0))["period"][1]
    params = jax.tree.map(lambda a: a[0], params)
    x = jax.random.normal(jax.random.key(9), (1, 5, cfg.d_model))
    pos = jnp.arange(5)[None]
    got, _ = apply_block(params, cfg, x, ("attn", "moe"), pos,
                         moe_impl="dense")
    norm = lambda p, v: rms_norm(p["scale"], v, cfg.rms_norm_eps)  # noqa
    h = x + 0.22 * self_attention(params["attn"], cfg,
                                  norm(params["norm1"], x), pos)
    y, _ = moe_dense(params["moe"], cfg,
                     norm(params["norm2"], h).reshape(5, -1))
    np.testing.assert_allclose(got, h + 0.22 * y.reshape(h.shape),
                               rtol=1e-5, atol=1e-6)
    one = dataclasses.replace(cfg, residual_multiplier=1.0)
    unscaled, _ = apply_block(params, one, x, ("attn", "moe"), pos,
                              moe_impl="dense")
    assert not np.allclose(unscaled, got)


#: The paged ``jit_step`` of each dense configuration at ``reduced()``
#: (batch 2, 4 blocks of 4 a row), as StableHLO text, and its greedy
#: streams, recorded from the program before the hybrid's fields
#: existed: the fields add no operation to a configuration that leaves
#: them off.  A change to the dense step itself records them anew.
DENSE = {
    "stablelm-3b": (
        "909d4e93f6d14798dc7b9146a436e122735285263082d664ba18145c29ec4bef",
        [[33, 95, 502, 54, 206, 149, 333, 206],
         [336, 336, 336, 256, 152, 121, 168, 313],
         [273, 268, 158, 282, 273, 282, 273, 377]]),
    "h2o-danube-3-4b": (
        "4575efe0e809adb05cda2aa8546cbaa48c37d80ffa8f0b0a4b2ed84520d8b661",
        [[33, 95, 502, 54, 206, 149, 333, 206],
         [336, 336, 336, 434, 5, 441, 290, 336],
         [158, 313, 29, 55, 29, 45, 348, 401]]),
}


@pytest.mark.parametrize("arch", sorted(DENSE))
def test_dense_step_programs_and_streams_unchanged(arch):
    digest, streams = DENSE[arch]
    api = build_model(get_config(arch).reduced())
    params = api.init(jax.random.key(0))
    B, BPS, BS = 2, 4, 4
    caches = api.init_paged_caches(B, B * BPS, BS, jnp.float32)
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    text = Stepper(api)._decode_paged.lower(
        params, caches, i32(B), i32(B), jnp.zeros((B,), bool),
        i32(B, BPS)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == digest
    eng = ContinuousEngine(api, params, config=EngineConfig(
        max_batch=2, max_context=32, prefill_chunk=4, block_size=4,
        megastep=4, hbm_budget=1 << 30, host_pool=0, prefix_cache=False,
        fault_seed=None))
    rng = np.random.default_rng(0)
    for i in range(3):
        eng.submit(Request(id=i, prompt=rng.integers(
            0, api.cfg.vocab_size, 5 + 3 * i).astype(np.int32),
            max_new_tokens=8))
    eng.run()
    assert [list(map(int, eng.completed[i].tokens)) for i in range(3)] \
        == streams


#: The paged ``jit_run_chunk`` of each hybrid configuration at
#: ``reduced()`` (batch 2, 4 blocks of 4 a row, a chunk of 4), as
#: StableHLO text, recorded from the program before attention-only
#: models took the chunk as one block: a model with SSM layers keeps
#: the scan of one-token steps, op for op.
HYBRID_CHUNK = {
    "granite-4.0-h-small":
        "dd7f8392dd296cd7df1e2026b6b9b2917cc1a4229edb0b6b89372957bf15419f",
    "jamba-v0.1-52b":
        "fb5a981ac10fa56a05556519add387669a2c5bed34876dfbc12c6efc3e49492d",
}


@pytest.mark.parametrize("arch", sorted(HYBRID_CHUNK))
def test_hybrid_chunk_program_unchanged(arch):
    api = build_model(get_config(arch).reduced())
    params = api.init(jax.random.key(0))
    B, BPS, BS, C = 2, 4, 4, 4
    caches = api.init_paged_caches(B, B * BPS, BS, jnp.float32)
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    stepper = Stepper(api)
    assert not stepper.block_chunk
    text = stepper._chunk_paged.lower(
        params, caches, i32(B, C), i32(B), i32(B), i32(B, BPS)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == HYBRID_CHUNK[arch]


def test_state_reset_is_its_own_program_span_and_counter():
    """Every admission wave clears its rows' SSM and conv state in one
    dispatch of the reset program, inside an ``engine.reset`` span; the
    ``engine.state_resets`` counter counts the rows cleared."""
    cfg, file = small()
    params = ref.init_params(file, 4, jnp.float32)
    tele = Telemetry(trace=True)
    eng = _engine(build_model(cfg), params, telemetry=tele)
    rng = np.random.default_rng(1)
    for i in range(5):
        eng.submit(Request(id=i, prompt=rng.integers(
            0, cfg.vocab_size, 6).astype(np.int32), max_new_tokens=5))
    eng.run()
    resets = [e for e in tele.events if e["kind"] == "reset"]
    assert resets and all(e["dur"] >= 0 for e in resets)
    rows = tele.metrics.snapshot()["counters"]["engine.state_resets"]
    assert rows == sum(e["args"]["rows"] for e in resets) == 5
    assert eng.stepper._reset.lower(
        eng.caches, jnp.zeros(3, bool)).as_text().startswith(
        f"module @{RESET_PROGRAM} ")


def test_registry_holds_the_configuration_files_sizes():
    """The harness checks only the dense keys against the registry; the
    hybrid's own sizes and scalars reach the program from the registry
    entry, so they are checked here."""
    g, m, s = GRANITE, GRANITE.moe, GRANITE.ssm
    kinds = ["attention" if g.is_attn_layer(i) else "mamba"
             for i in range(g.num_layers)]
    assert kinds == FILE["layer_types"][:FILE["num_hidden_layers"]]
    assert all(g.is_moe_layer(i) for i in range(g.num_layers))
    assert (m.num_experts, m.num_experts_per_tok, m.num_local_experts,
            g.expert_width(), g.shared_expert_width()) == (
        FILE["router_experts"], FILE["num_experts_per_tok"],
        FILE["num_local_experts"], FILE["intermediate_size"],
        FILE["shared_intermediate_size"])
    assert (s.d_state, s.head_dim, s.expand, s.chunk, s.conv_width,
            s.n_groups) == (
        FILE["mamba_d_state"], FILE["mamba_d_head"], FILE["mamba_expand"],
        FILE["mamba_chunk_size"], FILE["mamba_d_conv"],
        FILE["mamba_n_groups"])
    assert s.expand * g.d_model // s.head_dim == FILE["mamba_n_heads"]
    assert (g.nope, g.attn_scale, g.embedding_multiplier,
            g.residual_multiplier, g.logits_scaling, g.rms_norm_eps,
            g.tie_embeddings) == (
        FILE["position_embedding_type"] == "nope",
        FILE["attention_multiplier"], FILE["embedding_multiplier"],
        FILE["residual_multiplier"], FILE["logits_scaling"],
        FILE["rms_norm_eps"], FILE["tie_word_embeddings"])
    # 2.414 B parameters on this chip; 32.2 B (8.8 B active) published
    assert round(g.param_count() / 1e9, 2) == 2.41
    whole = dataclasses.replace(g, num_layers=40, moe=dataclasses.replace(
        m, num_local_experts=0))
    assert round(whole.param_count() / 1e9, 1) == 32.2
    assert round(whole.active_param_count() / 1e9, 1) == 8.8
