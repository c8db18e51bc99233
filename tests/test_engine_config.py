"""Tests: EngineConfig precedence (explicit > env > default), CLI
generation, validation, and the deprecated-kwarg shim.

Pure-config tests — no model build, no JAX dispatch.  Engine-level
stream equivalence between the config and legacy constructors is
asserted bitwise in the sync child (tests/test_openloop.py)."""

import argparse
import warnings

import pytest

from repro.core.scheduler import MEM_BUDGET_ENV
from repro.runtime.config import (HOST_POOL_ENV, MEGASTEP_ENV,
                                  EngineConfig)
from repro.runtime.faults import FAULT_SEED_ENV


# -- precedence matrix -------------------------------------------------------

def test_defaults_without_env(monkeypatch):
    for var in (MEGASTEP_ENV, HOST_POOL_ENV, FAULT_SEED_ENV,
                MEM_BUDGET_ENV):
        monkeypatch.delenv(var, raising=False)
    c = EngineConfig()
    assert c.megastep == 8
    assert c.host_pool == 0
    assert c.fault_seed is None
    assert c.max_batch == 8 and c.block_size == 16
    assert c.max_context == 64 and c.max_queue is None
    assert c.hbm_budget > 0          # probed from /proc/meminfo


def test_env_beats_default(monkeypatch):
    monkeypatch.setenv(MEGASTEP_ENV, "3")
    monkeypatch.setenv(HOST_POOL_ENV, "1M")
    monkeypatch.setenv(FAULT_SEED_ENV, "17")
    monkeypatch.setenv(MEM_BUDGET_ENV, "512M")
    c = EngineConfig()
    assert c.megastep == 3
    assert c.host_pool == 1 << 20
    assert c.fault_seed == 17
    assert c.hbm_budget == 512 << 20


def test_explicit_beats_env_including_falsy(monkeypatch):
    """The PR-8 contract: an explicit 0 / None wins over a set env var
    — passing the field at all IS the explicit choice."""
    monkeypatch.setenv(MEGASTEP_ENV, "3")
    monkeypatch.setenv(HOST_POOL_ENV, "256M")
    monkeypatch.setenv(FAULT_SEED_ENV, "17")
    c = EngineConfig(megastep=1, host_pool=0, fault_seed=None)
    assert c.megastep == 1
    assert c.host_pool == 0          # explicit 0 disables the tier
    assert c.fault_seed is None      # explicit None disarms faults


def test_byte_suffix_strings_accepted(monkeypatch):
    monkeypatch.delenv(HOST_POOL_ENV, raising=False)
    c = EngineConfig(hbm_budget="512M", host_pool="64K")
    assert c.hbm_budget == 512 << 20
    assert c.host_pool == 64 << 10


def test_bad_env_value_names_the_var(monkeypatch):
    monkeypatch.setenv(MEGASTEP_ENV, "soon")
    with pytest.raises(ValueError, match=MEGASTEP_ENV):
        EngineConfig()


def test_frozen_and_comparable():
    a, b = EngineConfig(hbm_budget=1 << 30), EngineConfig(hbm_budget=1 << 30)
    assert a == b
    with pytest.raises(Exception):
        a.megastep = 4


# -- validation --------------------------------------------------------------

@pytest.mark.parametrize("kw", [
    dict(hbm_budget=0), dict(margin=1.0), dict(margin=-0.1),
    dict(host_pool=-1), dict(max_batch=0), dict(prefill_chunk=0),
    dict(block_size=0), dict(megastep=0), dict(max_context=0),
    dict(max_queue=-1), dict(dispatch_retries=-1),
    dict(retry_backoff_s=-0.5),
])
def test_validation_rejects(kw):
    with pytest.raises(ValueError, match="EngineConfig"):
        EngineConfig(**kw)


def test_max_context_none_means_dynamic():
    assert EngineConfig(max_context=None).max_context is None
    assert EngineConfig(max_context="none").max_context is None


# -- CLI generation ----------------------------------------------------------

def _parse(argv):
    ap = argparse.ArgumentParser()
    EngineConfig.add_cli_args(ap)
    return ap.parse_args(argv)


def test_cli_flags_cover_every_field():
    args = _parse([])
    for name, _, _, _ in EngineConfig.field_specs():
        assert hasattr(args, name), f"--{name.replace('_', '-')} missing"
        assert getattr(args, name) is None    # absent = UNSET


def test_cli_roundtrip_and_precedence(monkeypatch):
    monkeypatch.setenv(MEGASTEP_ENV, "3")
    monkeypatch.setenv(HOST_POOL_ENV, "256M")
    args = _parse(["--max-batch", "5", "--host-pool", "0",
                   "--hbm-budget", "128M", "--no-prefix-sharing",
                   "--max-context", "none"])
    c = EngineConfig.from_cli_args(args)
    assert c.max_batch == 5
    assert c.host_pool == 0          # flag beats env
    assert c.megastep == 3           # absent flag falls to env
    assert c.hbm_budget == 128 << 20
    assert c.prefix_sharing is False
    assert c.max_context is None
    d = EngineConfig.from_cli_args(args, max_batch=9)
    assert d.max_batch == 9          # overrides beat flags


def test_cli_help_documents_env_and_default():
    ap = argparse.ArgumentParser(prog="x")
    EngineConfig.add_cli_args(ap)
    text = ap.format_help()
    assert MEGASTEP_ENV in text and HOST_POOL_ENV in text
    assert "--megastep" in text and "--no-prefix-sharing" in text


# -- deprecated kwarg shim (constructor-level, no model) ---------------------

def test_shim_conflict_detection():
    from repro.runtime.engine import _shim_config
    with pytest.raises(ValueError, match="config= and"):
        _shim_config(EngineConfig(hbm_budget=1), dict(max_batch=4),
                     "ContinuousEngine")


def test_shim_legacy_path_warns_and_resolves():
    from repro.runtime.engine import _shim_config
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        c = _shim_config(None, dict(max_batch=4, megastep=None),
                         "ContinuousEngine")
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    assert c.max_batch == 4
    assert c.megastep == 8           # None = unset -> env/default


def test_shim_config_path_silent():
    from repro.runtime.engine import _shim_config
    conf = EngineConfig(hbm_budget=1 << 20)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        out = _shim_config(conf, dict(max_batch=None), "ContinuousEngine")
    assert out is conf
    assert not w
