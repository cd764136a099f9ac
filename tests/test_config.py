"""Config system: MATREL_* env overrides, dict overrides, and the
shared Pallas gates (SURVEY.md §5 "Config / flag system")."""

import numpy as np
import pytest

from matrel_tpu.config import (MatrelConfig, pallas_enabled,
                               pallas_interpret_mode, resolve_interpret)


class TestFromEnv:
    def test_typed_overrides(self, monkeypatch):
        monkeypatch.setenv("MATREL_BLOCK_SIZE", "128")
        monkeypatch.setenv("MATREL_SPARSITY_THRESHOLD", "0.25")
        monkeypatch.setenv("MATREL_USE_PALLAS", "false")
        monkeypatch.setenv("MATREL_STRATEGY_OVERRIDE", "cpmm")
        monkeypatch.setenv("MATREL_MESH_SHAPE", "2x4")
        cfg = MatrelConfig.from_env()
        assert cfg.block_size == 128
        assert cfg.sparsity_threshold == 0.25
        assert cfg.use_pallas is False
        assert cfg.strategy_override == "cpmm"
        assert cfg.mesh_shape == (2, 4)

    def test_bool_spellings(self, monkeypatch):
        for raw, want in [("1", True), ("true", True), ("YES", True),
                          ("on", True), ("0", False), ("off", False),
                          ("no", False)]:
            monkeypatch.setenv("MATREL_CHAIN_OPT", raw)
            assert MatrelConfig.from_env().chain_opt is want, raw

    def test_mesh_shape_comma_form(self, monkeypatch):
        monkeypatch.setenv("MATREL_MESH_SHAPE", "4,2")
        assert MatrelConfig.from_env().mesh_shape == (4, 2)

    def test_unset_env_keeps_base(self, monkeypatch):
        base = MatrelConfig(block_size=64)
        assert MatrelConfig.from_env(base).block_size == 64

    def test_round2_knobs_via_env(self, monkeypatch):
        monkeypatch.setenv("MATREL_PALLAS_INTERPRET", "1")
        monkeypatch.setenv("MATREL_JOIN_PAIR_CAP_ENTRIES", "1024")
        monkeypatch.setenv("MATREL_PLAN_CACHE_MAX_PLANS", "7")
        cfg = MatrelConfig.from_env()
        assert cfg.pallas_interpret is True
        assert cfg.join_pair_cap_entries == 1024
        assert cfg.plan_cache_max_plans == 7

    def test_round3_autotune_knobs_via_env(self, monkeypatch):
        monkeypatch.setenv("MATREL_AUTOTUNE", "true")
        monkeypatch.setenv("MATREL_AUTOTUNE_TABLE_PATH", "/tmp/t.json")
        monkeypatch.setenv("MATREL_AUTOTUNE_MAX_DIM", "2048")
        cfg = MatrelConfig.from_env()
        assert cfg.autotune is True
        assert cfg.autotune_table_path == "/tmp/t.json"
        assert cfg.autotune_max_dim == 2048


class TestFromDict:
    def test_valid_and_unknown_keys(self):
        cfg = MatrelConfig.from_dict({"block_size": 256,
                                      "use_pallas": False})
        assert cfg.block_size == 256 and cfg.use_pallas is False
        with pytest.raises(KeyError, match="unknown MatrelConfig keys"):
            MatrelConfig.from_dict({"blok_size": 1})


class TestPallasGates:
    # conftest pins the cpu backend, so the gates' backend term is False
    def test_gates_on_cpu(self):
        assert pallas_enabled(MatrelConfig()) is False
        assert pallas_enabled(MatrelConfig(pallas_interpret=True)) is True
        assert pallas_enabled(MatrelConfig(use_pallas=False,
                                           pallas_interpret=True)) is False
        assert pallas_interpret_mode(
            MatrelConfig(pallas_interpret=True)) is True
        assert pallas_interpret_mode(MatrelConfig()) is False

    def test_resolve_interpret_precedence(self):
        cfg_on = MatrelConfig(pallas_interpret=True)
        assert resolve_interpret(None, cfg_on) is True
        assert resolve_interpret(None, MatrelConfig()) is False
        assert resolve_interpret(False, cfg_on) is False   # explicit wins
        assert resolve_interpret(True, MatrelConfig()) is True


class TestAxisCostWeights:
    """Round 7 topology knob: validated at construction (a zero weight
    silently makes an axis free — worse than a crash), env-parseable in
    both mesh_shape spellings, normalised to a float tuple (the form
    every cache key embeds)."""

    def test_default_and_normalisation(self):
        assert MatrelConfig().axis_cost_weights == (1.0, 1.0)
        w = MatrelConfig(axis_cost_weights=(1, 8)).axis_cost_weights
        assert w == (1.0, 8.0)
        assert all(isinstance(v, float) for v in w)

    @pytest.mark.parametrize("bad", [(0.0, 1.0), (1.0, -2.0),
                                     (1.0,), (1.0, 2.0, 3.0),
                                     ("a", 1.0)])
    def test_invalid_rejected(self, bad):
        with pytest.raises((ValueError, TypeError)):
            MatrelConfig(axis_cost_weights=bad)

    def test_env_both_spellings(self, monkeypatch):
        monkeypatch.setenv("MATREL_AXIS_COST_WEIGHTS", "1,8")
        assert MatrelConfig.from_env().axis_cost_weights == (1.0, 8.0)
        monkeypatch.setenv("MATREL_AXIS_COST_WEIGHTS", "1.5x32")
        assert MatrelConfig.from_env().axis_cost_weights == (1.5, 32.0)


class TestDeviceStory:
    """One helper says "is this a TPU"; one function places the
    persistent compile cache (PR 22)."""

    def test_on_tpu_is_false_on_the_cpu(self):
        from matrel_tpu.config import on_tpu
        assert on_tpu() is False
        assert pallas_enabled(MatrelConfig()) is False

    @pytest.mark.parametrize("env_dir", [None, "/some/where/else"])
    def test_compile_cache_dir(self, monkeypatch, env_dir):
        """JAX_COMPILATION_CACHE_DIR set: it is used and no directory is
        set in code; unset: the fixed <checkout>/.jax_cache."""
        import os
        import jax
        import matrel_tpu
        from matrel_tpu.config import configure_compile_cache
        updates = []
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: updates.append((k, v)))
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(matrel_tpu.__file__))), ".jax_cache")
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
            want = env_dir
        assert configure_compile_cache() == want
        if env_dir is not None:
            assert updates == []
        else:
            assert all(u == ("jax_compilation_cache_dir", want)
                       for u in updates)
