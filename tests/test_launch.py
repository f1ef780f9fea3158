"""Launch helpers: where the compilation cache lives, and the chip peaks table."""
import dataclasses
import pathlib

import jax
import pytest

from repro.launch import compile_cache
from repro.launch.peaks import PEAKS, V5E, peaks


def test_compile_cache_follows_env(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it and no code sets another."""
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    want = pathlib.Path(__file__).resolve().parents[1] / ".jax_cache"
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable() == str(want)
        assert jax.config.jax_compilation_cache_dir == str(want)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_peaks_are_the_published_v5e_figures():
    p = peaks(V5E)
    assert (p.bf16_flops, p.int8_ops, p.hbm_bw) == (197e12, 393e12, 819e9)
    assert list(PEAKS) == [V5E]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")


def test_bench_model_cache_is_keyed_on_config_steps_and_seed(monkeypatch):
    from benchmarks import common

    key = common.bench_model_key(400)
    assert key == common.bench_model_key(400)
    assert key != common.bench_model_key(200)
    monkeypatch.setattr(common, "SEED", 1)
    assert key != common.bench_model_key(400)
    monkeypatch.setattr(common, "SEED", 0)
    monkeypatch.setattr(common, "BENCH_CFG",
                        dataclasses.replace(common.BENCH_CFG, n_layers=2))
    assert key != common.bench_model_key(400)
