"""EngineConfig surface (DESIGN.md §3.11): every invalid knob combination
raises the same typed error through ``EngineConfig`` as through the legacy
kwarg path, the deprecation shim is parity-exact (same served tokens, exactly
one warning), and JSON round-trips are lossless."""
import argparse
import dataclasses
import json
import warnings

import jax
import numpy as np
import pytest

from repro.configs import get
from repro.models import model as M
from repro.serving import engine as E
from repro.serving.config import (ChunkedStateError, EngineConfig, EngineStats,
                                  PrefixReuseStateError, SpeculativeStateError,
                                  UnsupportedModelError, add_config_args,
                                  config_from_args)


@pytest.fixture(scope="module")
def small():
    cfg = dataclasses.replace(get("starcoder2-7b", smoke=True), dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


# Every cross-field invalid combination, with the error-message fragment both
# surfaces must raise (pure-config checks: no model needed).
BAD_COMBOS = [
    (dict(batch_size=0, max_len=32), "batch_size"),
    (dict(batch_size=2, max_len=0), "max_len"),
    (dict(batch_size=2, max_len=32, path="nope"), "unknown serving path"),
    (dict(batch_size=2, max_len=32, kv_cache="int4"), "kv_cache"),
    (dict(batch_size=2, max_len=32, cache_layout="ragged"), "cache_layout"),
    (dict(batch_size=2, max_len=32, scheduler="fifo"), "scheduler"),
    (dict(batch_size=2, max_len=32, page_size=0), "page_size"),
    (dict(batch_size=2, max_len=32, cache_layout="paged",
          scheduler="grouped"), "grouped baseline stays dense"),
    (dict(batch_size=2, max_len=32, chunked=True),
     "needs cache_layout='paged'"),
    (dict(batch_size=4, max_len=32, cache_layout="paged", chunked=True,
          token_budget=2), "token_budget"),
    (dict(batch_size=2, max_len=32, speculate=0), "speculate"),
    (dict(batch_size=2, max_len=32, speculate=2, temperature=0.7),
     "greedy sampling"),
    (dict(batch_size=2, max_len=32, speculate=2, scheduler="grouped"),
     "continuous scheduler"),
]


@pytest.mark.parametrize("kw,msg", BAD_COMBOS,
                         ids=[m.split("'")[0].strip()[:24].replace(" ", "-")
                              for _, m in BAD_COMBOS])
def test_invalid_combo_same_error_both_surfaces(small, kw, msg):
    cfg, params = small
    with pytest.raises(ValueError, match=msg) as via_config:
        EngineConfig(**kw)
    with pytest.raises(ValueError, match=msg) as via_legacy:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            E.ServeEngine(cfg, params, **kw)
    assert str(via_config.value) == str(via_legacy.value)


# One entry per typed rejection reason (DESIGN.md §3.13): speculative decoding
# cannot rewind the recurrence, radix prefix reuse cannot restart it mid-prompt,
# and chunked serving cannot scatter it positionally. Everything else —
# continuous, paged (without reuse), grouped, sharded — serves SSM/hybrid.
STATE_REJECTIONS = [
    (dict(batch_size=2, max_len=32, speculate=2), SpeculativeStateError,
     "rewind"),
    (dict(batch_size=2, max_len=32, cache_layout="paged"),
     PrefixReuseStateError, "prefix_reuse=False"),
    (dict(batch_size=4, max_len=32, cache_layout="paged", prefix_reuse=False,
          chunked=True, token_budget=16), ChunkedStateError, "ragged chunks"),
]


@pytest.mark.parametrize("family", ["mamba2-130m", "zamba2-1.2b"])
@pytest.mark.parametrize("kw,err,msg", STATE_REJECTIONS,
                         ids=[e.__name__ for _, e, _ in STATE_REJECTIONS])
def test_family_checks_need_the_model(kw, err, msg, family):
    """SSM/hybrid restrictions live in check_model (the pure config cannot see
    the family), raise one typed UnsupportedModelError subclass per reason,
    and fire identically through both engine surfaces."""
    ssm = dataclasses.replace(get(family, smoke=True), dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), ssm)
    config = EngineConfig(**kw)           # pure-config validation passes
    with pytest.raises(err, match=msg):
        config.check_model(ssm)
    with pytest.raises(err, match=msg):
        E.ServeEngine(ssm, params, config=config)
    with pytest.raises(err, match=msg):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            E.ServeEngine(ssm, params, **kw)
    # typed errors stay catchable as plain ValueError (pre-§3.13 callers)
    assert issubclass(err, UnsupportedModelError)
    assert issubclass(err, ValueError)


def test_state_families_pass_relaxed_check():
    """Continuous + paged-without-reuse + grouped all pass check_model for
    SSM/hybrid now (§3.13) — the pre-§3.13 blanket chunked/speculate rejection
    must not have left collateral rejections behind."""
    for family in ("mamba2-130m", "zamba2-1.2b"):
        cfg = get(family, smoke=True)
        for kw in (dict(batch_size=2, max_len=32),
                   dict(batch_size=2, max_len=32, cache_layout="paged",
                        prefix_reuse=False),
                   dict(batch_size=2, max_len=32, scheduler="grouped")):
            EngineConfig(**kw).check_model(cfg)   # must not raise


def test_unknown_field_typeerror(small):
    cfg, params = small
    with pytest.raises(TypeError, match="blocksize"):
        EngineConfig.from_kwargs(batch_size=2, max_len=32, blocksize=9)
    with pytest.raises(TypeError, match="blocksize"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            E.ServeEngine(cfg, params, batch_size=2, max_len=32, blocksize=9)


def test_config_plus_legacy_kwargs_typeerror(small):
    cfg, params = small
    config = EngineConfig(batch_size=2, max_len=32)
    with pytest.raises(TypeError, match="not both"):
        E.ServeEngine(cfg, params, config=config, batch_size=2)


def test_shim_parity_and_warns_once(small):
    """The legacy kwarg surface builds the identical engine (token-for-token)
    and emits exactly one DeprecationWarning per process."""
    cfg, params = small
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab, size=n).astype(np.int32)
               for n in (5, 9, 6)]
    kw = dict(batch_size=2, max_len=32, kv_cache="int8", cache_layout="paged",
              page_size=8)

    new = E.ServeEngine(cfg, params, config=EngineConfig(**kw))
    new.submit([p.copy() for p in prompts], max_new=5)
    want = {r.rid: r.out for r in new.run()}

    E._LEGACY_KWARGS_WARNED = False
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        old = E.ServeEngine(cfg, params, **kw)
        E.ServeEngine(cfg, params, batch_size=2, max_len=32)   # second build
    dep = [w for w in caught if issubclass(w.category, DeprecationWarning)]
    assert len(dep) == 1, "shim must warn exactly once per process"
    assert "EngineConfig" in str(dep[0].message)
    assert old.config == new.config          # shim built the identical config
    old.submit([p.copy() for p in prompts], max_new=5)
    got = {r.rid: r.out for r in old.run()}
    assert got == want


def test_json_round_trip_lossless():
    cfg = EngineConfig(batch_size=4, max_len=64, eos_id=7, path="fused-int8",
                       kv_cache="int8", cache_layout="paged", page_size=4,
                       n_pages=48, prefix_reuse=False, cache_dtype="bfloat16",
                       prefill_buckets=(8, 16, 64), chunked=True,
                       token_budget=16, speculate=4, drafter_ngram=2, seed=3)
    assert EngineConfig.from_json(cfg.to_json()) == cfg
    assert EngineConfig.from_dict(json.loads(cfg.to_json(indent=2))) == cfg
    # JSON lists normalize back to the tuple field, dtype to its canonical name
    loud = dict(cfg.to_dict(), prefill_buckets=[8, 16, 64],
                cache_dtype="bfloat16")
    assert EngineConfig.from_dict(loud) == cfg


def test_cli_flags_derive_from_fields():
    """add_config_args exposes every dataclass field; config_from_args layers
    explicit flags over a --config base over script defaults."""
    ap = argparse.ArgumentParser()
    add_config_args(ap)
    helptext = ap.format_help()
    for f in dataclasses.fields(EngineConfig):
        assert f"--{f.name.replace('_', '-')}" in helptext, f.name
    base = EngineConfig(batch_size=2, max_len=32, cache_layout="paged",
                        kv_cache="fp")
    args = ap.parse_args(["--kv-cache", "int8", "--prefill-buckets", "8,32",
                          "--no-prefix-reuse"])
    got = config_from_args(args, base=base)
    assert got.kv_cache == "int8"            # explicit flag wins
    assert got.cache_layout == "paged"       # from the base config
    assert got.prefill_buckets == (8, 32)
    assert got.prefix_reuse is False
    # unset flags never clobber the base
    assert got.batch_size == 2 and got.max_len == 32


def test_stats_accessors_delegate(small):
    """stats() derives its four rates from the raw counters, and to_dict()
    flattens derived rates + raw counters into one stable schema."""
    cfg, params = small
    eng = E.ServeEngine(cfg, params,
                        config=EngineConfig(batch_size=2, max_len=32,
                                            cache_layout="paged"))
    rng = np.random.default_rng(1)
    shared = rng.integers(1, cfg.vocab, size=8).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rng.integers(1, cfg.vocab, size=4 + i)
                               .astype(np.int32)]) for i in range(3)]
    eng.submit(prompts, max_new=4)
    eng.run()
    st = eng.stats()
    assert isinstance(st, EngineStats)
    c = eng.counters
    assert st.occupancy == c["active_slot_steps"] / (c["decode_steps"] * eng.B)
    assert st.prefix_hit_rate == c["prefix_tokens_reused"] / c["prompt_tokens"] > 0.0
    assert st.accept_rate == 0.0 == c["spec_drafted"]      # no speculation
    assert st.tokens_per_step == 0.0 == c["spec_slot_steps"]
    d = st.to_dict()
    assert d["prefix_hit_rate"] == st.prefix_hit_rate
    for k, v in eng.counters.items():
        assert d[k] == v
