"""EngineConfig surface (serving/config.py): flat-name routing round-trips,
the legacy-kwarg deprecation shim, the argparse adapter, and the tuned-plan
adapter — the whole redesigned constructor surface of ServingEngine."""

import argparse
import dataclasses

import jax
import pytest
from hypothesis import given, settings, strategies as st

import repro.configs as C
import repro.serving.config as SC
from repro.core import autotune as AT
from repro.models.api import get_api
from repro.serving.config import (
    CacheConfig,
    EngineConfig,
    FaultConfig,
    SchedulerConfig,
    SpecConfig,
    config_from_args,
)
from repro.serving.engine import ServingEngine


def _tiny_engine(**kw):
    cfg = C.get_config("tinyllama-1.1b", smoke=True)
    api = get_api(cfg)
    params = api.init_params(cfg, jax.random.key(0))
    return ServingEngine(cfg, params, **kw)


# canonical flat names with value pools the engine accepts structurally
# (the property only exercises routing, never engine construction)
_FLAT_POOLS = {
    "max_len": [1, 32, 512],
    "max_batch": [None, 1, 64],
    "seed": [0, 7, 2**31 - 1],
    "kv_dtype": [None, "int8"],
    "page_size": [None, 8, 64],
    "num_pages": [None, 2, 4096],
    "share_prefix": [False, True],
    "expected_context": [None, 1, 512],
    "prefill_chunk": [None, 1, 64],
    "prefill_budget": [None, 1, 256],
    "evict_policy": ["fifo", "priority"],
    "request_timeout_s": [None, 0.5, 60.0],
    "ttft_deadline_s": [None, 0.5, 60.0],
    "max_retries": [0, 1, 5],
    "retry_backoff_s": [0.0, 0.25, 5.0],
    "deadline_slack_s": [0.0, 0.25, 5.0],
    "spec_k": [0, 4, 8],
    "fallback_accept": [None, 0.0, 0.7],
    "fallback_min_ticks": [1, 8, 64],
    "watchdog_timeout_s": [None, 0.5, 60.0],
    "audit_every_step": [False, True],
}


def _draw_flat(seed: int) -> dict:
    """A seeded random subset of the canonical flat fields with values from
    each field's pool — same property coverage under hypothesis or the
    seeded-example fallback, no strategy combinators needed."""
    import numpy as np

    rng = np.random.default_rng(seed)
    names = sorted(_FLAT_POOLS)
    picked = rng.choice(len(names), size=rng.integers(0, 9), replace=False)
    return {names[i]: _FLAT_POOLS[names[i]][
        rng.integers(0, len(_FLAT_POOLS[names[i]]))] for i in picked}


def _check_round_trip(seed: int):
    """of(**kw).flat() == defaults overridden by exactly kw — every flat
    name routes into the right sub-config and back out unchanged."""
    kw = _draw_flat(seed)
    expect = EngineConfig().flat()
    expect.update(kw)
    # the two spec_* aliases mirror their canonical fields
    expect["spec_fallback_accept"] = expect["fallback_accept"]
    expect["spec_fallback_min_ticks"] = expect["fallback_min_ticks"]
    assert EngineConfig.of(**kw).flat() == expect


class TestFlatRouting:
    @settings(max_examples=50, deadline=None)
    @given(seed=st.integers(0, 2**32 - 1))
    def test_of_flat_round_trip(self, seed):
        _check_round_trip(seed)

    def test_of_flat_round_trip_examples(self):
        # seeded examples so the property runs even without hypothesis
        for seed in range(40):
            _check_round_trip(seed)

    def test_legacy_spec_aliases_route(self):
        ec = EngineConfig.of(spec_fallback_accept=0.25,
                             spec_fallback_min_ticks=3)
        assert ec.spec.fallback_accept == 0.25
        assert ec.spec.fallback_min_ticks == 3

    def test_of_accepts_whole_subconfigs(self):
        cache = CacheConfig(page_size=16)
        ec = EngineConfig.of(max_len=64, cache=cache, prefill_chunk=8)
        assert ec.cache is cache
        assert ec.scheduler.prefill_chunk == 8

    def test_of_merges_flat_into_passed_subconfig(self):
        ec = EngineConfig.of(cache=CacheConfig(page_size=16), kv_dtype="int8")
        assert ec.cache.page_size == 16 and ec.cache.kv_dtype == "int8"

    def test_unknown_field_raises(self):
        with pytest.raises(TypeError, match="unknown engine config field"):
            EngineConfig.of(page_sized=16)

    def test_subconfigs_are_frozen(self):
        for cls in (EngineConfig, CacheConfig, SchedulerConfig, SpecConfig,
                    FaultConfig):
            with pytest.raises(dataclasses.FrozenInstanceError):
                setattr(cls(), "new_knob", 1)


class TestLegacyShim:
    def test_legacy_kwargs_warn_once_and_serve(self):
        SC._LEGACY_WARNED = False
        with pytest.warns(DeprecationWarning, match="EngineConfig"):
            eng = _tiny_engine(max_len=32, max_batch=2, page_size=8)
        assert eng.paged and eng.max_len == 32 and eng.max_batch == 2
        # once per process: the second legacy call is silent
        import warnings as W

        with W.catch_warnings():
            W.simplefilter("error", DeprecationWarning)
            eng2 = _tiny_engine(max_len=16, max_batch=1)
        assert eng2.max_len == 16

    def test_config_and_legacy_together_is_a_type_error(self):
        with pytest.raises(TypeError, match="not both"):
            _tiny_engine(config=EngineConfig(max_len=32), max_batch=2)

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 2**32 - 1))
    def test_from_legacy_equals_of(self, seed):
        """The shim is .of plus a warning — never a different routing."""
        kw = _draw_flat(seed)
        SC._LEGACY_WARNED = True  # silence; warning behavior tested above
        assert EngineConfig.from_legacy(**kw) == EngineConfig.of(**kw)

    def test_from_legacy_equals_of_examples(self):
        SC._LEGACY_WARNED = True
        for seed in range(20):
            kw = _draw_flat(seed)
            assert EngineConfig.from_legacy(**kw) == EngineConfig.of(**kw)


class TestConfigFromArgs:
    def test_maps_serve_style_flags(self):
        ns = argparse.Namespace(
            max_len=128, max_batch=4, seed=7, kv_dtype="int8", page_size=16,
            pool_pages=99, share_prefix=True, prefill_chunk=8,
            prefill_budget=32, evict_policy="priority", request_timeout=2.5,
            ttft_deadline=1.0, max_retries=3, spec_k=4)
        ec = config_from_args(ns, expected_context=20)
        assert ec.max_len == 128 and ec.max_batch == 4 and ec.seed == 7
        assert ec.cache == CacheConfig(kv_dtype="int8", page_size=16,
                                       num_pages=99, share_prefix=True,
                                       expected_context=20)
        assert ec.scheduler.prefill_chunk == 8
        assert ec.scheduler.prefill_budget == 32
        assert ec.scheduler.evict_policy == "priority"
        assert ec.scheduler.request_timeout_s == 2.5
        assert ec.scheduler.ttft_deadline_s == 1.0
        assert ec.scheduler.max_retries == 3
        # spec_k without a draft model is dropped, not smuggled through
        assert ec.spec.spec_k == 0

    def test_zero_means_unset(self):
        ns = argparse.Namespace(max_len=64, page_size=0, pool_pages=0,
                                prefill_chunk=0, request_timeout=0.0)
        ec = config_from_args(ns)
        assert ec.cache.page_size is None and ec.cache.num_pages is None
        assert ec.scheduler.prefill_chunk is None
        assert ec.scheduler.request_timeout_s is None

    def test_sparse_namespace_falls_back_to_defaults(self):
        ec = config_from_args(argparse.Namespace(max_len=64))
        assert ec == EngineConfig(max_len=64)

    def test_clock_and_draft_route(self):
        clk = lambda: 0.0  # noqa: E731
        draft = C.get_config("tinyllama-1.1b", smoke=True)
        ec = config_from_args(
            argparse.Namespace(max_len=64, spec_k=2), clock=clk,
            draft_cfg=draft, draft_params={"w": 1})
        assert ec.fault.clock is clk
        assert ec.spec.spec_k == 2 and ec.spec.draft_cfg is draft


class TestTunedPlanAdapter:
    DOC = {"serving": {"max_batch": 8, "max_len": 64, "kv_dtype": "int8",
                       "page_size": 16, "num_pages": 40,
                       "expected_context": 24, "spec_k": 3}}

    def test_engine_config_routes_artifact(self):
        ec = AT.engine_config(self.DOC)
        assert ec.max_batch == 8 and ec.max_len == 64
        assert ec.cache == CacheConfig(kv_dtype="int8", page_size=16,
                                       num_pages=40, expected_context=24)
        assert ec.spec.spec_k == 0  # no draft supplied -> dropped

    def test_engine_config_overrides_win(self):
        draft = C.get_config("tinyllama-1.1b", smoke=True)
        ec = AT.engine_config(self.DOC, max_len=128, draft_cfg=draft,
                              draft_params={"w": 1})
        assert ec.max_len == 128
        assert ec.spec.spec_k == 3 and ec.spec.draft_cfg is draft
