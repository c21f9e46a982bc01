"""EngineConfig: the serving engine's constructor surface as data.

``ServingEngine`` grew ~25 keyword arguments across the paging, sharding,
speculative, continuous-batching and fault-tolerance PRs — every new
subsystem widened one ``__init__`` and every caller hand-rolled the same
flag->kwarg block.  This module is the redesigned surface: four frozen
dataclasses group the knobs by subsystem, composed into one ``EngineConfig``
that is the ONLY configuration object the engine accepts —

    ServingEngine(cfg, params, config=EngineConfig(
        max_len=256,
        cache=CacheConfig(kv_dtype="int8", page_size=16),
        scheduler=SchedulerConfig(prefill_chunk=32),
    ))

``plan`` (the compressed WeightPlan) and ``sizer`` (the BatchSizer) stay
first-class engine arguments: they are serving *data*, not configuration.

Three construction paths cover every caller:

* ``EngineConfig(...)`` — nested, for humans writing configs by hand;
* ``EngineConfig.of(**flat)`` — flat keyword names routed into the right
  sub-config (``EngineConfig.of(page_size=16, prefill_chunk=32)``), the
  mechanical port for the old call sites, with ``.flat()`` as its inverse;
* ``config_from_args(ns)`` — one argparse-namespace adapter shared by
  ``launch/serve.py`` and ``tools/autotune.py``, replacing their
  hand-rolled flag->kwarg blocks.

Legacy ``ServingEngine(**kwargs)`` calls still work through
``EngineConfig.from_legacy`` (a deprecation shim: warns once per process,
then routes through ``.of``), so out-of-tree callers keep serving while
they migrate.  ``tools/check_engine_api.py`` lints the engine signature so
new knobs land in these dataclasses instead of re-growing ``__init__``.
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Any, Callable, Optional


def positional_state_gate(cfg, feature: str) -> Optional[str]:
    """THE gate for features that need multi-token decode on positionally-
    addressed caches (``api.supports_spec_decode``): speculative decode and
    prefix sharing write or map positions and rely on position masking or
    position identity, which state a step rewrites in place cannot give (a
    rejected draft's update cannot be undone; a shared prefix's state is
    not in its pages).  Returns None when ``cfg`` qualifies, else the one
    shared error text."""
    from repro.models.api import supports_spec_decode

    if supports_spec_decode(cfg):
        return None
    return (f"{cfg.name}: {feature} needs multi-token decode on a "
            f"positionally-addressed cache ({cfg.family} does not qualify)")


def chunked_prefill_gate(cfg) -> Optional[str]:
    """The gate for chunked prefill (``api.supports_chunked_prefill``):
    positionally-addressed stacks, and stacks whose state is carried from
    chunk to chunk exactly (Mamba-2 beside paged attention).  Returns None
    when ``cfg`` qualifies, else the error text."""
    from repro.models.api import supports_chunked_prefill

    if supports_chunked_prefill(cfg):
        return None
    return (f"{cfg.name}: chunked prefill needs a positionally-addressed "
            f"cache or state carried exactly from chunk to chunk "
            f"({cfg.family} does not qualify)")


@dataclasses.dataclass(frozen=True)
class CacheConfig:
    """KV-cache geometry: dtype, paging, prefix sharing."""

    kv_dtype: Any = None  # "int8" / jnp.int8 selects the quantized cache
    page_size: Optional[int] = None  # tokens/page: selects the paged cache
    num_pages: Optional[int] = None  # pool capacity (None: contiguous parity)
    share_prefix: bool = False  # map common prompt prefixes copy-on-write
    expected_context: Optional[int] = None  # mean (S + max_new) for the sizer
    # mixed-family serving (serving/mixed.py): a shared PageAllocator makes
    # several engines draw pages from ONE capacity pool — each family keeps
    # its own physical pools, but a page id is owned by exactly one family
    # at a time, so shared-capacity accounting (and the audit) stay exact.
    allocator: Any = None


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    """Admission, chunked prefill, deadlines, retries, eviction."""

    prefill_chunk: Optional[int] = None  # C-token chunks (None: synchronous)
    prefill_budget: Optional[int] = None  # prompt tokens/tick across jobs
    evict_policy: str = "fifo"  # "fifo" back-pressure | "priority" preempt
    request_timeout_s: Optional[float] = None  # default total deadline
    ttft_deadline_s: Optional[float] = None  # default TTFT deadline
    max_retries: int = 1  # transient-failure retries per request
    retry_backoff_s: float = 0.0  # backoff base (doubles per retry)
    deadline_slack_s: float = 0.0  # TTFT pressure window for preemption


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Speculative decode: the draft model and the acceptance fallback."""

    draft_cfg: Any = None
    draft_params: Any = None
    spec_k: int = 0  # draft tokens per tick (0 = plain decode)
    fallback_accept: Optional[float] = None  # EMA floor; None = off
    fallback_min_ticks: int = 8  # spec ticks before the EMA check

    def validated_k(self, cfg) -> int:
        """The effective spec_k for a target ``cfg``: the single validated
        check the engine's spec path runs (ISSUE: the gate used to live in
        two places with drifting error text).  Raises on structural misuse
        (missing draft, vocab mismatch); warns and returns 0 when either
        model's cache family disqualifies speculation."""
        k = int(self.spec_k or 0)
        if not k:
            return 0
        if self.draft_cfg is None or self.draft_params is None:
            raise ValueError("spec_k > 0 needs draft_cfg and draft_params")
        reasons = [r for r in (
            positional_state_gate(cfg, "speculative decode"),
            positional_state_gate(self.draft_cfg, "speculative decode"),
        ) if r]
        if reasons:
            warnings.warn(
                "; ".join(reasons) + "; serving without speculation",
                stacklevel=3)
            return 0
        if self.draft_cfg.vocab != cfg.vocab:
            raise ValueError(
                f"draft vocab {self.draft_cfg.vocab} != target vocab "
                f"{cfg.vocab}: verification compares token ids")
        return k


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """Fault tolerance: watchdog, chaos injection, clock, paranoia."""

    watchdog_timeout_s: Optional[float] = None  # HeartbeatMonitor stall
    fault_injector: Any = None  # serving/faultinject.FaultInjector
    clock: Callable[[], float] = time.monotonic
    audit_every_step: bool = False  # PageAllocator.audit() each tick


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """The whole serving surface: top-level shape/placement knobs plus the
    four subsystem configs."""

    max_len: int = 256
    max_batch: Optional[int] = None
    mesh: Any = None  # jax Mesh: shard params/caches via the registry
    rules: Optional[dict] = None  # logical->physical overrides
    seed: int = 0
    cache: CacheConfig = CacheConfig()
    scheduler: SchedulerConfig = SchedulerConfig()
    spec: SpecConfig = SpecConfig()
    fault: FaultConfig = FaultConfig()

    @classmethod
    def of(cls, **flat) -> "EngineConfig":
        """Build from flat keyword names (the legacy ``ServingEngine``
        kwargs), routing each into its sub-config.  Whole sub-configs may
        also be passed (``of(max_len=64, cache=CacheConfig(...))``)."""
        groups: dict = {"cache": {}, "scheduler": {}, "spec": {}, "fault": {}}
        top: dict = {}
        for name, value in flat.items():
            if name in ("cache", "scheduler", "spec", "fault"):
                top[name] = value
                continue
            dest = _FLAT_FIELDS.get(name)
            if dest is None:
                raise TypeError(f"unknown engine config field {name!r}")
            group, field = dest
            if group is None:
                top[field] = value
            else:
                groups[group][field] = value
        for group, cls_g in (("cache", CacheConfig),
                             ("scheduler", SchedulerConfig),
                             ("spec", SpecConfig), ("fault", FaultConfig)):
            if groups[group]:
                if group in top:
                    top[group] = dataclasses.replace(
                        top[group], **groups[group])
                else:
                    top[group] = cls_g(**groups[group])
        return cls(**top)

    def flat(self) -> dict:
        """Inverse of ``of``: the full flat-name -> value mapping (property-
        tested round-trip in tests/test_engine_config.py)."""
        out = {}
        for name, (group, field) in _FLAT_FIELDS.items():
            src = self if group is None else getattr(self, group)
            out[name] = getattr(src, field)
        return out

    @classmethod
    def from_legacy(cls, **flat) -> "EngineConfig":
        """Deprecation shim for ``ServingEngine(**legacy_kwargs)``: same
        routing as ``of``, plus a once-per-process DeprecationWarning."""
        global _LEGACY_WARNED
        if not _LEGACY_WARNED:
            warnings.warn(
                "passing ServingEngine configuration as loose keyword "
                "arguments is deprecated; pass "
                "config=EngineConfig(...)/EngineConfig.of(...) "
                "(repro/serving/config.py)",
                DeprecationWarning, stacklevel=4)
            _LEGACY_WARNED = True
        return cls.of(**flat)


_LEGACY_WARNED = False

# legacy flat kwarg name -> (sub-config, field); None routes to EngineConfig
# itself.  Generated from the dataclass fields so the shim can never drift
# from the real surface; the two spec_* renames keep the historical names.
_FLAT_FIELDS: dict = {}
for _f in dataclasses.fields(EngineConfig):
    if _f.name not in ("cache", "scheduler", "spec", "fault"):
        _FLAT_FIELDS[_f.name] = (None, _f.name)
for _group, _cls in (("cache", CacheConfig), ("scheduler", SchedulerConfig),
                     ("spec", SpecConfig), ("fault", FaultConfig)):
    for _f in dataclasses.fields(_cls):
        _FLAT_FIELDS[_f.name] = (_group, _f.name)
_FLAT_FIELDS["spec_fallback_accept"] = ("spec", "fallback_accept")
_FLAT_FIELDS["spec_fallback_min_ticks"] = ("spec", "fallback_min_ticks")


def config_from_args(ns, *, mesh=None, rules=None, clock=None,
                     expected_context=None, draft_cfg=None,
                     draft_params=None) -> EngineConfig:
    """The ONE argparse-namespace -> EngineConfig adapter, shared by
    ``launch/serve.py`` and ``tools/autotune.py`` (previously three
    hand-rolled flag->kwarg blocks).  Flags use 0/"" as "unset" for
    numeric/string knobs; missing attributes fall back to the dataclass
    defaults, so a parser only needs the flags it actually exposes.
    Objects argparse cannot carry (mesh, clock, draft params, the sizer's
    expected context) come in as keyword arguments."""

    def get(name, default=None):
        return getattr(ns, name, default)

    return EngineConfig(
        max_len=int(get("max_len", 256) or 256),
        max_batch=int(get("max_batch") or 0) or None,
        mesh=mesh,
        rules=rules,
        seed=int(get("seed", 0) or 0),
        cache=CacheConfig(
            kv_dtype="int8" if get("kv_dtype") == "int8" else None,
            page_size=int(get("page_size") or 0) or None,
            num_pages=int(get("pool_pages") or 0) or None,
            share_prefix=bool(get("share_prefix", False)),
            expected_context=expected_context,
        ),
        scheduler=SchedulerConfig(
            prefill_chunk=int(get("prefill_chunk") or 0) or None,
            prefill_budget=int(get("prefill_budget") or 0) or None,
            evict_policy=get("evict_policy", "fifo") or "fifo",
            request_timeout_s=float(get("request_timeout") or 0) or None,
            ttft_deadline_s=float(get("ttft_deadline") or 0) or None,
            max_retries=int(get("max_retries", 1)),
        ),
        spec=SpecConfig(
            draft_cfg=draft_cfg,
            draft_params=draft_params,
            spec_k=int(get("spec_k") or 0) if draft_cfg is not None else 0,
        ),
        fault=FaultConfig(clock=clock) if clock is not None else FaultConfig(),
    )
