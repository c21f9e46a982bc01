"""Fault-tolerant serving: lifecycle, deadlines, eviction, numeric
guardrails, degradation ladder, and the deterministic chaos harness.

Fast classes (no model compile) cover the state machine, the fault
schedule, the allocator audit (property-tested), and the speculative
payoff model.  Engine classes are slow-marked: they drive real
tinyllama-smoke engines through injected faults and assert the ISSUE's
acceptance bar — every request terminal, audit clean every tick, and
greedy streams of surviving requests bit-identical to fault-free runs.
"""

import warnings

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro.configs as C
from repro.core.batching import BatchSizer
from repro.models.api import get_api
from repro.serving.config import EngineConfig
from repro.serving.engine import (
    InvalidTransition,
    Request,
    RequestState,
    ServingEngine,
)
from repro.serving.faultinject import (
    Fault,
    FaultInjected,
    FaultInjector,
    TickClock,
    run_chaos,
    seeded_schedule,
)
from repro.serving.paged import PageAllocator, PageAuditError

ARCH = "tinyllama-1.1b"

_cache = {}


def _cfg_params(seed=0):
    if seed not in _cache:
        cfg = C.get_config(ARCH, smoke=True)
        api = get_api(cfg)
        _cache[seed] = (cfg, api, api.init_params(cfg, jax.random.key(seed)))
    return _cache[seed]


def _reqs(cfg, n, max_new=6, plen=8, seed=0, **kw):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, size=plen).astype(np.int32),
                    max_new_tokens=max_new, **kw) for i in range(n)]


def _clone(reqs):
    """Fresh Request objects with the same uid/prompt/budget (engines
    mutate their requests, so comparisons need independent copies)."""
    return [Request(uid=r.uid, prompt=r.prompt.copy(),
                    max_new_tokens=r.max_new_tokens, priority=r.priority)
            for r in reqs]


def _baseline_outputs(reqs, **engine_kw):
    cfg, api, params = _cfg_params()
    eng = ServingEngine(cfg, params, config=EngineConfig.of(
            **engine_kw))
    mine = _clone(reqs)
    for r in mine:
        eng.submit(r)
    eng.run_until_done()
    assert all(r.state is RequestState.FINISHED for r in mine)
    return {r.uid: list(r.output) for r in mine}


# ---------------------------------------------------------------------------
# fast: request lifecycle state machine


class TestLifecycle:
    def test_happy_path_transitions(self):
        r = Request(uid=0, prompt=np.zeros(2, np.int32), max_new_tokens=1)
        assert r.state is RequestState.QUEUED and not r.terminal
        r.transition(RequestState.PREFILLING)
        r.transition(RequestState.DECODING)
        r.transition(RequestState.FINISHED)
        assert r.terminal and r.done
        assert r.history == [RequestState.QUEUED, RequestState.PREFILLING,
                             RequestState.DECODING, RequestState.FINISHED]

    def test_eviction_detour_and_retry_reentry(self):
        r = Request(uid=0, prompt=np.zeros(2, np.int32), max_new_tokens=1)
        r.transition(RequestState.PREFILLING)
        r.transition(RequestState.DECODING)
        r.transition(RequestState.EVICTED)
        r.transition(RequestState.PREFILLING)  # readmission
        r.transition(RequestState.QUEUED, error="transient")  # retry path
        assert r.error == "transient"
        r.transition(RequestState.TIMED_OUT)
        assert r.terminal

    def test_terminal_states_are_closed(self):
        for term in (RequestState.FINISHED, RequestState.FAILED,
                     RequestState.TIMED_OUT):
            r = Request(uid=0, prompt=np.zeros(2, np.int32), max_new_tokens=1)
            r.state = term
            for new in RequestState:
                with pytest.raises(InvalidTransition):
                    r.transition(new)

    def test_illegal_edges_raise(self):
        r = Request(uid=0, prompt=np.zeros(2, np.int32), max_new_tokens=1)
        with pytest.raises(InvalidTransition):
            r.transition(RequestState.DECODING)  # must prefill first
        with pytest.raises(InvalidTransition):
            r.transition(RequestState.EVICTED)  # only live slots evict


# ---------------------------------------------------------------------------
# fast: fault schedule + injector + clock


class TestFaultSchedule:
    def test_fault_validation(self):
        with pytest.raises(ValueError):
            Fault("bogus", tick=1)
        with pytest.raises(ValueError):
            Fault("nan_logits", tick=0)
        with pytest.raises(ValueError):
            Fault("drop_tick", tick=1, n_ticks=0)

    def test_fault_active_window(self):
        f = Fault("drop_tick", tick=3, n_ticks=2)
        assert [f.active(t) for t in (2, 3, 4, 5)] == [False, True, True, False]

    def test_tick_clock_monotonic(self):
        clk = TickClock(10.0)
        assert clk() == 10.0
        clk.advance(2.5)
        assert clk() == 12.5
        with pytest.raises(ValueError):
            clk.advance(-1.0)

    def test_injector_hooks_and_log(self):
        clk = TickClock()
        fi = FaultInjector([
            Fault("drop_tick", tick=2), Fault("alloc_fail", tick=3),
            Fault("nan_logits", tick=4, uid=7),
            Fault("dead_draft", tick=5), Fault("kernel_fault", tick=6),
            Fault("slow_tick", tick=7, delay_s=4.0),
        ], clock=clk)
        assert not fi.drop_tick(1) and fi.drop_tick(2)
        assert not fi.alloc_fail(2) and fi.alloc_fail(3)
        assert fi.poison_uids(3) is None
        assert fi.poison_uids(4) == {7}
        fi.check_draft(4)
        with pytest.raises(FaultInjected):
            fi.check_draft(5)
        with pytest.raises(FaultInjected):
            fi.check_kernel(6, degraded=False)
        fi.check_kernel(6, degraded=True)  # reference path unaffected
        fi.begin_tick(7)
        assert clk() == 4.0  # slow tick advanced the shared clock
        kinds = [k for _, k, _ in fi.fired]
        assert kinds == ["drop_tick", "alloc_fail", "nan_logits",
                         "dead_draft", "kernel_fault", "slow_tick"]

    def test_poison_all_live_sentinel(self):
        fi = FaultInjector([Fault("nan_logits", tick=1)])  # uid=None
        assert fi.poison_uids(1) == set()  # empty set = every live slot

    def test_seeded_schedule_deterministic(self):
        kw = dict(n_ticks=50, uids=[1, 2, 3],
                  rates={"nan_logits": 0.2, "drop_tick": 0.1})
        a = seeded_schedule(7, **kw)
        b = seeded_schedule(7, **kw)
        c = seeded_schedule(8, **kw)
        assert a == b and a != c
        assert all(f.kind in ("nan_logits", "drop_tick") for f in a)
        assert all(f.uid in (1, 2, 3) for f in a if f.kind == "nan_logits")


# ---------------------------------------------------------------------------
# fast: allocator audit (property-tested)


class TestAllocatorAudit:
    def test_clean_books_pass(self):
        a = PageAllocator(8)
        pages = a.alloc(3)
        a.audit(pages)
        a.retain(pages[:1])
        a.audit(pages + pages[:1])
        a.release(pages[:1])
        a.audit(pages)
        a.release(pages)
        a.audit([])

    def test_leak_detected(self):
        a = PageAllocator(8)
        pages = a.alloc(2)
        with pytest.raises(PageAuditError, match="leaked"):
            a.audit(pages[:1])  # one live ref lost: allocator over-counts

    def test_over_share_detected(self):
        a = PageAllocator(8)
        pages = a.alloc(1)
        with pytest.raises(PageAuditError, match="over-shared"):
            a.audit(pages + pages)  # two owners, refcount 1

    def test_null_page_reference_detected(self):
        a = PageAllocator(8)
        with pytest.raises(PageAuditError, match="null page"):
            a.audit([0])

    def test_corrupted_free_list_detected(self):
        a = PageAllocator(8)
        pages = a.alloc(1)
        a._free.append(pages[0])  # stale free-list entry for an owned page
        with pytest.raises(PageAuditError):
            a.audit(pages)

    @given(ops=st.lists(st.tuples(st.sampled_from(["alloc", "retain",
                                                   "release", "release_all"]),
                                  st.integers(0, 5)), max_size=60))
    @settings(max_examples=50, deadline=None)
    def test_audit_clean_under_random_admit_evict_finish(self, ops):
        """Model an engine's admit/evict/finish traffic against a shadow
        owner list: after every operation the audit must pass, and the
        shadow's reference multiset must match the allocator's books."""
        a = PageAllocator(16)
        owners = []  # list of page-lists, one per live 'request'
        for op, n in ops:
            if op == "alloc" and a.can_alloc(n):
                owners.append(a.alloc(n))
            elif op == "retain" and owners:
                donor = owners[n % len(owners)]
                a.retain(donor)
                owners.append(list(donor))  # prefix share
            elif op == "release" and owners:
                a.release(owners.pop(n % len(owners)))  # evict/finish one
            elif op == "release_all":
                while owners:
                    a.release(owners.pop())
            a.audit([p for pages in owners for p in pages])
        live = sum(len(p) for p in owners)
        assert a.used_pages <= live  # sharing can only compress the count


# ---------------------------------------------------------------------------
# fast: speculative payoff model


class TestSpecPayoff:
    def _sizer(self, accept):
        return BatchSizer(n_params=1_000_000_000, kv_bytes_per_token=1e5,
                          context_len=512, spec_k=3, spec_accept=accept,
                          draft_n_params=50_000_000)

    def test_payoff_monotone_in_acceptance(self):
        payoffs = [self._sizer(a).spec_payoff(8) for a in (0.0, 0.3, 0.6, 0.9)]
        assert payoffs == sorted(payoffs)

    def test_worthwhile_thresholds(self):
        assert self._sizer(0.9).spec_worthwhile(8)
        assert not self._sizer(0.0).spec_worthwhile(8)  # payoff < 1 at 0
        # the acceptance floor is a separate, caller-set trigger
        assert not self._sizer(0.9).spec_worthwhile(8, min_accept=0.95)

    def test_plain_sizer_never_worthwhile(self):
        s = BatchSizer(n_params=1_000_000_000)
        assert not s.spec_worthwhile(8)
        assert s.spec_payoff(8) == 1.0


# ---------------------------------------------------------------------------
# slow: engines under deadlines, cancellation, and eviction


@pytest.mark.slow
class TestDeadlines:
    def test_total_latency_timeout_frees_slot_and_pages(self):
        cfg, api, params = _cfg_params()
        clk = TickClock()
        eng = ServingEngine(cfg, params, config=EngineConfig.of(
                max_len=64, max_batch=2, page_size=16, clock=clk,
                request_timeout_s=3.0))
        (req,) = _reqs(cfg, 1, max_new=32)
        eng.submit(req)
        for _ in range(6):
            eng.step()
            clk.advance(1.0)
            eng.audit_pages()
        assert req.state is RequestState.TIMED_OUT
        assert "total-latency" in req.error
        assert eng.stats.timed_out == 1 and eng.pages_in_use == 0
        assert 0 < len(req.output) < 32  # partial stream survives the timeout

    def test_ttft_deadline_times_out_queued_request(self):
        cfg, api, params = _cfg_params()
        clk = TickClock()
        eng = ServingEngine(cfg, params, config=EngineConfig.of(
                max_len=64, max_batch=1, clock=clk, ttft_deadline_s=2.0))
        blocker, starved = _reqs(cfg, 2, max_new=24)
        eng.submit(blocker)
        eng.step()  # blocker takes the only slot
        eng.submit(starved)
        for _ in range(4):
            clk.advance(1.0)
            eng.step()
        assert starved.state is RequestState.TIMED_OUT
        assert "TTFT" in starved.error
        assert blocker.state is RequestState.DECODING  # unharmed

    def test_per_request_deadline_overrides_engine_default(self):
        cfg, api, params = _cfg_params()
        clk = TickClock()
        eng = ServingEngine(cfg, params, config=EngineConfig.of(
                max_len=64, max_batch=2, clock=clk, request_timeout_s=100.0))
        tight, lax = _reqs(cfg, 2, max_new=32)
        tight.deadline_s = 2.0
        for r in (tight, lax):
            eng.submit(r)
        for _ in range(5):
            eng.step()
            clk.advance(1.0)
        assert tight.state is RequestState.TIMED_OUT
        assert lax.state is RequestState.DECODING

    def test_cancel_queued_and_live(self):
        cfg, api, params = _cfg_params()
        eng = ServingEngine(cfg, params, config=EngineConfig.of(
                max_len=64, max_batch=1, page_size=16))
        live, queued = _reqs(cfg, 2, max_new=16)
        eng.submit(live)
        eng.submit(queued)
        eng.step()
        assert eng.cancel(queued) and queued.error == "cancelled"
        assert eng.cancel(live) and live.state is RequestState.FAILED
        assert not eng.cancel(live)  # terminal: no-op
        eng.audit_pages()
        assert eng.pages_in_use == 0 and eng.stats.failed == 2

    def test_resubmit_rejected(self):
        cfg, api, params = _cfg_params()
        eng = ServingEngine(cfg, params, config=EngineConfig.of(
                max_len=64, max_batch=1))
        (req,) = _reqs(cfg, 1, max_new=2)
        eng.submit(req)
        with pytest.raises(ValueError, match="already submitted"):
            eng.submit(req)


@pytest.mark.slow
class TestEvictionReadmit:
    def test_priority_evicts_and_readmits_bit_identically(self):
        """A high-priority arrival preempts the low-priority slot; after
        readmission (prefill-from-prefix) BOTH greedy streams are
        bit-identical to an uncontended run."""
        cfg, api, params = _cfg_params()
        base = _reqs(cfg, 2, max_new=10)
        base[1].priority = 5
        expect = _baseline_outputs(base, max_len=64, max_batch=2, page_size=16)

        eng = ServingEngine(cfg, params, config=EngineConfig.of(
                max_len=64, max_batch=1, page_size=16,
                evict_policy="priority"))
        low, high = _clone(base)
        low.priority, high.priority = 0, 5
        eng.submit(low)
        for _ in range(3):
            eng.step()
            eng.audit_pages()
        assert low.state is RequestState.DECODING
        eng.submit(high)
        eng.step()  # high preempts low
        eng.audit_pages()
        assert low.evictions == 1 and eng.stats.evicted == 1
        assert high.state is RequestState.DECODING
        eng.run_until_done()
        eng.audit_pages()
        assert low.state is RequestState.FINISHED
        assert high.state is RequestState.FINISHED
        assert list(low.output) == expect[0]
        assert list(high.output) == expect[1]
        assert eng.pages_in_use == 0
        # the evicted request resumed, not restarted: history shows the detour
        assert RequestState.EVICTED in low.history

    def test_fifo_policy_never_preempts(self):
        cfg, api, params = _cfg_params()
        eng = ServingEngine(cfg, params, config=EngineConfig.of(
                max_len=64, max_batch=1, evict_policy="fifo"))
        low, high = _reqs(cfg, 2, max_new=6)
        high.priority = 9
        eng.submit(low)
        eng.step()
        eng.submit(high)
        eng.step()
        assert eng.stats.evicted == 0  # back-pressure only
        assert high.state is RequestState.QUEUED
        eng.run_until_done()
        assert low.state is high.state is RequestState.FINISHED

    def test_equal_priority_never_thrashes(self):
        cfg, api, params = _cfg_params()
        eng = ServingEngine(cfg, params, config=EngineConfig.of(
                max_len=64, max_batch=1, evict_policy="priority"))
        a, b = _reqs(cfg, 2, max_new=5)
        eng.submit(a)
        eng.step()
        eng.submit(b)
        eng.run_until_done()
        assert eng.stats.evicted == 0  # strict-inequality victim rule
        assert a.state is b.state is RequestState.FINISHED

    def test_page_pool_pressure_evicts_lower_priority(self):
        cfg, api, params = _cfg_params()
        # pool fits ~one request: 8+10 tokens => 2 pages of 16 (+1 null)
        eng = ServingEngine(cfg, params, config=EngineConfig.of(
                max_len=64, max_batch=2, page_size=16, num_pages=4,
                evict_policy="priority"))
        low, high = _reqs(cfg, 2, max_new=10)
        high.priority = 3
        eng.submit(low)
        eng.step()
        eng.submit(high)
        eng.step()
        eng.audit_pages()
        assert low.evictions == 1  # slots were free; *pages* were not
        eng.run_until_done()
        eng.audit_pages()
        assert low.state is high.state is RequestState.FINISHED
        assert eng.pages_in_use == 0

    def test_eviction_mid_speculative_tick_boundary_page(self):
        """Regression for the COW span [pos, pos+k]: evict a prefix-sharing
        slot exactly when its speculative write span straddles a page
        boundary — refcounts must balance and the survivor must keep its
        shared pages intact."""
        cfg, api, params = _cfg_params()
        dparams = _cfg_params(1)[2]
        eng = ServingEngine(cfg, params, config=EngineConfig.of(
                max_len=64, max_batch=2, page_size=8, share_prefix=True,
                draft_cfg=cfg, draft_params=dparams, spec_k=2,
                evict_policy="priority", audit_every_step=True))
        rng = np.random.default_rng(3)
        shared = rng.integers(0, cfg.vocab, size=12).astype(np.int32)
        a = Request(uid=0, prompt=shared.copy(), max_new_tokens=10)
        b = Request(uid=1, prompt=shared.copy(), max_new_tokens=10)
        eng.submit(a)
        eng.step()  # a admits and registers its prefix
        eng.submit(b)
        eng.step()  # b maps a's full pages by refcount
        assert eng.stats.pages_shared > 0
        # drive both toward a page boundary: pos starts at 12, boundary at 16
        eng.step()
        # preempt the low-priority slot while spans straddle the boundary
        c = Request(uid=2, prompt=rng.integers(0, cfg.vocab, size=8).astype(np.int32),
                    max_new_tokens=10, priority=7)
        eng.submit(c)
        eng.run_until_done()
        eng.audit_pages()
        assert eng.stats.evicted >= 1
        for r in (a, b, c):
            assert r.state is RequestState.FINISHED, r.state
            assert len(r.output) == 10
        assert eng.pages_in_use == 0

    def test_finish_mid_spec_tick_frees_boundary_pages(self):
        """A request that finishes mid-speculative-tick (its budget ends
        inside the [pos, pos+k] span crossing a page boundary) must free
        every page it owned, including the boundary page COW'd that tick."""
        cfg, api, params = _cfg_params()
        dparams = _cfg_params(1)[2]
        eng = ServingEngine(cfg, params, config=EngineConfig.of(
                max_len=64, max_batch=2, page_size=8, share_prefix=True,
                draft_cfg=cfg, draft_params=dparams, spec_k=3,
                audit_every_step=True))
        rng = np.random.default_rng(4)
        shared = rng.integers(0, cfg.vocab, size=12).astype(np.int32)
        # budgets chosen so the shorter request's last tick writes across
        # the 16-token page boundary (pos 12 + a few committed + k span)
        a = Request(uid=0, prompt=shared.copy(), max_new_tokens=5)
        b = Request(uid=1, prompt=shared.copy(), max_new_tokens=14)
        eng.submit(a)
        eng.step()
        eng.submit(b)
        eng.run_until_done()
        eng.audit_pages()
        assert a.state is b.state is RequestState.FINISHED
        assert len(a.output) == 5 and len(b.output) == 14
        assert eng.pages_in_use == 0


# ---------------------------------------------------------------------------
# slow: numeric guardrails + degradation ladder


@pytest.mark.slow
class TestNumericGuard:
    def test_nan_slot_quarantined_neighbor_untouched(self):
        cfg, api, params = _cfg_params()
        base = _reqs(cfg, 2, max_new=8)
        expect = _baseline_outputs(base, max_len=64, max_batch=2)
        fi = FaultInjector([Fault("nan_logits", tick=3, uid=0)])
        eng = ServingEngine(cfg, params, config=EngineConfig.of(
                max_len=64, max_batch=2, fault_injector=fi, max_retries=1))
        reqs = _clone(base)
        for r in reqs:
            eng.submit(r)
        eng.run_until_done()
        assert [(t, k, u) for t, k, u in fi.fired] == [(3, "nan_logits", 0)]
        assert eng.stats.retried == 1
        for r in reqs:
            assert r.state is RequestState.FINISHED
            # greedy + resume-from-prefix: even the poisoned request's
            # committed stream is bit-identical (the poisoned token was
            # never committed)
            assert list(r.output) == expect[r.uid], r.uid

    def test_retries_exhausted_fails_only_the_poisoned_request(self):
        cfg, api, params = _cfg_params()
        fi = FaultInjector([Fault("nan_logits", tick=2, uid=0, n_ticks=50)])
        eng = ServingEngine(cfg, params, config=EngineConfig.of(
                max_len=64, max_batch=2, fault_injector=fi, max_retries=2))
        victim, bystander = _reqs(cfg, 2, max_new=6)
        for r in (victim, bystander):
            eng.submit(r)
        eng.run_until_done()
        assert victim.state is RequestState.FAILED
        assert "non-finite" in victim.error
        assert victim.retries == 2
        assert bystander.state is RequestState.FINISHED
        assert eng.stats.failed == 1

    def test_poison_all_live_does_not_crash_engine(self):
        cfg, api, params = _cfg_params()
        fi = FaultInjector([Fault("nan_logits", tick=2, n_ticks=99)])
        eng = ServingEngine(cfg, params, config=EngineConfig.of(
                max_len=64, max_batch=2, page_size=16, fault_injector=fi,
                max_retries=0))
        reqs = _reqs(cfg, 2, max_new=6)
        for r in reqs:
            eng.submit(r)
        eng.run_until_done()
        eng.audit_pages()
        assert all(r.state is RequestState.FAILED for r in reqs)
        assert eng.pages_in_use == 0


@pytest.mark.slow
class TestDegradationLadder:
    def test_dead_draft_degrades_to_plain_bit_identically(self):
        cfg, api, params = _cfg_params()
        dparams = _cfg_params(1)[2]
        base = _reqs(cfg, 2, max_new=10)
        expect = _baseline_outputs(base, max_len=64, max_batch=2)
        fi = FaultInjector([Fault("dead_draft", tick=3, n_ticks=999)])
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            eng = ServingEngine(cfg, params, config=EngineConfig.of(
                    max_len=64, max_batch=2, draft_cfg=cfg,
                    draft_params=dparams, spec_k=2, fault_injector=fi))
            reqs = _clone(base)
            for r in reqs:
                eng.submit(r)
            eng.run_until_done()
        assert "speculative" in eng.degraded
        assert not eng.spec_active
        assert eng.stats.fallback_ticks > 0
        for r in reqs:
            assert r.state is RequestState.FINISHED
            assert list(r.output) == expect[r.uid]

    def test_kernel_fault_degrades_to_reference_bit_identically(self):
        from repro.models import layers

        cfg, api, params = _cfg_params()
        base = _reqs(cfg, 2, max_new=8)
        expect = _baseline_outputs(base, max_len=64, max_batch=2,
                                   page_size=16)
        fi = FaultInjector([Fault("kernel_fault", tick=4, n_ticks=999)])
        prev = layers.force_attention_kernel(None)
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                eng = ServingEngine(cfg, params, config=EngineConfig.of(
                        max_len=64, max_batch=2, page_size=16,
                        fault_injector=fi))
                reqs = _clone(base)
                for r in reqs:
                    eng.submit(r)
                eng.run_until_done()
                eng.audit_pages()
            assert "attention_kernel" in eng.degraded
            # the degraded tick itself was retried through the reference
            # path — no request saw the fault
            for r in reqs:
                assert r.state is RequestState.FINISHED
                assert list(r.output) == expect[r.uid]
            assert eng.pages_in_use == 0
        finally:
            layers.force_attention_kernel(prev)

    def test_real_kernel_error_propagates_without_degrading(self):
        """Only an injected kernel fault takes the reference rung: any
        other exception from the decode step (a kernel the compiler
        refuses, a device error) reaches the caller, and the engine does
        not report itself degraded."""
        from repro.models import layers

        cfg, api, params = _cfg_params()
        prev = layers.force_attention_kernel(None)
        try:
            eng = ServingEngine(cfg, params, config=EngineConfig.of(
                    max_len=64, max_batch=2, page_size=16))

            def refused(*args):
                raise RuntimeError("kernel refused by the compiler")

            eng._decode = refused
            for r in _reqs(cfg, 2, max_new=4):
                eng.submit(r)
            with pytest.raises(RuntimeError, match="refused"):
                eng.run_until_done()
            assert eng.degraded == {}
            assert layers.force_attention_kernel(None) is None
        finally:
            layers.force_attention_kernel(prev)

    def test_acceptance_collapse_switches_speculation_off(self):
        cfg, api, params = _cfg_params()
        dparams = _cfg_params(1)[2]
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            # an unreachable floor guarantees the collapse trigger fires
            # right after warmup, independent of the actual draft quality
            eng = ServingEngine(cfg, params, config=EngineConfig.of(
                    max_len=96, max_batch=2, draft_cfg=cfg,
                    draft_params=dparams, spec_k=2,
                    spec_fallback_accept=1.01, spec_fallback_min_ticks=3))
            reqs = _reqs(cfg, 2, max_new=24)
            for r in reqs:
                eng.submit(r)
            eng.run_until_done()
        assert "speculative" in eng.degraded
        assert "acceptance collapsed" in eng.degraded["speculative"]
        assert all(r.state is RequestState.FINISHED for r in reqs)


@pytest.mark.slow
class TestWatchdog:
    def test_dropped_ticks_starve_the_watchdog(self):
        cfg, api, params = _cfg_params()
        clk = TickClock()
        fi = FaultInjector([Fault("drop_tick", tick=3, n_ticks=4)], clock=clk)
        eng = ServingEngine(cfg, params, config=EngineConfig.of(
                max_len=64, max_batch=1, clock=clk, fault_injector=fi,
                watchdog_timeout_s=2.5))
        (req,) = _reqs(cfg, 1, max_new=20)
        eng.submit(req)
        stalled = []
        for _ in range(10):
            eng.step()
            clk.advance(1.0)
            stalled.append(not eng.watchdog.healthy())
        # healthy while ticking, dead during the 4-tick gap, healthy after
        assert any(stalled) and not stalled[0] and not stalled[-1]
        assert eng.watchdog.silence_s(0) <= 1.0  # beating again

    def test_slow_tick_advances_clock_and_blows_deadlines(self):
        """The slow_tick stall is real simulated time: the shared TickClock
        jumps, so a request whose total-latency budget the stall exceeds
        times out on that very tick — and the watchdog, beaten after the
        stalled step executes, recovers immediately."""
        cfg, api, params = _cfg_params()
        clk = TickClock()
        fi = FaultInjector([Fault("slow_tick", tick=4, delay_s=10.0)],
                           clock=clk)
        eng = ServingEngine(cfg, params, config=EngineConfig.of(
                max_len=64, max_batch=1, clock=clk, fault_injector=fi,
                watchdog_timeout_s=5.0, request_timeout_s=8.0))
        (req,) = _reqs(cfg, 1, max_new=16)
        eng.submit(req)
        for _ in range(6):
            eng.step()
            clk.advance(1.0)
        assert any(t == 4 and k == "slow_tick" for t, k, _ in fi.fired)
        assert clk() == 6 + 10.0  # the stall is on the books
        # tick 4 ran at t=3, jumped to 13, and 13 - 0 > 8s killed the budget
        assert req.state is RequestState.TIMED_OUT
        assert "total-latency" in req.error
        assert eng.watchdog.healthy()  # the stalled step still beat


# ---------------------------------------------------------------------------
# slow: seeded chaos soaks across engine configs


@pytest.mark.slow
class TestChaosSoak:
    # kernel_fault is only recoverable on paged engines (the reference
    # rung is the paged gather path), so the fp soak omits it
    RATES = {"nan_logits": 0.10, "alloc_fail": 0.06, "drop_tick": 0.06,
             "dead_draft": 0.04, "kernel_fault": 0.04, "slow_tick": 0.03}
    RATES_FP = {k: v for k, v in RATES.items() if k != "kernel_fault"}

    def _soak(self, seed, *, spec=False, rates=None, baseline_kw=None,
              **engine_kw):
        from repro.models import layers

        cfg, api, params = _cfg_params()
        base = _reqs(cfg, 6, max_new=8, plen=8, seed=seed)
        expect = _baseline_outputs(base, **(baseline_kw or {}))
        clk = TickClock()
        faults = seeded_schedule(
            seed, n_ticks=60, uids=[r.uid for r in base],
            rates=rates or self.RATES, slow_delay_s=0.5)
        fi = FaultInjector(faults, clock=clk)
        if spec:
            engine_kw.update(draft_cfg=cfg, draft_params=_cfg_params(1)[2],
                             spec_k=2)
        prev = layers.force_attention_kernel(None)
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                eng = ServingEngine(cfg, params, config=EngineConfig.of(
                        clock=clk, fault_injector=fi, max_retries=3,
                        **engine_kw))
                reqs = _clone(base)
                trace = [(1 + (i % 5), r) for i, r in enumerate(reqs)]
                report = run_chaos(eng, trace, tick_dt=1.0, max_ticks=300)
        finally:
            layers.force_attention_kernel(prev)
        # acceptance bar: every request terminal, zero leaked pages, and
        # every FINISHED request's greedy stream bit-identical to fault-free
        assert report.all_terminal, report.states
        assert report.leaked_pages == 0
        assert len(fi.fired) > 0  # the schedule actually exercised the run
        finished = {r.uid: list(r.output) for r in reqs
                    if r.state is RequestState.FINISHED}
        assert finished, "soak finished no requests — schedule too hostile"
        for uid, out in finished.items():
            assert out == expect[uid], f"uid {uid} diverged under faults"
        return eng, reqs, report

    def test_fp_contiguous(self):
        self._soak(11, max_len=64, max_batch=3, rates=self.RATES_FP,
                   baseline_kw=dict(max_len=64, max_batch=3))

    def test_int8_paged(self):
        eng, _, report = self._soak(
            12, max_len=64, max_batch=3, kv_dtype="int8", page_size=16,
            baseline_kw=dict(max_len=64, max_batch=3, kv_dtype="int8",
                             page_size=16))
        eng.audit_pages()

    def test_paged_speculative(self):
        eng, _, _ = self._soak(
            13, spec=True, max_len=64, max_batch=3, page_size=16,
            baseline_kw=dict(max_len=64, max_batch=3, page_size=16))
        eng.audit_pages()

    def test_paged_prefix_priority(self):
        cfg, api, params = _cfg_params()
        # distinct setup: shared prompt prefix + priority eviction pressure
        rng = np.random.default_rng(14)
        shared = rng.integers(0, cfg.vocab, size=8).astype(np.int32)
        base = [Request(uid=i, prompt=shared.copy(), max_new_tokens=8,
                        priority=i % 3) for i in range(6)]
        expect = _baseline_outputs(base, max_len=64, max_batch=3,
                                   page_size=16, share_prefix=True)
        clk = TickClock()
        fi = FaultInjector(seeded_schedule(
            14, n_ticks=60, uids=[0, 1, 2, 3, 4, 5], rates=self.RATES),
            clock=clk)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            eng = ServingEngine(cfg, params, config=EngineConfig.of(
                    max_len=64, max_batch=2, page_size=16, share_prefix=True,
                    evict_policy="priority", clock=clk, fault_injector=fi,
                    max_retries=3))
            reqs = _clone(base)
            for i, r in enumerate(reqs):
                r.priority = i % 3
            report = run_chaos(eng, [(1 + i, r) for i, r in enumerate(reqs)],
                               max_ticks=300)
        assert report.all_terminal and report.leaked_pages == 0
        for r in reqs:
            if r.state is RequestState.FINISHED:
                assert list(r.output) == expect[r.uid]

    def test_fault_free_chaos_equals_run_until_done(self):
        """The harness itself must be inert: run_chaos with no injector
        reproduces run_until_done exactly."""
        cfg, api, params = _cfg_params()
        base = _reqs(cfg, 4, max_new=6, seed=15)
        expect = _baseline_outputs(base, max_len=64, max_batch=2,
                                   page_size=16)
        eng = ServingEngine(cfg, params, config=EngineConfig.of(
                max_len=64, max_batch=2, page_size=16, clock=TickClock()))
        reqs = _clone(base)
        report = run_chaos(eng, [(1, r) for r in reqs])
        assert report.all_terminal and report.leaked_pages == 0
        assert report.outputs == expect
        assert report.stats.failed == report.stats.retried == 0
