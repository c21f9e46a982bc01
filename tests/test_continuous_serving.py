"""Continuous batching: chunked prefill, open-loop admission, streaming.

Fast classes (no model compile) property-test the pure scheduler pieces —
``chunk_spans`` coverage/overlap invariants, the ``TickBudget`` charge
discipline, the sizer's prefill-chunk term, and the load generator's
seed-determinism.  Engine classes are slow-marked: they drive real
tinyllama-smoke engines and assert the ISSUE's acceptance bar — greedy
bit-parity with the synchronous engine across fp/int8/paged/spec
variants, decode never starving during a long chunked prefill, the
per-tick prefill budget respected, mid-prefill preemption, open-loop
determinism, and a chaos soak with zero page leaks.  Randomized
arrival/finish/evict/cancel sequences run both as hypothesis properties
and as deterministic seeded examples.
"""

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro.configs as C
from repro.core.batching import BatchSizer
from repro.models.api import get_api
from repro.serving.config import EngineConfig
from repro.serving.engine import Request, RequestState, ServingEngine
from repro.serving.faultinject import (
    FaultInjector,
    TickClock,
    run_chaos,
    seeded_schedule,
)
from repro.serving.loadgen import (
    Arrival,
    LengthMixture,
    chat_mixture,
    load_trace,
    make_requests,
    poisson_trace,
    run_open_loop,
    save_trace,
)
from repro.serving.scheduler import TickBudget, chunk_spans

ARCH = "tinyllama-1.1b"
TERMINAL = (RequestState.FINISHED, RequestState.FAILED, RequestState.TIMED_OUT)

_cache = {}


def _cfg_params(seed=0):
    if seed not in _cache:
        cfg = C.get_config(ARCH, smoke=True)
        api = get_api(cfg)
        _cache[seed] = (cfg, api, api.init_params(cfg, jax.random.key(seed)))
    return _cache[seed]


def _reqs(cfg, lens, max_new=6, seed=0, **kw):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, size=n).astype(np.int32),
                    max_new_tokens=max_new, **kw)
            for i, n in enumerate(lens)]


def _clone(reqs):
    return [Request(uid=r.uid, prompt=r.prompt.copy(),
                    max_new_tokens=r.max_new_tokens, priority=r.priority)
            for r in reqs]


def _drain(eng, reqs, max_ticks=500, per_tick=None):
    for r in reqs:
        eng.submit(r)
    for _ in range(max_ticks):
        if not eng.queue and not eng._live_slots():
            break
        eng.step()
        eng.audit_pages()
        if per_tick is not None:
            per_tick(eng)
    assert all(r.terminal for r in reqs), [r.state.value for r in reqs]
    return {r.uid: list(r.output or []) for r in reqs}


# ---------------------------------------------------------------------------
# fast: chunk-span arithmetic


def _check_span_invariants(S, chunk):
    spans = chunk_spans(S, chunk)
    assert spans[0][0] == 0
    assert spans[-1][1] == S
    covered = set()
    prev_stop = 0
    for start, stop in spans:
        assert 0 < stop - start <= chunk, (start, stop)
        assert start <= prev_stop, "gap between spans"  # overlap, never a gap
        assert stop > prev_stop, "span makes no progress"
        covered.update(range(start, stop))
        prev_stop = stop
    assert covered == set(range(S))


class TestChunkSpans:
    def test_examples(self):
        assert chunk_spans(5, 8) == [(0, 5)]
        assert chunk_spans(8, 8) == [(0, 8)]
        assert chunk_spans(16, 8) == [(0, 8), (8, 16)]
        # ragged tail: final span overlaps back to S - chunk
        assert chunk_spans(19, 8) == [(0, 8), (8, 16), (11, 19)]

    def test_errors(self):
        with pytest.raises(ValueError):
            chunk_spans(0, 8)
        with pytest.raises(ValueError):
            chunk_spans(8, 0)

    def test_invariants_sweep(self):
        for S in range(1, 50):
            for chunk in range(1, 14):
                _check_span_invariants(S, chunk)

    @given(S=st.integers(1, 4096), chunk=st.integers(1, 512))
    @settings(max_examples=200, deadline=None)
    def test_invariants_property(self, S, chunk):
        _check_span_invariants(S, chunk)


class TestTickBudget:
    def test_charge_discipline(self):
        b = TickBudget(8)
        assert b.try_charge(5) and b.used == 5 and b.remaining == 3
        assert not b.try_charge(4)  # would overrun
        assert b.try_charge(3) and b.remaining == 0
        b.reset()
        assert b.used == 0 and b.try_charge(8)

    def test_oversize_only_from_fresh_tick(self):
        b = TickBudget(4)
        assert b.try_charge(9)  # fresh tick: oversize span still progresses
        b.reset()
        assert b.try_charge(1)
        assert not b.try_charge(9)  # mid-tick oversize refused

    def test_errors(self):
        with pytest.raises(ValueError):
            TickBudget(0)
        with pytest.raises(ValueError):
            TickBudget(4).try_charge(0)

    @given(budget=st.integers(1, 64),
           charges=st.lists(st.integers(1, 96), max_size=32))
    @settings(max_examples=200, deadline=None)
    def test_never_overruns_property(self, budget, charges):
        b = TickBudget(budget)
        for n in charges:
            before = b.used
            if b.try_charge(n):
                assert b.used == before + n
                assert b.used <= budget or (before == 0 and n > budget)
            else:
                assert b.used == before


class TestStepTimePrefill:
    def test_monotone_and_backward_compatible(self):
        sizer = BatchSizer(n_params=1e9, kv_bytes_per_token=4096,
                           context_len=1024)
        t0 = sizer.step_time(8)
        assert sizer.step_time(8, prefill_tokens=0) == t0
        ts = [sizer.step_time(8, prefill_tokens=p) for p in (4, 16, 64)]
        assert t0 < ts[0] < ts[1] < ts[2]


# ---------------------------------------------------------------------------
# fast: load-generator determinism (no engine)


class TestLoadgen:
    def test_poisson_trace_deterministic(self):
        mix = chat_mixture()
        a = poisson_trace(0.5, 20, mix, seed=7)
        b = poisson_trace(0.5, 20, mix, seed=7)
        assert a == b
        assert a != poisson_trace(0.5, 20, mix, seed=8)
        ts = [x.t for x in a]
        assert ts == sorted(ts) and ts[0] > 0

    def test_mixture_bounds_and_errors(self):
        mix = LengthMixture(((1.0, (3, 5), (2, 4)),))
        rng = np.random.default_rng(0)
        for _ in range(50):
            p, n = mix.sample(rng)
            assert 3 <= p <= 5 and 2 <= n <= 4
        assert mix.max_context == 9
        with pytest.raises(ValueError):
            LengthMixture(())
        with pytest.raises(ValueError):
            LengthMixture(((1.0, (5, 3), (2, 4)),))
        with pytest.raises(ValueError):
            poisson_trace(0.0, 4, mix)

    def test_trace_round_trip(self, tmp_path):
        arrivals = poisson_trace(1.0, 12, chat_mixture(), seed=3)
        path = str(tmp_path / "trace.jsonl")
        save_trace(path, arrivals)
        assert load_trace(path) == arrivals

    def test_make_requests_deterministic(self):
        arrivals = poisson_trace(1.0, 6, chat_mixture(), seed=1)
        a = make_requests(arrivals, vocab=256, seed=0)
        b = make_requests(arrivals, vocab=256, seed=0)
        for ra, rb, arr in zip(a, b, arrivals):
            assert np.array_equal(ra.prompt, rb.prompt)
            assert len(ra.prompt) == arr.prompt_len
            assert ra.max_new_tokens == arr.max_new

    @given(seed=st.integers(0, 2**32 - 1))
    @settings(max_examples=50, deadline=None)
    def test_poisson_trace_deterministic_property(self, seed):
        mix = chat_mixture()
        assert poisson_trace(0.7, 8, mix, seed=seed) \
            == poisson_trace(0.7, 8, mix, seed=seed)


# ---------------------------------------------------------------------------
# slow: engine gating + bit parity vs the synchronous engine


@pytest.mark.slow
class TestChunkedGating:
    def test_bad_chunk_rejected(self):
        cfg, api, params = _cfg_params()
        with pytest.raises(ValueError):
            ServingEngine(cfg, params, config=EngineConfig.of(
                    max_len=32, max_batch=1, prefill_chunk=0))

    def test_budget_defaults_to_chunk(self):
        cfg, api, params = _cfg_params()
        eng = ServingEngine(cfg, params, config=EngineConfig.of(
                max_len=32, max_batch=1, prefill_chunk=4))
        assert eng.prefill_chunk == 4 and eng.prefill_budget == 4
        eng = ServingEngine(cfg, params, config=EngineConfig.of(
                max_len=32, max_batch=1, prefill_chunk=4, prefill_budget=12))
        assert eng.prefill_budget == 12


@pytest.mark.slow
class TestChunkedParity:
    """Chunked prefill + mid-stream admission must not perturb the
    compiled decode step: same requests, token-identical greedy streams
    vs the synchronous engine, across every cache/decode variant."""

    LENS = (4, 20, 33, 9)  # shorter than, longer than, and ~4x the chunk

    def _variant_kw(self, name):
        cfg, api, params = _cfg_params()
        kw = dict(max_len=96, max_batch=3)
        if name == "int8":
            kw["kv_dtype"] = "int8"
        elif name == "paged":
            kw.update(page_size=16)
        elif name == "spec":
            kw.update(draft_cfg=cfg, draft_params=_cfg_params(1)[2],
                      spec_k=2)
        return kw

    @pytest.mark.parametrize("variant", ["fp", "int8", "paged", "spec"])
    def test_parity(self, variant):
        cfg, api, params = _cfg_params()
        kw = self._variant_kw(variant)
        reqs = _reqs(cfg, self.LENS)
        sync = _drain(ServingEngine(cfg, params, config=EngineConfig.of(
                **kw)), _clone(reqs))
        chunked = _drain(
            ServingEngine(cfg, params, config=EngineConfig.of(
                    prefill_chunk=8, prefill_budget=8, **kw)),
            _clone(reqs))
        assert chunked == sync


# ---------------------------------------------------------------------------
# slow: continuous-batching behavior


@pytest.mark.slow
class TestContinuousEngine:
    def _engine(self, **kw):
        cfg, api, params = _cfg_params()
        base = dict(max_len=96, max_batch=2, page_size=16,
                    prefill_chunk=4, prefill_budget=4, clock=TickClock())
        base.update(kw)
        return cfg, ServingEngine(cfg, params, config=EngineConfig.of(
                **base))

    def test_streaming_callbacks(self):
        cfg, eng = self._engine()
        reqs = _reqs(cfg, (14, 6), max_new=5)
        seen = {r.uid: [] for r in reqs}
        ticks = {r.uid: [] for r in reqs}
        for r in reqs:
            r.on_token = lambda req, tok: (seen[req.uid].append(tok),
                                           ticks[req.uid].append(eng.tick))
        _drain(eng, reqs)
        for r in reqs:
            assert seen[r.uid] == list(r.output)  # streamed == final
            assert len(set(ticks[r.uid])) >= 2  # across ticks, not end-of-run

    def test_decode_not_starved_during_long_prefill(self):
        cfg, eng = self._engine()
        short, long = _reqs(cfg, (6, 40), max_new=12)
        eng.submit(short)
        while short.state is not RequestState.DECODING:
            eng.step()
        eng.submit(long)
        eng.step()  # admits long mid-stream; its first chunk runs
        assert long.state is RequestState.PREFILLING
        # the long prompt needs ceil(40/4)=10 budgeted ticks of prefill;
        # the decoding neighbor must commit one token on every one of them
        while long.state is RequestState.PREFILLING \
                and not short.terminal:
            before = len(short.output)
            eng.step()
            eng.audit_pages()
            assert len(short.output) == before + 1, "decode starved"
        while not (short.terminal and long.terminal):
            eng.step()
        assert eng.stats.prefill_chunks >= 10
        assert short.state is RequestState.FINISHED
        assert long.state is RequestState.FINISHED

    def test_prefill_budget_respected(self):
        cfg, eng = self._engine(max_batch=3, prefill_chunk=4,
                                prefill_budget=8)

        def check(e):
            assert e.last_tick_prefill_tokens <= e.prefill_budget

        _drain(eng, _reqs(cfg, (30, 28, 26), max_new=4), per_tick=check)
        assert eng.stats.prefill_chunks >= 3 * (26 // 4)

    def test_mid_prefill_priority_eviction(self):
        cfg, eng = self._engine(max_batch=1, evict_policy="priority")
        low, high = _reqs(cfg, (40, 6), max_new=4)
        high.priority = 1
        eng.submit(low)
        eng.step()  # admits low; first chunk runs, prefill in flight
        assert low.state is RequestState.PREFILLING
        eng.submit(high)
        eng.step()  # priority admission preempts the mid-prefill slot
        assert RequestState.EVICTED in low.history
        for _ in range(200):
            if low.terminal and high.terminal:
                break
            eng.step()
            eng.audit_pages()
        assert low.state is RequestState.FINISHED  # readmitted after evict
        assert high.state is RequestState.FINISHED
        assert eng.stats.evicted >= 1

    def test_run_open_loop_requires_tickclock(self):
        cfg, api, params = _cfg_params()
        eng = ServingEngine(cfg, params, config=EngineConfig.of(
                max_len=32, max_batch=1))
        with pytest.raises(TypeError):
            run_open_loop(eng, [Arrival(uid=0, t=0.0, prompt_len=4,
                                        max_new=2)])

    def test_open_loop_determinism(self):
        arrivals = poisson_trace(
            0.5, 6, LengthMixture(((0.8, (4, 10), (3, 6)),
                                   (0.2, (24, 40), (3, 4)),)), seed=11)

        def run():
            _, eng = self._engine()
            return run_open_loop(eng, arrivals, seed=0)

        a, b = run(), run()
        assert a.all_terminal and b.all_terminal
        assert a.summary() == b.summary()
        assert a.outputs == b.outputs
        assert a.token_ticks == b.token_ticks

    def test_chaos_soak_zero_leaks(self):
        """Faultinject hooks under open-loop arrivals on the chunked paged
        engine: every request terminal, allocator audits clean every tick
        (run_chaos), zero pages in use at the end."""
        cfg, api, params = _cfg_params()
        lens = (6, 30, 8, 26, 5, 12)
        reqs = _reqs(cfg, lens, max_new=5)
        fi = FaultInjector(seeded_schedule(
            3, n_ticks=60, uids=[r.uid for r in reqs],
            rates={"nan_logits": 0.1, "alloc_fail": 0.1, "drop_tick": 0.05}))
        eng = ServingEngine(cfg, params, config=EngineConfig.of(
                max_len=96, max_batch=2, page_size=16, prefill_chunk=4,
                prefill_budget=8, max_retries=3, clock=TickClock(),
                fault_injector=fi))
        trace = [(1 + 2 * i, r) for i, r in enumerate(reqs)]
        report = run_chaos(eng, trace)
        assert report.all_terminal, report.states
        assert report.leaked_pages == 0, report.leaked_pages


# ---------------------------------------------------------------------------
# slow: randomized scheduler invariant suite (arrival/finish/evict/cancel)


def _random_ops_invariants(seed):
    """One randomized open-loop episode on the chunked paged engine:
    random arrivals (mixed lengths/priorities), random cancels, priority
    preemption — asserting after every tick that no slot is
    double-assigned, the prefill budget held, and the allocator audits
    clean; at the end, that every request reached exactly one terminal
    state."""
    cfg, api, params = _cfg_params()
    rng = np.random.default_rng(seed)
    eng = ServingEngine(cfg, params, config=EngineConfig.of(
            max_len=96, max_batch=3, page_size=16, prefill_chunk=4,
            prefill_budget=8, evict_policy="priority", clock=TickClock()))
    reqs = []
    uid = 0
    for _ in range(120):
        if uid < 10 and rng.random() < 0.35:
            plen = int(rng.integers(2, 41))
            r = Request(
                uid=uid,
                prompt=rng.integers(0, cfg.vocab, size=plen).astype(np.int32),
                max_new_tokens=int(rng.integers(1, 7)),
                priority=int(rng.integers(0, 3)))
            reqs.append(r)
            eng.submit(r)
            uid += 1
        if reqs and rng.random() < 0.05:
            eng.cancel(reqs[int(rng.integers(0, len(reqs)))])
        eng.step()
        eng.clock.advance(1.0)
        live = [r for r in eng.slot_req if r is not None]
        assert len({id(r) for r in live}) == len(live), "slot double-assigned"
        assert eng.last_tick_prefill_tokens <= eng.prefill_budget
        eng.audit_pages()
        if uid >= 10 and not eng.queue and not eng._live_slots():
            break
    for _ in range(300):  # drain whatever the op loop left in flight
        if not eng.queue and not eng._live_slots():
            break
        eng.step()
        eng.clock.advance(1.0)
        eng.audit_pages()
    assert all(r.terminal for r in reqs), [r.state.value for r in reqs]
    assert eng.pages_in_use == 0
    for r in reqs:
        terminal_entries = [s for s in r.history if s in TERMINAL]
        assert len(terminal_entries) == 1, (r.uid, r.history)


@pytest.mark.slow
class TestSchedulerInvariants:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_random_ops(self, seed):
        _random_ops_invariants(seed)

    @given(seed=st.integers(0, 2**16))
    @settings(max_examples=3, deadline=None)
    def test_random_ops_property(self, seed):
        _random_ops_invariants(seed)
