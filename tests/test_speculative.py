"""Speculative multi-token decode: perf-model units, multi-token decode
parity against sequential steps, engine accept/rollback correctness, and
host-mesh sharded parity.

The engine tests pin the acceptance criterion: a speculative engine with
k >= 2 commits the IDENTICAL token stream as the non-speculative engine
under greedy sampling, across the fp / int8-KV / paged caches — rejected
draft positions must be invisible (masked, then overwritten) rather than
rolled back.  The multi-device class runs in the CI ``mesh-smoke`` lane
(XLA_FLAGS=--xla_force_host_platform_device_count=8) and skips elsewhere.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.core import perf_model as pm
from repro.core import weight_plan as WP
from repro.core.batching import BatchSizer
from repro.launch import mesh as M
from repro.models.api import get_api, supports_spec_decode
from repro.serving.config import EngineConfig
from repro.serving.engine import Request, ServingEngine


# ---------------------------------------------------------------------------
# perf model (paper model extended with the draft-token sample axis)
# ---------------------------------------------------------------------------


class TestSpecPerfModel:
    def test_expected_committed_bounds(self):
        # alpha=0: every tick still commits exactly the one resampled token
        assert pm.expected_committed(0.0, 4) == 1.0
        # alpha=1: all k drafts + the bonus token
        assert pm.expected_committed(1.0, 4) == 5.0
        assert pm.expected_committed(0.5, 2) == pytest.approx(1.75)
        with pytest.raises(ValueError):
            pm.expected_committed(1.5, 2)

    def test_spec_nopt_divides_by_verified_positions(self):
        """One verify step streams weights once for B*(k+1) rows.  With the
        per-position kv re-fetch (single_pass_kv=False) BOTH terms scale
        with (k+1) and the sequence batch is exactly n_opt / (k+1); the
        shipped single-pass kernel charges the page stream once per tick,
        so the kv tilt doesn't grow with k and the balance batch sits
        slightly below the old point (the compute term alone carries the
        (k+1) factor)."""
        kw = dict(b_weight=1.0, n_params=10**9,
                  kv_bytes_per_token=1000.0, context_len=128)
        base = pm.decode_n_opt(**kw)
        assert pm.spec_decode_n_opt(0, **kw) == pytest.approx(base)
        assert pm.spec_decode_n_opt(
            3, single_pass_kv=False, **kw) == pytest.approx(base / 4)
        # single-pass: equal to decode_n_opt at kv/(k+1), divided by (k+1)
        kw_amort = dict(kw, kv_bytes_per_token=1000.0 / 4)
        assert pm.spec_decode_n_opt(3, **kw) == pytest.approx(
            pm.decode_n_opt(**kw_amort) / 4)
        # the kv tilt shrinks: single-pass balance < re-fetch balance
        assert pm.spec_decode_n_opt(3, **kw) < base / 4

    def test_spec_nopt_inf_passthrough(self):
        # memory-bound-at-any-batch stays memory-bound under speculation
        kw = dict(n_params=10**9, kv_bytes_per_token=1e9, context_len=4096)
        assert not np.isfinite(pm.decode_n_opt(**kw))
        assert not np.isfinite(pm.spec_decode_n_opt(4, **kw))

    def test_spec_step_time_charges_verified_positions(self):
        s = pm.spec_step_time(10**9, 8, 3, 0.5, kv_bytes_per_token=500.0,
                              context_len=64)
        # compute charged at B*(k+1) positions, kv charged ONCE per tick
        # (single-pass kernel): kv_read = 8*4 * 64 * 500/4 = 8 * 64 * 500
        plain = pm.decode_step_time(10**9, 8 * 4, 500.0 / 4, 64)
        assert s["t_proc"] == pytest.approx(plain["t_proc"])
        # the re-fetch datapath charges kv per verified position
        s_old = pm.spec_step_time(10**9, 8, 3, 0.5, kv_bytes_per_token=500.0,
                                  context_len=64, single_pass_kv=False)
        plain_old = pm.decode_step_time(10**9, 8 * 4, 500.0, 64)
        assert s_old["t_proc"] == pytest.approx(plain_old["t_proc"])
        assert s["committed_per_tick"] == pytest.approx(
            8 * pm.expected_committed(0.5, 3))
        # draft cost is additive on the tick
        s2 = pm.spec_step_time(10**9, 8, 3, 0.5, draft_n_params=10**8,
                               kv_bytes_per_token=500.0, context_len=64)
        assert s2["t_tick"] > s["t_tick"] and s2["t_draft"] > 0.0

    def test_sizer_spec_fields(self):
        base = BatchSizer(n_params=10**9)
        spec = BatchSizer(n_params=10**9, spec_k=3, spec_accept=0.5)
        assert spec.n_opt == max(1, int(round(base.n_opt / 4)))
        # a spec tick streams (k+1) verified positions per sequence
        assert spec.step_time(4) == pytest.approx(base.step_time(16))
        assert spec.committed_per_tick(4) == pytest.approx(
            4 * pm.expected_committed(0.5, 3))
        assert base.committed_per_tick(4) == 4.0
        # the latency clamp must charge the draft chain too, not just verify
        with_draft = BatchSizer(n_params=10**9, spec_k=3,
                                draft_n_params=10**8)
        assert with_draft.step_time(4) > spec.step_time(4)


class TestSupportsSpecDecode:
    def test_attention_stacks_qualify(self):
        for arch in ("tinyllama-1.1b", "llama3.2-1b", "gemma3-4b",
                     "qwen2-moe-a2.7b"):
            assert supports_spec_decode(C.get_config(arch, smoke=True)), arch

    def test_stateful_and_nonstandard_families_excluded(self):
        # recurrent / xLSTM states integrate sequentially (no rollback);
        # VLM / enc-dec stay excluded at the engine level (draft prefill
        # carries tokens only, caches don't size for the verify overhang)
        # even though the enc-dec decoder now threads multi-position decode.
        for arch in ("recurrentgemma-2b", "xlstm-350m", "whisper-tiny",
                     "internvl2-2b"):
            assert not supports_spec_decode(C.get_config(arch, smoke=True)), arch


# ---------------------------------------------------------------------------
# multi-token decode step vs sequential single-token steps
# ---------------------------------------------------------------------------


def _paged_copy_of(k, ps, num_pages, table):
    """Pack a contiguous (B, S, ...) cache into head-major (num_pages, KVH, ps, ...) pools
    laid out per ``table`` (mirrors tests/test_paged_cache.py)."""
    B, S = k.shape[:2]
    pool = jnp.zeros((num_pages, k.shape[2], ps) + k.shape[3:], k.dtype)
    for b in range(B):
        for lp in range(S // ps):
            pool = pool.at[int(table[b, lp])].set(
                jnp.swapaxes(k[b, lp * ps : (lp + 1) * ps], 0, 1))
    return pool


def _model(arch="tinyllama-1.1b"):
    cfg = C.get_config(arch, smoke=True)
    api = get_api(cfg)
    return cfg, api, api.init_params(cfg, jax.random.key(0))


def _prefill(cfg, api, params, S=8, L=64, **cache_kw):
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (2, S)), jnp.int32)
    cache = api.init_cache(cfg, 2, L, jnp.dtype(cfg.compute_dtype), **cache_kw)
    logits, cache = jax.jit(functools.partial(api.prefill, cfg))(
        params, {"tokens": prompt}, cache)
    return prompt, logits, cache


class TestMultiTokenDecode:
    """decode_step(tokens (B, T)) must equal T sequential (B, 1) steps fed
    the same token chain — same logits (fp tolerance), same cache writes."""

    def _compare(self, **cache_kw):
        cfg, api, params = _model()
        T, S = 3, 8
        prompt, logits, cache0 = _prefill(cfg, api, params, S=S, **cache_kw)
        chain = [int(jnp.argmax(logits[0, -1])), 7, 123]  # arbitrary drafts
        tokens = jnp.asarray([chain, chain], jnp.int32)
        pos0 = jnp.full((2,), S, jnp.int32)

        seq_cache = jax.tree.map(lambda x: x, cache0)
        seq_logits = []
        for t in range(T):
            lg, seq_cache = api.decode_step(
                cfg, params, seq_cache, tokens[:, t : t + 1], pos0 + t)
            seq_logits.append(lg[:, 0])
        mt_logits, mt_cache = api.decode_step(cfg, params, cache0, tokens, pos0)
        for t in range(T):
            np.testing.assert_allclose(
                np.asarray(mt_logits[:, t], np.float32),
                np.asarray(seq_logits[t], np.float32), atol=2e-5, rtol=2e-5)
        for a, b in zip(jax.tree.leaves(mt_cache), jax.tree.leaves(seq_cache)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=2e-5, rtol=2e-5)

    def test_fp_contiguous(self):
        self._compare()

    def test_int8_cache(self):
        self._compare(kv_dtype=jnp.int8)

    def _paged_setup(self, ps=8, B=2, S=32, KVH=2, G=3, hd=16):
        from repro.models import layers as L

        key = jax.random.key(1)
        H = KVH * G
        P = S // ps
        k = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KVH, hd))
        v = jax.random.normal(jax.random.fold_in(key, 3), (B, S, KVH, hd))
        perm = np.random.default_rng(0).permutation(B * P)
        table = jnp.asarray(1 + perm.reshape(B, P), jnp.int32)
        num_pages = 1 + B * P
        kp = _paged_copy_of(k, ps, num_pages, table)
        vp = _paged_copy_of(v, ps, num_pages, table)
        q = jax.random.normal(jax.random.fold_in(key, 4), (B, 3, H, hd))
        pos = jnp.asarray([5, 17], jnp.int32)
        return L, q, k, v, kp, vp, table, pos, ps

    def test_paged_multitoken_gather_matches_contiguous(self):
        """T=3 attention through the page table == the contiguous ring —
        bit-exact (same score geometry, scrambled physical layout)."""
        L, q, k, v, kp, vp, table, pos, ps = self._paged_setup()
        ref = L.decode_attention(q, k, v, pos)
        out = L.paged_decode_attention(q, kp, vp, table, pos, use_kernel=False)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_paged_multitoken_kernel_matches_reference(self):
        """The single-position Pallas kernel looped per verify position
        (ops.paged_decode_attention T>1) matches the gather reference."""
        L, q, k, v, kp, vp, table, pos, ps = self._paged_setup()
        ref = L.paged_decode_attention(q, kp, vp, table, pos, use_kernel=False)
        out = L.paged_decode_attention(q, kp, vp, table, pos, use_kernel=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_paged_multitoken_scatter_matches_sequential(self):
        """paged_cache_update with T entries == T single-entry scatters,
        including across a page boundary."""
        L, q, k, v, kp, vp, table, pos, ps = self._paged_setup(ps=4)
        new = jax.random.normal(
            jax.random.key(9), (2, 3, kp.shape[1]) + kp.shape[3:])
        seq = kp
        for t in range(3):
            seq = L.paged_cache_update(seq, new[:, t : t + 1], table, pos + t)
        mt = L.paged_cache_update(kp, new, table, pos)
        np.testing.assert_array_equal(np.asarray(mt), np.asarray(seq))

    def test_local_window_ring_extension(self):
        """A sliding-window layer needs the window + k ring: the verify
        write span must not clobber positions the earliest query's window
        still reads (gemma3 smoke has 5:1 local:global layers)."""
        cfg, api, params = _model("gemma3-4b")
        T = 3
        prompt, logits, cache0 = _prefill(cfg, api, params, S=8, spec_k=T - 1)
        chain = [int(jnp.argmax(logits[0, -1])), 3, 99]
        tokens = jnp.asarray([chain, chain], jnp.int32)
        pos0 = jnp.full((2,), 8, jnp.int32)
        seq_cache = jax.tree.map(lambda x: x, cache0)
        seq_logits = []
        for t in range(T):
            lg, seq_cache = api.decode_step(
                cfg, params, seq_cache, tokens[:, t : t + 1], pos0 + t)
            seq_logits.append(lg[:, 0])
        mt_logits, _ = api.decode_step(cfg, params, cache0, tokens, pos0)
        for t in range(T):
            np.testing.assert_allclose(
                np.asarray(mt_logits[:, t], np.float32),
                np.asarray(seq_logits[t], np.float32), atol=2e-5, rtol=2e-5)

    def test_fused_gate_up_single_kernel_at_verify_tile(self):
        """The fused gate+up kernel must stay ONE pallas_call when the
        verify step widens rows to B * (k+1) — the draft positions ride
        the same DMA'd weight blocks (the whole point of speculation
        through the compressed datapath)."""
        import dataclasses

        rng = np.random.default_rng(0)
        pc = WP.PlanConfig(default="quant_sparse", q_prune=0.25, bk=16, bn=16,
                           min_size=128, min_contract=16)
        g = WP.pack_block_sparse(
            jnp.asarray(rng.normal(size=(64, 128)), jnp.float32), pc, quant=True)
        u = WP.pack_block_sparse(
            jnp.asarray(rng.normal(size=(64, 128)), jnp.float32), pc, quant=True)
        gk = dataclasses.replace(g, use_kernel=True)
        uk = dataclasses.replace(u, use_kernel=True)
        x = jnp.asarray(rng.normal(size=(2, 4, 64)), jnp.float32)  # (B, k+1, d)
        jaxpr = str(jax.make_jaxpr(
            lambda xx: WP.apply_gate_up(xx, gk, uk, "silu"))(x))
        assert jaxpr.count("pallas_call") == 1
        # and the verify tile computes the same numbers as two dispatches
        two = WP.GATE_ACTS["silu"](WP.apply_linear(x, g)) * WP.apply_linear(x, u)
        np.testing.assert_allclose(
            np.asarray(WP.apply_gate_up(x, gk, uk, "silu")), np.asarray(two),
            rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# engine: accept / rollback / parity
# ---------------------------------------------------------------------------


def _requests(cfg, lens=(6, 9, 3, 12, 7), max_new=(8, 6, 8, 5, 7)):
    return [
        Request(uid=i,
                prompt=np.random.default_rng(i).integers(
                    0, cfg.vocab, size=ln).astype(np.int32),
                max_new_tokens=mn)
        for i, (ln, mn) in enumerate(zip(lens, max_new))
    ]


def _run(cfg, params, reqs=None, **kw):
    eng = ServingEngine(cfg, params, config=EngineConfig.of(
            max_len=64, max_batch=3, **kw))
    reqs = reqs or _requests(cfg)
    for r in reqs:
        eng.submit(r)
    stats = eng.run_until_done()
    assert stats.completed == len(reqs)
    assert all(len(r.output) == r.max_new_tokens for r in reqs)
    return [tuple(r.output) for r in reqs], stats, eng


@pytest.mark.slow
class TestSpeculativeEngine:
    @pytest.fixture(scope="class")
    def setup(self):
        cfg, api, params = _model()
        draft_good = params  # the target itself: high acceptance
        draft_bad = api.init_params(cfg, jax.random.key(7))  # ~0 acceptance
        return cfg, api, params, draft_good, draft_bad

    def test_greedy_parity_k2_fp(self, setup):
        cfg, api, params, good, _ = setup
        base, _, _ = _run(cfg, params)
        out, stats, _ = _run(cfg, params, draft_cfg=cfg, draft_params=good,
                             spec_k=2)
        assert out == base
        assert stats.accept_rate > 0.5  # the draft IS the target
        assert stats.decode_steps < 34  # base needs sum(max_new - 1) ticks

    def test_greedy_parity_k1_degenerate(self, setup):
        """k=1: the smallest speculative tick, across every cache
        representation — bit-exact committed streams vs plain decode."""
        cfg, api, params, good, _ = setup
        for kw in ({}, {"kv_dtype": "int8"}, {"page_size": 8},
                   {"page_size": 8, "kv_dtype": "int8"}):
            base, _, _ = _run(cfg, params, **kw)
            out, _, _ = _run(cfg, params, draft_cfg=cfg, draft_params=good,
                             spec_k=1, **kw)
            assert out == base, kw

    def test_greedy_parity_k3_int8(self, setup):
        cfg, api, params, good, _ = setup
        base, _, _ = _run(cfg, params, kv_dtype="int8")
        out, stats, _ = _run(cfg, params, draft_cfg=cfg, draft_params=good,
                             spec_k=3, kv_dtype="int8")
        assert out == base
        assert stats.accept_rate > 0.3  # fp draft vs int8 target differs more

    def test_all_rejected_ticks_still_commit(self, setup):
        """A draft that never matches: every tick must still commit exactly
        the one resampled token and the stream must equal plain decode."""
        cfg, api, params, _, bad = setup
        base, base_stats, _ = _run(cfg, params)
        out, stats, _ = _run(cfg, params, draft_cfg=cfg, draft_params=bad,
                             spec_k=3)
        assert out == base
        assert stats.accept_rate < 0.2
        # one committed token per live slot per tick == plain tick count
        assert stats.decode_steps == base_stats.decode_steps
        assert stats.decode_tokens == base_stats.decode_tokens

    def test_stats_count_committed_not_verified(self, setup):
        """mean_batch stays in committed tokens: the verified-position
        inflation is reported separately, so throughput numbers remain
        comparable with the non-speculative engine."""
        cfg, api, params, good, _ = setup
        base, base_stats, _ = _run(cfg, params)
        out, stats, _ = _run(cfg, params, draft_cfg=cfg, draft_params=good,
                             spec_k=2)
        assert stats.decode_tokens == base_stats.decode_tokens  # committed
        assert stats.verified_positions > stats.decode_tokens
        assert stats.mean_batch == pytest.approx(
            stats.decode_tokens / stats.decode_steps)
        assert stats.mean_context == pytest.approx(base_stats.mean_context)
        assert 0.0 <= stats.accept_rate <= 1.0

    def test_paged_rollback_across_page_boundary(self, setup):
        """page_size=4 with k=3: verify writes straddle page boundaries
        every few ticks; rejected tail entries land in later pages and are
        overwritten.  Refcounts must drain to zero and the stream must
        match the contiguous spec engine exactly."""
        cfg, api, params, good, bad = setup
        base, _, _ = _run(cfg, params)
        for draft in (good, bad):
            out, stats, eng = _run(cfg, params, draft_cfg=cfg,
                                   draft_params=draft, spec_k=3, page_size=4)
            assert out == base
            assert eng.pages_in_use == 0  # all pages freed at completion
            assert eng.allocator.free_pages == eng.num_pages - 1

    def test_paged_spec_prefix_sharing_cow(self, setup):
        """Shared prefix pages + speculative writes: the boundary page is
        COW'd per writer at admission, so the donor's pages survive a
        sharer's rejected speculative scatter bit-for-bit."""
        cfg, api, params, good, _ = setup
        prompt = np.random.default_rng(42).integers(
            0, cfg.vocab, size=9).astype(np.int32)  # 2 full pages + 1 tok
        reqs = [Request(uid=i, prompt=prompt.copy(), max_new_tokens=6)
                for i in range(3)]

        def run(share):
            rs = [Request(uid=r.uid, prompt=r.prompt.copy(),
                          max_new_tokens=r.max_new_tokens) for r in reqs]
            return _run(cfg, params, reqs=rs, draft_cfg=cfg,
                        draft_params=good, spec_k=2, page_size=4,
                        share_prefix=share)

        out_noshare, _, _ = run(False)
        out_share, stats, eng = run(True)
        assert out_share == out_noshare
        assert stats.pages_shared > 0
        assert stats.cow_copies > 0
        assert eng.pages_in_use == 0

    def test_temperature_sampling_completes(self, setup):
        """Stochastic rejection sampling: not a parity path (separate host
        RNG), but every tick must commit >= 1 token and requests finish."""
        cfg, api, params, good, _ = setup
        reqs = [Request(uid=i,
                        prompt=np.random.default_rng(i).integers(
                            0, cfg.vocab, size=5).astype(np.int32),
                        max_new_tokens=6, temperature=0.8)
                for i in range(3)]
        out, stats, _ = _run(cfg, params, reqs=reqs, draft_cfg=cfg,
                             draft_params=good, spec_k=2)
        assert stats.decode_tokens >= stats.decode_steps  # >= 1 per tick

    def test_vocab_mismatch_rejected(self, setup):
        cfg, api, params, good, _ = setup
        other = C.get_config("llama3.2-1b")  # 128k vocab vs smoke 256
        with pytest.raises(ValueError, match="vocab"):
            ServingEngine(cfg, params, config=EngineConfig.of(
                    max_len=64, max_batch=2, draft_cfg=other,
                    draft_params={"x": 0}, spec_k=2))

    def test_unsupported_family_falls_back(self, setup):
        """A stateful (recurrent) family warns and serves without
        speculation instead of corrupting its integrator states."""
        cfg, api, params, good, _ = setup
        rec = C.get_config("recurrentgemma-2b", smoke=True)
        rec_api = get_api(rec)
        rec_params = rec_api.init_params(rec, jax.random.key(0))
        with pytest.warns(UserWarning, match="speculative"):
            eng = ServingEngine(rec, rec_params, config=EngineConfig.of(
                    max_len=32, max_batch=2, draft_cfg=rec,
                    draft_params=rec_params, spec_k=2))
        assert eng.spec_k == 0

    def test_spec_headroom_enforced(self, setup):
        cfg, api, params, good, _ = setup
        eng = ServingEngine(cfg, params, config=EngineConfig.of(
                max_len=16, max_batch=1, draft_cfg=cfg, draft_params=good,
                spec_k=4))
        eng.submit(Request(uid=0,
                           prompt=np.arange(6, dtype=np.int32),
                           max_new_tokens=8))  # 6 + 8 + 4 > 16
        with pytest.raises(AssertionError, match="spec_k"):
            eng.step()


# ---------------------------------------------------------------------------
# multi-device parity (mesh-smoke lane: XLA_FLAGS forces 8 host devices)
# ---------------------------------------------------------------------------

needs_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8",
)


@needs_devices
class TestSpeculativeMesh:
    """Speculative serving through a host mesh: the draft model, the
    multi-token verify step, and the paged + int8 compressed datapath all
    place through the axis-rules registry and must reproduce the 1-device
    speculative engine's greedy stream exactly."""

    @pytest.fixture(scope="class")
    def setup(self):
        cfg = C.get_config("tinyllama-1.1b", smoke=True)
        api = get_api(cfg)
        params = api.init_params(cfg, jax.random.key(0))
        plan = api.compress(cfg, params, WP.PlanConfig(
            default="quant_sparse", q_prune=0.5, bk=16, bn=16, min_size=1024))
        return cfg, api, params, plan

    def _serve(self, cfg, plan, mesh, rules, spec_k):
        # the draft serves the SAME compressed pytree (PackedLinear nodes
        # place through the registry's node expanders like the target's):
        # draft argmax == target argmax, so acceptance is high and the
        # accepted-prefix path is actually exercised under the mesh.
        eng = ServingEngine(cfg, None, plan=plan, config=EngineConfig.of(
                max_len=64, max_batch=3, kv_dtype="int8", page_size=8,
                share_prefix=True, mesh=mesh, rules=rules, draft_cfg=cfg,
                draft_params=plan.params, spec_k=spec_k))
        reqs = _requests(cfg, lens=(8, 8, 5), max_new=(6, 6, 5))
        for r in reqs:
            eng.submit(r)
        eng.run_until_done()
        assert all(r.done for r in reqs)
        return [tuple(r.output) for r in reqs], eng

    def test_parity_4x2_sharded_spec(self, setup):
        cfg, api, params, plan = setup
        base, _ = self._serve(cfg, plan, None, None, spec_k=2)
        mesh = M.make_serving_mesh("4x2")
        out, eng = self._serve(cfg, plan, mesh,
                               M.rules_for(cfg, None, mesh=mesh), spec_k=2)
        assert eng.model_parallel == 2 and eng.spec_k == 2
        assert out == base
        assert eng.stats.accept_rate > 0.3

    def test_parity_1x8_kv_fallback_spec(self, setup):
        cfg, api, params, plan = setup
        base, _ = self._serve(cfg, plan, None, None, spec_k=2)
        mesh = M.make_serving_mesh("1x8")
        out, eng = self._serve(cfg, plan, mesh,
                               M.rules_for(cfg, None, mesh=mesh), spec_k=2)
        assert eng.model_parallel == 8 and eng.kv_parallel == 1
        assert out == base
