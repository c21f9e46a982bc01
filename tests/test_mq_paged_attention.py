"""Single-pass multi-query paged decode attention.

Pins the tentpole acceptance criteria of the page-stream amortization:

* ``ops.paged_decode_attention`` with T > 1 lowers to ONE ``pallas_call``
  (jaxpr-asserted) and is bit-identical to running the single-query kernel
  once per position — across fp, int8, windowed, and page-boundary
  positions — and matches the pure-JAX gather reference to fp tolerance.
* The enc-dec cross-attention path streams the static encoder pool through
  the same kernel (identity page table, non-causal masking) and matches the
  plain non-causal reference, including padded frame counts.
* The serving engine, forced onto the kernel datapath off-TPU, commits the
  IDENTICAL greedy stream with spec_k in {1, 2, 3} as without speculation
  (the multi-query verify is bit-equal to T sequential kernel steps).
* The same holds through an 8-device host mesh (CI ``mesh-smoke`` lane,
  XLA_FLAGS=--xla_force_host_platform_device_count=8; skips elsewhere).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.kernels import flash_attention as FA
from repro.kernels import ops
from repro.launch import mesh as M
from repro.models import layers as L
from repro.models.api import get_api
from repro.serving.config import EngineConfig
from repro.serving.engine import Request, ServingEngine


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _paged_copy_of(x, ps, num_pages, table):
    """Pack a contiguous (B, S, ...) cache into head-major (num_pages, KVH, ps, ...) pools
    laid out per ``table`` (mirrors tests/test_paged_cache.py)."""
    B, S = x.shape[:2]
    pool = jnp.zeros((num_pages, x.shape[2], ps) + x.shape[3:], x.dtype)
    for b in range(B):
        for lp in range(S // ps):
            pool = pool.at[int(table[b, lp])].set(
                jnp.swapaxes(x[b, lp * ps : (lp + 1) * ps], 0, 1))
    return pool


def _setup(B=3, S=32, KVH=2, G=4, hd=16, ps=8, quantized=False, seed=0):
    """Scrambled physical page layout; pos values sit mid-page, at a page's
    last slot (7), and near the cache end, so a T-token span crosses page
    boundaries."""
    key = jax.random.key(seed)
    H = KVH * G
    P = S // ps
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KVH, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KVH, hd))
    perm = np.random.default_rng(seed).permutation(B * P)
    table = jnp.asarray(1 + perm.reshape(B, P), jnp.int32)
    num_pages = 1 + B * P
    pos = jnp.asarray([7, 17, 27], jnp.int32)[:B]
    extra = {}
    if quantized:
        k, ks = L.quantize_kv(k)
        v, vs = L.quantize_kv(v)
        extra = {
            "k_scale_pages": _paged_copy_of(ks, ps, num_pages, table),
            "v_scale_pages": _paged_copy_of(vs, ps, num_pages, table),
        }
    kp = _paged_copy_of(k, ps, num_pages, table)
    vp = _paged_copy_of(v, ps, num_pages, table)

    def q_for(T, fold=9):
        return jax.random.normal(jax.random.fold_in(key, fold), (B, T, H, hd))

    return q_for, kp, vp, table, pos, extra


def _loop_reference(q, kp, vp, table, pos, **kw):
    """Per-position single-query kernel sweep — the pre-single-pass
    datapath, kept as the bit-parity oracle."""
    T = q.shape[1]
    outs = [
        FA.paged_decode_attention(
            q[:, t : t + 1], kp, vp, table, pos + t, interpret=True, **kw)
        for t in range(T)
    ]
    return jnp.concatenate(outs, axis=1)


# ---------------------------------------------------------------------------
# kernel parity: multi-query vs per-position loop and gather reference
# ---------------------------------------------------------------------------


class TestMQKernelParity:
    @pytest.mark.parametrize("T", [1, 2, 4])
    def test_bit_parity_with_per_position_loop_fp(self, T):
        q_for, kp, vp, table, pos, _ = _setup()
        q = q_for(T)
        out = ops.paged_decode_attention(q, kp, vp, table, pos, interpret=True)
        ref = _loop_reference(q, kp, vp, table, pos)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    @pytest.mark.parametrize("T", [2, 4])
    def test_bit_parity_int8(self, T):
        q_for, kp, vp, table, pos, sc = _setup(quantized=True)
        q = q_for(T)
        out = ops.paged_decode_attention(
            q, kp, vp, table, pos, interpret=True, **sc)
        ref = _loop_reference(q, kp, vp, table, pos, **sc)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    @pytest.mark.parametrize("window", [5, 8, 13])
    def test_bit_parity_windowed(self, window):
        """Sliding-window masking is per-query: row t's window ends at
        pos + t, so each row of the tile sees a different span."""
        q_for, kp, vp, table, pos, _ = _setup()
        q = q_for(3)
        out = ops.paged_decode_attention(
            q, kp, vp, table, pos, window=window, interpret=True)
        ref = _loop_reference(q, kp, vp, table, pos, window=window)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_matches_gather_reference(self):
        """fp-tolerance parity against the pure-JAX gather + ring-mask
        reference — a different mask derivation, so this guards the
        per-query position arithmetic, not just kernel self-consistency."""
        for quantized in (False, True):
            q_for, kp, vp, table, pos, sc = _setup(quantized=quantized)
            q = q_for(3)
            out = ops.paged_decode_attention(
                q, kp, vp, table, pos, interpret=True, **sc)
            ref = L.paged_decode_attention(
                q, kp, vp, table, pos, use_kernel=False,
                k_scale_pages=sc.get("k_scale_pages"),
                v_scale_pages=sc.get("v_scale_pages"))
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_page_boundary_positions(self):
        """pos at a page's last slot: the T-span's writes/reads straddle
        the boundary and the null-page masking must hold on both sides."""
        q_for, kp, vp, table, pos, _ = _setup()
        for base in (0, 7, 8, 23):
            p = jnp.full((3,), base, jnp.int32)
            q = q_for(4, fold=base + 20)
            out = ops.paged_decode_attention(q, kp, vp, table, p, interpret=True)
            ref = _loop_reference(q, kp, vp, table, p)
            np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_single_pallas_call_at_verify_width(self):
        """The acceptance criterion: T > 1 lowers to ONE pallas_call — the
        page stream is fetched once per tick, not once per position."""
        q_for, kp, vp, table, pos, _ = _setup()
        for T in (2, 4):
            jaxpr = str(jax.make_jaxpr(
                lambda qq: ops.paged_decode_attention(
                    qq, kp, vp, table, pos, interpret=True))(q_for(T)))
            assert jaxpr.count("pallas_call") == 1, T

    def test_layers_dispatch_single_pallas_call(self):
        """The layers-level dispatch (what the models call) inherits the
        single-call lowering when forced onto the kernel path."""
        q_for, kp, vp, table, pos, _ = _setup()
        jaxpr = str(jax.make_jaxpr(
            lambda qq: L.paged_decode_attention(
                qq, kp, vp, table, pos, use_kernel=True))(q_for(3)))
        assert jaxpr.count("pallas_call") == 1


# ---------------------------------------------------------------------------
# enc-dec cross-attention through the same kernel
# ---------------------------------------------------------------------------


class TestCrossDecodeAttention:
    def _kv(self, B=2, Sf=20, KVH=2, hd=16, seed=3):
        key = jax.random.key(seed)
        xk = jax.random.normal(jax.random.fold_in(key, 1), (B, Sf, KVH, hd))
        xv = jax.random.normal(jax.random.fold_in(key, 2), (B, Sf, KVH, hd))
        return xk, xv

    @pytest.mark.parametrize("Sf", [5, 20, 130])
    @pytest.mark.parametrize("T", [1, 3])
    def test_parity_vs_noncausal_reference(self, Sf, T):
        """All T queries see all Sf real frames; padded slots (Sf rounded
        up to the page multiple) must be masked out."""
        B, KVH, hd, H = 2, 2, 16, 8
        xk, xv = self._kv(B=B, Sf=Sf, KVH=KVH, hd=hd)
        q = jax.random.normal(jax.random.key(7), (B, T, H, hd))
        out = ops.cross_decode_attention(q, xk, xv, interpret=True)
        ref = L.attention(q, xk, xv, causal=False)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_layers_dispatch_parity(self):
        xk, xv = self._kv()
        q = jax.random.normal(jax.random.key(8), (2, 3, 8, 16))
        out = L.cross_decode_attention(q, xk, xv, use_kernel=True)
        ref = L.cross_decode_attention(q, xk, xv, use_kernel=False)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_single_pallas_call(self):
        xk, xv = self._kv()
        q = jax.random.normal(jax.random.key(9), (2, 4, 8, 16))
        jaxpr = str(jax.make_jaxpr(
            lambda qq: ops.cross_decode_attention(qq, xk, xv, interpret=True))(q))
        assert jaxpr.count("pallas_call") == 1

    def test_encdec_multitoken_decode_step(self):
        """The enc-dec decoder now threads (B, T) decode spans: T=3 in one
        step must equal 3 sequential steps, on both datapaths."""
        cfg = C.get_config("whisper-tiny", smoke=True)
        api = get_api(cfg)
        params = api.init_params(cfg, jax.random.key(0))
        B, S, T = 2, 6, 3
        rng = np.random.default_rng(0)
        batch = {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32),
            "frames": jnp.asarray(
                rng.normal(size=(B, cfg.n_frames, cfg.d_model)), jnp.float32),
        }
        cache0 = api.init_cache(cfg, B, 32, jnp.dtype(cfg.compute_dtype))
        logits, cache0 = jax.jit(functools.partial(api.prefill, cfg))(
            params, batch, cache0)
        chain = [int(jnp.argmax(logits[0, -1])), 7, 123]
        tokens = jnp.asarray([chain, chain], jnp.int32)
        pos0 = jnp.full((B,), S, jnp.int32)
        for force in (False, True):
            prev = L.force_attention_kernel(force)
            try:
                seq_cache = jax.tree.map(lambda x: x, cache0)
                seq_logits = []
                for t in range(T):
                    lg, seq_cache = api.decode_step(
                        cfg, params, seq_cache, tokens[:, t : t + 1], pos0 + t)
                    seq_logits.append(lg[:, 0])
                mt_logits, mt_cache = api.decode_step(
                    cfg, params, cache0, tokens, pos0)
            finally:
                L.force_attention_kernel(prev)
            for t in range(T):
                np.testing.assert_allclose(
                    np.asarray(mt_logits[:, t], np.float32),
                    np.asarray(seq_logits[t], np.float32),
                    atol=2e-5, rtol=2e-5, err_msg=f"force={force} t={t}")
            for a, b in zip(jax.tree.leaves(mt_cache), jax.tree.leaves(seq_cache)):
                np.testing.assert_allclose(
                    np.asarray(a, np.float32), np.asarray(b, np.float32),
                    atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# engine greedy bit-parity on the kernel datapath
# ---------------------------------------------------------------------------


def _requests(cfg, lens=(6, 9, 3), max_new=(8, 6, 8)):
    return [
        Request(uid=i,
                prompt=np.random.default_rng(i).integers(
                    0, cfg.vocab, size=ln).astype(np.int32),
                max_new_tokens=mn)
        for i, (ln, mn) in enumerate(zip(lens, max_new))
    ]


def _run_forced(cfg, params, force_kernel, **kw):
    """Run the engine with the process-wide kernel override pinned for the
    whole lifetime of its jitted closures (trace-time dispatch)."""
    prev = L.force_attention_kernel(force_kernel)
    try:
        eng = ServingEngine(cfg, params, config=EngineConfig.of(
                max_len=64, max_batch=3, **kw))
        reqs = _requests(cfg)
        for r in reqs:
            eng.submit(r)
        stats = eng.run_until_done()
    finally:
        L.force_attention_kernel(prev)
    assert stats.completed == len(reqs)
    return [tuple(r.output) for r in reqs], stats


@pytest.mark.slow
class TestEngineKernelParity:
    """Greedy bit-parity through the serving engine with the Pallas
    (interpret-mode) datapath forced on: the multi-query verify step is
    bit-equal to T single-query kernel steps, so the speculative engine
    must commit the identical stream as plain kernel decode."""

    @pytest.fixture(scope="class")
    def setup(self):
        cfg = C.get_config("tinyllama-1.1b", smoke=True)
        api = get_api(cfg)
        params = api.init_params(cfg, jax.random.key(0))
        return cfg, params

    def test_plain_decode_kernel_vs_reference(self, setup):
        """T=1 sanity: the kernel datapath serves the same greedy stream
        as the gather reference (fp-level numerics agree on argmax for
        this model/seed — the cross-datapath anchor for the spec tests)."""
        cfg, params = setup
        base, _ = _run_forced(cfg, params, False, page_size=8)
        out, _ = _run_forced(cfg, params, True, page_size=8)
        assert out == base

    @pytest.mark.parametrize("spec_k", [1, 2, 3])
    def test_greedy_parity_speculative(self, setup, spec_k):
        cfg, params = setup
        base, _ = _run_forced(cfg, params, True, page_size=8)
        out, stats = _run_forced(
            cfg, params, True, page_size=8,
            draft_cfg=cfg, draft_params=params, spec_k=spec_k)
        assert out == base
        assert stats.accept_rate > 0.5  # the draft IS the target

    def test_greedy_parity_int8_pages(self, setup):
        cfg, params = setup
        base, _ = _run_forced(cfg, params, True, page_size=8, kv_dtype="int8")
        out, _ = _run_forced(
            cfg, params, True, page_size=8, kv_dtype="int8",
            draft_cfg=cfg, draft_params=params, spec_k=2)
        assert out == base

    def test_sizer_tracks_measured_acceptance(self, setup):
        """EngineStats.accept_rate feeds BatchSizer.spec_accept (EMA): a
        sizer configured with a pessimistic prior converges toward the
        observed rate over the run."""
        from repro.core.batching import BatchSizer

        cfg, params = setup
        sizer = BatchSizer(n_params=10**6, spec_k=2, spec_accept=0.0)
        prev = L.force_attention_kernel(False)
        try:
            eng = ServingEngine(cfg, params, sizer=sizer, config=EngineConfig.of(
                    max_len=64, max_batch=3, page_size=8, draft_cfg=cfg,
                    draft_params=params, spec_k=2))
            reqs = _requests(cfg)
            for r in reqs:
                eng.submit(r)
            stats = eng.run_until_done()
        finally:
            L.force_attention_kernel(prev)
        assert eng.sizer.spec_accept > 0.0
        assert abs(eng.sizer.spec_accept - stats.accept_rate) < 0.35
        assert eng.sizer.committed_per_tick(4) > 4.0  # acceptance > 0 now


# ---------------------------------------------------------------------------
# multi-device parity (mesh-smoke lane: XLA_FLAGS forces 8 host devices)
# ---------------------------------------------------------------------------

needs_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8",
)


@needs_devices
class TestMeshKernelParity:
    """The single-pass kernel under a host mesh: pools shard over kv_heads
    via the axis-rules registry; the speculative engine on the kernel
    datapath must reproduce the unsharded kernel stream exactly."""

    @pytest.fixture(scope="class")
    def setup(self):
        cfg = C.get_config("tinyllama-1.1b", smoke=True)
        api = get_api(cfg)
        params = api.init_params(cfg, jax.random.key(0))
        return cfg, params

    def test_parity_4x2_spec_kernel(self, setup):
        cfg, params = setup
        base, _ = _run_forced(cfg, params, True, page_size=8,
                              draft_cfg=cfg, draft_params=params, spec_k=2)
        mesh = M.make_serving_mesh("4x2")
        out, stats = _run_forced(
            cfg, params, True, page_size=8, mesh=mesh,
            rules=M.rules_for(cfg, None, mesh=mesh),
            draft_cfg=cfg, draft_params=params, spec_k=2)
        assert out == base
        assert stats.accept_rate > 0.5
