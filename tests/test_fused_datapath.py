"""Fused compressed decode datapath (PR 2): multi-column block-sparse
kernel, fused gate+up FFN, and the int8 KV cache.

Parity contracts asserted here:
  * multi-column walk kernel == PR-1 per-column kernel == gather reference
    (exact up to float association), including empty columns and the int8
    scales epilogue;
  * fused gate+up == two-launch reference (exact for fp payloads, int8
    tolerance for quant_sparse), and it really is ONE pallas_call in the
    jaxpr;
  * int8 KV decode == fp-cache decode within the documented logit
    tolerance, in the pure-JAX path, the Pallas flash kernel, and the
    engine end-to-end;
  * the kv-aware n_opt sits exactly on decode_step_time's balance point.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import perf_model as pm
from repro.core import weight_plan as WP
from repro.core.batching import BatchSizer
from repro.core.pruning import BlockPruneConfig
from repro.core.sparse_format import build_walk, pad_walk, to_block_sparse
from repro.kernels import block_sparse as BS
from repro.kernels import ops, ref
from repro.models import layers as L
from repro.models.api import get_api, kv_bytes_per_token
from repro.serving.config import EngineConfig
from repro.serving.engine import Request, ServingEngine

RNG = np.random.default_rng(0)

TINY = ModelConfig(
    name="tiny", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab=256, compute_dtype="float32",
    activation="silu",
)

PC = WP.PlanConfig(default="quant_sparse", q_prune=0.25, bk=16, bn=16, min_size=1024)


def _x(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.normal(size=shape), dtype)


class TestMultiColumnKernel:
    @pytest.mark.parametrize("q", [0.0, 0.3, 0.6, 0.95])
    def test_matches_per_column_kernel_and_ref(self, q):
        """Walk kernel == static-sweep kernel == gather oracle; q=0.95
        exercises empty block-columns (FIRST|LAST no-compute steps)."""
        w = _x((256, 256))
        sp = to_block_sparse(w, q, BlockPruneConfig(bk=64, bn=64))
        x = _x((16, 256))
        walk = build_walk(sp.block_rows, sp.counts, sp.max_blocks)
        y_mc = BS.block_sparse_matmul_mc(x, sp, walk, block_b=16, interpret=True)
        y_col = BS.block_sparse_matmul(x, sp, block_b=16, interpret=True)
        y_ref = ref.block_sparse_matmul(x, sp)
        np.testing.assert_allclose(np.asarray(y_mc), np.asarray(y_col),
                                   rtol=1e-5, atol=2e-4)
        np.testing.assert_allclose(np.asarray(y_mc), np.asarray(y_ref),
                                   rtol=1e-5, atol=2e-4)

    def test_walk_steps_scale_with_survivors(self):
        """The whole point: grid steps == survivors (+1 per empty column),
        not n_cols * max_blocks."""
        w = _x((256, 256))
        sp = to_block_sparse(w, 0.75, BlockPruneConfig(bk=64, bn=64))
        walk = build_walk(sp.block_rows, sp.counts, sp.max_blocks)
        n_cols = 256 // 64
        survivors = int(np.asarray(sp.counts).sum())
        empties = int((np.asarray(sp.counts) == 0).sum())
        assert walk["idx"].shape[0] == survivors + empties
        assert walk["idx"].shape[0] < n_cols * sp.max_blocks

    def test_quant_scales_epilogue(self):
        w, x = _x((64, 96)), _x((8, 64))
        pc = dataclasses.replace(PC, min_size=64)
        p = WP.pack_block_sparse(w, pc, quant=True)
        pk = dataclasses.replace(p, use_kernel=True)
        np.testing.assert_allclose(
            np.asarray(WP.apply_linear(x, pk)),
            np.asarray(WP.apply_linear(x, p)), rtol=1e-5, atol=1e-4)

    def test_walk_survives_jit(self):
        """The pack-time walk is pytree data: the mc kernel fuses under jit
        (the PR-1 kernel path would silently run otherwise)."""
        w, x = _x((64, 96)), _x((8, 64))
        pc = dataclasses.replace(PC, min_size=64)
        pk = dataclasses.replace(
            WP.pack_block_sparse(w, pc, quant=True), use_kernel=True)
        assert pk.walk is not None
        y = jax.jit(WP.apply_linear)(x, pk)
        np.testing.assert_allclose(np.asarray(y), np.asarray(WP.apply_linear(x, pk)),
                                   rtol=1e-5, atol=1e-4)

    def test_stacked_walk_pads_and_slices(self):
        """Stacked slices pad their walks to one length with no-op steps so
        scan/vmap slicing works; padded steps must not change results."""
        ws = _x((3, 64, 96))
        pc = dataclasses.replace(PC, q_prune=0.5, min_size=64)
        p = WP.pack_block_sparse(ws, pc, quant=True)
        assert p.walk["idx"].shape[0] == 3
        x = _x((8, 64))
        for l in range(3):
            sl_ = jax.tree.map(lambda a: a[l], p)
            sl_ = dataclasses.replace(sl_, use_kernel=True)
            pl_ = WP.pack_block_sparse(ws[l], pc, quant=True)
            np.testing.assert_allclose(
                np.asarray(WP.apply_linear(x, sl_)),
                np.asarray(WP.apply_linear(x, pl_)), rtol=1e-5, atol=1e-4)

    def test_pad_walk_noop_flags(self):
        w = _x((64, 64))
        sp = to_block_sparse(w, 0.5, BlockPruneConfig(bk=16, bn=16))
        walk = build_walk(sp.block_rows, sp.counts, sp.max_blocks)
        n = walk["idx"].shape[0]
        padded = pad_walk(walk, n + 3)
        assert (padded["flags"][n:] == 0).all()
        x = _x((8, 64))
        y1 = BS.block_sparse_matmul_mc(x, sp, walk, block_b=8, interpret=True)
        y2 = BS.block_sparse_matmul_mc(x, sp, padded, block_b=8, interpret=True)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)


class TestFusedGateUp:
    def _pair(self, quant, q=0.25):
        pc = dataclasses.replace(PC, q_prune=q, min_size=64)
        g = WP.pack_block_sparse(_x((64, 96)), pc, quant=quant)
        u = WP.pack_block_sparse(_x((64, 96)), pc, quant=quant)
        return g, u

    @pytest.mark.parametrize("quant", [False, True])
    @pytest.mark.parametrize("act", ["silu", "gelu", "relu"])
    def test_kernel_matches_two_launch(self, quant, act):
        g, u = self._pair(quant)
        x = _x((8, 64))
        two = WP._GATE_ACTS[act](WP.apply_linear(x, g)) * WP.apply_linear(x, u)
        gk = dataclasses.replace(g, use_kernel=True)
        uk = dataclasses.replace(u, use_kernel=True)
        one = WP.apply_gate_up(x, gk, uk, act)
        np.testing.assert_allclose(np.asarray(one), np.asarray(two),
                                   rtol=1e-4, atol=1e-4)

    def test_different_max_blocks(self):
        """Gate and up are pruned independently: unequal mb must still pair."""
        pc = dataclasses.replace(PC, min_size=64)
        g = WP.pack_block_sparse(_x((64, 96)), dataclasses.replace(pc, q_prune=0.6),
                                 quant=True)
        u = WP.pack_block_sparse(_x((64, 96)), dataclasses.replace(pc, q_prune=0.1),
                                 quant=True)
        x = _x((8, 64))
        two = WP._GATE_ACTS["silu"](WP.apply_linear(x, g)) * WP.apply_linear(x, u)
        gk = dataclasses.replace(g, use_kernel=True)
        uk = dataclasses.replace(u, use_kernel=True)
        np.testing.assert_allclose(
            np.asarray(WP.apply_gate_up(x, gk, uk, "silu")), np.asarray(two),
            rtol=1e-4, atol=1e-4)

    def test_single_kernel_launch_in_jaxpr(self):
        """Acceptance: the fused quant_sparse FFN pair is ONE launch."""
        g, u = self._pair(quant=True)
        gk = dataclasses.replace(g, use_kernel=True)
        uk = dataclasses.replace(u, use_kernel=True)
        x = _x((8, 64))
        jaxpr = str(jax.make_jaxpr(lambda xx: WP.apply_gate_up(xx, gk, uk, "silu"))(x))
        assert jaxpr.count("pallas_call") == 1
        # the two-launch path really is two
        jaxpr2 = str(jax.make_jaxpr(
            lambda xx: WP._GATE_ACTS["silu"](WP.apply_linear(xx, gk))
            * WP.apply_linear(xx, uk))(x))
        assert jaxpr2.count("pallas_call") == 2

    def test_stacked_pair_vmaps(self):
        pc = dataclasses.replace(PC, min_size=64)
        g = WP.pack_block_sparse(_x((3, 64, 96)), pc, quant=True)
        u = WP.pack_block_sparse(_x((3, 64, 96)), pc, quant=True)
        x = _x((3, 8, 64))
        y = WP.apply_gate_up(x, g, u, "silu")
        for l in range(3):
            gl = jax.tree.map(lambda a: a[l], g)
            ul = jax.tree.map(lambda a: a[l], u)
            np.testing.assert_allclose(
                np.asarray(y[l]), np.asarray(WP.apply_gate_up(x[l], gl, ul, "silu")),
                rtol=1e-5, atol=1e-4)

    def test_dense_fallback_matches_mlp_math(self):
        """Non-packed representations fall back to two dispatches with
        identical math to the pre-fusion apply_mlp."""
        wg, wu, x = _x((64, 96)), _x((64, 96)), _x((2, 8, 64))
        y = WP.apply_gate_up(x, wg, wu, "silu")
        ref_y = jax.nn.silu(x @ wg) * (x @ wu)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref_y),
                                   rtol=1e-5, atol=1e-4)

    def test_model_prefill_routes_through_fused_pair(self):
        """Tiny gated model under a quant_sparse plan: prefill/decode work
        and match the unfused reference within int8 tolerance."""
        api = get_api(TINY)
        params = api.init_params(TINY, jax.random.key(0))
        # q_prune=0: every block survives, so the only gap vs dense is int8
        plan = api.compress(TINY, params, dataclasses.replace(PC, q_prune=0.0))
        assert plan.fused_pairs > 0
        batch = {"tokens": jnp.asarray(RNG.integers(0, TINY.vocab, (2, 8)), jnp.int32)}
        cache = api.init_cache(TINY, 2, 32, jnp.float32)
        lg_d, _ = api.prefill(TINY, params, batch, cache)
        lg_c, _ = api.prefill(TINY, plan.params, batch, cache)
        rel = float(jnp.linalg.norm(lg_d - lg_c) / jnp.linalg.norm(lg_d))
        assert rel < 0.05, rel


class TestInt8KVCache:
    def _setup(self, kv_dtype=None):
        api = get_api(TINY)
        params = api.init_params(TINY, jax.random.key(0))
        batch = {"tokens": jnp.asarray(RNG.integers(0, TINY.vocab, (2, 8)), jnp.int32)}
        cache = api.init_cache(TINY, 2, 32, jnp.float32, kv_dtype=kv_dtype)
        return api, params, batch, cache

    def test_cache_structure_and_bytes(self):
        api, _, _, cache = self._setup(jnp.int8)
        leaf = jax.tree.leaves(cache["unit"][0])
        kinds = {jnp.dtype(a.dtype) for a in leaf}
        assert jnp.dtype(jnp.int8) in kinds and jnp.dtype(jnp.float32) in kinds
        assert kv_bytes_per_token(TINY, jnp.int8) < 0.6 * kv_bytes_per_token(TINY)

    def test_decode_logit_parity(self):
        api, params, batch, cache_f = self._setup()
        _, _, _, cache_q = self._setup(jnp.int8)
        lg_f, cf = api.prefill(TINY, params, batch, cache_f)
        lg_q, cq = api.prefill(TINY, params, batch, cache_q)
        # prefill logits never touch the cache: identical
        np.testing.assert_allclose(np.asarray(lg_f), np.asarray(lg_q), atol=1e-5)
        pos = jnp.full((2,), 8, jnp.int32)
        tok = batch["tokens"][:, -1:]
        for _ in range(3):  # a few steps so quantized writes feed later reads
            ld_f, cf = api.decode_step(TINY, params, cf, tok, pos)
            ld_q, cq = api.decode_step(TINY, params, cq, tok, pos)
            pos = pos + 1
            tok = jnp.argmax(ld_f[:, 0:1], axis=-1).astype(jnp.int32)
        rel = float(jnp.linalg.norm(ld_f - ld_q) / jnp.linalg.norm(ld_f))
        assert rel < 0.05, rel

    def test_engine_end_to_end_int8(self):
        """Engine with int8 cache completes and matches the sequential
        prefill+decode loop over the same int8 caches (continuous batching
        must not change results)."""
        api, params, _, _ = self._setup()
        plan = api.compress(TINY, params, PC)
        eng = ServingEngine(TINY, plan.params, plan=plan, config=EngineConfig.of(
                max_len=64, max_batch=3, kv_dtype="int8"))
        rng = np.random.default_rng(2)
        reqs = [
            Request(uid=i, prompt=rng.integers(0, TINY.vocab, size=6).astype(np.int32),
                    max_new_tokens=5)
            for i in range(5)
        ]
        for r in reqs:
            eng.submit(r)
        stats = eng.run_until_done()
        assert stats.completed == len(reqs)
        for r in reqs:
            cache = api.init_cache(TINY, 1, 64, jnp.float32, kv_dtype=jnp.int8)
            lg, cache = api.prefill(
                TINY, plan.params, {"tokens": jnp.asarray(r.prompt)[None]}, cache)
            toks = [int(jnp.argmax(lg[0, -1]))]
            pos = len(r.prompt)
            for _ in range(4):
                lg, cache = api.decode_step(
                    TINY, plan.params, cache,
                    jnp.asarray([[toks[-1]]], jnp.int32), jnp.asarray([pos], jnp.int32))
                toks.append(int(jnp.argmax(lg[0, 0])))
                pos += 1
            assert r.output == toks, f"request {r.uid} diverged under int8 KV"

    def test_flash_kernel_int8_dequant(self):
        """Pallas flash kernel with int8 K/V + scales == fp oracle on the
        dequantized cache."""
        B, S, H, KVH, hd = 2, 256, 4, 2, 64
        q = _x((B, S, H, hd))
        k = _x((B, S, KVH, hd))
        v = _x((B, S, KVH, hd))
        kq, ks = L.quantize_kv(k)
        vq, vs = L.quantize_kv(v)
        o = ops.flash_attention(q, kq, vq, causal=True,
                                block_q=64, block_k=64, k_scale=ks, v_scale=vs)
        r = ref.flash_attention(q, kq.astype(jnp.float32) * ks[..., None],
                                vq.astype(jnp.float32) * vs[..., None], causal=True)
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=5e-4)

    def test_quantize_kv_roundtrip(self):
        k = _x((2, 5, 3, 64))
        kq, ks = L.quantize_kv(k)
        rec = kq.astype(jnp.float32) * ks[..., None]
        rel = float(jnp.linalg.norm(rec - k) / jnp.linalg.norm(k))
        assert rel < 0.01, rel

    def test_cache_axes_quantized(self):
        axes = L.attn_cache_axes(quantized=True)
        assert set(axes) == {"k", "v", "k_scale", "v_scale"}
        assert len(axes["k_scale"]) == 3


class TestKvAwareNOpt:
    N, CTX, KV_FP, KV_I8 = 10**9, 128, 45056.0, 11968.0

    def test_nopt_sits_on_balance_point(self):
        """Acceptance: sizer n_opt == decode_step_time's t_calc/t_mem
        crossover, for both cache dtypes."""
        for kv in (self.KV_FP, self.KV_I8):
            s = BatchSizer(n_params=self.N, b_weight=1.0,
                           kv_bytes_per_token=kv, context_len=self.CTX)
            t = pm.decode_step_time(self.N, s.n_opt, kv, self.CTX, b_weight=1.0)
            assert t["t_calc"] == pytest.approx(t["t_mem"], rel=0.02)

    def test_int8_cache_lowers_nopt_toward_weight_only(self):
        base = BatchSizer(n_params=self.N, b_weight=1.0).n_opt
        fp = BatchSizer(n_params=self.N, b_weight=1.0,
                        kv_bytes_per_token=self.KV_FP, context_len=self.CTX).n_opt
        i8 = BatchSizer(n_params=self.N, b_weight=1.0,
                        kv_bytes_per_token=self.KV_I8, context_len=self.CTX).n_opt
        assert base < i8 < fp

    def test_kv_dominated_is_unbounded(self):
        s = BatchSizer(n_params=10**6, b_weight=1.0,
                       kv_bytes_per_token=self.KV_FP, context_len=4096)
        assert s.n_opt >= 1 << 20

    def test_no_kv_keeps_legacy_nopt(self):
        a = BatchSizer(n_params=self.N)
        b = BatchSizer(n_params=self.N, kv_bytes_per_token=0.0, context_len=0)
        assert a.n_opt == b.n_opt

    def test_api_kv_bytes_helper(self):
        fp = kv_bytes_per_token(TINY)
        i8 = kv_bytes_per_token(TINY, jnp.int8)
        # 2 layers * 2 (k+v) * KVH=2 * (hd=16 payload + 4B scale) at f32
        assert fp == 2 * 2 * 2 * 16 * 4
        assert i8 == 2 * 2 * (2 * 16 + 2 * 4)
        assert i8 < fp


class TestPlanCache:
    def test_round_trip_serves_identically(self, tmp_path):
        api = get_api(TINY)
        params = api.init_params(TINY, jax.random.key(0))
        plan = api.compress(TINY, params, PC)
        WP.save_plan(str(tmp_path), plan)
        plan2 = WP.load_plan(str(tmp_path), params)
        assert plan2.cfg == plan.cfg
        assert plan2.fused_pairs == plan.fused_pairs
        for a, b in zip(jax.tree.leaves(plan.params), jax.tree.leaves(plan2.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        batch = {"tokens": jnp.asarray(RNG.integers(0, TINY.vocab, (2, 8)), jnp.int32)}
        cache = api.init_cache(TINY, 2, 32, jnp.float32)
        lg1, _ = api.prefill(TINY, plan.params, batch, cache)
        lg2, _ = api.prefill(TINY, plan2.params, batch, cache)
        np.testing.assert_allclose(np.asarray(lg1), np.asarray(lg2), atol=1e-6)

    def test_structure_mismatch_rejected(self, tmp_path):
        api = get_api(TINY)
        params = api.init_params(TINY, jax.random.key(0))
        plan = api.compress(TINY, params, PC)
        WP.save_plan(str(tmp_path), plan)
        other = ModelConfig(
            name="other", family="dense", n_layers=3, d_model=64, n_heads=4,
            n_kv_heads=2, d_ff=128, vocab=256, compute_dtype="float32")
        params2 = get_api(other).init_params(other, jax.random.key(0))
        with pytest.raises(ValueError):
            WP.load_plan(str(tmp_path), params2)

    def test_missing_cache_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            WP.load_plan(str(tmp_path / "nope"), {})
