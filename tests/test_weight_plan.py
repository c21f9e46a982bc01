"""Compressed-weight execution plan: representation assignment, packed
datapath parity, engine end-to-end with quant+sparse, and the Section 5.6
n_opt corrections.

Documented tolerances (asserted below):
  * int8 quantization (quant / quant_sparse) moves full-model logits by
    < 5% relative L2 on the tiny config (~2% measured);
  * the block-sparse packed datapath is exact (float assoc slack only)
    against masked-dense: same surviving weights, same math.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, MoEConfig
from repro.core import weight_plan as WP
from repro.core.batching import BatchSizer
from repro.core.pruning import BlockPruneConfig, block_mask, expand_block_mask
from repro.models.api import get_api
from repro.serving.config import EngineConfig
from repro.serving.engine import Request, ServingEngine

TINY = ModelConfig(
    name="tiny", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab=256, compute_dtype="float32",
)

PC = WP.PlanConfig(default="quant_sparse", q_prune=0.25, bk=16, bn=16, min_size=1024)


def _mask_sparse_leaves(params, pc: WP.PlanConfig):
    """Masked-dense reference: zero the same blocks the plan prunes."""

    def m(path, leaf):
        if not (hasattr(leaf, "ndim")
                and WP._sparse_eligible(WP.leaf_name(path), leaf, pc)):
            return leaf
        ws = leaf if leaf.ndim == 3 else leaf[None]
        out = jnp.stack([
            ws[l] * expand_block_mask(block_mask(ws[l], pc.q_prune, pc.block), pc.block)
            for l in range(ws.shape[0])
        ])
        return out if leaf.ndim == 3 else out[0]

    return jax.tree_util.tree_map_with_path(m, params)


class TestPackedDatapath:
    def _wx(self, K=64, N=96, seed=0):
        rng = np.random.default_rng(seed)
        return (jnp.asarray(rng.normal(size=(K, N)), jnp.float32),
                jnp.asarray(rng.normal(size=(8, K)), jnp.float32))

    def test_block_sparse_matches_masked_dense(self):
        w, x = self._wx()
        pc = dataclasses.replace(PC, q_prune=0.25, min_size=64)
        p = WP.pack_block_sparse(w, pc, quant=False)
        bm = expand_block_mask(block_mask(w, 0.25, pc.block), pc.block)
        np.testing.assert_allclose(
            np.asarray(WP.apply_linear(x, p)), np.asarray(x @ (w * bm)),
            rtol=1e-5, atol=1e-4,
        )

    def test_quant_sparse_within_int8_tolerance(self):
        w, x = self._wx()
        pc = dataclasses.replace(PC, q_prune=0.25, min_size=64)
        p = WP.pack_block_sparse(w, pc, quant=True)
        bm = expand_block_mask(block_mask(w, 0.25, pc.block), pc.block)
        ref = x @ (w * bm)
        rel = float(jnp.linalg.norm(WP.apply_linear(x, p) - ref) / jnp.linalg.norm(ref))
        assert rel < 0.02, rel

    def test_kernel_path_matches_reference_path(self):
        """Pallas kernel (interpret mode, scales epilogue) == gather ref."""
        w, x = self._wx()
        pc = dataclasses.replace(PC, q_prune=0.25, min_size=64)
        for quant in (False, True):
            p_ref = WP.pack_block_sparse(w, pc, quant=quant)
            p_k = dataclasses.replace(p_ref, use_kernel=True)
            np.testing.assert_allclose(
                np.asarray(WP.apply_linear(x, p_k)),
                np.asarray(WP.apply_linear(x, p_ref)),
                rtol=1e-5, atol=1e-4,
            )

    def test_stacked_pack_slices_like_scan(self):
        """Stacked packing (scan units / experts) == per-slice packing."""
        rng = np.random.default_rng(1)
        ws = jnp.asarray(rng.normal(size=(3, 64, 96)), jnp.float32)
        x = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)
        pc = dataclasses.replace(PC, q_prune=0.25, min_size=64)
        p = WP.pack_block_sparse(ws, pc, quant=True)
        assert p.stacked
        y = WP.apply_linear(jnp.broadcast_to(x, (3, 8, 64)), p)
        for l in range(3):
            pl = WP.pack_block_sparse(ws[l], pc, quant=True)
            np.testing.assert_allclose(
                np.asarray(y[l]), np.asarray(WP.apply_linear(x, pl)),
                rtol=1e-5, atol=1e-4,
            )

    def test_dense_and_quant_dispatch_unchanged(self):
        w, x = self._wx()
        assert jnp.allclose(WP.apply_linear(x, w), x @ w)
        q = WP.quantize_leaf(w)
        ref = x @ (q["q"].astype(jnp.float32) * q["s"][None, :])
        np.testing.assert_allclose(
            np.asarray(WP.apply_linear(x, q)), np.asarray(ref), rtol=1e-5, atol=1e-4
        )


class TestPlanAssignment:
    def test_assignments_and_fallbacks(self):
        params = {
            "mlp": {"w_up": jnp.ones((64, 96)), "b": jnp.ones((96,))},
            "embed": {"tok": jnp.ones((256, 64))},
            "odd": {"w_odd": jnp.ones((64, 100))},  # 100 % 16 != 0 -> quant
            "small": {"w_s": jnp.ones((8, 8))},  # below min_size -> dense
        }
        pc = dataclasses.replace(PC, min_size=1024)
        plan = WP.compress(params, pc)
        kinds = {k: v.kind for k, v in plan.leaves.items()}
        assert kinds["mlp/w_up"] == "quant_sparse"
        assert kinds["embed/tok"] == "quant"  # gather table: never sparse
        assert kinds["odd/w_odd"] == "quant"  # shape fallback
        assert kinds["small/w_s"] == "dense"
        assert kinds["mlp/b"] == "dense"

    def test_rules_override(self):
        params = {"a": {"w_x": jnp.ones((64, 96))}, "b": {"w_x": jnp.ones((64, 96))}}
        pc = dataclasses.replace(PC, min_size=64, rules=(("a/", "dense"),))
        plan = WP.compress(params, pc)
        assert plan.leaves["a/w_x"].kind == "dense"
        assert plan.leaves["b/w_x"].kind == "quant_sparse"

    def test_three_tuple_rule_sets_per_leaf_q(self):
        """The autotuner emits (sub, repr, q_prune) rules: the matched leaf
        prunes at the rule's q, everything else at the plan-wide q."""
        rng = np.random.default_rng(0)
        params = {
            "a": {"w_x": jnp.asarray(rng.normal(size=(64, 96)), jnp.float32)},
            "b": {"w_x": jnp.asarray(rng.normal(size=(64, 96)), jnp.float32)},
        }
        pc = dataclasses.replace(
            PC, q_prune=0.25, min_size=64,
            rules=(("a/", "quant_sparse", 0.5), ("b/", "block_sparse", None)))
        plan = WP.compress(params, pc)
        assert plan.leaves["a/w_x"].q_prune == pytest.approx(0.5)
        assert plan.leaves["b/w_x"].kind == "block_sparse"
        assert plan.leaves["b/w_x"].q_prune == pytest.approx(0.25)  # None -> plan q

    def test_rule_validation(self):
        for rules in ((("a/",),), (("a/", "nope"),), (("a/", "dense", 1.5),)):
            with pytest.raises(ValueError):
                dataclasses.replace(PC, rules=rules)

    def test_summary_reports_q_provenance_and_round_trips(self, tmp_path):
        """summary() must carry each kind's q range (a tuned plan is
        unreadable without it) and survive save_plan/load_plan with
        3-tuple rules byte-for-byte."""
        rng = np.random.default_rng(1)
        params = {
            "a": {"w_x": jnp.asarray(rng.normal(size=(64, 96)), jnp.float32)},
            "b": {"w_x": jnp.asarray(rng.normal(size=(64, 96)), jnp.float32)},
        }
        pc = dataclasses.replace(
            PC, q_prune=0.0, min_size=64,
            rules=(("a/", "quant_sparse", 0.5), ("b/", "quant_sparse", 0.25)))
        plan = WP.compress(params, pc)
        s = plan.summary(per_leaf=True)
        assert "q=0.25..0.5" in s  # aggregated range for quant_sparse
        assert "a/w_x: quant_sparse q=0.50" in s
        assert "b/w_x: quant_sparse q=0.25" in s
        WP.save_plan(str(tmp_path / "plan"), plan)
        restored = WP.load_plan(str(tmp_path / "plan"), params)
        assert restored.cfg == plan.cfg  # 3-tuple rules survive JSON
        assert restored.summary(per_leaf=True) == s

    def test_plan_apply_linear_by_path(self):
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.normal(size=(64, 96)), jnp.float32)
        x = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
        plan = WP.compress({"w_up": w}, dataclasses.replace(PC, min_size=64))
        y = plan.apply_linear("w_up", x)
        assert y.shape == (4, 96)
        with pytest.raises(KeyError):
            plan.apply_linear("nope", x)

    def test_stats_feed_perf_model(self):
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
        plan = WP.compress(
            {"w_up": w}, dataclasses.replace(PC, q_prune=0.5, min_size=64)
        )
        lf = plan.leaves["w_up"]
        assert lf.kind == "quant_sparse"
        assert lf.surviving == 64 * 128 // 2
        assert plan.q_prune_effective == pytest.approx(0.5)
        assert plan.b_weight_effective == pytest.approx(1.0, abs=0.01)
        assert plan.q_overhead_effective > 1.0
        assert plan.weight_bytes < 64 * 128 * 2  # beat the bf16 dense stream


class TestModelParity:
    """Acceptance: tiny-config serving with a quant+sparse plan matches the
    dense / masked-dense reference within the documented tolerance."""

    def _setup(self):
        api = get_api(TINY)
        params = api.init_params(TINY, jax.random.key(0))
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, TINY.vocab, (2, 8)), jnp.int32)}
        cache = api.init_cache(TINY, 2, 32, jnp.float32)
        return api, params, batch, cache

    def test_prefill_decode_parity_unpruned(self):
        # q_prune=0: the sparse datapath stores every block; the only error
        # left is int8 quantization (< 5% relative on logits).
        api, params, batch, cache = self._setup()
        pc = dataclasses.replace(PC, q_prune=0.0)
        plan = api.compress(TINY, params, pc)
        lg_d, _ = api.prefill(TINY, params, batch, cache)
        lg_c, cc = api.prefill(TINY, plan.params, batch, cache)
        rel = float(jnp.linalg.norm(lg_d - lg_c) / jnp.linalg.norm(lg_d))
        assert rel < 0.05, rel
        pos = jnp.full((2,), 8, jnp.int32)
        ld_d, _ = api.decode_step(TINY, params, cc, batch["tokens"][:, -1:], pos)
        ld_c, _ = api.decode_step(TINY, plan.params, cc, batch["tokens"][:, -1:], pos)
        rel = float(jnp.linalg.norm(ld_d - ld_c) / jnp.linalg.norm(ld_d))
        assert rel < 0.05, rel

    def test_pruned_parity_vs_masked_dense(self):
        # q_prune=0.25: compressed == masked-dense with the same survivors,
        # so the gap is again only int8 (the sparse format itself is exact).
        api, params, batch, cache = self._setup()
        plan = api.compress(TINY, params, PC)
        masked = _mask_sparse_leaves(params, PC)
        lg_m, _ = api.prefill(TINY, masked, batch, cache)
        lg_c, _ = api.prefill(TINY, plan.params, batch, cache)
        rel = float(jnp.linalg.norm(lg_m - lg_c) / jnp.linalg.norm(lg_m))
        assert rel < 0.05, rel

    def test_engine_end_to_end_quant_sparse(self):
        """ServingEngine with a quant+sparse plan completes, and greedy
        decode through the engine equals greedy decode through the plain
        prefill+decode loop over the same compressed params (continuous
        batching changes scheduling, never results)."""
        api, params, _, _ = self._setup()
        plan = api.compress(TINY, params, PC)
        eng = ServingEngine(TINY, plan.params, plan=plan, config=EngineConfig.of(
                max_len=64, max_batch=3))
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
            cache = api.init_cache(TINY, 1, 64, jnp.float32)
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
            assert r.output == toks, f"request {r.uid} diverged under the plan"

    def test_moe_stacked_experts_compress(self):
        cfg = ModelConfig(
            name="tiny-moe", family="moe", n_layers=2, d_model=64, n_heads=4,
            n_kv_heads=2, d_ff=0, vocab=256, compute_dtype="float32",
            moe=MoEConfig(n_experts=4, top_k=2, expert_d_ff=64),
        )
        api = get_api(cfg)
        params = api.init_params(cfg, jax.random.key(0))
        plan = api.compress(cfg, params, PC)
        kinds = {k: v.kind for k, v in plan.leaves.items()}
        assert kinds["unit/0/moe/w_up"] == "quant_sparse"  # stacked (E, d, f)
        assert kinds["unit/0/moe/router"] == "dense"
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (2, 8)), jnp.int32)}
        cache = api.init_cache(cfg, 2, 32, jnp.float32)
        lg, cc = api.prefill(cfg, plan.params, batch, cache)
        assert bool(jnp.isfinite(lg).all())
        ld, _ = api.decode_step(cfg, plan.params, cc, batch["tokens"][:, -1:],
                                jnp.full((2,), 8, jnp.int32))
        assert bool(jnp.isfinite(ld).all())


class TestNOptCorrection:
    """BatchSizer moves the way Section 5.6 predicts."""

    def test_sparse_compute_cancels_q_prune(self):
        base = BatchSizer(n_params=10**9)
        pruned = BatchSizer(n_params=10**9, q_prune=0.6, sparse_compute=True)
        # both t_calc and t_mem scale with (1 - q_prune): balance unchanged
        assert pruned.n_opt == base.n_opt

    def test_masked_dense_scales_n_opt(self):
        base = BatchSizer(n_params=10**9)
        pruned = BatchSizer(n_params=10**9, q_prune=0.5, sparse_compute=False)
        assert pruned.n_opt == pytest.approx(base.n_opt * 0.5, rel=0.02)

    def test_q_overhead_raises_n_opt(self):
        base = BatchSizer(n_params=10**9)
        ov = BatchSizer(n_params=10**9, q_overhead=64.0 / 48.0)
        assert ov.n_opt == pytest.approx(base.n_opt * 64 / 48, rel=0.02)

    def test_int8_halves_n_opt(self):
        # b_weight 2 -> 1: the stream halves, balance batch halves
        b2 = BatchSizer(n_params=10**9, b_weight=2.0)
        b1 = BatchSizer(n_params=10**9, b_weight=1.0)
        assert b1.n_opt == pytest.approx(b2.n_opt / 2, rel=0.02)

    def test_plan_sizer_wiring(self):
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
        plan = WP.compress({"w_up": w}, dataclasses.replace(PC, q_prune=0.5, min_size=64))
        s = plan.sizer()
        assert s.q_prune == pytest.approx(0.5)
        assert s.b_weight == pytest.approx(1.0, abs=0.01)
        assert s.n_params == w.size
        # masked-dense execution of the same plan halves n_opt
        assert plan.sizer(sparse_compute=False).n_opt < s.n_opt

    def test_step_time_memory_term_shrinks(self):
        s_dense = BatchSizer(n_params=10**9)
        s_sparse = BatchSizer(n_params=10**9, q_prune=0.5)
        assert s_sparse.step_time(1) == pytest.approx(s_dense.step_time(1) * 0.5, rel=0.01)
