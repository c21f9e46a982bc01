"""Sharded compressed serving: axis-rules registry units + multi-device
parity.

The registry / perf-model units run on any host.  The engine and step
parity tests need >= 8 devices: the CI ``mesh-smoke`` step (and local runs)
force them with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; on
a single-device host they skip rather than fake it (the flag must be set
before the first jax import, so it cannot be applied from inside the
suite — see src/repro/launch/dryrun.py).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import repro.configs as C
from repro.core import perf_model as pm
from repro.core.batching import BatchSizer
from repro.core.weight_plan import PlanConfig, compress
from repro.distributed import shardlib as sl
from repro.launch import mesh as M
from repro.models import layers as L  # noqa: F401 — registers cache kinds
from repro.models import transformer as T  # noqa: F401 — registers page_table
from repro.models.api import get_api
from repro.serving.config import EngineConfig
from repro.serving.engine import Request, ServingEngine


def _fake_mesh(shape=(2, 2), axes=("data", "model")):
    n = int(np.prod(shape))
    devs = np.asarray([jax.devices()[0]] * n).reshape(shape)
    return Mesh(devs, axes)


def _tiny_plan(q_prune=0.5):
    w_up = jax.random.normal(jax.random.key(0), (32, 64))
    w_down = jax.random.normal(jax.random.key(1), (64, 32))
    params = {"mlp": {"w_up": w_up, "w_down": w_down}}
    axes = {"mlp": {"w_up": ("d", "ff"), "w_down": ("ff", "d")}}
    cfg = PlanConfig(default="quant_sparse", q_prune=q_prune, bk=8, bn=8,
                     min_size=128, min_contract=8)
    return compress(params, cfg, axes=axes)


class TestRegistry:
    def test_named_cache_kinds_registered(self):
        table = sl.registry_table()
        for kind in ("attn.kv", "attn.kv_scale", "attn.kv_pages",
                     "attn.kv_scale_pages", "page_table"):
            assert kind in table, kind
        assert "packed" in table["node_kinds"]
        assert "quant" in table["node_kinds"]

    def test_cache_axes_route_through_registry(self):
        axes = L.attn_cache_axes(quantized=True)
        assert axes["k"] == sl.axes_for("attn.kv")
        assert axes["k_scale"] == sl.axes_for("attn.kv_scale")
        paged = L.paged_attn_cache_axes(quantized=True)
        assert paged["k_pages"] == sl.axes_for("attn.kv_pages")
        assert paged["v_scale_pages"] == sl.axes_for("attn.kv_scale_pages")
        # pools shard over the model axis on kv_heads; page axes replicated
        assert sl.axes_for("attn.kv_pages")[1] == "kv_heads"
        assert sl.axes_for("attn.kv_pages")[0] is None

    def test_page_table_in_transformer_cache_axes(self):
        cfg = C.get_config("tinyllama-1.1b", smoke=True)
        axes = T.cache_axes(cfg, quantized_kv=True, paged=True)
        assert axes["page_table"] == sl.axes_for("page_table")
        inner = axes["unit"][0]
        # stacked unit caches carry a leading None over the registry axes
        assert inner["k_pages"] == (None,) + sl.axes_for("attn.kv_pages")
        assert inner["k_scale_pages"] == (None,) + sl.axes_for("attn.kv_scale_pages")

    def test_packed_expansion_blocks_on_output_axis_walk_replicated(self):
        plan = _tiny_plan()
        node = plan._by_path["mlp/w_up"]
        expanded = sl.expand_axes(node, ("d", "ff"))
        assert expanded.blocks == ("ff", None, None)
        assert expanded.block_rows == ("ff", None)
        assert expanded.counts == ("ff",)
        assert expanded.scales == ("ff",)
        assert all(v == (None,) for v in expanded.walk.values())

    def test_packed_expansion_without_axes_is_replicated(self):
        node = _tiny_plan()._by_path["mlp/w_up"]
        expanded = sl.expand_axes(node, None)
        assert expanded.blocks == (None, None, None)
        assert expanded.scales == (None,)

    def test_quant_expansion_scales_drop_contraction_axis(self):
        node = {"q": jnp.zeros((8, 4), jnp.int8), "s": jnp.zeros((4,))}
        expanded = sl.expand_axes(node, ("d", "ff"))
        assert expanded == {"q": ("d", "ff"), "s": ("ff",)}
        stacked = sl.expand_axes(node, (None, "d", "ff"))
        assert stacked == {"q": (None, "d", "ff"), "s": (None, "ff")}

    def test_tree_shardings_compressed_plan(self):
        mesh = _fake_mesh()
        plan = _tiny_plan()
        sh = plan.param_shardings(mesh=mesh, rules=sl.DEFAULT_RULES)
        up = sh["mlp"]["w_up"]
        assert up.blocks.spec == P("model", None, None)
        assert up.scales.spec == P("model",)
        assert all(s.spec == P(None,) for s in jax.tree.leaves(up.walk))
        # w_down's output axis is "d" (replicated): everything unsharded
        down = sh["mlp"]["w_down"]
        assert down.blocks.spec == P(None, None, None)

    def test_tree_shardings_quantized_cache(self):
        mesh = _fake_mesh()
        cfg = C.get_config("tinyllama-1.1b", smoke=True)  # KVH=2, divisible
        cache = jax.eval_shape(
            functools.partial(T.init_cache, cfg, 4, 16,
                              jnp.dtype(cfg.compute_dtype), kv_dtype=jnp.int8))
        sh = sl.tree_shardings(cache, T.cache_axes(cfg, quantized_kv=True),
                               mesh=mesh, rules=sl.DEFAULT_RULES)
        one = sh["unit"][0]
        assert one["k"].spec == P(None, "data", None, "model", None)
        # the previously-dead scale leaves get their registered sharding
        assert one["k_scale"].spec == P(None, "data", None, "model")

    def test_whisper_heads_divisibility_fallback(self):
        # whisper-tiny: 6 kv heads.  A 16-way model axis cannot split them:
        # the mapping is dropped (replicated), not an error.
        wide = _fake_mesh((16,), ("model",))
        assert sl._resolve(wide, sl.DEFAULT_RULES, ("kv_heads",), (6,)) == P(None)
        assert sl.shard_degree(wide, sl.DEFAULT_RULES, ("kv_heads",), (6,)) == 1
        narrow = _fake_mesh((2,), ("model",))
        assert sl._resolve(narrow, sl.DEFAULT_RULES, ("kv_heads",), (6,)) == P("model")
        assert sl.shard_degree(narrow, sl.DEFAULT_RULES, ("kv_heads",), (6,)) == 2

    def test_parallelism_degrees(self):
        # the ONE (data, model, kv) derivation the engine and serve.py share
        mesh = _fake_mesh((4, 2))
        assert sl.parallelism_degrees(mesh, sl.DEFAULT_RULES, 2) == (4, 2, 2)
        wide = _fake_mesh((1, 8))
        assert sl.parallelism_degrees(wide, sl.DEFAULT_RULES, 2) == (1, 8, 1)
        assert sl.parallelism_degrees(None, sl.DEFAULT_RULES, 2) == (1, 1, 1)
        # no kv heads (attention-free stacks): kv degree is 1, not an error
        assert sl.parallelism_degrees(mesh, sl.DEFAULT_RULES, 0)[2] == 1

    def test_shard_degree_single_dim(self):
        mesh = _fake_mesh((2, 4))
        deg = sl.shard_degree(mesh, sl.DEFAULT_RULES,
                              sl.axes_for("attn.kv"), (8, 16, 4, 8), dim=2)
        assert deg == 4  # kv_heads dim on the 4-way model axis

    def test_plan_axes_survive_save_load(self, tmp_path):
        plan = _tiny_plan()
        from repro.core.weight_plan import load_plan, save_plan

        save_plan(str(tmp_path / "plan"), plan)
        dense = {"mlp": {"w_up": jnp.zeros((32, 64)), "w_down": jnp.zeros((64, 32))}}
        restored = load_plan(str(tmp_path / "plan"), dense)
        assert restored.leaves["mlp/w_up"].axes == ("d", "ff")
        mesh = _fake_mesh()
        sh = restored.param_shardings(mesh=mesh, rules=sl.DEFAULT_RULES)
        assert sh["mlp"]["w_up"].blocks.spec == P("model", None, None)


class TestMultiChipNopt:
    KV = dict(n_params=10**9, kv_bytes_per_token=11968.0, context_len=128,
              b_weight=1.0)

    def test_perfect_sharding_preserves_balance_point(self):
        base = pm.decode_n_opt(**self.KV)
        sharded = pm.decode_n_opt(**self.KV, model_parallel=8, kv_parallel=8)
        assert np.isclose(base, sharded)

    def test_replicated_kv_raises_nopt(self):
        base = pm.decode_n_opt(**self.KV)
        repl = pm.decode_n_opt(**self.KV, model_parallel=4, kv_parallel=1)
        assert repl > base  # replicated cache is relatively heavier per chip

    def test_replicated_kv_can_hit_memory_bound(self):
        assert pm.decode_n_opt(**self.KV, model_parallel=8, kv_parallel=1) == float("inf")

    def test_weight_only_nopt_invariant_under_model_parallel(self):
        assert np.isclose(pm.decode_n_opt(b_weight=1.0),
                          pm.decode_n_opt(b_weight=1.0, model_parallel=8))

    @pytest.mark.parametrize("m,kv_m", [(1, 1), (8, 8), (4, 1), (16, 2)])
    def test_balance_is_one_at_nopt(self, m, kv_m):
        n = pm.decode_n_opt(**self.KV, model_parallel=m, kv_parallel=kv_m)
        if not np.isfinite(n):
            pytest.skip("memory-bound at any batch for this (m, kv_m)")
        t = pm.decode_step_time(
            self.KV["n_params"], n, self.KV["kv_bytes_per_token"],
            self.KV["context_len"], b_weight=1.0,
            model_parallel=m, kv_parallel=kv_m)
        assert t["t_calc"] / t["t_mem"] == pytest.approx(1.0, rel=1e-9)

    def test_sizer_threads_degrees(self):
        a = BatchSizer(**{**self.KV, "model_parallel": 8, "kv_parallel": 8})
        b = BatchSizer(**self.KV)
        assert a.n_opt == b.n_opt
        c = BatchSizer(**{**self.KV, "model_parallel": 4, "kv_parallel": 1})
        assert c.n_opt > b.n_opt


# ---------------------------------------------------------------------------
# multi-device parity (mesh-smoke lane: XLA_FLAGS forces 8 host devices)
# ---------------------------------------------------------------------------

needs_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8",
)


def _smoke_setup():
    cfg = C.get_config("tinyllama-1.1b", smoke=True)
    api = get_api(cfg)
    params = api.init_params(cfg, jax.random.key(0))
    plan = api.compress(cfg, params, PlanConfig(
        default="quant_sparse", q_prune=0.5, bk=16, bn=16, min_size=1024))
    return cfg, api, plan


def _requests(cfg, n=5):
    rng = np.random.default_rng(0)
    return [
        Request(uid=i, prompt=rng.integers(0, cfg.vocab, size=8).astype(np.int32),
                max_new_tokens=6)
        for i in range(n)
    ]


def _serve(cfg, plan, mesh, rules):
    eng = ServingEngine(cfg, None, plan=plan, config=EngineConfig.of(
            max_len=64, max_batch=4, kv_dtype="int8", page_size=8,
            share_prefix=True, mesh=mesh, rules=rules))
    reqs = _requests(cfg)
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    assert all(r.done for r in reqs)
    return [tuple(r.output) for r in reqs], eng


@needs_devices
class TestMeshedServingParity:
    """Compressed + paged + int8-KV serving through a host mesh must produce
    the 1-device engine's token stream exactly (greedy decode; logits agree
    to f32 reduction-order noise, tokens bit-for-bit)."""

    @pytest.fixture(scope="class")
    def setup(self):
        cfg, api, plan = _smoke_setup()
        base, _ = _serve(cfg, plan, None, None)
        return cfg, api, plan, base

    def test_parity_1x8_kv_fallback(self, setup):
        # KVH=2 cannot split an 8-way model axis: pools replicate
        # (divisibility fallback) but the engine still serves correctly.
        cfg, api, plan, base = setup
        mesh = M.make_serving_mesh("1x8")
        out, eng = _serve(cfg, plan, mesh, M.rules_for(cfg, None, mesh=mesh))
        assert eng.model_parallel == 8 and eng.kv_parallel == 1
        assert out == base

    def test_parity_4x2_kv_sharded(self, setup):
        # KVH=2 on a 2-way model axis: pools genuinely shard on kv_heads.
        cfg, api, plan, base = setup
        mesh = M.make_serving_mesh("4x2")
        out, eng = _serve(cfg, plan, mesh, M.rules_for(cfg, None, mesh=mesh))
        assert eng.model_parallel == 2 and eng.kv_parallel == 2
        assert out == base

    def test_default_max_batch_scales_with_data_degree(self, setup):
        """The sizer's n_opt balances ONE model group; with data-parallel
        replicas the engine's global batch must be data_parallel * n_opt or
        every replica decodes below the balance point."""
        cfg, api, plan, _ = setup
        sizer = BatchSizer(n_params=10**6, hbm_bw=pm.TPU_V5E_HBM_BW * 20)
        n_opt = sizer.n_opt
        assert 1 < n_opt < 16  # a real (clampable) balance point
        solo = ServingEngine(cfg, None, plan=plan, sizer=sizer, config=EngineConfig.of(
                max_len=64))
        assert solo.max_batch == n_opt
        mesh = M.make_serving_mesh("4x2")
        meshed = ServingEngine(cfg, None, plan=plan, sizer=sizer, config=EngineConfig.of(
                max_len=64, mesh=mesh,
                rules=M.rules_for(cfg, None, mesh=mesh)))
        assert meshed.data_parallel == 4
        assert meshed.max_batch == min(64, 4 * n_opt)

    def test_step_logits_close(self, setup):
        """Single compiled decode step, meshed vs not: logits agree to f32
        reduction-order tolerance (contraction splits change summation
        order; exactness is at the sampled-token level)."""
        cfg, api, plan, _ = setup
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(0, cfg.vocab, (4, 8)), jnp.int32)
        pos = jnp.full((4,), 8, jnp.int32)
        dt = jnp.dtype(cfg.compute_dtype)

        cache = api.init_cache(cfg, 4, 32, dt, kv_dtype=jnp.int8)
        _, c0 = jax.jit(functools.partial(api.prefill, cfg))(
            plan.params, {"tokens": toks}, cache)
        d0, _ = jax.jit(functools.partial(api.decode_step, cfg))(
            plan.params, c0, toks[:, -1:], pos)

        mesh = M.make_serving_mesh("4x2")
        rules = M.rules_for(cfg, None, mesh=mesh)
        p = jax.device_put(plan.params, plan.param_shardings(mesh=mesh, rules=rules))
        cache = api.init_cache(cfg, 4, 32, dt, kv_dtype=jnp.int8)
        cache = jax.device_put(cache, sl.tree_shardings(
            cache, api.cache_axes(cfg, quantized_kv=True), mesh=mesh, rules=rules))

        def pf(params, batch, c):
            with sl.use_mesh(mesh, rules):
                return api.prefill(cfg, params, batch, c)

        def dec(params, c, t, pp):
            with sl.use_mesh(mesh, rules):
                return api.decode_step(cfg, params, c, t, pp)

        _, c1 = jax.jit(pf)(p, {"tokens": toks}, cache)
        d1, _ = jax.jit(dec)(p, c1, toks[:, -1:], pos)
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d0),
                                   rtol=1e-4, atol=1e-4)
        assert (np.argmax(np.asarray(d1), -1) == np.argmax(np.asarray(d0), -1)).all()
