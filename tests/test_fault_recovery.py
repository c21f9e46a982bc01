"""Crash-recovery and sharding-rule property tests (fault-tolerance
evidence beyond the happy path)."""

import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import Mesh, PartitionSpec as P

from repro import checkpoint as ckpt
from repro.serving.config import EngineConfig
from repro.distributed import shardlib as sl


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(rng.normal(size=(16, 8)), jnp.float32),
            "step": jnp.asarray(seed, jnp.int32)}


class TestCrashRecovery:
    def test_torn_write_never_corrupts_latest(self, tmp_path):
        """Simulate a crash mid-save: a .tmp directory (no manifest rename)
        must be invisible to latest_step/restore."""
        base = str(tmp_path)
        ckpt.save(base, 5, _tree(5))
        # crash: partial tmp dir with some leaves but no manifest
        torn = os.path.join(base, "step_000000009.tmp")
        os.makedirs(torn)
        np.save(os.path.join(torn, "leaf_00000.npy"), np.zeros(3))
        assert ckpt.latest_step(base) == 5
        like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), _tree())
        tree, meta = ckpt.restore(base, like)
        assert int(tree["step"]) == 5

    def test_corrupt_manifest_directory_skipped(self, tmp_path):
        base = str(tmp_path)
        ckpt.save(base, 3, _tree(3))
        # a completed-looking dir whose manifest is garbage must fail loudly
        # on explicit restore but not break latest-step discovery of others
        bad = os.path.join(base, "step_000000007")
        shutil.copytree(os.path.join(base, "step_000000003"), bad)
        with open(os.path.join(bad, "MANIFEST.json"), "w") as f:
            f.write("{not json")
        like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), _tree())
        with pytest.raises(json.JSONDecodeError):
            ckpt.restore(base, like, step=7)
        tree, _ = ckpt.restore(base, like, step=3)  # older one still fine
        assert int(tree["step"]) == 3

    def test_save_restore_save_cycle_is_stable(self, tmp_path):
        base = str(tmp_path)
        t = _tree(1)
        for step in (1, 2, 3):
            ckpt.save(base, step, t)
            like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
            t, _ = ckpt.restore(base, like)
        np.testing.assert_array_equal(np.asarray(t["w"]), np.asarray(_tree(1)["w"]))


def _mesh(shape, axes):
    n = int(np.prod(shape))
    devs = np.asarray([jax.devices()[0]] * n).reshape(shape)
    return Mesh(devs, axes)


class TestShardlibProperties:
    @given(
        dim=st.integers(1, 4096),
        mesh_n=st.sampled_from([2, 4, 8, 16]),
    )
    @settings(max_examples=50, deadline=None)
    def test_sharded_dim_always_divisible(self, dim, mesh_n):
        """Invariant: _resolve never produces a spec whose mesh-axis product
        does not divide the dimension (the lowering-safety property every
        dry-run cell relies on)."""
        mesh = _mesh((mesh_n,), ("model",))
        spec = sl._resolve(mesh, sl.DEFAULT_RULES, ("ff",), (dim,))
        if spec[0] is not None:
            assert dim % mesh_n == 0

    @given(
        dims=st.tuples(st.integers(1, 512), st.integers(1, 512)),
        names=st.tuples(st.sampled_from(["batch", "ff", "heads", None]),
                        st.sampled_from(["batch", "ff", "heads", None])),
    )
    @settings(max_examples=60, deadline=None)
    def test_each_mesh_axis_used_at_most_once(self, dims, names):
        mesh = _mesh((2, 2), ("data", "model"))
        spec = sl._resolve(mesh, sl.DEFAULT_RULES, names, dims)
        used = []
        for s in spec:
            if s is None:
                continue
            used.extend([s] if isinstance(s, str) else list(s))
        assert len(used) == len(set(used))

    @given(dim0=st.integers(1, 256))
    @settings(max_examples=30, deadline=None)
    def test_unconstrained_only_for_dropped_rules(self, dim0):
        mesh = _mesh((4,), ("model",))
        spec = sl._resolve(mesh, sl.DEFAULT_RULES, ("heads",), (dim0,),
                           unconstrained_ok=True)
        if dim0 % 4 == 0:
            assert spec[0] == "model"
        else:
            assert spec[0] is P.UNCONSTRAINED


@pytest.mark.slow
class TestEngineWatchdog:
    """The launcher's fault monitors wired to the serving engine: the
    engine beats a single-host ``HeartbeatMonitor`` once per *executed*
    tick, so dropped/stalled ticks surface exactly like a silent training
    host, and ``StragglerDetector`` consumes engine tick durations the
    same way it consumes training step times."""

    def _engine(self, clk, faults, **kw):
        import repro.configs as C
        from repro.models.api import get_api
        from repro.serving.engine import ServingEngine
        from repro.serving.faultinject import FaultInjector

        cfg = C.get_config("tinyllama-1.1b", smoke=True)
        params = get_api(cfg).init_params(cfg, jax.random.key(0))
        return cfg, ServingEngine(cfg, params, config=EngineConfig.of(
                max_len=64, max_batch=1, clock=clk,
                fault_injector=FaultInjector(faults, clock=clk), **kw))

    def test_engine_watchdog_is_the_heartbeat_monitor(self):
        from repro.distributed.fault import HeartbeatMonitor
        from repro.serving.engine import Request
        from repro.serving.faultinject import Fault, TickClock

        clk = TickClock()
        cfg, eng = self._engine(
            clk, [Fault("drop_tick", tick=3, n_ticks=4)],
            watchdog_timeout_s=2.5)
        assert isinstance(eng.watchdog, HeartbeatMonitor)
        rng = np.random.default_rng(0)
        eng.submit(Request(uid=0,
                           prompt=rng.integers(0, cfg.vocab, 8).astype(np.int32),
                           max_new_tokens=20))
        health = []
        for _ in range(10):
            eng.step()
            clk.advance(1.0)
            health.append(eng.watchdog.healthy())
        # alive while ticking, dead during the dropped-tick gap (no beats),
        # alive again once the engine resumes — the training-host stall
        # signal, produced by the serving tick loop
        assert health[0] and not all(health) and health[-1]
        assert eng.watchdog.dead_hosts() == []
        assert eng.watchdog.silence_s(0) <= 1.0

    def test_straggler_detector_flags_stalled_engine(self):
        from repro.distributed.fault import StragglerDetector
        from repro.serving.engine import Request
        from repro.serving.faultinject import Fault, TickClock

        clk = TickClock()
        cfg, eng = self._engine(
            clk, [Fault("slow_tick", tick=t, delay_s=2.0)
                  for t in range(4, 8)])
        rng = np.random.default_rng(0)
        eng.submit(Request(uid=0,
                           prompt=rng.integers(0, cfg.vocab, 8).astype(np.int32),
                           max_new_tokens=12))
        det = StragglerDetector(n_hosts=3, window=8, ratio=1.5)
        for _ in range(10):
            t0 = clk()
            eng.step()
            # tick duration on the shared clock: slow_tick stalls land here
            det.record(0, (clk() - t0) + 0.1)  # engine "host"
            det.record(1, 0.1)  # nominal peers: the median the
            det.record(2, 0.1)  # stalled engine is compared against
            clk.advance(0.1)
        assert det.stragglers() == [0]
