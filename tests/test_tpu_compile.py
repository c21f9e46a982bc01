"""The main path's Pallas kernels compile for a TPU v5e, at tinyllama-1.1b
widths, with no chip attached.

Interpret mode (what every other test runs) accepts block shapes and
layouts that the TPU compiler refuses; these tests hand the kernels to the
real compiler for a described ``v5e:2x2`` topology and check that each
lowers to a ``tpu_custom_call``, on one chip and per shard on a 1x4 mesh.
The topology is described inside a module fixture, so only the worker
that runs this file loads the TPU library; the tests skip where it cannot
be described.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import repro.configs as C
from repro.core import weight_plan as WP
from repro.core.pruning import BlockPruneConfig
from repro.core.sparse_format import BlockSparse, build_walk
from repro.distributed import shardlib as sl
from repro.kernels import ops
from repro.launch import mesh as M
from repro.models import layers

# tinyllama-1.1b: d_model 2048, d_ff 5632, 32 query / 4 kv heads of 64
D, FF, H, KVH, HD = 2048, 5632, 32, 4, 64
BATCH, PAGE, PAGES_PER_SEQ = 8, 16, 10
BLOCK = 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # executables for a described chip are written to the persistent cache
    # but cannot be read back without one: keep them out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _sparse_shapes(sds, K, N):
    """Shapes of a half-pruned (K, N) int8 BlockSparse, its per-channel
    scales and its multi-column walk."""
    ncb, nrb = N // BLOCK, K // BLOCK
    mb = nrb // 2
    rows = np.tile(np.arange(0, nrb, 2, dtype=np.int32), (ncb, 1))
    walk = build_walk(rows, np.full((ncb,), mb, np.int32), mb)
    return (
        sds((ncb * mb, BLOCK, BLOCK), jnp.int8),
        sds((ncb, mb), jnp.int32),
        sds((ncb,), jnp.int32),
        sds((N,), jnp.float32),
        {k: sds(v.shape, jnp.int32) for k, v in walk.items()},
    )


def _sparse(K, N, blocks, rows, counts):
    return BlockSparse(blocks=blocks, block_rows=rows, counts=counts,
                       shape=(K, N), cfg=BlockPruneConfig(bk=BLOCK, bn=BLOCK))


@pytest.fixture(scope="module")
def sds(one_chip):
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("T", [1, 5])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_decode_attention(sds, kv, T):
    num_pages = 1 + BATCH * PAGES_PER_SEQ
    kdt = jnp.int8 if kv == "int8" else jnp.bfloat16
    pool = sds((num_pages, KVH, PAGE, HD), kdt)
    shapes = [sds((BATCH, T, H, HD), jnp.bfloat16), pool, pool,
              sds((BATCH, PAGES_PER_SEQ), jnp.int32), sds((BATCH,), jnp.int32)]
    if kv == "int8":
        shapes += [sds((num_pages, KVH, PAGE), jnp.float32)] * 2

    def attend(q, k, v, table, pos, *scales):
        kw = dict(zip(("k_scale_pages", "v_scale_pages"), scales))
        return ops.paged_decode_attention(
            q, k, v, table, pos, interpret=False, **kw)

    assert "tpu_custom_call" in _compiled_text(attend, *shapes)


@pytest.mark.parametrize("K,N", [(D, FF), (FF, D)], ids=["up", "down"])
def test_block_sparse_walk_int8(sds, K, N):
    blocks, rows, counts, scales, walk = _sparse_shapes(sds, K, N)

    def matmul(x, blocks, rows, counts, scales, walk):
        return ops.block_sparse_matmul(
            x, _sparse(K, N, blocks, rows, counts), scales=scales,
            walk=walk, interpret=False)

    text = _compiled_text(matmul, sds((BATCH, K), jnp.bfloat16),
                          blocks, rows, counts, scales, walk)
    assert "tpu_custom_call" in text


def test_fused_gate_up_int8(sds):
    g = _sparse_shapes(sds, D, FF)[:4]
    u = _sparse_shapes(sds, D, FF)[:4]

    def gate_up(x, gb, gr, gc, gs, ub, ur, uc, us):
        return ops.fused_gate_up(
            x, _sparse(D, FF, gb, gr, gc), _sparse(D, FF, ub, ur, uc),
            gate_scales=gs, up_scales=us, activation="silu", interpret=False)

    text = _compiled_text(gate_up, sds((BATCH, D), jnp.bfloat16), *g, *u)
    assert "tpu_custom_call" in text


def test_meshed_kernels_run_per_shard(topo, monkeypatch):
    """Under a 1x4 mesh each kernel call runs per shard (``shard_map``):
    the partitioner cannot split a Mosaic custom call, so without it the
    meshed step does not compile."""
    # the kernels' TPU branch: off-TPU ops.py would pick interpret mode
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    rules = M.rules_for(C.get_config("tinyllama-1.1b"), None, mesh=mesh)
    rep = NamedSharding(mesh, P())

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    def packed(K, N, out_axis, blocks, rows, counts, scales, walk):
        return WP.PackedLinear(
            blocks=blocks, block_rows=rows, counts=counts, scales=scales,
            walk=walk, kind="quant_sparse", shape=(K, N), bk=BLOCK, bn=BLOCK,
            use_kernel=True, out_axis=out_axis)

    num_pages = 1 + BATCH * PAGES_PER_SEQ
    pool = sds((num_pages, KVH, PAGE, HD), jnp.int8)
    scale = sds((num_pages, KVH, PAGE), jnp.float32)
    attn_args = (sds((BATCH, 1, H, HD), jnp.bfloat16), pool, pool, scale,
                 scale, sds((BATCH, PAGES_PER_SEQ), jnp.int32),
                 sds((BATCH,), jnp.int32))
    ffn_args = (sds((BATCH, D), jnp.bfloat16),
                _sparse_shapes(sds, D, FF), _sparse_shapes(sds, D, FF),
                _sparse_shapes(sds, FF, D))

    def step(attn_args, ffn_args):
        q, kp, vp, ks, vs, table, pos = attn_args
        x, g, u, d = ffn_args
        with sl.use_mesh(mesh, rules):
            o = layers.paged_decode_attention(
                q, kp, vp, table, pos, k_scale_pages=ks, v_scale_pages=vs,
                use_kernel=True)
            h = WP.apply_gate_up(x, packed(D, FF, "ff", *g),
                                 packed(D, FF, "ff", *u), "silu")
            return o, WP.apply_linear(h, packed(FF, D, "d", *d))

    text = jax.jit(step).lower(attn_args, ffn_args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3


def test_ssm_decode_updates_the_stacked_state_in_place(sds):
    """granite-4.0-h-micro's decode state update at its published shape:
    the float32 state of 36 Mamba layers stacked (128 rows, state 128, 64
    heads of 64; 9.66 GB), one layer updated per call.  The stack is
    aliased to the kernel's state output and the compiled call holds no
    copy of it."""
    L, B, N, HP = 36, 128, 128, 64 * 64
    f32 = jnp.float32

    def update(state, layer, x, dt, a, d, b, c):
        return ops.ssm_decode(state, layer, x, dt, a, d, b, c, interpret=False)

    compiled = jax.jit(update, donate_argnums=(0,)).lower(
        sds((L, B, N, HP), f32), sds((), jnp.int32), sds((B, HP), f32),
        sds((B, HP), f32), sds((HP,), f32), sds((HP,), f32), sds((B, N), f32),
        sds((B, N), f32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "output_to_operand_aliasing={{1}: (1, {})}" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == L * B * N * HP * 4
    assert mem.temp_size_in_bytes < 64 << 20


def test_hybrid_decode_step_memory_by_layer_layout(sds, monkeypatch):
    """granite-4.0-h-micro's decode step at the cell's cache sizes (128
    rows, 3072 positions in 24577 int8 pages of 16, 36 Mamba layers of
    state stacked: 11.6 GB) with small weight widths, compiled in the
    served layout (``find_unit``: a one-layer Mamba unit scanned 5 times,
    then the other 35 layers as 8 scanned runs) and with the published
    10-layer period as the unit scanned 4 times.  Served, the stacked state
    is only carried and aliased (no op makes a copy or a slice of its
    shape).  The period's scan takes the attention layers' pools through
    its per-layer inputs and outputs, which allocates them anew (4 stacked
    layers, 1.8 GB): its temporaries exceed the served layout's by at
    least that much, for 10 distinct layer bodies against the served 9."""
    import re
    import dataclasses

    from repro.models import transformer as T
    from repro.models.api import get_api

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    period = ("mamba",) * 5 + ("global",) + ("mamba",) * 4
    cfg = dataclasses.replace(
        C.get_config("granite-4.0-h-micro", smoke=True), n_layers=40,
        pattern=period * 4, d_model=256, n_heads=32, n_kv_heads=8,
        head_dim=64, d_ff=512, vocab=512, mamba_n_heads=64, mamba_d_head=64,
        mamba_d_state=128, compute_dtype="bfloat16")
    api = get_api(cfg)
    B, S, PS = 128, 3072, 16
    pages = 1 + B * S // PS
    state = f"f32[36,{B},128,4096]"
    pool_bytes = 4 * 2 * pages * 8 * PS * (64 + 4)  # k, v int8 and their scales

    def compile_step():
        params = jax.eval_shape(
            lambda: api.init_params(cfg, jax.random.key(0)))
        cache = jax.eval_shape(lambda: api.init_cache(
            cfg, B, S, jnp.bfloat16, kv_dtype=jnp.int8, page_size=PS,
            num_pages=pages))
        put = lambda t: jax.tree.map(lambda a: sds(a.shape, a.dtype), t)

        def step(params, cache, tokens, pos):
            return api.decode_step(cfg, params, cache, tokens, pos)

        return jax.jit(step, donate_argnums=(1,)).lower(
            put(params), put(cache), sds((B, 1), jnp.int32),
            sds((B,), jnp.int32)).compile()

    prev = layers.force_attention_kernel(True)
    try:
        served = compile_step()
        monkeypatch.setattr(T, "find_unit", lambda kinds: (period, 4, ()))
        by_period = compile_step()
    finally:
        layers.force_attention_kernel(prev)
    text = served.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 9
    made = {m.group(1) for m in re.finditer(
        r"= " + re.escape(state) + r"\S* ([\w-]+)\(", text)}
    assert made <= {"parameter", "get-tuple-element"}, made
    mem, mem_p = served.memory_analysis(), by_period.memory_analysis()
    print(f"served: arguments {mem.argument_size_in_bytes}, temporaries "
          f"{mem.temp_size_in_bytes}; 10-layer period: arguments "
          f"{mem_p.argument_size_in_bytes}, temporaries "
          f"{mem_p.temp_size_in_bytes}")
    assert mem.alias_size_in_bytes >= 36 * B * 128 * 4096 * 4 + pool_bytes
    assert mem_p.temp_size_in_bytes - mem.temp_size_in_bytes >= pool_bytes
