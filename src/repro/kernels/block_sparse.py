"""Block-sparse matmul — the pruning datapath (paper Section 5.6), TPU-adapted.

The FPGA design streams (w, z_w) tuples and uses an offset-calculation IP to
find each weight's input activation.  The TPU equivalent (DESIGN.md §2) works
at MXU-tile granularity: surviving (bk, bn) weight blocks are stored
contiguously per block-column with an int32 row index each (the z_w
analogue).  The kernel walks the block list with *scalar prefetch* — the
block-row indices arrive in SMEM ahead of the grid so the BlockSpec
index_map can compute each step's HBM source address, which is precisely the
paper's offset-calculation IP one level up the memory hierarchy:

    FPGA:  address_l = l + sum_{k<l} z_k      (element into BRAM)
    here:  x tile    = block_rows[j, s]       (tile into VMEM)

Pruned blocks are never read from HBM and never enter the MXU, so both t_mem
and t_calc scale with (1 - q_prune) — the paper's throughput claim.  Because
every block-column stores `max_blocks` entries (zero-padded), the grid is
static; padding costs only the column's slack vs its true count, and the
`counts` array lets the kernel skip the tail MACs with @pl.when.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sparse_format import (
    WALK_COMPUTE,
    WALK_FIRST,
    WALK_LAST,
    BlockSparse,
)


def _bsmm_kernel(
    # scalar prefetch operands (SMEM)
    block_rows_ref,  # (n_cols * max_blocks,) flattened row index per block
    counts_ref,  # (n_cols,)
    # array operands: x_ref, w_ref, [scale_ref], o_ref, acc_ref
    x_ref,  # (block_b, bk) activation tile, selected by block_rows
    w_ref,  # (1, bk, bn) weight block payload (fp or int8)
    *refs,
    max_blocks: int,
    has_scales: bool,
):
    if has_scales:
        scale_ref, o_ref, acc_ref = refs
    else:
        scale_ref, (o_ref, acc_ref) = None, refs
    s = pl.program_id(2)  # position in the block-column's list

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    j = pl.program_id(1)  # block column
    # Skip padded tail blocks: they hold zeros, but skipping also models the
    # FPGA's "computations ... entirely skipped for neurons with only pruned
    # weights" (Fig. 3) — on real TPU this also skips the HBM read via the
    # index map pinning padded steps to the last valid block.
    @pl.when(s < counts_ref[j])
    def _mac():
        acc_ref[...] += jnp.dot(
            x_ref[...].astype(jnp.float32),
            w_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

    @pl.when(s == max_blocks - 1)
    def _out():
        acc = acc_ref[...]
        if has_scales:
            # int8 payload epilogue (quant+sparse): per-output-channel
            # dequant, deferred out of the MAC loop exactly as in
            # kernels/quant_matmul — scales factor out of the k-sum.
            acc = acc * scale_ref[...].astype(jnp.float32)
        o_ref[...] = acc.astype(o_ref.dtype)


def block_sparse_matmul(
    x: jax.Array,
    sparse: BlockSparse,
    *,
    scales: jax.Array | None = None,
    block_b: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """y = x @ W  with W block-sparse.  x: (B, K) -> y: (B, N).

    B must be a multiple of block_b; K, N are multiples of (bk, bn) by
    construction of BlockSparse.  ``scales`` (N,) enables the quant+sparse
    composition: int8 block payloads dequantized per output channel in the
    kernel epilogue — the weight stream is then (1 - q_prune) * 1 byte/weight.
    """
    B, K = x.shape
    Kw, N = sparse.shape
    assert K == Kw, (K, Kw)
    assert B % block_b == 0, (B, block_b)
    cfg = sparse.cfg
    n_cols = N // cfg.bn
    mb = sparse.max_blocks

    grid = (B // block_b, n_cols, mb)
    flat_rows = sparse.block_rows.reshape(-1)  # (n_cols * mb,)

    def x_index(bt, j, s, rows, counts):
        # Activation tile for block s of column j: row-block rows[j*mb+s].
        # Clamp padded steps to the last valid index (no out-of-bounds read;
        # the MAC is skipped by @pl.when anyway).
        return (bt, rows[j * mb + s])

    def w_index(bt, j, s, rows, counts):
        return (j * mb + s, 0, 0)

    def o_index(bt, j, s, rows, counts):
        return (bt, j)

    in_specs = [
        pl.BlockSpec((block_b, cfg.bk), x_index),
        pl.BlockSpec((1, cfg.bk, cfg.bn), w_index),
    ]
    operands = [x, sparse.blocks]
    if scales is not None:
        assert scales.shape == (N,), (scales.shape, N)
        in_specs.append(pl.BlockSpec((1, cfg.bn), lambda bt, j, s, rows, counts: (0, j)))
        operands.append(scales.reshape(1, N))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, cfg.bn), o_index),
        scratch_shapes=[pltpu.VMEM((block_b, cfg.bn), jnp.float32)],
    )

    kernel = functools.partial(
        _bsmm_kernel, max_blocks=mb, has_scales=scales is not None
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, N), x.dtype),
        interpret=interpret,
    )(flat_rows, sparse.counts, *operands)


# ---------------------------------------------------------------------------
# Multi-column kernel (PR 2): one grid step per surviving block, with the
# payload double-buffered by explicit DMA.
# ---------------------------------------------------------------------------


def _bsmm_mc_kernel(
    # scalar prefetch operands (SMEM): the walk
    idx_ref,  # (n_walk,) index into the rectangular payload
    rows_ref,  # (n_walk,) activation row-block per step
    cols_ref,  # (n_walk,) output block-column per step (non-decreasing)
    flags_ref,  # (n_walk,) WALK_FIRST | WALK_LAST | WALK_COMPUTE
    # array operands
    x_ref,  # (block_b, bk) activation tile, selected by rows[s]
    w_hbm,  # (n_cols * mb, bk, bn) full payload, left in HBM
    *refs,  # [scale_ref], o_ref, acc_ref, w_buf, sem
    n_walk: int,
    has_scales: bool,
):
    if has_scales:
        scale_ref, o_ref, acc_ref, w_buf, sem = refs
    else:
        scale_ref, (o_ref, acc_ref, w_buf, sem) = None, refs
    s = pl.program_id(1)
    flags = flags_ref[s]
    first = flags & WALK_FIRST
    last = flags & WALK_LAST
    compute = flags & WALK_COMPUTE

    # Double-buffered payload stream: while block s multiplies out of slot
    # s % 2, block s+1's DMA fills the other slot — the paper's FIFO
    # prefetch (Guo et al.'s double-buffered streaming) at block-list
    # granularity.  Pruned blocks have no walk entry and padded / empty-
    # column steps carry no COMPUTE bit, so neither ever issues a DMA:
    # only surviving payload crosses the HBM interface.
    def dma(slot, t):
        return pltpu.make_async_copy(w_hbm.at[idx_ref[t]], w_buf.at[slot], sem.at[slot])

    @pl.when((s == 0) & (compute != 0))
    def _warmup():
        dma(0, 0).start()

    nxt = jnp.minimum(s + 1, n_walk - 1)

    @pl.when((s + 1 < n_walk) & ((flags_ref[nxt] & WALK_COMPUTE) != 0))
    def _prefetch():
        dma((s + 1) % 2, s + 1).start()

    @pl.when(first != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(compute != 0)
    def _mac():
        dma(s % 2, s).wait()
        acc_ref[...] += jnp.dot(
            x_ref[...].astype(jnp.float32),
            w_buf[s % 2].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

    @pl.when(last != 0)
    def _out():
        acc = acc_ref[...]
        if has_scales:
            acc = acc * scale_ref[...].astype(jnp.float32)
        o_ref[...] = acc.astype(o_ref.dtype)


def block_sparse_matmul_mc(
    x: jax.Array,
    sparse: BlockSparse,
    walk: dict,
    *,
    scales: jax.Array | None = None,
    block_b: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """y = x @ W, multi-column walk variant of :func:`block_sparse_matmul`.

    Instead of a static ``(column, max_blocks)`` sweep, the grid walks the
    pack-time block list (``sparse_format.build_walk``): adjacent block-
    columns share one grid, so a mostly-pruned column costs exactly its
    survivor count in grid steps rather than ``max_blocks``, and the payload
    is streamed HBM -> VMEM by explicit double-buffered DMA (block s+1 in
    flight while block s multiplies).  Semantics and the int8-scales
    epilogue match the per-column kernel exactly.
    """
    B, K = x.shape
    Kw, N = sparse.shape
    assert K == Kw, (K, Kw)
    assert B % block_b == 0, (B, block_b)
    cfg = sparse.cfg
    n_walk = int(walk["idx"].shape[0])

    grid = (B // block_b, n_walk)

    def x_index(bt, s, idx, rows, cols, flags):
        return (bt, rows[s])

    def o_index(bt, s, idx, rows, cols, flags):
        return (bt, cols[s])

    in_specs = [
        pl.BlockSpec((block_b, cfg.bk), x_index),
        pl.BlockSpec(memory_space=pl.ANY),  # payload stays in HBM; DMA'd
    ]
    operands = [x, sparse.blocks]
    if scales is not None:
        assert scales.shape == (N,), (scales.shape, N)
        in_specs.append(
            pl.BlockSpec((1, cfg.bn), lambda bt, s, idx, rows, cols, flags: (0, cols[s]))
        )
        operands.append(scales.reshape(1, N))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, cfg.bn), o_index),
        scratch_shapes=[
            pltpu.VMEM((block_b, cfg.bn), jnp.float32),  # accumulator
            pltpu.VMEM((2, cfg.bk, cfg.bn), sparse.blocks.dtype),  # DMA slots
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )

    kernel = functools.partial(
        _bsmm_mc_kernel, n_walk=n_walk, has_scales=scales is not None
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, N), x.dtype),
        interpret=interpret,
    )(
        jnp.asarray(walk["idx"], jnp.int32),
        jnp.asarray(walk["rows"], jnp.int32),
        jnp.asarray(walk["cols"], jnp.int32),
        jnp.asarray(walk["flags"], jnp.int32),
        *operands,
    )
