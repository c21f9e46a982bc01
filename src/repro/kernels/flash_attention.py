"""Flash attention as a Pallas TPU kernel (forward).

The §Roofline analysis shows the pure-JAX chunked attention materializes its
(cq x ck) score tiles in HBM (XLA does not fuse the online-softmax chain
into the matmuls).  This kernel keeps the running (m, l, acc) statistics in
VMEM scratch across the K/V grid walk — score tiles never leave the chip,
which removes the dominant memory-term contribution of the 32k prefill
cells (the paper's "keep the working set on-chip" discipline, one level up).

Grid: (batch*kv_heads, q_blocks, kv_blocks), kv innermost so the VMEM
accumulator carries across the kv sweep for one (bh, q) tile.  Causal +
sliding-window masking via block-index arithmetic; GQA by folding the group
dim into the q-tile rows.

TPU is the target; CPU validation runs interpret=True against
``ref.flash_attention`` (the dense oracle).  The training path keeps the
pure-JAX custom-VJP flash (differentiable); this kernel is the
serving/prefill fast path and is wired behind ``ops.flash_attention``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _flash_kernel(
    q_ref,  # (1, block_q * G, hd)
    k_ref,  # (1, block_k, hd)
    v_ref,  # (1, block_k, hd)
    *refs,  # [ks_ref, vs_ref], o_ref, m_ref, l_ref, acc_ref
    kv_blocks: int,
    block_q: int,
    block_k: int,
    groups: int,
    causal: bool,
    window: int,
    scale: float,
    quantized_kv: bool,
):
    if quantized_kv:
        # int8 KV cache: per-(position, head) scales ride along as
        # (1, block_k) tiles and dequantize the loaded K/V tiles in VMEM —
        # the HBM cache stream stays 1 byte/element.
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = refs
    kb = pl.program_id(2)
    qb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale  # (bq*G, hd)
    k = k_ref[0].astype(jnp.float32)  # (bk, hd)
    if quantized_kv:
        k = k * ks_ref[...].reshape(block_k, 1).astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (bq*G, bk)

    # absolute positions: q rows are (q_pos, group) pairs, row // G = offset
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // groups
    q_pos = qb * block_q + rows
    k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones(s.shape, jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, -1e30)

    m_prev = m_ref[...]  # (bq*G, 1)
    m_new = jnp.maximum(m_prev[:, 0], s.max(axis=1))[:, None]
    alpha = jnp.exp(m_prev - m_new)  # (bq*G, 1)
    p = jnp.exp(s - m_new)  # (bq*G, bk)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)[:, None]
    if quantized_kv:
        v = v_ref[0].astype(jnp.float32) * vs_ref[...].reshape(block_k, 1).astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
    else:
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = m_new

    @pl.when(kb == kv_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Sk, KVH, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 128,
    block_k: int = 128,
    k_scale: jax.Array | None = None,  # (B, Sk, KVH): int8-KV dequant scales
    v_scale: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Pallas flash attention (GQA-aware).  Sq/Sk must be multiples of the
    block sizes (ops.flash_attention pads).

    ``k_scale``/``v_scale`` select the int8-KV path: K/V are int8 payloads
    dequantized per (position, head) *inside the tile load*, so the cache
    crosses HBM at 1 byte/element — the serving-side kv_read halving that
    ``perf_model.decode_step_time`` charges.
    """
    B, Sq, H, hd = q.shape
    Sk, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    quantized_kv = k_scale is not None
    assert (k_scale is None) == (v_scale is None)
    scale = 1.0 / math.sqrt(hd)
    q_blocks, kv_blocks = Sq // block_q, Sk // block_k

    # fold (B, KVH) into one grid axis; q rows interleave (q_pos, group)
    qf = (
        q.reshape(B, Sq, KVH, G, hd)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B * KVH, Sq * G, hd)
    )
    kf = k.transpose(0, 2, 1, 3).reshape(B * KVH, Sk, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KVH, Sk, hd)

    kernel = functools.partial(
        _flash_kernel,
        kv_blocks=kv_blocks, block_q=block_q, block_k=block_k, groups=G,
        causal=causal, window=window or 0, scale=scale,
        quantized_kv=quantized_kv,
    )
    in_specs = [
        pl.BlockSpec((1, block_q * G, hd), lambda bh, qb, kb: (bh, qb, 0)),
        pl.BlockSpec((1, block_k, hd), lambda bh, qb, kb: (bh, kb, 0)),
        pl.BlockSpec((1, block_k, hd), lambda bh, qb, kb: (bh, kb, 0)),
    ]
    operands = [qf, kf, vf]
    if quantized_kv:
        assert k_scale.shape == (B, Sk, KVH), (k_scale.shape, (B, Sk, KVH))
        ksf = k_scale.transpose(0, 2, 1).reshape(B * KVH, Sk)
        vsf = v_scale.transpose(0, 2, 1).reshape(B * KVH, Sk)
        sc_spec = pl.BlockSpec((1, block_k), lambda bh, qb, kb: (bh, kb))
        in_specs += [sc_spec, sc_spec]
        operands += [ksf, vsf]
    of = pl.pallas_call(
        kernel,
        grid=(B * KVH, q_blocks, kv_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q * G, hd), lambda bh, qb, kb: (bh, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KVH, Sq * G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q * G, 1), jnp.float32),
            pltpu.VMEM((block_q * G, 1), jnp.float32),
            pltpu.VMEM((block_q * G, hd), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    return (
        of.reshape(B, KVH, Sq, G, hd).transpose(0, 2, 1, 3, 4).reshape(B, Sq, H, hd)
    )


# ---------------------------------------------------------------------------
# multi-query paged decode attention: each KV page streamed ONCE per step
# ---------------------------------------------------------------------------
#
# The decode-time analogue of the block-sparse walk: the page table is a
# scalar-prefetch operand (SMEM, available before the grid runs), so each
# grid step's BlockSpec index_map computes the *physical* page to DMA from
# the logical (sequence, page) coordinate — the offset-calculation IP of the
# paper's sparse stream, applied to the KV cache.  Only the pages a sequence
# actually owns cross HBM, and every page crosses exactly once per step no
# matter how many query positions T the step carries: all T positions of a
# speculative verify tick score against the page while it sits in VMEM
# (batch processing along the token axis, applied to the cache stream the
# way the weight kernels already apply it to the weight stream).  The
# pure-JAX reference (models/layers.paged_decode_attention) materializes
# the same gather per step instead.


def _paged_decode_kernel(
    pt_ref,  # (B * P,) scalar prefetch: flattened page table
    pos_ref,  # (B,)    scalar prefetch: position of each sequence's q[:, 0]
    q_ref,  # (1, KVH, T * G, hd) — rows interleave (query offset t, group g)
    k_ref,  # (1, KVH, ps, hd) one physical page, every kv head
    v_ref,
    *refs,  # [ks_ref (1, KVH, ps), vs_ref], o_ref, m_ref, l_ref, acc_ref
    pages_per_seq: int,
    page_size: int,
    kv_heads: int,
    groups: int,
    causal: bool,
    window: int,
    scale: float,
    softcap: float,
    quantized_kv: bool,
):
    if quantized_kv:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[b]
    rows = q_ref.shape[2]
    kv_pos = p * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (rows, page_size), 1)
    # per-query masking: row (t, g) is query position pos + t, and entries
    # beyond it — including every slot of logical pages the sequence has
    # not reached (their table entries point at the null page) — never
    # contribute.  Non-causal (cross-attention) steps see everything up to
    # pos from every query row.
    q_pos = pos
    if causal:
        q_pos = pos + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 0) // groups
    mask = kv_pos <= q_pos
    if window > 0:
        mask &= kv_pos > q_pos - window

    # one page holds every kv head: each head's (ps, hd) tile is a whole
    # trailing block, so the loads stay tile-aligned for any page size
    for h in range(kv_heads):
        q = q_ref[0, h].astype(jnp.float32) * scale  # (T*G, hd)
        k = k_ref[0, h].astype(jnp.float32)  # (ps, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (T*G, ps)
        if quantized_kv:
            # int8 pages: the per-(slot, head) scale of K is a column scale
            # of the scores, and V's folds into the probabilities
            s = s * ks_ref[0, h : h + 1, :].astype(jnp.float32)
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(mask, s, -1e30)

        m_prev = m_ref[h]  # (T*G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(s - m_new)  # (T*G, ps)
        l_ref[h] = l_ref[h] * alpha + pexp.sum(axis=1, keepdims=True)
        if quantized_kv:
            pexp = pexp * vs_ref[0, h : h + 1, :].astype(jnp.float32)
        v = v_ref[0, h].astype(jnp.float32)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[h] = m_new

    @pl.when(p == pages_per_seq - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,  # (B, T, H, hd) — T=1 decode, T=k+1 speculative verify
    k_pages: jax.Array,  # (num_pages, KVH, page_size, hd)
    v_pages: jax.Array,
    page_table: jax.Array,  # (B, pages_per_seq) int32
    pos: jax.Array,  # (B,) int32, position of q[:, 0]
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float = 0.0,
    k_scale_pages: jax.Array | None = None,  # (num_pages, KVH, page_size)
    v_scale_pages: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Single-pass multi-query attention against the paged KV cache.

    Grid (B, pages_per_seq), pages innermost: the online-softmax (m, l, acc)
    statistics of every kv head live in VMEM scratch across each sequence's
    page sweep, and the K/V page for step (b, p) is addressed through the
    prefetched page table — pages a sequence doesn't own are never fetched
    into VMEM (the null page rides on masked positions only).  A grid step
    fetches the page for all kv heads at once: pools are head-major
    ``(num_pages, KVH, page_size, hd)``, so each head's ``(page_size, hd)``
    tile spans the array's two trailing dims and satisfies the TPU's block
    tiling rule whatever the page size.  All T query positions fold into
    the q-tile rows as (t, group) pairs, so each page is DMA'd exactly once
    per step and scored against every query while it sits in VMEM — the
    verify step's page-stream cost is independent of T.  Row t's causal
    mask is ``kv_pos <= pos + t`` (entries the verify step already wrote
    at positions > pos + t mask out); ``causal=False`` gives every row the
    full [0, pos] view (enc-dec cross-attention against a static encoder
    pool).  The int8 scale pools select dequant-on-load, mirroring the
    contiguous kernel.
    """
    B, T, H, hd = q.shape
    num_pages, KVH, page_size, _ = k_pages.shape
    P = page_table.shape[1]
    G = H // KVH
    quantized_kv = k_scale_pages is not None
    assert (k_scale_pages is None) == (v_scale_pages is None)
    scale = 1.0 / math.sqrt(hd)

    # (T, G) fold into the q-tile rows: row t * G + g of kv head kvh is
    # query position pos[b] + t
    qf = (
        q.reshape(B, T, KVH, G, hd)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B, KVH, T * G, hd)
    )
    pt_flat = page_table.reshape(-1).astype(jnp.int32)

    kernel = functools.partial(
        _paged_decode_kernel,
        pages_per_seq=P, page_size=page_size, kv_heads=KVH, groups=G,
        causal=causal, window=window or 0, scale=scale, softcap=softcap,
        quantized_kv=quantized_kv,
    )

    def q_index(b, p, pt, pos_s):
        return (b, 0, 0, 0)

    def kv_index(b, p, pt, pos_s):
        return (pt[b * P + p], 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, KVH, T * G, hd), q_index),
        pl.BlockSpec((1, KVH, page_size, hd), kv_index),
        pl.BlockSpec((1, KVH, page_size, hd), kv_index),
    ]
    operands = [qf, k_pages, v_pages]
    if quantized_kv:
        def sc_index(b, p, pt, pos_s):
            return (pt[b * P + p], 0, 0)

        sc_spec = pl.BlockSpec((1, KVH, page_size), sc_index)
        in_specs += [sc_spec, sc_spec]
        operands += [k_scale_pages, v_scale_pages]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KVH, T * G, hd), q_index),
        scratch_shapes=[
            pltpu.VMEM((KVH, T * G, 1), jnp.float32),
            pltpu.VMEM((KVH, T * G, 1), jnp.float32),
            pltpu.VMEM((KVH, T * G, hd), jnp.float32),
        ],
    )
    of = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, T * G, hd), q.dtype),
        interpret=interpret,
    )(pt_flat, pos.astype(jnp.int32), *operands)
    return (
        of.reshape(B, KVH, T, G, hd).transpose(0, 2, 1, 3, 4).reshape(B, T, H, hd)
    )
