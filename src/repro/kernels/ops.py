"""Public jit'd wrappers for the Pallas kernels.

These pad ragged shapes to block multiples, pick interpret mode automatically
off-TPU (so the whole framework runs CPU-correct while targeting TPU), and
expose a uniform fp32/bf16 API used by the models and the serving engine.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.sparse_format import BlockSparse, build_walk
from repro.kernels import batched_ffn as _bffn
from repro.kernels import block_sparse as _bs
from repro.kernels import flash_attention as _fa
from repro.kernels import fused_gate_up as _fgu
from repro.kernels import quant_matmul as _qmm
from repro.kernels import ssm_decode as _ssm


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_dim(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("activation", "block_b", "block_n", "block_k", "interpret"))
def batched_ffn(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array,
    activation: str = "relu",
    block_b: int = 128,
    block_n: int = 128,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """act(x @ w + b), weight-stationary Pallas schedule, padded as needed."""
    if interpret is None:
        interpret = not _on_tpu()
    B, K = x.shape
    N = w.shape[1]
    block_b = min(block_b, max(8, B))
    xp = _pad_dim(_pad_dim(x, 0, block_b), 1, block_k)
    wp = _pad_dim(_pad_dim(w, 0, block_k), 1, block_n)
    bp = _pad_dim(b, 0, block_n)
    y = _bffn.batched_ffn(
        xp, wp, bp,
        activation=activation,
        block_b=block_b, block_n=block_n, block_k=block_k,
        interpret=interpret,
    )
    return y[:B, :N]


def block_sparse_matmul(
    x: jax.Array,
    sparse: BlockSparse,
    scales: jax.Array | None = None,
    block_b: int = 128,
    interpret: bool | None = None,
    walk: dict | None = None,
) -> jax.Array:
    """x @ W_blocksparse. Pads the batch dim only (K/N are block-aligned).

    ``scales`` (N,) selects the quant+sparse epilogue (int8 block payloads
    dequantized per output channel inside the kernel).

    ``walk`` routes through the multi-column kernel (one grid step per
    surviving block, double-buffered payload DMA) instead of the static
    per-column sweep.  When absent it is built on the spot from concrete
    metadata; inside a trace (counts are tracers) the walk cannot be
    derived, so the per-column kernel runs — pass the pack-time walk
    (``PackedLinear.walk``) to fuse under jit.
    """
    if interpret is None:
        interpret = not _on_tpu()
    B = x.shape[0]
    block_b = min(block_b, max(8, B))
    xp = _pad_dim(x, 0, block_b)
    if walk is None and not isinstance(sparse.counts, jax.core.Tracer):
        # the walk is pack-time-static: memoize it on the BlockSparse so
        # repeated eager calls don't redo the host-side block loop (the
        # plan path carries it on PackedLinear.walk instead)
        walk = getattr(sparse, "_walk_cache", None)
        if walk is None:
            import numpy as _np

            walk = build_walk(
                _np.asarray(sparse.block_rows), _np.asarray(sparse.counts),
                sparse.max_blocks,
            )
            sparse._walk_cache = walk
    if walk is not None:
        y = _bs.block_sparse_matmul_mc(
            xp, sparse, walk, scales=scales, block_b=block_b, interpret=interpret
        )
    else:
        y = _bs.block_sparse_matmul(
            xp, sparse, scales=scales, block_b=block_b, interpret=interpret
        )
    return y[:B]


def fused_gate_up(
    x: jax.Array,
    gate: BlockSparse,
    up: BlockSparse,
    gate_scales: jax.Array | None = None,
    up_scales: jax.Array | None = None,
    activation: str = "silu",
    block_b: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """act(x @ Wg) * (x @ Wu) in ONE kernel launch (block-sparse pair).

    Pads the batch dim only; gate/up must share shape and block geometry.
    """
    if interpret is None:
        interpret = not _on_tpu()
    B = x.shape[0]
    block_b = min(block_b, max(8, B))
    xp = _pad_dim(x, 0, block_b)
    y = _fgu.fused_gate_up(
        xp, gate, up,
        gate_scales=gate_scales, up_scales=up_scales,
        activation=activation, block_b=block_b, interpret=interpret,
    )
    return y[:B]


@functools.partial(jax.jit, static_argnames=("activation", "block_b", "block_n", "block_k", "interpret"))
def quant_matmul(
    x: jax.Array,
    w_q: jax.Array,
    scales: jax.Array,
    activation: str = "linear",
    block_b: int = 128,
    block_n: int = 128,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """act((x @ int8_w) * scales), int8 weight stream."""
    if interpret is None:
        interpret = not _on_tpu()
    B, K = x.shape
    N = w_q.shape[1]
    block_b = min(block_b, max(8, B))
    xp = _pad_dim(_pad_dim(x, 0, block_b), 1, block_k)
    wp = _pad_dim(_pad_dim(w_q, 0, block_k), 1, block_n)
    sp = _pad_dim(scales.reshape(-1), 0, block_n)
    y = _qmm.quant_matmul(
        xp, wp, sp,
        activation=activation,
        block_b=block_b, block_n=block_n, block_k=block_k,
        interpret=interpret,
    )
    return y[:B, :N]


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 128,
    block_k: int = 128,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Pallas flash attention; pads ragged sequence lengths.

    Padding keys are masked via the causal/window logic: padded q rows are
    sliced off, padded k columns sit at positions > every real q position,
    so causal masking drops them (non-causal calls get an explicit window
    covering only real keys is NOT applied — use causal=True or pre-mask).

    ``k_scale``/``v_scale`` (B, Sk, KVH) select the int8-KV path: payloads
    are dequantized per (position, head) inside the kernel's tile loads.
    """
    if interpret is None:
        interpret = not _on_tpu()
    B, Sq = q.shape[:2]
    Sk = k.shape[1]
    block_q = min(block_q, max(8, Sq))
    block_k = min(block_k, max(8, Sk))
    qp = _pad_dim(q, 1, block_q)
    kp = _pad_dim(k, 1, block_k)
    vp = _pad_dim(v, 1, block_k)
    if k_scale is not None:
        k_scale = _pad_dim(k_scale, 1, block_k)
        v_scale = _pad_dim(v_scale, 1, block_k)
    o = _fa.flash_attention(
        qp, kp, vp, causal=causal, window=window,
        block_q=block_q, block_k=block_k,
        k_scale=k_scale, v_scale=v_scale, interpret=interpret,
    )
    return o[:, :Sq]


@functools.partial(jax.jit, static_argnames=("window", "softcap", "interpret"))
def paged_decode_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    pos: jax.Array,
    window: int | None = None,
    softcap: float = 0.0,
    k_scale_pages: jax.Array | None = None,
    v_scale_pages: jax.Array | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """One decode step against the paged KV cache, K/V fetched page-by-page
    through the page table (scalar-prefetch indirection — no materialized
    gather).  ``k_scale_pages``/``v_scale_pages`` select the int8 pools with
    dequant-on-load.  No padding needed: page geometry is static.

    ``q`` may carry T > 1 new tokens per sequence (the speculative verify
    step).  All T positions fold into the kernel's q-tile rows, so the step
    lowers to ONE ``pallas_call`` that streams each KV page exactly once and
    scores every query position against it on-chip — the page stream is
    amortized across the verify batch the same way the matmul kernels
    amortize the weight stream across B*T rows.  Per-query causality is the
    kernel's mask: row t sees entries ≤ pos + t, so entries the verify step
    already wrote at positions > pos + t mask out, bit-identical to running
    the single-query kernel once per position.
    """
    if interpret is None:
        interpret = not _on_tpu()
    # Guardrail: the table indexes physical pages via scalar prefetch, and
    # an out-of-range id (corrupted host table, torn update) would read —
    # and worse, let the paired scatter WRITE — arbitrary pool memory.
    # Clamping is free next to the page stream and turns that failure into
    # a wrong-but-bounded attention output the engine's numeric guard and
    # page audit can catch.
    page_table = jnp.clip(page_table, 0, k_pages.shape[0] - 1)
    return _fa.paged_decode_attention(
        q, k_pages, v_pages, page_table, pos,
        causal=True, window=window, softcap=softcap,
        k_scale_pages=k_scale_pages, v_scale_pages=v_scale_pages,
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def cross_decode_attention(
    q: jax.Array,  # (B, T, H, hd)
    k: jax.Array,  # (B, Sf, KVH, hd) static encoder K
    v: jax.Array,
    softcap: float = 0.0,
    interpret: bool | None = None,
) -> jax.Array:
    """Decode-time enc-dec cross-attention through the single-pass kernel.

    The encoder K/V are a static pool: every decode step re-reads the same
    (B, Sf) entries.  Reshaping them into page-sized tiles with an identity
    page table reuses the multi-query paged kernel, so all T query positions
    of a step score against each encoder tile while it sits in VMEM — one
    stream of the encoder cache per step, independent of T.  ``causal=False``
    with pos = Sf - 1 gives every query row the full encoder view; padded
    frame slots sit at positions ≥ Sf and mask out.
    """
    if interpret is None:
        interpret = not _on_tpu()
    B, T, H, hd = q.shape
    Sf = k.shape[1]
    KVH = k.shape[2]
    page_size = min(128, max(8, Sf))
    kp = _pad_dim(k, 1, page_size)
    vp = _pad_dim(v, 1, page_size)
    P = kp.shape[1] // page_size

    def pool(x):  # (B, P * ps, KVH, hd) -> head-major (B * P, KVH, ps, hd)
        return x.reshape(B, P, page_size, KVH, hd).swapaxes(2, 3).reshape(
            B * P, KVH, page_size, hd)

    k_pool, v_pool = pool(kp), pool(vp)
    # identity table: sequence b's logical page p is physical page b*P + p
    table = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)
    pos = jnp.full((B,), Sf - 1, dtype=jnp.int32)
    return _fa.paged_decode_attention(
        q, k_pool, v_pool, table, pos,
        causal=False, softcap=softcap, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("block_b", "block_n", "block_k", "interpret"))
def q78_matmul(
    a_q: jax.Array,
    w_q: jax.Array,
    block_b: int = 128,
    block_n: int = 128,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Bit-exact Q7.8 integer matmul -> Q15.16 int32."""
    if interpret is None:
        interpret = not _on_tpu()
    B, K = a_q.shape
    N = w_q.shape[1]
    block_b = min(block_b, max(8, B))
    ap = _pad_dim(_pad_dim(a_q, 0, block_b), 1, block_k)
    wp = _pad_dim(_pad_dim(w_q, 0, block_k), 1, block_n)
    y = _qmm.q78_matmul_kernel(
        ap, wp, block_b=block_b, block_n=block_n, block_k=block_k, interpret=interpret
    )
    return y[:B, :N]


def ssm_decode(state, layer, x, dt, a, d, b, c, interpret: bool | None = None):
    """One Mamba-2 decode step of layer ``layer`` over every row, the
    stacked state ``(L, B, N, H*P)`` updated in place
    (``kernels/ssm_decode``; argument layout there).  Not jitted here: it
    runs inside the decode step's layer scan, whose carried state buffer
    the kernel aliases."""
    if interpret is None:
        interpret = not _on_tpu()
    return _ssm.ssm_decode(state, layer, x, dt, a, d, b, c, interpret=interpret)
