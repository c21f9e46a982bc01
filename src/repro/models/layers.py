"""Shared model layers: norms, RoPE, GQA attention (dense + flash + local),
MLP — pure JAX, logically sharded via ``repro.distributed.shardlib``.

Everything is functional: ``init_*`` returns a param pytree, ``*_axes``
returns a matching pytree of logical-axis tuples (consumed by the launcher
to build NamedShardings), and apply functions are pure.

The attention stack matters for the roofline: ``train_4k``/``prefill_32k``
use a chunked flash attention (custom_vjp, O(S) memory) so the 32k cells
lower without materializing (S, S) score tensors; ``local`` layers (gemma3,
recurrentgemma) use an exact sliding-window variant whose cost is O(S * W).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.weight_plan import apply_gate_up, apply_linear
from repro.core import weight_plan as _wp
from repro.distributed import shardlib as sl

# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------


def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    """LeCun-normal over the fan-in axis."""
    fan_in = shape[in_axis]
    return jax.random.normal(key, shape, dtype) * (1.0 / math.sqrt(fan_in))


def embed_init(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * 0.02


# ---------------------------------------------------------------------------
# linear application — one dispatch for every weight representation
# ---------------------------------------------------------------------------
#
# ``qdense`` is the historical name of the dispatch; it now routes through the
# compressed-weight execution plan (core/weight_plan.apply_linear), so every
# layer transparently consumes dense arrays, int8 {"q","s"} dicts, and
# block-sparse / quant+sparse PackedLinear weights — whatever the plan
# assigned that matmul.

qdense = apply_linear

_QUANT_KEYS = _wp.QUANT_KEYS  # leaves consumed by qdense/embed/unembed


def quantize_for_serving(params, min_size: int = 16384):
    """int8-quantize matmul weights into the {"q", "s"} form qdense consumes.

    Kept as the quant-everywhere special case of ``weight_plan.compress``
    (serving b_weight drops 4 -> 1, the paper's Section 4.1 technique);
    use a ``PlanConfig`` for the pruning-composed representations.
    """
    return _wp.quantize_for_serving(params, min_size=min_size)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def init_norm(d: int, kind: str = "rmsnorm"):
    if kind == "rmsnorm":
        return {"scale": jnp.zeros((d,), jnp.float32)}  # gemma-style (1+scale)
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def norm_axes(kind: str = "rmsnorm"):
    if kind == "rmsnorm":
        return {"scale": ("d",)}
    return {"scale": ("d",), "bias": ("d",)}


def apply_norm(p, x, kind: str = "rmsnorm", eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * (1.0 + p["scale"])
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(hd: int, base: float) -> jax.Array:
    return base ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)  # (hd/2,)


def apply_rope(x: jax.Array, positions: jax.Array, base: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, base)  # (hd/2,)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    sin, cos = jnp.sin(ang)[..., None, :], jnp.cos(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# dense attention oracle (reference; used for small S and by tests)
# ---------------------------------------------------------------------------


def _softcap(s, cap: float):
    return jnp.tanh(s / cap) * cap if cap > 0.0 else s


def dense_attention(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Sk, KVH, hd)
    v: jax.Array,  # (B, Sk, KVH, hd)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_positions: Optional[jax.Array] = None,  # (B, Sq) absolute positions
    kv_positions: Optional[jax.Array] = None,  # (B, Sk)
    softcap: float = 0.0,
) -> jax.Array:
    B, Sq, H, hd = q.shape
    KVH = k.shape[2]
    G = H // KVH
    scale = 1.0 / math.sqrt(hd)
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(Sq)[None], (B, Sq))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(k.shape[1])[None], (B, k.shape[1]))
    qg = q.reshape(B, Sq, KVH, G, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32), k.astype(jnp.float32)) * scale
    s = _softcap(s, softcap)
    mask = jnp.ones((B, Sq, k.shape[1]), bool)
    if causal:
        mask &= kv_positions[:, None, :] <= q_positions[:, :, None]
    if window is not None:
        mask &= kv_positions[:, None, :] > (q_positions[:, :, None] - window)
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# flash attention: chunked, O(S) memory, custom VJP
# ---------------------------------------------------------------------------


def _chunk_mask(qpos, kpos, causal: bool, window: Optional[int]):
    """(cq, ck) boolean mask from absolute positions."""
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        m &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        m &= kpos[None, :] > (qpos[:, None] - window)
    return m


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8)
)
def flash_attention(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Sk, KVH, hd)
    v: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    softcap: float = 0.0,
    chunk_q: int = 512,
    chunk_k: int = 512,
) -> jax.Array:
    """Exact attention, computed in (chunk_q x chunk_k) tiles with an online
    softmax — the pure-JAX analogue of flash attention.  Differentiable via a
    recomputing custom VJP (no (S, S) residuals).  `q_offset` is the absolute
    position of q[?, 0] (prefill continuation / windowed decode).
    """
    o, _ = _flash_fwd_impl(q, k, v, causal, window, q_offset, softcap, chunk_q, chunk_k)
    return o


def _pad_seq(x, c):
    S = x.shape[1]
    pad = (-S) % c
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return x


def _flash_fwd_impl(q, k, v, causal, window, q_offset, softcap, cq, ck):
    B, Sq, H, hd = q.shape
    Sk, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    scale = 1.0 / math.sqrt(hd)
    qp = _pad_seq(q, cq)
    kp, vp = _pad_seq(k, ck), _pad_seq(v, ck)
    nq, nk = qp.shape[1] // cq, kp.shape[1] // ck
    # (B, KVH, G, nq, cq, hd) / (B, KVH, nk, ck, hd)
    qb = qp.reshape(B, nq, cq, KVH, G, hd).transpose(0, 3, 4, 1, 2, 5) * scale
    kb = kp.reshape(B, nk, ck, KVH, hd).transpose(0, 3, 1, 2, 4)
    vb = vp.reshape(B, nk, ck, KVH, hd).transpose(0, 3, 1, 2, 4)
    qpos = jnp.arange(nq * cq) + q_offset
    kpos = jnp.arange(nk * ck)
    kvalid = kpos < Sk  # padding mask

    def q_chunk(qi, q_i):
        # q_i: (B, KVH, G, cq, hd)
        pos_i = jax.lax.dynamic_slice_in_dim(qpos, qi * cq, cq)

        def kv_step(carry, inputs):
            m, l, acc = carry
            k_j, v_j, pos_j, valid_j = inputs
            # native-dtype operands + preferred_element_type: a bf16->f32
            # convert of the whole K/V would otherwise be hoisted out of the
            # scan by XLA, materializing (and resharding) a full-precision
            # copy of the cache in HBM.
            s = jnp.einsum(
                "bkgqd,bkcd->bkgqc", q_i, k_j,
                preferred_element_type=jnp.float32,
            )
            s = _softcap(s, softcap)
            msk = _chunk_mask(pos_i, pos_j, causal, window) & valid_j[None, :]
            s = jnp.where(msk[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + p.sum(-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqc,bkcd->bkgqd", p.astype(v_j.dtype), v_j,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l_new, acc_new), None

        init = (
            jnp.full((B, KVH, G, cq), -1e30, jnp.float32),
            jnp.zeros((B, KVH, G, cq), jnp.float32),
            jnp.zeros((B, KVH, G, cq, hd), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(
            kv_step,
            init,
            (
                kb.transpose(2, 0, 1, 3, 4),
                vb.transpose(2, 0, 1, 3, 4),
                kpos.reshape(nk, ck),
                kvalid.reshape(nk, ck),
            ),
        )
        l = jnp.maximum(l, 1e-30)
        o_i = acc / l[..., None]
        lse_i = m + jnp.log(l)
        return o_i, lse_i

    o_chunks, lse_chunks = jax.lax.map(
        lambda qi: q_chunk(qi, jax.lax.dynamic_index_in_dim(qb, qi, 3, keepdims=False)),
        jnp.arange(nq),
    )
    # o_chunks: (nq, B, KVH, G, cq, hd) -> (B, Sq, H, hd)
    o = o_chunks.transpose(1, 0, 4, 2, 3, 5).reshape(B, nq * cq, H, hd)[:, :Sq]
    lse = lse_chunks.transpose(1, 0, 4, 2, 3).reshape(B, nq * cq, H)[:, :Sq]
    return o.astype(q.dtype), lse


def _flash_fwd(q, k, v, causal, window, q_offset, softcap, cq, ck):
    o, lse = _flash_fwd_impl(q, k, v, causal, window, q_offset, softcap, cq, ck)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, window, q_offset, softcap, cq, ck, res, do):
    q, k, v, o, lse = res
    B, Sq, H, hd = q.shape
    Sk, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    scale = 1.0 / math.sqrt(hd)
    qp, op, dop = _pad_seq(q, cq), _pad_seq(o, cq), _pad_seq(do, cq)
    lsep = jnp.pad(lse, ((0, 0), (0, (-Sq) % cq), (0, 0)), constant_values=0.0)
    kp, vp = _pad_seq(k, ck), _pad_seq(v, ck)
    nq, nk = qp.shape[1] // cq, kp.shape[1] // ck
    qb = qp.reshape(B, nq, cq, KVH, G, hd).transpose(1, 0, 3, 4, 2, 5)  # (nq,B,KVH,G,cq,hd)
    ob = op.reshape(B, nq, cq, KVH, G, hd).transpose(1, 0, 3, 4, 2, 5)
    dob = dop.reshape(B, nq, cq, KVH, G, hd).transpose(1, 0, 3, 4, 2, 5)
    lseb = lsep.reshape(B, nq, cq, KVH, G).transpose(1, 0, 3, 4, 2)  # (nq,B,KVH,G,cq)
    kb = kp.reshape(B, nk, ck, KVH, hd).transpose(1, 0, 3, 2, 4)  # (nk,B,KVH,ck,hd)
    vb = vp.reshape(B, nk, ck, KVH, hd).transpose(1, 0, 3, 2, 4)
    qpos_all = jnp.arange(nq * cq) + q_offset
    kpos_all = jnp.arange(nk * ck)
    kvalid = kpos_all < Sk
    # delta_i = rowsum(do * o)
    delta = jnp.einsum(
        "nbkgqd,nbkgqd->nbkgq", dob, ob, preferred_element_type=jnp.float32
    )

    def q_step(carry, inputs):
        dk_acc, dv_acc = carry
        q_i, do_i, lse_i, delta_i, qi = inputs
        pos_i = jax.lax.dynamic_slice_in_dim(qpos_all, qi * cq, cq)

        def kv_step(_, inputs2):
            k_j, v_j, pos_j, valid_j = inputs2
            s_raw = (
                jnp.einsum(
                    "bkgqd,bkcd->bkgqc", q_i, k_j,
                    preferred_element_type=jnp.float32,
                )
                * scale
            )
            if softcap > 0.0:
                t = jnp.tanh(s_raw / softcap)
                s = t * softcap
                dcap = 1.0 - t * t
            else:
                s = s_raw
                dcap = None
            msk = _chunk_mask(pos_i, pos_j, causal, window) & valid_j[None, :]
            s = jnp.where(msk[None, None, None], s, -1e30)
            p = jnp.exp(s - lse_i[..., None])  # (B,KVH,G,cq,ck) f32
            pc = p.astype(k_j.dtype)
            dv_part = jnp.einsum(
                "bkgqc,bkgqd->bkcd", pc, do_i, preferred_element_type=jnp.float32
            )
            dp = jnp.einsum(
                "bkgqd,bkcd->bkgqc", do_i, v_j, preferred_element_type=jnp.float32
            )
            ds = p * (dp - delta_i[..., None])
            if dcap is not None:
                ds = ds * dcap
            ds = jnp.where(msk[None, None, None], ds, 0.0)
            dsc = ds.astype(k_j.dtype)
            dq_i_part = (
                jnp.einsum("bkgqc,bkcd->bkgqd", dsc, k_j, preferred_element_type=jnp.float32)
                * scale
            )
            dk_part = (
                jnp.einsum("bkgqc,bkgqd->bkcd", dsc, q_i, preferred_element_type=jnp.float32)
                * scale
            )
            return None, (dk_part, dv_part, dq_i_part)

        _, (dk_parts, dv_parts, dq_parts) = jax.lax.scan(
            kv_step,
            None,
            (kb, vb, kpos_all.reshape(nk, ck), kvalid.reshape(nk, ck)),
        )
        dq_i = dq_parts.sum(0)
        return (dk_acc + dk_parts, dv_acc + dv_parts), dq_i

    zeros_kv = jnp.zeros((nk, B, KVH, ck, hd), jnp.float32)
    (dkb, dvb), dqb = jax.lax.scan(
        q_step, (zeros_kv, zeros_kv), (qb, dob, lseb, delta, jnp.arange(nq))
    )
    dq = dqb.transpose(1, 0, 4, 2, 3, 5).reshape(B, nq * cq, H, hd)[:, :Sq].astype(q.dtype)
    dk = dkb.transpose(1, 0, 3, 2, 4).reshape(B, nk * ck, KVH, hd)[:, :Sk].astype(k.dtype)
    dv = dvb.transpose(1, 0, 3, 2, 4).reshape(B, nk * ck, KVH, hd)[:, :Sk].astype(v.dtype)
    return dq, dk, dv


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def attention(
    q, k, v, *, causal=True, window=None, q_offset=0, softcap=0.0,
    dense_threshold: int = 1024, chunk: int = 512,
):
    """Dispatch: dense for small sequences, flash for long ones."""
    if q.shape[1] <= dense_threshold and k.shape[1] <= dense_threshold:
        qpos = jnp.arange(q.shape[1])[None] + q_offset
        return dense_attention(
            q, k, v, causal=causal, window=window, softcap=softcap,
            q_positions=jnp.broadcast_to(qpos, q.shape[:2]),
        )
    cq = min(chunk, max(128, q.shape[1]))
    ck = min(chunk, max(128, k.shape[1]))
    return flash_attention(q, k, v, causal, window, q_offset, softcap, cq, ck)


def decode_attention(
    q: jax.Array,  # (B, T, H, hd) — T new tokens per sequence (T=1 classic)
    k_cache: jax.Array,  # (B, S, KVH, hd)
    v_cache: jax.Array,
    pos: jax.Array,  # (B,) position of q[:, 0]; cache holds entries <= pos+T-1
    *,
    window: Optional[int] = None,
    softcap: float = 0.0,
    k_scale: Optional[jax.Array] = None,  # (B, S, KVH) int8-cache dequant scales
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Attention against a KV cache for T new tokens per sequence.

    T=1 is the classic decode step; T=k+1 is the speculative *verify* step:
    the k draft tokens ride the same weight stream as the committed one
    (the paper's batch-processing amortization with draft positions as the
    extra samples), and each query position pos+t is causally masked to
    kv_pos <= pos+t, so all T positions verify in one step against a cache
    that already contains all T new entries.

    The cache is a ring buffer of length S: slot i holds the most recent
    absolute position p with p % S == i and p <= pos+T-1.  For a
    full-length cache (S > pos+T-1) that degenerates to slot i ==
    position i; for a sliding-window cache it is the rolling window — a
    speculative engine sizes the ring at window + k (see
    ``transformer.init_layer_cache``) so the earliest verify query still
    sees its whole window after the T-entry scatter.  Slots whose derived
    kv_pos falls outside [0, q_pos] or the window are masked, which is what
    makes *rejected* speculative writes harmless: a stale entry's slot
    arithmetic resolves to a position the masks exclude until the entry is
    overwritten by the next verify step (rollback-free commit).

    ``k_scale``/``v_scale`` enable the int8 cache: payloads are int8 with
    per-(slot, head) scales, dequantized by folding the scales into the
    score / probability tensors — (q . k*s) == (q . k) * s and
    p @ (v*s) == (p*s) @ v — so the int8 cache stream is read as-is and the
    fp correction rides on the (B, KVH, G, T, S) intermediates.  This is
    the portable reference path; ``kernels/flash_attention`` dequantizes
    the same way inside its tile loads on the TPU fast path.
    """
    B, S, KVH, hd = k_cache.shape
    T, H = q.shape[1], q.shape[2]
    G = H // KVH
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, T, KVH, G, hd)
    if k_scale is None:
        # native-dtype cache operands + f32 accumulation: casting the cache
        # would materialize (and possibly reshard) a full f32 copy in HBM.
        s = jnp.einsum(
            "btkgd,bskd->bkgts", qg.astype(k_cache.dtype), k_cache,
            preferred_element_type=jnp.float32,
        ) * scale
    else:
        # int8 cache operands stay int8 in the contraction (mixed-dtype dot
        # with f32 accumulation); the per-slot scales fold into the (B, KVH,
        # G, T, S) score tensor afterwards.  Casting the cache first would
        # materialize a full fp copy of it in HBM every step.
        s = jnp.einsum(
            "btkgd,bskd->bkgts", qg.astype(jnp.float32), k_cache,
            preferred_element_type=jnp.float32,
        ) * scale
        s = s * k_scale.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, None, :]
    s = _softcap(s, softcap)
    newest = pos[:, None] + (T - 1)  # (B, 1) newest written position
    slot = jnp.arange(S)[None]  # (1, S)
    kv_pos = newest - ((newest - slot) % S)  # (B, S) absolute pos per slot
    q_pos = pos[:, None] + jnp.arange(T)[None]  # (B, T)
    mask = (kv_pos[:, None, :] >= 0) & (kv_pos[:, None, :] <= q_pos[:, :, None])
    if window is not None:
        mask &= kv_pos[:, None, :] > (q_pos[:, :, None] - window)
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    if v_scale is None:
        o = jnp.einsum(
            "bkgts,bskd->btkgd", p.astype(v_cache.dtype), v_cache,
            preferred_element_type=jnp.float32,
        )
    else:
        # p @ (v*s) == (p*s) @ v: the scales ride on the probability tensor,
        # so the int8 V cache is contracted as-is (no fp cast of the cache).
        o = jnp.einsum(
            "bkgts,bskd->btkgd",
            p * v_scale.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, None, :],
            v_cache,
            preferred_element_type=jnp.float32,
        )
    return o.reshape(B, T, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# attention block (QKV/O projections + rope + cache plumbing)
# ---------------------------------------------------------------------------


def init_attn(cfg, key):
    d, H, KVH, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, H * hd)),
        "wk": dense_init(ks[1], (d, KVH * hd)),
        "wv": dense_init(ks[2], (d, KVH * hd)),
        "wo": dense_init(ks[3], (H * hd, d)),
    }


def attn_axes():
    return {"wq": ("d", "qkv"), "wk": ("d", "qkv"), "wv": ("d", "qkv"), "wo": ("qkv", "d")}


def apply_attn(
    cfg,
    p,
    x: jax.Array,  # (B, S, d)
    *,
    kind: str = "global",  # global | local
    rope_base: Optional[float] = None,
    cache: Optional[dict] = None,  # {"k": (B,S,KVH,hd), "v": ..., } decode path
    pos: Optional[jax.Array] = None,  # (B,) decode positions
    cross_kv: Optional[tuple] = None,  # (k, v) for cross-attention
    page_table: Optional[jax.Array] = None,  # (B, P) paged-cache indirection
):
    """Returns (out, new_cache).  Three modes:
    - training/prefill (cache None): full/local causal attention over x;
    - decode (cache given): write new token kv at pos, attend to cache;
      a paged cache ({"k_pages", ...} + ``page_table``) routes through the
      page-table scatter/gather instead of the contiguous ring buffer;
    - cross (cross_kv given): encoder-decoder cross attention (no mask).
    """
    B, S, d = x.shape
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    window = cfg.local_window if kind == "local" else None
    dt = x.dtype
    q = qdense(x, p["wq"]).reshape(B, S, H, hd)
    q = sl.shard(q, "batch", "seq", "heads", None)
    if cross_kv is not None:
        k, v = cross_kv
        o = attention(q, k, v, causal=False, softcap=cfg.logit_softcap)
        new_cache = cache
    else:
        k = qdense(x, p["wk"]).reshape(B, S, KVH, hd)
        v = qdense(x, p["wv"]).reshape(B, S, KVH, hd)
        k = sl.shard(k, "batch", "seq", "kv_heads", None)
        v = sl.shard(v, "batch", "seq", "kv_heads", None)
        base = rope_base if rope_base is not None else cfg.rope_base
        if cfg.position_embedding == "nope":
            rope = lambda t, positions, base: t  # no positional embedding
        else:
            rope = apply_rope
        if cache is None:
            positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
            q = rope(q, positions, base)
            k = rope(k, positions, base)
            o = attention(q, k, v, causal=True, window=window, softcap=cfg.logit_softcap)
            new_cache = None
        elif "k_pages" in cache:
            # paged decode: scatter this step's K/V through the page table,
            # then attend via the gather reference (kernels/flash_attention
            # has the indirection kernel that skips the materialized gather).
            # S > 1 is the speculative verify step: all S draft positions
            # scatter and attend in this one step.
            positions = pos[:, None] + jnp.arange(S)[None]
            q = rope(q, positions, base)
            k = rope(k, positions, base)
            new_cache = dict(cache)
            if "k_scale_pages" in cache:
                k, ks = quantize_kv(k)
                v, vs = quantize_kv(v)
                new_cache["k_scale_pages"] = sl.shard_pinned(
                    paged_cache_update(cache["k_scale_pages"], ks, page_table, pos),
                    *sl.axes_for("attn.kv_scale_pages"))
                new_cache["v_scale_pages"] = sl.shard_pinned(
                    paged_cache_update(cache["v_scale_pages"], vs, page_table, pos),
                    *sl.axes_for("attn.kv_scale_pages"))
            # pin pools to their registered layout: the scatter's inferred
            # sharding would otherwise make GSPMD reshard the whole pool at
            # the step boundary (same failure mode as the contiguous cache)
            new_cache["k_pages"] = sl.shard_pinned(
                paged_cache_update(cache["k_pages"], k, page_table, pos),
                *sl.axes_for("attn.kv_pages"))
            new_cache["v_pages"] = sl.shard_pinned(
                paged_cache_update(cache["v_pages"], v, page_table, pos),
                *sl.axes_for("attn.kv_pages"))
            o = paged_decode_attention(
                q, new_cache["k_pages"], new_cache["v_pages"], page_table, pos,
                window=window, softcap=cfg.logit_softcap,
                k_scale_pages=new_cache.get("k_scale_pages"),
                v_scale_pages=new_cache.get("v_scale_pages"),
            )
        else:
            positions = pos[:, None] + jnp.arange(S)[None]  # (B, S) decode span
            q = rope(q, positions, base)
            k = rope(k, positions, base)
            if "k_scale" in cache:
                # int8 cache: quantize this step's K/V per (token, head) and
                # write payload + scale; the read side folds the scales into
                # the attention math (decode_attention docstring).
                k, ks = quantize_kv(k)
                v, vs = quantize_kv(v)
                ksc = _cache_update(cache["k_scale"], ks, pos)
                vsc = _cache_update(cache["v_scale"], vs, pos)
                ksc = sl.shard_pinned(ksc, *sl.axes_for("attn.kv_scale"))
                vsc = sl.shard_pinned(vsc, *sl.axes_for("attn.kv_scale"))
            else:
                ksc = vsc = None
            kc = _cache_update(cache["k"], k, pos)
            vc = _cache_update(cache["v"], v, pos)
            # pin to the declared cache layout: any deviation makes GSPMD
            # reshard the whole cache at the step boundary (measured as a
            # multi-GB all-gather per decode step before this constraint)
            kc = sl.shard_pinned(kc, *sl.axes_for("attn.kv"))
            vc = sl.shard_pinned(vc, *sl.axes_for("attn.kv"))
            o = decode_attention(
                q, kc, vc, pos, window=window, softcap=cfg.logit_softcap,
                k_scale=ksc, v_scale=vsc,
            )
            new_cache = {"k": kc, "v": vc}
            if ksc is not None:
                new_cache["k_scale"] = ksc
                new_cache["v_scale"] = vsc
    o = o.reshape(B, S, H * hd)
    out = qdense(o, p["wo"])
    return sl.shard(out, "batch", "seq_sp", None), new_cache


def _cache_update(cache: jax.Array, new: jax.Array, pos: jax.Array) -> jax.Array:
    """Scatter T new (B, T, KVH, hd) entries at per-sequence positions
    pos..pos+T-1 (T=1 is the classic decode write).

    For a sliding-window cache the write indices wrap independently per
    entry (ring buffer); masking in decode_attention uses absolute
    positions, so the caller passes ``pos % window`` semantics via cache
    shape.
    """
    S = cache.shape[1]
    T = new.shape[1]
    if T == 1:
        idx = pos % S

        def upd(c, n, i):
            return jax.lax.dynamic_update_slice_in_dim(c, n.astype(c.dtype), i, axis=0)

        return jax.vmap(upd)(cache, new, idx)
    idx = (pos[:, None] + jnp.arange(T)[None]) % S  # (B, T) may wrap per entry

    def upd_t(c, n, i):
        return c.at[i].set(n.astype(c.dtype))

    return jax.vmap(upd_t)(cache, new, idx)


def quantize_kv(x: jax.Array):
    """Per-(token, head) int8 quantization of a K or V tensor (..., hd).

    Returns (int8 values, fp32 scales without the hd axis).  The scale
    granularity matches the cache write pattern: one scale per written
    vector, so the decode-step scatter stays a single dynamic-update per
    leaf and the read side folds scales into the attention intermediates.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def init_attn_cache(cfg, batch: int, length: int, dtype=jnp.bfloat16):
    """KV cache for one attention layer.  ``dtype=jnp.int8`` selects the
    quantized cache: int8 payloads + per-(slot, head) fp32 scales, halving
    the decode-time cache read stream (the kv_read term of
    ``perf_model.decode_step_time``)."""
    KVH, hd = cfg.n_kv_heads, cfg.hd
    if jnp.dtype(dtype) == jnp.int8:
        z = jnp.zeros((batch, length, KVH, hd), jnp.int8)
        s = jnp.zeros((batch, length, KVH), jnp.float32)
        return {"k": z, "v": z, "k_scale": s, "v_scale": s}
    z = jnp.zeros((batch, length, KVH, hd), dtype)
    return {"k": z, "v": z}


# cache-kind registry entries (distributed/shardlib): the KV-cache leaf
# layouts register their logical axes AND their serving classification
# (positionally addressed, pageable) once, here, where the layouts are
# defined; the engine's cache placement, the launcher's in_shardings, the
# in-step shard_pinned constraints, and the capability gates all read the
# same entries.
_KV_AXES = sl.register_cache_kind(
    "attn.kv", ("batch", "cache_seq", "kv_heads", None), positional=True)
_KV_SCALE_AXES = sl.register_cache_kind(
    "attn.kv_scale", ("batch", "cache_seq", "kv_heads"), positional=True)


def attn_cache_axes(quantized: bool = False):
    axes = {"k": _KV_AXES, "v": _KV_AXES}
    if quantized:
        axes["k_scale"] = _KV_SCALE_AXES
        axes["v_scale"] = _KV_SCALE_AXES
    return axes


# ---------------------------------------------------------------------------
# paged KV cache (global pool of fixed-size pages + per-sequence page table)
# ---------------------------------------------------------------------------
#
# Layout: pools are head-major (num_pages, KVH, page_size, hd) per layer (the
# scale pools drop hd) so one head's page is a whole trailing (page_size, hd)
# tile — the block shape the TPU kernel can fetch for any page size.  The
# batch axis is gone; sequences own *pages*, assigned by the host-side allocator
# (serving/paged.py), and the int32 page table (B, pages_per_seq) maps each
# slot's logical page index to a physical page.  Logical addressing is
# position-identity (position p lives at page p // ps, slot p % ps): no ring
# semantics, because capacity is managed by allocation, not wraparound.
# Physical page 0 is the null page — free slots point at it so dead-slot
# scatters in the one compiled decode step are harmless.


def init_paged_attn_cache(cfg, num_pages: int, page_size: int, dtype=jnp.bfloat16):
    """Paged KV pools for one attention layer.  ``dtype=jnp.int8`` selects
    the quantized pools: int8 payloads + per-(slot, head) fp32 scale pools,
    composing the paged layout with the halved int8 cache stream."""
    KVH, hd = cfg.n_kv_heads, cfg.hd
    if jnp.dtype(dtype) == jnp.int8:
        z = jnp.zeros((num_pages, KVH, page_size, hd), jnp.int8)
        s = jnp.zeros((num_pages, KVH, page_size), jnp.float32)
        return {"k_pages": z, "v_pages": z, "k_scale_pages": s, "v_scale_pages": s}
    z = jnp.zeros((num_pages, KVH, page_size, hd), dtype)
    return {"k_pages": z, "v_pages": z}


# Pools have no batch axis: they shard over the model axis on kv_heads
# (tensor-parallel attention — every chip holds all pages but only its
# heads' slice of each, so the page table stays host-side per-replica and
# the decode gather never crosses chips).  The page axes stay replicated:
# the table maps any slot to any physical page.
_KV_PAGES_AXES = sl.register_cache_kind(
    "attn.kv_pages", (None, "kv_heads", None, None),
    positional=True, paged=True)
_KV_SCALE_PAGES_AXES = sl.register_cache_kind(
    "attn.kv_scale_pages", (None, "kv_heads", None),
    positional=True, paged=True)


def paged_attn_cache_axes(quantized: bool = False):
    axes = {"k_pages": _KV_PAGES_AXES, "v_pages": _KV_PAGES_AXES}
    if quantized:
        axes["k_scale_pages"] = _KV_SCALE_PAGES_AXES
        axes["v_scale_pages"] = _KV_SCALE_PAGES_AXES
    return axes


def paged_cache_update(
    pool: jax.Array,  # (num_pages, KVH, page_size, ...) K/V or scale pool
    new: jax.Array,  # (B, T, KVH, ...) this step's entries (T=1 classic decode)
    page_table: jax.Array,  # (B, pages_per_seq) int32
    pos: jax.Array,  # (B,) absolute position of new[:, 0]
) -> jax.Array:
    """Scatter T new entries per sequence through the page table.

    Every target page must be privately owned (refcount 1) — the engine
    guarantees it via copy-on-write before the step, across the whole
    [pos, pos+T-1] write range (a speculative verify step can straddle a
    page boundary).  Dead slots have their table rows pointed at the null
    page; their scatters collide there and write garbage nobody reads —
    the same holds for speculative writes past a sequence's allocated
    pages, whose table entries are NULL_PAGE.
    """
    page_size = pool.shape[2]
    B, T, KVH = new.shape[:3]
    positions = pos[:, None] + jnp.arange(T)[None]  # (B, T)
    phys = page_table[jnp.arange(B)[:, None], positions // page_size]
    return pool.at[
        phys[..., None], jnp.arange(KVH), (positions % page_size)[..., None]
    ].set(new.astype(pool.dtype))


def write_pages(pools: jax.Array, phys, off, vals: jax.Array) -> jax.Array:
    """Host-side write into layer-stacked pools (L, num_pages, KVH, ps, ...):
    entry i of ``vals`` (L, n, KVH, ...) lands at slot ``off[i]`` of page
    ``phys[i]`` in every layer — how the engine copies a contiguous
    prefill cache into a sequence's pages."""
    phys = np.asarray(phys)[:, None]
    off = np.asarray(off)[:, None]
    return pools.at[:, phys, np.arange(pools.shape[2]), off].set(
        vals.astype(pools.dtype))


def gather_pages(pool: jax.Array, page_table: jax.Array) -> jax.Array:
    """(num_pages, KVH, ps, ...) pool -> (B, pages_per_seq * ps, KVH, ...)
    view of each sequence's logical cache, via the page table."""
    g = jnp.swapaxes(pool[page_table], 2, 3)  # (B, P, ps, KVH, ...)
    B, P, ps = g.shape[:3]
    return g.reshape((B, P * ps) + g.shape[3:])


def finite_rows(logits: jax.Array) -> jax.Array:
    """(B,) bool: whether every logit in batch row b is finite — the
    serving engine's numeric guardrail, folded into the ONE compiled
    decode step so quarantining a NaN-poisoned slot costs a (B,) bool
    fetch per tick instead of a host pass over the (B, T, V) logits.
    Reduces over all non-batch axes, so the same reduction guards T=1
    decode and T=k+1 speculative verify."""
    return jnp.isfinite(logits).all(axis=tuple(range(1, logits.ndim)))


# Process-wide override for the kernel-vs-gather dispatch below.  Tests use
# it to force the (interpret-mode) Pallas datapath through whole engine runs
# off-TPU, where per-call plumbing can't reach (decode steps are jit'd
# closures created inside the engine).  None = no override.
_FORCE_KERNEL: Optional[bool] = None


def force_attention_kernel(value: Optional[bool]) -> Optional[bool]:
    """Set the process-wide kernel-dispatch override; returns the previous
    value so callers can restore it (try/finally).  Takes effect at trace
    time — call before the first decode step of the run being forced."""
    global _FORCE_KERNEL
    prev = _FORCE_KERNEL
    _FORCE_KERNEL = value
    return prev


def paged_decode_attention(
    q: jax.Array,  # (B, T, H, hd) — T=1 decode, T=k+1 speculative verify
    k_pages: jax.Array,  # (num_pages, KVH, ps, hd)
    v_pages: jax.Array,
    page_table: jax.Array,  # (B, pages_per_seq) int32
    pos: jax.Array,  # (B,) position of q[:, 0]
    *,
    window: Optional[int] = None,
    softcap: float = 0.0,
    k_scale_pages: Optional[jax.Array] = None,  # (num_pages, KVH, ps)
    v_scale_pages: Optional[jax.Array] = None,
    use_kernel: Optional[bool] = None,  # None = kernel on TPU, gather elsewhere
) -> jax.Array:
    """Attention for T new tokens per sequence through the page table.

    Two numerically-matching datapaths (parity in tests/test_paged_cache.py
    and tests/test_mq_paged_attention.py):

    * **gather reference** (portable pure JAX): gather the sequence's pages
      into a contiguous (B, L, KVH, hd) view and run ``decode_attention``.
      L = pages_per_seq * page_size always exceeds ``pos`` (the table
      covers the logical context cap), so the ring-buffer masking
      degenerates to position identity and results are bit-identical to
      the contiguous cache.  The gather materializes the full logical
      context per step — fine off-TPU, wasteful on it.
    * **Pallas kernel** (``kernels/flash_attention.paged_decode_attention``):
      K/V tiles are fetched page-by-page via scalar-prefetch indirection
      with int8 dequant-on-load; only owned pages cross HBM, and each page
      crosses ONCE per step no matter how many verify positions T the step
      carries (single-pass multi-query — one ``pallas_call`` for all T).

    ``use_kernel=None`` picks the kernel on the TPU backend and the gather
    reference elsewhere (interpret-mode Pallas would be far slower than the
    gather for CPU serving ticks); pass True/False to force either, or set
    the process-wide ``force_attention_kernel`` override.
    """
    if use_kernel is None:
        use_kernel = (
            _FORCE_KERNEL if _FORCE_KERNEL is not None
            else jax.default_backend() == "tpu"
        )
    if use_kernel:
        from repro.kernels import ops  # deferred: models stay importable solo

        def kernel(q, k_pages, v_pages, page_table, pos, ks, vs):
            return ops.paged_decode_attention(
                q, k_pages, v_pages, page_table, pos, window=window,
                softcap=softcap, k_scale_pages=ks, v_scale_pages=vs)

        return _per_kv_head_shard(
            kernel, q, k_pages.shape[1],
            (k_pages, v_pages, page_table, pos, k_scale_pages, v_scale_pages),
            lambda kv, b: (P(None, kv, None, None), P(None, kv, None, None),
                           P(b, None), P(b), P(None, kv, None),
                           P(None, kv, None)))
    kc = gather_pages(k_pages, page_table)
    vc = gather_pages(v_pages, page_table)
    ksc = vsc = None
    if k_scale_pages is not None:
        ksc = gather_pages(k_scale_pages, page_table)
        vsc = gather_pages(v_scale_pages, page_table)
    return decode_attention(
        q, kc, vc, pos, window=window, softcap=softcap, k_scale=ksc, v_scale=vsc
    )


def _per_kv_head_shard(kernel, q, kv_heads: int, kv_args, kv_specs):
    """Run an attention kernel per shard under the active mesh.

    ``q`` (B, T, H, hd) is viewed as (B, T, KVH, G, hd) so its heads shard
    exactly with the kv heads they attend to (a kv_heads mapping the mesh
    cannot divide replicates both); ``kv_specs(kv, b)`` gives the other
    operands' PartitionSpecs from the resolved kv-head and batch mesh
    axes.  Without a mesh this is a plain kernel call."""
    B, T, H, hd = q.shape
    G = H // kv_heads
    kv = sl.mesh_axes("kv_heads", kv_heads)
    b = sl.mesh_axes("batch", B)
    spec = P(b, None, kv, None, None)

    def local(q5, *rest):
        Bl, _, kvl = q5.shape[:3]
        return kernel(q5.reshape(Bl, T, kvl * G, hd), *rest).reshape(q5.shape)

    o = sl.kernel_call(
        local, (q.reshape(B, T, kv_heads, G, hd),) + tuple(kv_args),
        (spec,) + kv_specs(kv, b), spec)
    return o.reshape(B, T, H, hd)


def cross_decode_attention(
    q: jax.Array,  # (B, T, H, hd) decode-step queries
    xk: jax.Array,  # (B, Sf, KVH, hd) static encoder K
    xv: jax.Array,
    *,
    softcap: float = 0.0,
    use_kernel: Optional[bool] = None,
) -> jax.Array:
    """Decode-time enc-dec cross-attention: T queries against the static
    encoder KV pool.  The kernel path (``kernels/ops.cross_decode_attention``)
    reuses the single-pass multi-query paged kernel with an identity page
    table, so the encoder cache streams once per step regardless of T; the
    reference path is plain non-causal attention.  Dispatch mirrors
    ``paged_decode_attention`` (kernel on TPU, reference elsewhere, same
    process-wide override).
    """
    if use_kernel is None:
        use_kernel = (
            _FORCE_KERNEL if _FORCE_KERNEL is not None
            else jax.default_backend() == "tpu"
        )
    if use_kernel:
        from repro.kernels import ops  # deferred: models stay importable solo

        return _per_kv_head_shard(
            lambda q, xk, xv: ops.cross_decode_attention(
                q, xk, xv, softcap=softcap),
            q, xk.shape[2], (xk, xv),
            lambda kv, b: (P(b, None, kv, None),) * 2)
    return attention(q, xk, xv, causal=False, softcap=softcap)


# ---------------------------------------------------------------------------
# MLP (dense FFN)
# ---------------------------------------------------------------------------

GATED = ("silu", "swiglu", "geglu", "gelu_glu")


def init_mlp(cfg, key, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], (d, f)), "w_down": dense_init(ks[1], (f, d))}
    if cfg.activation in GATED:
        p["w_gate"] = dense_init(ks[2], (d, f))
    return p


def mlp_axes(cfg):
    a = {"w_up": ("d", "ff"), "w_down": ("ff", "d")}
    if cfg.activation in GATED:
        a["w_gate"] = ("d", "ff")
    return a


# one activation table for the whole stack (core/weight_plan.GATE_ACTS):
# the fused gate+up kernel, the plan dispatch, and these layers must agree
_ACT = dict(_wp.GATE_ACTS)


def apply_mlp(cfg, p, x):
    if "w_gate" in p:
        # fused-pair plan node: a sparse-packed (w_gate, w_up) pair runs as
        # ONE kernel launch (act(x@Wg) * (x@Wu) never round-trips HBM);
        # other representations fall back to two dispatches inside.
        h = apply_gate_up(x, p["w_gate"], p["w_up"], cfg.activation)
    else:
        h = _ACT[cfg.activation](qdense(x, p["w_up"]))
    h = sl.shard(h, "batch", "seq", "ff")
    return sl.shard(qdense(h, p["w_down"]), "batch", "seq_sp", None)


# ---------------------------------------------------------------------------
# embedding / unembedding
# ---------------------------------------------------------------------------


def init_embed(cfg, key):
    p = {"tok": embed_init(key, (cfg.vocab, cfg.d_model))}
    if not cfg.tie_embeddings:
        p["head"] = dense_init(jax.random.fold_in(key, 1), (cfg.d_model, cfg.vocab))
    return p


def embed_axes(cfg):
    a = {"tok": ("vocab", "d")}
    if not cfg.tie_embeddings:
        a["head"] = ("d", "vocab")
    return a


def embed_tokens(cfg, p, tokens):
    tok = p["tok"]
    if isinstance(tok, dict):  # int8-quantized table: dequant the gathered rows
        x = jnp.take(tok["q"], tokens, axis=0).astype(_cdtype(cfg))
        x = x * tok["s"].astype(x.dtype)
    else:
        x = jnp.take(tok, tokens, axis=0).astype(_cdtype(cfg))
    if getattr(cfg, "scale_embed", False):
        x = x * math.sqrt(cfg.d_model)  # gemma convention
    return sl.shard(x, "batch", "seq_sp", None)


def unembed(cfg, p, x):
    dt = x.dtype
    if "head" in p:
        logits = qdense(x, p["head"])
    else:
        tok = p["tok"]
        if isinstance(tok, dict):
            # (q * s[None,:]).T == scale x by s, then contract with q.T
            logits = jax.lax.dot_general(
                x * tok["s"].astype(dt), tok["q"].astype(dt),
                (((x.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).astype(dt)
        else:
            logits = x @ tok.T.astype(dt)
    if cfg.logit_softcap > 0.0:
        logits = _softcap(logits.astype(jnp.float32), cfg.logit_softcap).astype(dt)
    return sl.shard(logits, "batch", "seq", "vocab")


def _cdtype(cfg):
    return jnp.dtype(cfg.compute_dtype)
