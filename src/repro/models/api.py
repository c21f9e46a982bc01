"""Uniform model API across families: one namespace of functions per config,
so the trainer / server / dry-run never branch on architecture family.

    api = get_api(cfg)
    params = api.init_params(cfg, key)
    loss, metrics = api.loss_fn(cfg, params, batch)          # batch incl. extras
    logits, cache = api.prefill(cfg, params, batch, cache)
    logits, cache = api.decode_step(cfg, params, cache, tok, pos)

``params`` everywhere may be the *compressed* pytree produced by
``api.compress(cfg, params, plan_cfg)`` (core/weight_plan): prefill and
decode route their matmuls through the plan dispatch, so pruned+quantized
weights serve through the same compiled step functions as dense ones.

``input_specs`` produces ShapeDtypeStruct stand-ins for every input of the
lowered step functions (the dry-run path: weak-type-correct, shardable, no
device allocation).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import weight_plan as WP
from repro.models import encdec as E
from repro.models import transformer as T
from repro.models import vlm as V


def _compress(cfg, params, plan_cfg: WP.PlanConfig) -> WP.WeightPlan:
    """Default compression: family-agnostic plan walk (every family's
    matmuls already route through the plan dispatch).  The family's dense
    param axes ride along so every LeafPlan records its logical sharding
    axes and the plan can emit NamedShardings for its packed pytree."""
    return WP.compress(params, plan_cfg, axes=get_api(cfg).param_axes(cfg))


@dataclasses.dataclass(frozen=True)
class ModelAPI:
    init_params: Callable
    param_axes: Callable
    loss_fn: Callable  # (cfg, params, batch) -> (loss, metrics)
    prefill: Callable  # (cfg, params, batch, cache) -> (logits, cache)
    decode_step: Callable  # (cfg, params, cache, tokens, pos) -> (logits, cache)
    init_cache: Callable  # (cfg, batch, length, dtype) -> cache
    cache_axes: Callable
    n_params_exact: Callable
    extra_keys: tuple = ()  # frontend-stub inputs in the batch dict
    # absolute decode positions = prefix_len(cfg) + text position: VLMs
    # prepend patch embeddings to the decoder sequence, so their KV cache
    # slots are offset by n_patches.
    prefix_len: Callable = staticmethod(lambda cfg: 0)
    # (cfg, params, PlanConfig) -> WeightPlan whose .params is treedef-
    # compatible with the dense pytree (prefill/decode/engine accept it).
    compress: Callable = staticmethod(_compress)


def _t_prefill(cfg, params, batch, cache):
    return T.prefill(cfg, params, batch["tokens"], cache)


def _v_prefill(cfg, params, batch, cache):
    return V.prefill(cfg, params, batch["tokens"], batch["patches"], cache)


def _e_prefill(cfg, params, batch, cache):
    return E.prefill(cfg, params, batch["tokens"], batch["frames"], cache)


_TRANSFORMER_API = ModelAPI(
    init_params=T.init_params, param_axes=T.param_axes, loss_fn=T.loss_fn,
    prefill=_t_prefill, decode_step=T.decode_step, init_cache=T.init_cache,
    cache_axes=T.cache_axes, n_params_exact=T.n_params_exact,
)

_VLM_API = ModelAPI(
    init_params=V.init_params, param_axes=V.param_axes, loss_fn=V.loss_fn,
    prefill=_v_prefill, decode_step=V.decode_step, init_cache=V.init_cache,
    cache_axes=V.cache_axes, n_params_exact=V.n_params_exact,
    extra_keys=("patches",),
    prefix_len=staticmethod(lambda cfg: cfg.n_patches),
)

_ENCDEC_API = ModelAPI(
    init_params=E.init_params, param_axes=E.param_axes, loss_fn=E.loss_fn,
    prefill=_e_prefill, decode_step=E.decode_step, init_cache=E.init_cache,
    cache_axes=E.cache_axes, n_params_exact=E.n_params_exact,
    extra_keys=("frames",),
)


def get_api(cfg) -> ModelAPI:
    if cfg.family == "audio":
        return _ENCDEC_API
    if cfg.family == "vlm":
        return _VLM_API
    return _TRANSFORMER_API  # dense / moe / ssm / hybrid


def supports_int8_kv(cfg) -> bool:
    """Whether this family's cache actually materializes int8 KV leaves
    when asked (encdec ignores kv_dtype) — shape-level probe, no
    allocation.  Callers must not charge the int8 stream otherwise."""
    api = get_api(cfg)
    probe = jax.eval_shape(
        functools.partial(api.init_cache, cfg, 1, 2,
                          jnp.dtype(cfg.compute_dtype), kv_dtype=jnp.int8))
    return any(l.dtype == jnp.int8 for l in jax.tree.leaves(probe))


def supports_spec_decode(cfg) -> bool:
    """Whether this family can serve as the target OR the draft of the
    speculative decode path (serving/engine.py ``spec_k``).

    Multi-token verify with rollback-free commit needs every piece of
    per-sequence state to be *positionally addressed*: attention KV caches
    (contiguous ring or paged pool) re-derive an entry's validity from its
    position, so rejected speculative writes are simply masked until the
    next verify step overwrites them.  O(1) recurrent / xLSTM states are
    sequential integrators with no position axis — a rejected token's
    update cannot be undone without snapshotting the state.  The enc-dec
    decoder now threads multi-position decode (single-pass cross-attention),
    but the engine's draft prefill carries tokens only (no frames/patches)
    and the VLM/enc-dec caches don't size for the verify overhang
    (``init_cache(..., spec_k=)``), so speculation stays transformer-only:
    decoder-only stacks whose layers are all attention."""
    if get_api(cfg) is not _TRANSFORMER_API:
        return False
    kinds = getattr(cfg, "layer_kinds", ()) or ()
    return bool(kinds) and all(k in ("global", "local") for k in kinds)


def supports_chunked_prefill(cfg) -> bool:
    """Whether the engine may prefill this stack in chunks: a chunk is a
    multi-token decode step from the state the previous chunk left, and the
    last chunk is padded to the chunk width (serving/scheduler.py).

    Stacks that qualify for speculation do (their positionally-addressed
    caches mask what lies past the frontier).  So does a decoder-only stack
    of paged-width attention (``global``) and Mamba-2 layers: the Mamba
    state is carried from chunk to chunk exactly, and a padded position
    changes neither it nor the conv state (``models/mamba2.py``).  Other
    recurrent kinds (RG-LRU, xLSTM) would take padded positions into their
    state, and a sliding-window ring would let them overwrite live
    entries."""
    if supports_spec_decode(cfg):
        return True
    if get_api(cfg) is not _TRANSFORMER_API:
        return False
    kinds = getattr(cfg, "layer_kinds", ()) or ()
    return bool(kinds) and all(k in ("global", "mamba") for k in kinds)


def carries_state(cfg) -> bool:
    """Whether the stack holds per-sequence state that a step rewrites in
    place (Mamba-2 layers): chunked prefill then runs non-overlapping spans
    with a padded tail, and a finished prefill's state is inserted into its
    slot in place."""
    return "mamba" in (getattr(cfg, "layer_kinds", ()) or ())


def supports_paged_kv(cfg) -> bool:
    """Whether this family serves through the paged KV cache.  Decoder-only
    transformer stacks thread the page table through their decode step; the
    enc-dec/VLM decoders do too (paged self-attn plus pooled encoder frames
    through ``xpage_table`` for enc-dec; the VLM decoder IS the transformer
    decode path).  Attention-free stacks (pure recurrent/xLSTM) have no
    positionally-addressed cache to page — the engine falls back to the
    contiguous per-slot cache for them."""
    if get_api(cfg) is _ENCDEC_API:
        return True
    kinds = getattr(cfg, "layer_kinds", ()) or ()
    return "global" in kinds


@functools.lru_cache(maxsize=None)
def state_bytes_per_step(cfg) -> float:
    """HBM bytes of NON-positional serving state read per decode step per
    sequence: recurrent/xLSTM summaries and the enc-dec cross-attention
    frames — everything the step streams in full regardless of context
    length.  Derived structurally: shape-probe the family's cache and sum
    the leaves whose registered axes carry no ``cache_seq`` dimension
    (those leaves don't grow with context, so the step reads all of them).
    Pure-attention stacks return 0.0 — their whole cache is the
    context-proportional stream ``kv_bytes_per_token`` charges."""
    api = get_api(cfg)
    cache = jax.eval_shape(functools.partial(
        api.init_cache, cfg, 1, 2, jnp.dtype(cfg.compute_dtype)))
    axes = api.cache_axes(cfg)
    total = 0.0
    for leaf, ax in zip(jax.tree.leaves(cache),
                        jax.tree.leaves(axes, is_leaf=lambda x:
                                        isinstance(x, tuple))):
        if "cache_seq" not in tuple(ax or ()):
            total += leaf.size * leaf.dtype.itemsize
    return float(total)


def kv_bytes_per_token(cfg, kv_dtype=None, context_len: int | None = None) -> float:
    """HBM bytes of cache/state read per decoded token per unit of context —
    the ``kv_bytes_per_token`` the perf model / BatchSizer charge.

    Attention layers are the context-proportional stream.  ``kv_dtype=
    jnp.int8`` accounts the quantized cache: 1-byte payloads plus one fp32
    scale per (token, head) for each of K and V.  ``context_len`` caps
    sliding-window (``local``) layers at their actual ring-buffer length
    ``cfg.local_window``, and folds in the per-step state stream
    (``state_bytes_per_step``: recurrent summaries, enc-dec frames) at
    ``state / context_len`` — in both cases the effective per-context-token
    rate is scaled so that rate * context_len == true bytes read per step.
    This is what lets one ``BatchSizer`` charge every family its own
    bytes/token in a mixed blend.
    """
    per_kv = cfg.n_kv_heads * cfg.hd
    if kv_dtype is not None and jnp.dtype(kv_dtype) == jnp.int8:
        per_layer = 2.0 * (per_kv * 1 + cfg.n_kv_heads * 4)
    else:
        per_layer = 2.0 * per_kv * jnp.dtype(cfg.compute_dtype).itemsize
    kinds = getattr(cfg, "layer_kinds", None)
    if kinds is None:
        total = float(cfg.n_layers * per_layer)
    else:
        total = 0.0
        for k in kinds:
            if k == "global":
                total += per_layer
            elif k == "local":
                frac = 1.0
                if context_len:
                    frac = min(context_len, cfg.local_window) / context_len
                total += per_layer * frac
    if context_len:
        total += state_bytes_per_step(cfg) / context_len
    return float(total)


# ---------------------------------------------------------------------------
# ShapeDtypeStruct input specs (dry-run)
# ---------------------------------------------------------------------------


def _extras_specs(cfg, api: ModelAPI, batch: int):
    out = {}
    if "patches" in api.extra_keys:
        out["patches"] = jax.ShapeDtypeStruct((batch, cfg.n_patches, cfg.d_model), jnp.float32)
    if "frames" in api.extra_keys:
        out["frames"] = jax.ShapeDtypeStruct((batch, cfg.n_frames, cfg.d_model), jnp.float32)
    return out


def input_specs(cfg, shape, mode: str | None = None) -> dict:
    """Stand-ins for the inputs of the step lowered for this shape cell.

    mode defaults to the cell's kind: train -> {"batch": ...};
    prefill -> {"batch": ..., "cache": ...};
    decode -> {"tokens", "pos", "cache"}.
    """
    api = get_api(cfg)
    mode = mode or shape.kind
    B, S = shape.global_batch, shape.seq_len
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
    if mode == "train":
        return {"batch": {"tokens": tok, "labels": tok, **_extras_specs(cfg, api, B)}}
    cache_dtype = jnp.dtype(cfg.compute_dtype)
    cache = jax.eval_shape(functools.partial(api.init_cache, cfg, B, S, cache_dtype))
    if mode == "prefill":
        return {"batch": {"tokens": tok, **_extras_specs(cfg, api, B)}, "cache": cache}
    if mode == "decode":
        return {
            "tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
            "pos": jax.ShapeDtypeStruct((B,), jnp.int32),
            "cache": cache,
        }
    raise ValueError(mode)
