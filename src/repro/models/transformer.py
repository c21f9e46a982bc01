"""Decoder-only LM over heterogeneous layer kinds, with unit-scan compile
discipline.

A model is a sequence of layer *kinds* (``cfg.layer_kinds``): ``global`` /
``local`` attention layers (with dense-MLP or MoE FFN), ``rec`` (RG-LRU)
blocks, ``mamba`` (Mamba-2 mixer + dense MLP) blocks, ``mlstm`` / ``slstm``
xLSTM blocks.  The kind sequence is factored
into its smallest repeating *unit*; parameters for each unit position are
stacked across units and the forward pass is a single ``jax.lax.scan`` over
units (plus an unrolled remainder).  HLO size is therefore O(unit), not
O(depth) — the compile-time discipline that keeps 512-device lowering cheap
even for 40-layer models.

Three execution modes share one layer implementation:
  - ``train``:   full-sequence, no cache, returns MoE aux losses;
  - ``prefill``: full-sequence, writes the KV cache / recurrent states;
  - ``decode``:  one token per sequence against the cache (ring-buffer
                 semantics for sliding-window layers).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.weight_plan import apply_linear
from repro.distributed import shardlib as sl
from repro.models import layers as L
from repro.models import mamba2 as MB
from repro.models import moe as M
from repro.models import recurrent as R
from repro.models import ssm as S

ATTN_KINDS = ("global", "local")


# ---------------------------------------------------------------------------
# unit factorization
# ---------------------------------------------------------------------------


def find_unit(kinds: tuple) -> tuple:
    """Factor a kind sequence into (unit, n_units, remainder): the prefix is
    n_units repetitions of `unit`, the tail is `remainder`.  Picks the period
    with maximal scanned coverage (ties -> shortest unit)."""
    Ln = len(kinds)

    def cost(unit, rem):
        # distinct layer bodies in the HLO: unit positions + remainder runs
        return len(unit) + len(rem_runs(rem))

    best = (kinds, 1, ())  # fallback: whole thing is one unit
    best_cost = cost(kinds, ())
    for p in range(1, min(Ln, 12) + 1):
        unit = kinds[:p]
        k = 0
        while (k + 1) * p <= Ln and kinds[k * p : (k + 1) * p] == unit:
            k += 1
        if k < 1:
            continue
        rem = kinds[p * k:]
        c = cost(unit, rem)
        if c < best_cost or (c == best_cost and p * k > len(best[0]) * best[1]):
            best = (unit, k, rem)
            best_cost = c
    return best


def rem_runs(rem: tuple) -> list:
    """Group the remainder into (kind, count) runs — each run is scanned so
    the remainder, too, costs O(1) HLO (gemma3's 4-local tail would
    otherwise unroll four flash-attention bodies)."""
    runs = []
    for kind in rem:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return [(k, c) for k, c in runs]


# ---------------------------------------------------------------------------
# one layer (by kind)
# ---------------------------------------------------------------------------


def init_layer(cfg, kind: str, key):
    ks = jax.random.split(key, 4)
    if kind in ATTN_KINDS:
        p = {
            "ln1": L.init_norm(cfg.d_model, cfg.norm),
            "attn": L.init_attn(cfg, ks[0]),
            "ln2": L.init_norm(cfg.d_model, cfg.norm),
        }
        if cfg.moe is not None:
            p["moe"] = M.init_moe(cfg, ks[1])
        else:
            p["mlp"] = L.init_mlp(cfg, ks[1])
        return p
    if kind == "rec":
        return {
            "ln1": L.init_norm(cfg.d_model, cfg.norm),
            "rec": R.init_rglru(cfg, ks[0]),
            "ln2": L.init_norm(cfg.d_model, cfg.norm),
            "mlp": L.init_mlp(cfg, ks[1]),
        }
    if kind == "mamba":
        return {
            "ln1": L.init_norm(cfg.d_model, cfg.norm),
            "mamba": MB.init_mamba2(cfg, ks[0]),
            "ln2": L.init_norm(cfg.d_model, cfg.norm),
            "mlp": L.init_mlp(cfg, ks[1]),
        }
    if kind == "mlstm":
        return {"ln": L.init_norm(cfg.d_model, cfg.norm), "cell": S.init_mlstm(cfg, ks[0])}
    if kind == "slstm":
        return {"ln": L.init_norm(cfg.d_model, cfg.norm), "cell": S.init_slstm(cfg, ks[0])}
    raise ValueError(kind)


def layer_axes(cfg, kind: str):
    na = L.norm_axes(cfg.norm)
    if kind in ATTN_KINDS:
        a = {"ln1": na, "attn": L.attn_axes(), "ln2": na}
        if cfg.moe is not None:
            a["moe"] = M.moe_axes(cfg)
        else:
            a["mlp"] = L.mlp_axes(cfg)
        return a
    if kind == "rec":
        return {"ln1": na, "rec": R.rglru_axes(), "ln2": na, "mlp": L.mlp_axes(cfg)}
    if kind == "mamba":
        return {"ln1": na, "mamba": MB.mamba2_axes(), "ln2": na,
                "mlp": L.mlp_axes(cfg)}
    if kind == "mlstm":
        return {"ln": na, "cell": S.mlstm_axes(cfg)}
    if kind == "slstm":
        return {"ln": na, "cell": S.slstm_axes(cfg)}
    raise ValueError(kind)


def init_layer_cache(cfg, kind: str, batch: int, length: int, dtype=jnp.bfloat16,
                     kv_dtype=None, page_size=None, num_pages=None, spec_k=0):
    """``kv_dtype`` overrides the dtype of *attention* KV caches only
    (``jnp.int8`` selects the quantized cache); recurrent/xLSTM states are
    numerical integrators and always keep the compute dtype.

    ``page_size``/``num_pages`` select the paged cache for ``global``
    attention layers: a pool of pages shared by all sequences instead of a
    per-slot ``length`` reservation.  ``local`` layers keep their
    contiguous ring buffer — the window already bounds them at O(window),
    which is exactly what paging would buy.

    ``spec_k`` (speculative decode, serving/engine.py) widens the
    sliding-window ring to ``local_window + spec_k``: a verify step writes
    k+1 consecutive positions before attending, so a ring of exactly
    ``window`` length would have the newest draft entries clobber the
    oldest positions the earliest verify query still needs.  The extra k
    slots hold the speculative tail; ``decode_attention``'s absolute-
    position masking keeps rejected entries invisible until the next
    verify step overwrites them."""
    if kind in ATTN_KINDS:
        if page_size is not None and kind == "global":
            return L.init_paged_attn_cache(
                cfg, num_pages, page_size, kv_dtype if kv_dtype is not None else dtype
            )
        ln = min(length, cfg.local_window + spec_k) if kind == "local" else length
        return L.init_attn_cache(cfg, batch, ln, kv_dtype if kv_dtype is not None else dtype)
    if kind == "rec":
        return R.init_rglru_state(cfg, batch, dtype)
    if kind == "mamba":
        return MB.init_conv_state(cfg, batch, dtype)
    if kind == "mlstm":
        return S.init_mlstm_state(cfg, batch, dtype)
    if kind == "slstm":
        return S.init_slstm_state(cfg, batch, dtype)
    raise ValueError(kind)


def layer_cache_axes(kind: str, quantized_kv: bool = False, paged: bool = False):
    if kind in ATTN_KINDS:
        if paged and kind == "global":
            return L.paged_attn_cache_axes(quantized_kv)
        return L.attn_cache_axes(quantized_kv)
    if kind == "rec":
        return R.rglru_state_axes()
    if kind == "mamba":
        return MB.conv_state_axes()
    if kind == "mlstm":
        return S.mlstm_state_axes()
    if kind == "slstm":
        return S.slstm_state_axes()
    raise ValueError(kind)


def apply_layer(cfg, kind: str, p, x, *, mode: str, cache=None, pos=None,
                page_table=None, layer=None, valid=None):
    """Returns (x, new_cache, aux).  A ``mamba`` layer's cache also holds
    the stack's SSM state (``"ssm"``), of which it updates entry ``layer``;
    ``valid`` (B, T) marks the real positions of a padded span."""
    aux = jnp.zeros((), jnp.float32)
    eps = cfg.rms_norm_eps
    if kind in ATTN_KINDS:
        base = (
            cfg.rope_base_global
            if (kind == "global" and cfg.rope_base_global) else cfg.rope_base
        )
        h = L.apply_norm(p["ln1"], x, cfg.norm, eps)
        # sequence-parallel boundary: blocks consume seq-replicated
        # activations (one all-gather here when seq_sp -> model) and emit
        # seq-sharded ones (reduce-scatter at the block-output constraint).
        # Without the explicit pin, GSPMD runs the chunked flash attention
        # on seq-sharded operands and falls into involuntary full
        # rematerialization (measured 2x regression on qwen2-moe).
        h = sl.shard_pinned(h, "batch", "seq", None)
        if mode == "decode":
            a, cache_a = L.apply_attn(
                cfg, p["attn"], h, kind=kind, rope_base=base, cache=cache, pos=pos,
                page_table=page_table,
            )
        elif mode == "prefill":
            a, cache_a = _attn_prefill(cfg, p["attn"], h, kind, base, cache)
        else:
            a, cache_a = L.apply_attn(cfg, p["attn"], h, kind=kind, rope_base=base)
        x = x + a
        h = L.apply_norm(p["ln2"], x, cfg.norm, eps)
        h = sl.shard_pinned(h, "batch", "seq", None)
        if cfg.moe is not None:
            if mode == "train":
                f, aux = M.apply_moe(cfg, p["moe"], h, return_aux=True)
            else:
                f = M.apply_moe(cfg, p["moe"], h)
        else:
            f = L.apply_mlp(cfg, p["mlp"], h)
        return x + f, cache_a, aux
    if kind == "rec":
        h = L.apply_norm(p["ln1"], x, cfg.norm, eps)
        y, new_state = R.apply_rglru(cfg, p["rec"], h, cache)
        x = x + y
        h = L.apply_norm(p["ln2"], x, cfg.norm, eps)
        return x + L.apply_mlp(cfg, p["mlp"], h), new_state, aux
    if kind == "mamba":
        h = L.apply_norm(p["ln1"], x, cfg.norm, eps)
        y, new_state = MB.apply_mamba2(cfg, p["mamba"], h, cache, layer=layer,
                                       valid=valid)
        x = x + y
        h = L.apply_norm(p["ln2"], x, cfg.norm, eps)
        return x + L.apply_mlp(cfg, p["mlp"], h), new_state, aux
    if kind == "mlstm":
        h = L.apply_norm(p["ln"], x, cfg.norm, eps)
        y, new_state = S.apply_mlstm(cfg, p["cell"], h, cache)
        return x + y, new_state, aux
    if kind == "slstm":
        h = L.apply_norm(p["ln"], x, cfg.norm, eps)
        y, new_state = S.apply_slstm(cfg, p["cell"], h, cache)
        return x + y, new_state, aux
    raise ValueError(kind)


def _attn_prefill(cfg, p, h, kind, base, cache):
    """Full-sequence attention that also fills the KV cache.

    For a ``local`` layer the cache is a ring buffer of window length; the
    last `window` positions land in their pos % window slots.
    """
    B, Sq, _ = h.shape
    window = cfg.local_window if kind == "local" else None
    dt = h.dtype
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = apply_linear(h, p["wq"]).reshape(B, Sq, H, hd)
    k = apply_linear(h, p["wk"]).reshape(B, Sq, KVH, hd)
    v = apply_linear(h, p["wv"]).reshape(B, Sq, KVH, hd)
    if cfg.position_embedding != "nope":
        positions = jnp.broadcast_to(jnp.arange(Sq)[None], (B, Sq))
        q = L.apply_rope(q, positions, base)
        k = L.apply_rope(k, positions, base)
    o = L.attention(q, k, v, causal=True, window=window, softcap=cfg.logit_softcap)
    out = apply_linear(o.reshape(B, Sq, H * hd), p["wo"])
    Sc = cache["k"].shape[1]

    def fill(c, new):
        if Sc >= Sq:
            return jax.lax.dynamic_update_slice_in_dim(c, new.astype(c.dtype), 0, 1)
        # ring buffer: keep the last Sc positions, rolled so slot = pos % Sc
        return jnp.roll(new[:, -Sc:], Sq % Sc, axis=1).astype(c.dtype)

    new_cache = {}
    if "k_scale" in cache:
        # int8 cache: quantize the whole prefill K/V per (token, head)
        k, ks = L.quantize_kv(k)
        v, vs = L.quantize_kv(v)
        new_cache["k_scale"] = sl.shard_pinned(
            fill(cache["k_scale"], ks), *sl.axes_for("attn.kv_scale"))
        new_cache["v_scale"] = sl.shard_pinned(
            fill(cache["v_scale"], vs), *sl.axes_for("attn.kv_scale"))
    kc = sl.shard_pinned(fill(cache["k"], k), *sl.axes_for("attn.kv"))
    vc = sl.shard_pinned(fill(cache["v"], v), *sl.axes_for("attn.kv"))
    new_cache.update(k=kc, v=vc)
    return sl.shard(out, "batch", "seq_sp", None), new_cache


# ---------------------------------------------------------------------------
# whole model
# ---------------------------------------------------------------------------


def init_params(cfg, key):
    kinds = cfg.layer_kinds
    unit, n_units, rem = find_unit(kinds)
    k_embed, k_layers, k_rem = jax.random.split(key, 3)
    params = {
        "embed": L.init_embed(cfg, k_embed),
        "final_norm": L.init_norm(cfg.d_model, cfg.norm),
        "unit": [],
        "rem": [],
    }
    for pi, kind in enumerate(unit):
        keys = jax.random.split(jax.random.fold_in(k_layers, pi), n_units)
        params["unit"].append(jax.vmap(lambda k: init_layer(cfg, kind, k))(keys))
    for ri, (kind, count) in enumerate(rem_runs(rem)):
        keys = jax.random.split(jax.random.fold_in(k_rem, ri), count)
        params["rem"].append(jax.vmap(lambda k: init_layer(cfg, kind, k))(keys))
    return params


def param_axes(cfg):
    """Pytree of logical-axis tuples matching init_params.  Stacked unit
    params get a leading None (the unit axis is never sharded)."""
    unit, n_units, rem = find_unit(cfg.layer_kinds)

    def stack_axes(tree):
        return jax.tree.map(lambda ax: (None,) + tuple(ax), tree,
                            is_leaf=lambda x: isinstance(x, tuple))

    return {
        "embed": L.embed_axes(cfg),
        "final_norm": L.norm_axes(cfg.norm),
        "unit": [stack_axes(layer_axes(cfg, k)) for k in unit],
        "rem": [stack_axes(layer_axes(cfg, k)) for k, _ in rem_runs(rem)],
    }


def init_cache(cfg, batch: int, length: int, dtype=jnp.bfloat16, kv_dtype=None,
               page_size=None, num_pages=None, spec_k=0):
    """``page_size``/``num_pages`` select the paged KV cache: global-attention
    layers get per-layer page pools (no batch axis) and the returned dict
    carries a ``page_table`` leaf (batch, ceil(length / page_size)) int32 —
    part of the cache pytree so ``decode_step`` keeps its signature and one
    compiled step.  The table is owned by the serving engine (host-side
    allocator); the model only reads it.  ``spec_k`` widens sliding-window
    rings for speculative decode (see ``init_layer_cache``)."""
    unit, n_units, rem = find_unit(cfg.layer_kinds)
    cache = {"unit": [], "rem": []}
    for kind in unit:
        one = init_layer_cache(cfg, kind, batch, length, dtype, kv_dtype,
                               page_size, num_pages, spec_k)
        cache["unit"].append(
            jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n_units,) + x.shape), one)
        )
    for kind, count in rem_runs(rem):
        one = init_layer_cache(cfg, kind, batch, length, dtype, kv_dtype,
                               page_size, num_pages, spec_k)
        cache["rem"].append(
            jax.tree.map(lambda x: jnp.broadcast_to(x[None], (count,) + x.shape), one)
        )
    if page_size is not None:
        pages_per_seq = -(-length // page_size)
        cache["page_table"] = jnp.zeros((batch, pages_per_seq), jnp.int32)
    n_mamba = cfg.layer_kinds.count("mamba")
    if n_mamba:
        # one leaf for every Mamba layer's SSM state (models/mamba2.py)
        cache["ssm"] = MB.init_ssm_state(cfg, n_mamba, batch)
    return cache


# the page table is owned host-side per replica (serving/paged.py): batch
# rides the data axes, the logical-page axis is never sharded — every chip
# in a model group resolves the same slot -> physical-page mapping.
_PAGE_TABLE_AXES = sl.register_cache_kind(
    "page_table", ("batch", None), positional=True, paged=True)


def cache_axes(cfg, quantized_kv: bool = False, paged: bool = False):
    unit, n_units, rem = find_unit(cfg.layer_kinds)

    def stack_axes(tree):
        return jax.tree.map(lambda ax: (None,) + tuple(ax), tree,
                            is_leaf=lambda x: isinstance(x, tuple))

    axes = {
        "unit": [stack_axes(layer_cache_axes(k, quantized_kv, paged)) for k in unit],
        "rem": [stack_axes(layer_cache_axes(k, quantized_kv, paged))
                for k, _ in rem_runs(rem)],
    }
    if paged:
        axes["page_table"] = _PAGE_TABLE_AXES
    if "mamba" in cfg.layer_kinds:
        axes["ssm"] = MB.SSM_AXES
    return axes


def _run_layers(cfg, params, x, *, mode: str, cache=None, pos=None,
                valid=None):
    """Scan the unit stack, then the remainder.  Returns (x, new_cache, aux).

    A paged cache carries its ``page_table`` alongside the layer caches; it
    is read-only inside the step (the engine owns allocation), so it rides
    into the scan bodies as a closure constant and is reattached to the
    returned cache unchanged.

    A stack with Mamba layers carries their stacked SSM state (``"ssm"``)
    through the scans' carry, not their per-layer inputs: every Mamba layer
    updates its own entry of the one buffer in place (index: unit iteration
    times the unit's Mamba layers, plus the layer's ordinal in the unit)."""
    unit, n_units, rem = find_unit(cfg.layer_kinds)
    page_table = cache.get("page_table") if cache is not None else None
    ssm = cache.get("ssm") if cache is not None else None

    remat = mode == "train" and getattr(cfg, "remat", False)

    def one_layer(kind, p, x):
        return apply_layer(cfg, kind, p, x, mode=mode, cache=None, pos=None)

    def stateful(kind, p, x, c, ssm, layer):
        """One Mamba layer with the stack's state: (x, conv cache, aux, ssm)."""
        x, nc, a = apply_layer(cfg, kind, p, x, mode=mode,
                               cache={**c, "ssm": ssm}, layer=layer,
                               valid=valid)
        nc = dict(nc)
        return x, nc, a, nc.pop("ssm")

    m_unit = [unit[:pi].count("mamba") for pi in range(len(unit))]
    n_mu = unit.count("mamba")

    def unit_body(carry, xs):
        if ssm is None:
            (x, aux), (layer_ps, layer_cs) = carry, xs
        else:
            (x, aux, st), (layer_ps, layer_cs, it) = carry, xs
        new_cs = []
        for pi, kind in enumerate(unit):
            c = layer_cs[pi] if layer_cs is not None else None
            if remat:
                x, nc, a = jax.checkpoint(
                    functools.partial(one_layer, kind), static_argnums=()
                )(layer_ps[pi], x)
            elif kind == "mamba" and ssm is not None:
                x, nc, a, st = stateful(kind, layer_ps[pi], x, c, st,
                                        it * n_mu + m_unit[pi])
            else:
                x, nc, a = apply_layer(cfg, kind, layer_ps[pi], x, mode=mode, cache=c,
                                       pos=pos, page_table=page_table, valid=valid)
            new_cs.append(nc)
            aux = aux + a
        new_carry = (x, aux) if ssm is None else (x, aux, st)
        return new_carry, tuple(new_cs) if cache is not None else None

    xs = (params["unit"], tuple(cache["unit"]) if cache is not None else None)
    carry = (x, jnp.zeros((), jnp.float32))
    if ssm is not None:
        xs = xs + (jnp.arange(n_units),)
        carry = carry + (ssm,)
    carry, unit_caches = jax.lax.scan(unit_body, carry, xs)
    x, aux = carry[:2]
    if ssm is not None:
        ssm = carry[2]
    m_base = n_units * n_mu
    rem_caches = []
    for ri, (kind, count) in enumerate(rem_runs(rem)):
        def run_body(carry, xs_r, kind=kind, m_base=m_base):
            if ssm is None or kind != "mamba":
                x, aux = carry[:2]
                p_r, c_r = xs_r[:2]
                if remat:
                    x, nc, a = jax.checkpoint(functools.partial(one_layer, kind))(p_r, x)
                else:
                    x, nc, a = apply_layer(cfg, kind, p_r, x, mode=mode, cache=c_r,
                                           pos=pos, page_table=page_table,
                                           valid=valid)
                return (x, aux + a) + carry[2:], nc
            (x, aux, st), (p_r, c_r, j) = carry, xs_r
            x, nc, a, st = stateful(kind, p_r, x, c_r, st, m_base + j)
            return (x, aux + a, st), nc

        xs_r = (params["rem"][ri], cache["rem"][ri] if cache is not None else None)
        carry = (x, aux)
        if ssm is not None:
            xs_r = xs_r + (jnp.arange(count),)
            carry = carry + (ssm,)
        carry, nc = jax.lax.scan(run_body, carry, xs_r)
        x, aux = carry[:2]
        if ssm is not None:
            ssm = carry[2]
        if kind == "mamba":
            m_base += count
        rem_caches.append(nc)
    if cache is None:
        return x, None, aux
    new_cache = {"unit": list(unit_caches), "rem": rem_caches}
    if page_table is not None:
        new_cache["page_table"] = page_table
    if ssm is not None:
        new_cache["ssm"] = ssm
    return x, new_cache, aux


def forward(cfg, params, tokens, extra_embeds: Optional[jax.Array] = None):
    """Training/eval forward: logits over the full sequence.

    extra_embeds: (B, P, d) precomputed frontend embeddings (VLM patches),
    prepended to the token embeddings.
    """
    x = L.embed_tokens(cfg, params["embed"], tokens)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
        x = sl.shard(x, "batch", "seq_sp", None)
    x, _, aux = _run_layers(cfg, params, x, mode="train")
    x = L.apply_norm(params["final_norm"], x, cfg.norm, cfg.rms_norm_eps)
    logits = L.unembed(cfg, params["embed"], x)
    if extra_embeds is not None:
        logits = logits[:, extra_embeds.shape[1]:]
    return logits, aux


def prefill(cfg, params, tokens, cache, extra_embeds: Optional[jax.Array] = None):
    """Serving prefill: returns (last-position logits, filled cache)."""
    x = L.embed_tokens(cfg, params["embed"], tokens)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    x, cache, _ = _run_layers(cfg, params, x, mode="prefill", cache=cache)
    x = L.apply_norm(params["final_norm"], x[:, -1:], cfg.norm, cfg.rms_norm_eps)
    logits = L.unembed(cfg, params["embed"], x)
    return logits, cache


def decode_step(cfg, params, cache, tokens, pos):
    """One decode step over T new tokens per sequence.

    tokens: (B, T) int32; pos: (B,) absolute position of tokens[:, 0].
    T=1 is the classic one-token step; T=k+1 is the speculative *verify*
    step: the cache scatters all T positions and every query attends with
    per-position causal masking, so one weight stream serves all T draft
    positions (the paper's batch-processing amortization along the token
    axis).  Returns logits (B, T, vocab) — logits[:, t] predicts the token
    after tokens[:, t].

    In a stack that carries state (Mamba layers), a negative token marks a
    padded position at the end of a span (the engine pads a chunked
    prefill's tail to the chunk width): it changes no state, and its logits
    are not read.  Its KV lands past the span's last real position, where
    the next decode step's write replaces it before any query can see it."""
    valid = None
    if "mamba" in cfg.layer_kinds:
        valid = tokens >= 0
        tokens = jnp.maximum(tokens, 0)
    x = L.embed_tokens(cfg, params["embed"], tokens)
    x, cache, _ = _run_layers(cfg, params, x, mode="decode", cache=cache, pos=pos,
                              valid=valid)
    x = L.apply_norm(params["final_norm"], x, cfg.norm, cfg.rms_norm_eps)
    logits = L.unembed(cfg, params["embed"], x)
    return logits, cache


def loss_fn(cfg, params, batch, extra_embeds=None):
    """Next-token cross entropy.  batch: {"tokens": (B,S), "labels": (B,S)}
    labels < 0 are masked out."""
    logits, aux = forward(cfg, params, batch["tokens"], extra_embeds)
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    lab = jnp.maximum(labels, 0)
    # Fusion-friendly NLL: never materializes a second (B, S, V) buffer —
    # both the logsumexp and the label pick are reductions XLA fuses with
    # the dtype converts, which matters at vocab=262k with sharded logits.
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    V = logits.shape[-1]
    label_logit = jnp.sum(
        jnp.where(jnp.arange(V)[None, None, :] == lab[..., None], lf, 0.0), axis=-1
    )
    nll = lse - label_logit
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss + aux, {"loss": loss, "aux": aux}


def n_params_exact(cfg) -> int:
    """Exact parameter count via eval_shape (no allocation)."""
    shapes = jax.eval_shape(functools.partial(init_params, cfg), jax.random.key(0))
    return int(sum(x.size for x in jax.tree.leaves(shapes)))
