"""Plain reference of a decoder-only transformer as the configuration states it.

Float32 at ``highest`` matmul precision, no kernels, no paging, no batching
of requests: one sequence at a time, whole-sequence causal attention.  It
reads the benchmark's dense weights (``bench/weights.py``) and applies the
configuration's serving representation itself:

* weight plan (``plan`` in the configuration file): a matmul weight named
  ``w*`` whose dims tile by ``block`` keeps the ``1 - q_prune`` share of its
  blocks with the largest mean magnitude (per matrix, per layer and expert)
  and is quantized to int8 with one scale per output column over the kept
  entries (``max|w| / 127``, round to nearest); the embedding table and the
  LM head are quantized to int8 the same way over their contraction axis;
  other weights (norm scales, the router) stay as made;
* KV cache: int8 with one scale per (token, kv head), ``max|x| / 127``;
* RMSNorm as ``x / rms(x) * (1 + scale)``, eps from the configuration;
  rotary embedding over the whole head (``rotate_half`` pairing); grouped
  query heads: q head ``h`` reads kv head ``h // (heads / kv_heads)``;
* MoE: softmax over all experts, top-k, the k gates renormalized to sum 1,
  no capacity limit (no token is dropped).

``act_dtype`` rounds every matmul's activation operand to a lower precision
(the control: ``float8_e4m3fn``).  Nothing of the program is imported.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512  # query rows per attention block (bounds the score tensor)
ROW_BLOCK = 128  # logit rows are computed in multiples of this


def _round_act(x, act_dtype):
    return x if act_dtype is None else x.astype(act_dtype).astype(jnp.float32)


def _quant_cols(w):
    """int8 round trip with one scale per column over axis -2."""
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    s = jnp.maximum(amax, 1e-8) / 127.0
    return jnp.clip(jnp.round(w / s), -127, 127) * s


def _prune_quant(w, q_prune: float, block: int):
    """Block-prune one (K, N) matrix by mean |w| per block, then int8."""
    K, N = w.shape
    blocks = jnp.abs(w).reshape(K // block, block, N // block, block)
    score = blocks.mean(axis=(1, 3)).reshape(-1)
    k = int(round(q_prune * score.size))
    if k:
        delta = jnp.sort(score)[k - 1]
        keep = (score > delta).reshape(K // block, 1, N // block, 1)
        w = (w.reshape(K // block, block, N // block, block) * keep).reshape(K, N)
    return _quant_cols(w)


def serve_kind(name: str, shape, plan: dict) -> str:
    """"sparse", "quant" or "dense": how the plan serves a weight, judged on
    the whole (layer-stacked) leaf as the configuration's plan states."""
    if len(shape) < 2:
        return "dense"
    K, N = shape[-2], shape[-1]
    if not (math.prod(shape) >= plan["min_size"] and K >= plan["min_contract"]
            and (name.startswith("w") or name in ("tok", "head"))):
        return "dense"
    if name.startswith("w") and K % plan["block"] == 0 and N % plan["block"] == 0:
        return "sparse"
    return "quant"


def serve_weight(kind: str, w, plan: dict):
    """A weight as the configuration's plan serves it (float32)."""
    w = jnp.asarray(w, jnp.float32)
    if kind == "dense":
        return w
    if kind == "quant":
        return _quant_cols(w)
    fn = functools.partial(_prune_quant, q_prune=plan["q_prune"],
                           block=plan["block"])
    for _ in range(w.ndim - 2):
        fn = jax.vmap(fn)
    return fn(w)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + scale)


def _rope(x, theta):
    """x (S, H, hd) at positions 0..S-1."""
    S, _, hd = x.shape
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _kv_int8(x):
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    s = jnp.maximum(amax, 1e-8) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _attention(q, k, v):
    """Causal attention, q (S, H, hd), k/v (S, KVH, hd), in query blocks."""
    S, H, hd = q.shape
    G = H // k.shape[1]
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    nb = S // Q_BLOCK
    qb = q.reshape(nb, Q_BLOCK, H, hd)

    def one(args):
        i, qi = args
        s = jnp.einsum("qhd,khd->hqk", qi, k) / math.sqrt(hd)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(jnp.arange(S)[None, None] <= qpos[None, :, None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    return jax.lax.map(one, (jnp.arange(nb), qb)).reshape(S, H, hd)


@functools.partial(jax.jit, static_argnames=("model", "act_dtype"))
def _layer(x, p, model, act_dtype):
    """One decoder layer on x (S, d); ``p`` holds served float32 weights."""
    m = dict(model)
    H, KVH, hd = m["heads"], m["kv_heads"], m["head_dim"]
    S = x.shape[0]
    act = functools.partial(_round_act, act_dtype=act_dtype)
    h = act(_rms(x, p["ln1"], m["norm_eps"]))
    q = (h @ p["wq"]).reshape(S, H, hd)
    k = (h @ p["wk"]).reshape(S, KVH, hd)
    v = (h @ p["wv"]).reshape(S, KVH, hd)
    q = _rope(q, m["rope_theta"])
    k = _kv_int8(_rope(k, m["rope_theta"]))
    v = _kv_int8(v)
    o = act(_attention(q, k, v).reshape(S, H * hd))
    x = x + o @ p["wo"]
    h = act(_rms(x, p["ln2"], m["norm_eps"]))
    if m["experts"]:
        probs = jax.nn.softmax(h @ p["router"], axis=-1)
        gate, idx = jax.lax.top_k(probs, m["top_k"])
        gate = gate / gate.sum(-1, keepdims=True)
        g = jnp.einsum("sd,edf->esf", h, p["w_gate"])
        u = jnp.einsum("sd,edf->esf", h, p["w_up"])
        y = jnp.einsum("esf,efd->esd", act(jax.nn.silu(g) * u), p["w_down"])
        w = jnp.zeros((S, m["experts"])).at[jnp.arange(S)[:, None], idx].add(gate)
        f = jnp.einsum("se,esd->sd", w, y)
    else:
        f = act(jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]
    return x + f


@functools.partial(jax.jit, static_argnames=("kinds", "plan"))
def _served(raw, kinds, plan):
    """Served float32 weights of one layer (or of the embedding), keyed by
    leaf name; ``kinds`` maps each leaf name to its representation."""
    pl, kd = dict(plan), dict(kinds)
    leaves = {}
    for path, w in jax.tree_util.tree_flatten_with_path(raw)[0]:
        names = [str(getattr(k, "key", k)) for k in path]
        name = names[-1] if names[-1] != "scale" else names[-2]
        leaves[name] = serve_weight(kd.get(name, "dense"), w, pl)
    return leaves


def _kinds(tree, plan: dict) -> tuple:
    out = {}
    for path, w in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = str(getattr(path[-1], "key", path[-1]))
        out[name] = serve_kind(name, tuple(w.shape), plan)
    return tuple(sorted(out.items()))


def _layers(params, plan: dict):
    """(kinds, per-layer params) for every layer, in order."""
    for group in list(params["unit"]) + list(params["rem"]):
        kinds = _kinds(group, plan)
        n = jax.tree.leaves(group)[0].shape[0]
        for i in range(n):
            yield kinds, jax.tree.map(lambda a: a[i], group)


V_BLOCK = 16384  # vocabulary entries per block of the LM head


@functools.partial(jax.jit, static_argnames=("kind",))
def _embed(tok, ids, kind):
    """Embedding rows of ``ids`` as served: an int8 table has one scale per
    column over the whole vocabulary."""
    rows = tok[ids].astype(jnp.float32)
    if kind == "dense":
        return rows
    s = jnp.maximum(jnp.max(jnp.abs(tok), axis=0).astype(jnp.float32), 1e-8) / 127.0
    return jnp.clip(jnp.round(rows / s), -127, 127) * s


@functools.partial(jax.jit, static_argnames=("kind", "tied"))
def _head_block(h, w, kind, tied):
    """Logits of ``h`` over one block of the vocabulary.  ``w`` is that
    block of the head (d, n), or of the tied table (n, d) with ``scale``
    per column over the whole table folded in by the caller."""
    w = w.astype(jnp.float32)
    if tied:
        return h @ w.T
    if kind != "dense":
        w = _quant_cols(w)
    return h @ w


def _unembed(h, emb: dict, kinds: dict):
    """float32 logits (rows, vocab), the vocabulary in blocks so that no
    whole float32 copy of the head lives on the device."""
    if "head" in emb:
        w, tied = emb["head"], False
        vocab = w.shape[1]
        block = lambda a, b: w[:, a:b]
    else:
        tok, tied = emb["tok"], True
        vocab = tok.shape[0]
        if kinds["tok"] != "dense":
            s = jnp.maximum(jnp.max(jnp.abs(tok), axis=0).astype(jnp.float32),
                            1e-8) / 127.0
            block = lambda a, b: jnp.clip(
                jnp.round(tok[a:b].astype(jnp.float32) / s), -127, 127) * s
        else:
            block = lambda a, b: tok[a:b]
    out = [_head_block(h, block(a, min(a + V_BLOCK, vocab)), kinds.get("head"), tied)
           for a in range(0, vocab, V_BLOCK)]
    return jnp.concatenate(out, axis=1)


def logits(model: dict, plan: dict, params, seqs, want, act_dtype=None):
    """Float32 logits of each sequence at its ``want`` positions.

    seqs: list of int token arrays; want: list of int position arrays (the
    row at position t predicts token t + 1).  Returns a list of
    (len(want_i), vocab) numpy arrays."""
    mkey = tuple(sorted(model.items()))
    pkey = tuple(sorted(plan.items()))
    emb = params["embed"]
    ekinds = dict(_kinds(emb, plan))
    with jax.default_matmul_precision("highest"):
        xs = []
        for s in seqs:
            S = len(s)
            Sp = -(-S // Q_BLOCK) * Q_BLOCK
            ids = np.zeros(Sp, np.int32)
            ids[:S] = s
            xs.append(_embed(emb["tok"], jnp.asarray(ids), ekinds["tok"]))
        for kinds, raw in _layers(params, plan):
            p = _served(raw, kinds, pkey)
            xs = [_layer(x, p, mkey, act_dtype) for x in xs]
            del p
        out = []
        for x, w in zip(xs, want):
            # rows padded to a multiple of ROW_BLOCK (the last row repeated)
            # so that the programs' shapes recur from run to run
            n = len(w)
            rows = np.full(-(-n // ROW_BLOCK) * ROW_BLOCK, w[-1], np.int32)
            rows[:n] = w
            h = _rms(x[jnp.asarray(rows)], params["final_norm"]["scale"],
                     model["norm_eps"])
            out.append(np.asarray(
                _unembed(_round_act(h, act_dtype), emb, ekinds))[:n])
    return out
