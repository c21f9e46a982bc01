"""Plain reference of a hybrid stack of Mamba-2 and attention layers
(granite-4.0-h) as the configuration states it.

Float32 at ``highest`` matmul precision, no kernels, no cache, no paging, no
batching of requests: one sequence at a time over its whole length.  It
reads the benchmark's dense weights (``bench/weights.py``) and applies the
configuration's serving representation as ``reference/decoder.py`` does,
whose weight plan, int8 KV, causal attention, embedding and LM head it
uses.  Each layer is ``x + mixer(rms(x))``, then ``+ mlp(rms(.))`` with a
dense SwiGLU MLP after either mixer:

* attention (``global`` layers): grouped query heads, int8 K/V per (token,
  kv head), no positional embedding (``position_embedding`` ``nope``);
* Mamba-2: ``in_proj`` as its column blocks ``w_z``, ``w_xbc``, ``w_dt``; a
  causal depthwise conv of width ``mamba_d_conv`` with bias over (x, B, C),
  then SiLU; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the
  recurrence token by token (a ``lax.scan`` over positions)::

      state = exp(dt A) state + (dt x) outer B;   y = state . C + D x

  then ``rms(y * silu(z)) * (1 + norm)`` over the inner width and
  ``w_out``;
* RMSNorm as ``x / rms(x) * (1 + scale)``, eps ``norm_eps``.

``act_dtype`` rounds every matmul's activation operand to a lower precision
(the control, ``bench/control.py``).  Nothing of the program is imported.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.decoder import (
    Q_BLOCK,
    ROW_BLOCK,
    _attention,
    _embed,
    _kinds,
    _kv_int8,
    _rms,
    _round_act,
    _served,
    _unembed,
)


def _mlp(x, p, m, act):
    h = act(_rms(x, p["ln2"], m["norm_eps"]))
    return x + act(jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


@functools.partial(jax.jit, static_argnames=("model", "act_dtype"))
def _attn_layer(x, p, model, act_dtype):
    m = dict(model)
    H, KVH, hd = m["heads"], m["kv_heads"], m["head_dim"]
    S = x.shape[0]
    act = functools.partial(_round_act, act_dtype=act_dtype)
    h = act(_rms(x, p["ln1"], m["norm_eps"]))
    q = (h @ p["wq"]).reshape(S, H, hd)
    k = _kv_int8((h @ p["wk"]).reshape(S, KVH, hd))
    v = _kv_int8((h @ p["wv"]).reshape(S, KVH, hd))
    x = x + act(_attention(q, k, v).reshape(S, H * hd)) @ p["wo"]
    return _mlp(x, p, m, act)


def recurrence(x, dt, a, b, c, d):
    """The SSM token by token from a zero state: x (S, H, P), dt (S, H),
    a, d (H,), b, c (S, N).  Returns y (S, H, P)."""
    H, P = x.shape[1:]
    N = b.shape[1]

    def step(s, inp):
        xt, dtt, bt, ct = inp
        s = (jnp.exp(dtt * a)[:, None, None] * s
             + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :])
        return s, s @ ct + d[:, None] * xt

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32), (x, dt, b, c))
    return y


@functools.partial(jax.jit, static_argnames=("model", "act_dtype"))
def _mamba_layer(x, p, model, act_dtype):
    m = dict(model)
    H, P, N, K = (m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"],
                  m["mamba_d_conv"])
    di = H * P
    S = x.shape[0]
    act = functools.partial(_round_act, act_dtype=act_dtype)
    h = act(_rms(x, p["ln1"], m["norm_eps"]))
    z, xbc, dt = h @ p["w_z"], h @ p["w_xbc"], h @ p["w_dt"]
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    xbc = sum(padded[k:k + S] * p["conv_w"][k] for k in range(K)) + p["conv_b"]
    xbc = jax.nn.silu(xbc)
    xs, b, c = xbc[:, :di], xbc[:, di:di + N], xbc[:, di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(xs.reshape(S, H, P), dt, -jnp.exp(p["A_log"]), b, c, p["D"])
    g = y.reshape(S, di) * jax.nn.silu(z)
    g = _rms(g, p["norm"], m["norm_eps"])
    x = x + act(g) @ p["w_out"]
    return _mlp(x, p, m, act)


def _layers(params, plan: dict):
    """(kind, kinds of the served leaves, raw params) of every layer, in
    order: a unit's positions are stacked over its repeats, so layer order
    runs repeat by repeat, then through the remainder's runs."""
    unit = list(params["unit"])
    n = jax.tree.leaves(unit[0])[0].shape[0] if unit else 0
    kinds = [_kinds(g, plan) for g in unit]
    for u in range(n):
        for g, kd in zip(unit, kinds):
            yield "mamba" in g, kd, jax.tree.map(lambda a: a[u], g)
    for g in params["rem"]:
        kd = _kinds(g, plan)
        for i in range(jax.tree.leaves(g)[0].shape[0]):
            yield "mamba" in g, kd, jax.tree.map(lambda a: a[i], g)


def logits(model: dict, plan: dict, params, seqs, want, act_dtype=None):
    """Float32 logits of each sequence at its ``want`` positions.

    seqs: list of int token arrays; want: list of int position arrays (the
    row at position t predicts token t + 1).  Returns a list of
    (len(want_i), vocab) numpy arrays."""
    mkey = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()))
    pkey = tuple(sorted(plan.items()))
    emb = params["embed"]
    ekinds = dict(_kinds(emb, plan))
    with jax.default_matmul_precision("highest"):
        xs = []
        for s in seqs:
            S = len(s)
            ids = np.zeros(-(-S // Q_BLOCK) * Q_BLOCK, np.int32)
            ids[:S] = s
            xs.append(_embed(emb["tok"], jnp.asarray(ids), ekinds["tok"]))
        for mamba, kinds, raw in _layers(params, plan):
            p = _served(raw, kinds, pkey)
            layer = _mamba_layer if mamba else _attn_layer
            xs = [layer(x, p, mkey, act_dtype) for x in xs]
            del p
        out = []
        for x, w in zip(xs, want):
            n = len(w)
            rows = np.full(-(-n // ROW_BLOCK) * ROW_BLOCK, w[-1], np.int32)
            rows[:n] = w
            h = _rms(x[jnp.asarray(rows)], params["final_norm"]["scale"],
                     model["norm_eps"])
            out.append(np.asarray(
                _unembed(_round_act(h, act_dtype), emb, ekinds))[:n])
    return out
