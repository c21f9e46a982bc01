"""Mamba-2 mixer (SSD, arXiv:2405.21060), as granite-4.0-h uses it.

    z | xBC | dt = h @ in_proj          (d -> d_inner | d_inner + 2N | H)
    xBC = silu(causal depthwise conv(xBC) + bias)       (width d_conv)
    x | B | C = xBC                     (one group: B, C shared by all heads)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    state_t = exp(dt_t A) state_{t-1} + dt_t x_t outer B_t     (per head)
    y_t = state_t . C_t + D x_t
    out = (rms(y * silu(z)) * w) @ out_proj                    (over d_inner)

``in_proj`` is held as three leaves (``w_z``, ``w_xbc``, ``w_dt``: its
column blocks), so the weight plan packs each by its own shape.

State: a float32 ``(N, H*P)`` summary per sequence and layer (the state
size on sublanes, the flattened (head, channel) pair on lanes, the layout
``kernels/ssm_decode`` streams), and the last ``d_conv - 1`` conv inputs in
the compute dtype.  A stack holds the SSM state of all its Mamba layers in
one leaf ``(L_mamba, B, N, H*P)`` (``transformer.init_cache``), so the
decode kernel updates one layer of it in place.

Three paths over the same equations:

* T = 1 (decode): ``kernels.ops.ssm_decode`` on the stacked state;
* T > 1 from a carried state (prefill, chunked-prefill chunks): the SSD
  chunked scan, exact for any starting state;
* training: the same scan from a zero state.

A ``valid`` mask (B, T) marks padded positions at the end of a span: their
``dt`` is 0, which leaves the state unchanged (exp(0) = 1, no input), and
the conv state is taken from the last valid inputs, so a span padded to a
fixed width carries state exactly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.weight_plan import apply_linear
from repro.distributed import shardlib as sl
from repro.models import layers as L

_HI = jax.lax.Precision.HIGHEST


def init_mamba2(cfg, key):
    d, H = cfg.d_model, cfg.mamba_n_heads
    di, cd, K = cfg.mamba_d_inner, cfg.mamba_conv_dim, cfg.mamba_d_conv
    ks = jax.random.split(key, 6)
    # dt_bias = softplus^-1 of a step in [1e-3, 1e-1]; A in [1, 16] (the
    # published initial ranges)
    dt0 = jnp.exp(jax.random.uniform(ks[4], (H,), jnp.float32,
                                     jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "w_z": L.dense_init(ks[0], (d, di)),
        "w_xbc": L.dense_init(ks[1], (d, cd)),
        "w_dt": L.dense_init(ks[2], (d, H)),
        "conv_w": jax.random.normal(ks[3], (K, cd), jnp.float32) / K ** 0.5,
        "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
        "A_log": jnp.log(jax.random.uniform(ks[5], (H,), jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((H,), jnp.float32),
        "norm": jnp.zeros((di,), jnp.float32),  # gated norm, (1 + scale)
        "w_out": L.dense_init(jax.random.fold_in(key, 7), (di, d)),
        "conv_b": jnp.zeros((cd,), jnp.float32),
    }


def mamba2_axes():
    return {"w_z": ("d", "ff"), "w_xbc": ("d", None), "w_dt": ("d", None),
            "conv_w": (None, None), "conv_b": (None,), "dt_bias": (None,),
            "A_log": (None,), "D": (None,), "norm": ("ff",),
            "w_out": ("ff", "d")}


def init_conv_state(cfg, batch: int, dtype):
    return {"conv": jnp.zeros((batch, cfg.mamba_d_conv - 1, cfg.mamba_conv_dim),
                              dtype)}


def init_ssm_state(cfg, n_layers: int, batch: int):
    """Every Mamba layer's SSM state, stacked: (L, B, N, H*P) float32."""
    return jnp.zeros((n_layers, batch, cfg.mamba_d_state, cfg.mamba_d_inner),
                     jnp.float32)


# Recurrent summaries, not positionally addressed: a step rewrites them in
# place, so nothing can be paged, masked or rolled back (no speculation, no
# prefix sharing); chunked prefill carries them exactly (``valid``).
_CONV_AXES = sl.register_cache_kind(
    "mamba.conv", {"conv": ("batch", None, None)},
    positional=False, family="recurrent")
SSM_AXES = sl.register_cache_kind(
    "mamba.ssm", (None, "batch", None, None),
    positional=False, family="recurrent")


def conv_state_axes():
    return dict(_CONV_AXES)


def _conv(xbc, w, b, state, n_valid):
    """Causal depthwise conv of xbc (B, T, C) after ``state`` (B, K-1, C);
    the new state is the K-1 inputs before position ``n_valid`` (B,)."""
    B, T, C = xbc.shape
    K = w.shape[0]
    full = jnp.concatenate([state.astype(xbc.dtype), xbc], axis=1)
    w = w.astype(jnp.float32)
    y = sum(full[:, k:k + T].astype(jnp.float32) * w[k] for k in range(K))
    y = y + b.astype(jnp.float32)
    if n_valid is None:
        new = full[:, T:]
    else:
        new = jax.vmap(lambda f, n: jax.lax.dynamic_slice_in_dim(f, n, K - 1))(
            full, n_valid)
    return jax.nn.silu(y), new


def ssd_scan(x, dt, a, b, c, state, chunk: int):
    """The SSD chunked scan from ``state``: exact for the recurrence above.

    x (B, T, H, P), dt (B, T, H) float32 (0 at padded positions), a (H,),
    b, c (B, T, N), state (B, N, H, P).  Returns (y without D x
    (B, T, H, P), final state).  Inside a chunk of Q positions the output is
    the masked (Q, Q) decay-weighted C.B products applied to dt x, plus the
    entering state read through C; the state leaving a chunk adds each
    position's input decayed to the chunk's end."""
    Bn, T, H, P = x.shape
    Q = min(chunk, T)
    nq = -(-T // Q)
    pad = nq * Q - T
    if pad:
        widths = lambda n: [(0, 0), (0, pad)] + [(0, 0)] * n
        x, dt = jnp.pad(x, widths(2)), jnp.pad(dt, widths(1))
        b, c = jnp.pad(b, widths(1)), jnp.pad(c, widths(1))

    def blocks(t):
        return jnp.moveaxis(t.reshape((Bn, nq, Q) + t.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def one(s, xs):
        xq, dtq, bq, cq = xs
        cs = jnp.cumsum(dtq * a, axis=1)  # (B, Q, H), non-increasing
        seg = cs[:, :, None, :] - cs[:, None, :, :]  # (B, t, s, H)
        decay = jnp.exp(jnp.where(causal[None, :, :, None], seg, -jnp.inf))
        cb = jnp.einsum("btn,bsn->bts", cq, bq, precision=_HI)
        w = decay * cb[..., None] * dtq[:, None, :, :]  # (B, t, s, H)
        y = jnp.einsum("btsh,bshp->bthp", w, xq, precision=_HI)
        y = y + jnp.einsum("btn,bnhp->bthp", cq, s,
                           precision=_HI) * jnp.exp(cs)[..., None]
        last = cs[:, -1]  # (B, H)
        into = jnp.exp(last[:, None] - cs) * dtq  # (B, Q, H)
        s = (jnp.exp(last)[:, None, :, None] * s
             + jnp.einsum("bsn,bsh,bshp->bnhp", bq, into, xq, precision=_HI))
        return s, y

    state, ys = jax.lax.scan(one, state, (blocks(x), blocks(dt), blocks(b),
                                          blocks(c)))
    y = jnp.moveaxis(ys, 0, 1).reshape(Bn, nq * Q, H, P)[:, :T]
    return y, state


def apply_mamba2(cfg, p, h, cache=None, *, layer=None, valid=None):
    """h (B, T, d) normed input -> (out (B, T, d), new cache).

    ``cache``: None (training, zero state) or {"conv": (B, K-1, C),
    "ssm": (L, B, N, H*P) every layer's state}; ``layer`` indexes this
    layer's state in the stack.  ``valid`` (B, T) bool marks real positions
    (a prefix of each row)."""
    B, T, _ = h.shape
    dt_c = h.dtype
    H, P, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    di = cfg.mamba_d_inner
    z = apply_linear(h, p["w_z"])
    xbc = apply_linear(h, p["w_xbc"])
    dt = apply_linear(h, p["w_dt"]).astype(jnp.float32)
    conv = (cache["conv"] if cache is not None
            else jnp.zeros((B, cfg.mamba_d_conv - 1, xbc.shape[-1]), dt_c))
    n_valid = None if valid is None else jnp.sum(valid, axis=1).astype(jnp.int32)
    xbc, conv = _conv(xbc, p["conv_w"], p["conv_b"], conv, n_valid)
    x = xbc[..., :di]
    bm, cm = xbc[..., di:di + N], xbc[..., di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    if valid is not None:
        dt = jnp.where(valid[..., None], dt, 0.0)
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    dvec = p["D"].astype(jnp.float32)
    new_cache = None
    if cache is not None and T == 1:
        from repro.kernels import ops

        rep = lambda v: jnp.repeat(v, P, axis=-1)
        y, ssm = ops.ssm_decode(
            cache["ssm"], layer, x[:, 0], rep(dt[:, 0]), rep(a), rep(dvec),
            bm[:, 0], cm[:, 0])
        y = y[:, None]
        new_cache = {"conv": conv, "ssm": ssm}
    else:
        if cache is None:
            s0 = jnp.zeros((B, N, H, P), jnp.float32)
        else:
            s0 = jax.lax.dynamic_index_in_dim(
                cache["ssm"], layer, 0, keepdims=False).reshape(B, N, H, P)
        xh = x.reshape(B, T, H, P)
        y, s = ssd_scan(xh, dt, a, bm, cm, s0, cfg.mamba_chunk_size)
        y = (y + dvec[:, None] * xh).reshape(B, T, di)
        if cache is not None:
            ssm = jax.lax.dynamic_update_index_in_dim(
                cache["ssm"], s.reshape(B, N, di), layer, 0)
            new_cache = {"conv": conv, "ssm": ssm}
    g = y * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(g * g, axis=-1, keepdims=True)
    g = g * jax.lax.rsqrt(var + cfg.rms_norm_eps) * (1.0 + p["norm"])
    out = apply_linear(g.astype(dt_c), p["w_out"])
    return sl.shard(out, "batch", "seq_sp", None), new_cache
