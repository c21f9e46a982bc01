"""Mamba-2 decode-step state update as a Pallas TPU kernel.

One decode token per sequence advances every head's (P x N) state:

    dA = exp(dt * A)
    state = dA * state + (dt * x) outer B
    y = state . C + D * x

At serving batch sizes the float32 state is most of what a decode step
reads and writes (granite-4.0-h-micro: 75.5 MB a sequence over 36 layers),
so the update is a pure memory stream.  The kernel takes the state of
*every* Mamba layer stacked ``(L, B, N, H*P)``, with the layer to update as
a scalar-prefetch operand, and aliases it to its state output: the grid
reads and writes only that layer's blocks, in place, and no layer slice of
the stack is ever materialised (a dynamic slice and update of a 268-MB
layer inside the layer scan would copy it twice a layer).

Layout: ``N`` (the state size, 128) on sublanes and the flattened
``(head, head_dim)`` pair on lanes, so per-(head, p) quantities (x, dt, A,
D, y) are lane vectors and only ``B`` and ``C`` (``N`` values a row, shared
by all heads: one group) are needed as columns.  Grid ``(B, H*P / lanes)``.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


LANES = 2048  # lanes a grid step takes: 1 MB of float32 state at N = 128


def _column(row):
    """(1, n) lane vector -> (n, 1) sublane column."""
    n = row.shape[-1]
    return jnp.transpose(jnp.broadcast_to(row, (8, n)))[:, :1]


def _ssm_decode_kernel(layer_ref, state_ref, x_ref, dt_ref, a_ref, d_ref,
                       b_ref, c_ref, y_ref, new_ref):
    del layer_ref  # consumed by the index maps
    x = x_ref[0]  # (1, lanes)
    dt = dt_ref[0]
    da = jnp.exp(dt * a_ref[...])
    state = da * state_ref[0, 0] + _column(b_ref[0]) * (dt * x)  # (N, lanes)
    new_ref[0, 0] = state
    y_ref[0] = (jnp.sum(state * _column(c_ref[0]), axis=0, keepdims=True)
                + d_ref[...] * x)


def ssm_decode(state, layer, x, dt, a, d, b, c, *, interpret: bool = False):
    """One decode step of Mamba layer ``layer`` over every row.

    state: (L, B, N, HP) float32, every layer's state, updated in place;
    layer: () int32; x, dt: (B, HP) float32 (dt already softplus'd and
    repeated over each head's P lanes); a, d: (HP,) float32 (A = -exp(A_log)
    and D, repeated likewise); b, c: (B, N) float32.
    Returns (y (B, HP) float32, the updated state)."""
    L, B, N, HP = state.shape
    lanes = min(LANES, HP)
    assert HP % lanes == 0, (HP, lanes)

    def row(i, j, layer_s):
        return (i, 0, j)

    def lane(i, j, layer_s):
        return (0, j)

    def vec(i, j, layer_s):
        return (i, 0, 0)

    def st(i, j, layer_s):
        return (layer_s[0], i, 0, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, HP // lanes),
        in_specs=[
            pl.BlockSpec((1, 1, N, lanes), st),
            pl.BlockSpec((1, 1, lanes), row),
            pl.BlockSpec((1, 1, lanes), row),
            pl.BlockSpec((1, lanes), lane),
            pl.BlockSpec((1, lanes), lane),
            pl.BlockSpec((1, 1, N), vec),
            pl.BlockSpec((1, 1, N), vec),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, lanes), row),
            pl.BlockSpec((1, 1, N, lanes), st),
        ],
    )
    y, state = pl.pallas_call(
        _ssm_decode_kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((B, 1, HP), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        # operand 1 (after the prefetched layer index) is the state
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        # the kernel's name in the custom call's attributes: a trace finds
        # this two-output call by it (its HLO name does not carry it)
        metadata={"kernel": "_ssm_decode_kernel"},
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), state,
      x.reshape(B, 1, HP), dt.reshape(B, 1, HP), a.reshape(1, HP),
      d.reshape(1, HP), b.reshape(B, 1, N), c.reshape(B, 1, N))
    return y.reshape(B, HP), state


def ssm_decode_reference(state, layer, x, dt, a, d, b, c):
    """The same update in ``jax.numpy`` (the kernel's oracle)."""
    s = state[layer]  # (B, N, HP)
    s = (jnp.exp(dt * a)[:, None, :] * s
         + b[:, :, None] * (dt * x)[:, None, :])
    y = jnp.einsum("bnl,bn->bl", s, c,
                   precision=jax.lax.Precision.HIGHEST) + d * x
    return y, state.at[layer].set(s)
