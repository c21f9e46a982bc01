"""Random weights for a configuration, made by the benchmark from the seed.

The program is handed a dense pytree with the structure its own
``init_params`` declares (read through ``jax.eval_shape``, so nothing of the
program's initialiser runs), filled on the device in one jitted call.  The
plain reference rebuilds the same pytree with the same call, so both sides
read identical weights and neither takes anything the other made.

Matmul weights are built from 128 x 128 blocks whose mean magnitude is set
exactly to a distinct level per block (a permutation of evenly spaced levels
in [0.5, 1.5]).  Block pruning keeps the blocks with the largest mean
magnitude, so which blocks survive is decided by levels a thousandth apart
and not by rounding: the program's packing and the reference's own pruning
agree on the mask without sharing it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_ROOT2PI = math.sqrt(2.0 / math.pi)  # mean |z| of a standard normal


def _key_str(k) -> str:
    for attr in ("key", "idx", "name"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def path_str(path) -> str:
    return "/".join(_key_str(k) for k in path)


def _blocky(key, shape, block: int, dtype):
    """(..., K, N) weight whose (block, block) tiles have distinct mean |w|."""
    *lead, K, N = shape
    nl = math.prod(lead) if lead else 1
    nr, nc = K // block, N // block
    kz, kp = jax.random.split(key)
    z = jax.random.normal(kz, (nl, nr, block, nc, block), jnp.float32)
    m = jnp.mean(jnp.abs(z), axis=(2, 4), keepdims=True)
    nb = nr * nc
    levels = 0.5 + (jnp.arange(nb, dtype=jnp.float32) + 0.5) / nb
    perm = jax.vmap(lambda k: jax.random.permutation(k, levels))(
        jax.random.split(kp, nl))
    c = perm.reshape(nl, nr, 1, nc, 1)
    w = z / m * (_ROOT2PI / math.sqrt(K)) * c
    return w.reshape(shape).astype(dtype)


def _leaf(key, name: str, shape, block: int):
    if len(shape) < 2:
        # norm scales: the program applies (1 + scale)
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    K, N = shape[-2], shape[-1]
    if name.startswith("w") and K % block == 0 and N % block == 0:
        return _blocky(key, shape, block, jnp.bfloat16)
    fan_in = shape[-1] if name == "tok" else K
    return (jax.random.normal(key, shape, jnp.float32)
            / math.sqrt(fan_in)).astype(jnp.bfloat16)


def param_shapes(api, cfg):
    """The program's parameter structure, without running its initialiser."""
    return jax.eval_shape(functools.partial(api.init_params, cfg),
                          jax.random.key(0))


@functools.lru_cache(maxsize=4)
def _builder(treedef, specs, block: int):
    names = tuple(p.rsplit("/", 1)[-1] for p, _ in specs)

    @jax.jit
    def build(key):
        leaves = [_leaf(jax.random.fold_in(key, i), names[i], shape, block)
                  for i, (_, shape) in enumerate(specs)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return build


def make_params(shapes, seed: int, block: int):
    """Dense weights in the structure of ``shapes``, from ``seed``, in one
    jitted call.  ``seed`` may be any non-negative integer (reduced to 64
    bits for the key)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = tuple((path_str(p), tuple(s.shape)) for p, s in flat)
    return _builder(treedef, specs, block)(seed_key(seed))


def seed_key(seed: int):
    """A PRNG key from any integer seed (seeds beyond 32 bits included)."""
    s = int(seed) % (1 << 64)
    return jax.random.wrap_key_data(
        jnp.asarray([s >> 32, s & 0xFFFFFFFF], jnp.uint32))
