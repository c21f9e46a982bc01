"""The Mamba-2 mixer (models/mamba2.py) and its decode kernel
(kernels/ssm_decode.py) against the recurrence written token by token.

Parameters are set for a long memory (dt * |A| about 0.01, so the state
forgets about 1% a token): a wrong carry of the state between spans then
shows in every later output, not only in the next few."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.kernels import ssm_decode as K
from repro.models import mamba2 as MB

H, P, N = 4, 8, 16


def _recurrence(x, dt, a, b, c, state):
    """Token by token, in float64: x (B, T, H, P), dt (B, T, H), b, c
    (B, T, N), state (B, N, H, P).  Returns (y without D x, final state)."""
    x, dt, a, b, c, state = (np.asarray(v, np.float64)
                             for v in (x, dt, a, b, c, state))
    ys = []
    for t in range(x.shape[1]):
        da = np.exp(dt[:, t] * a)  # (B, H)
        state = (da[:, None, :, None] * state
                 + b[:, t, :, None, None] * (dt[:, t, :, None] * x[:, t])[:, None])
        ys.append(np.einsum("bnhp,bn->bhp", state, c[:, t]))
    return np.stack(ys, axis=1), state


_scan16 = jax.jit(functools.partial(MB.ssd_scan, chunk=16))


def _f32(*arrays):
    return tuple(jnp.asarray(v, jnp.float32) for v in arrays)


def _inputs(T, seed=0, B=2):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(B, T, H, P))
    a = -np.ones((H,))  # A = -exp(0)
    dt = 0.01 * np.exp(0.3 * rng.normal(size=(B, T, H)))
    b = rng.normal(size=(B, T, N))
    c = rng.normal(size=(B, T, N))
    state = rng.normal(size=(B, N, H, P))
    return _f32(x, dt, a, b, c, state)


@pytest.mark.parametrize("T", [1, 7, 16, 37])
def test_chunked_scan_from_a_state_equals_the_recurrence(T):
    x, dt, a, b, c, s0 = _inputs(T)
    y, s = _scan16(x, dt, a, b, c, s0)
    y_ref, s_ref = _recurrence(x, dt, a, b, c, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=2e-5, rtol=2e-5)


def test_padded_positions_change_no_state():
    """dt = 0 at the tail: the state leaving a padded span is the state
    after its real positions."""
    x, dt, a, b, c, s0 = _inputs(16, seed=1)
    dt_pad = jnp.where(jnp.arange(16)[None, :, None] < 9, dt, 0.0)
    _, s = _scan16(x, dt_pad, a, b, c, s0)
    _, s_ref = _recurrence(x[:, :9], dt[:, :9], a, b[:, :9], c[:, :9], s0)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=2e-5, rtol=2e-5)


def _kernel_inputs(L=3, B=4, seed=2):
    HP = H * P * 4  # 128 lanes
    rng = np.random.default_rng(seed)
    return _f32(rng.normal(size=(L, B, N, HP)), rng.normal(size=(B, HP)),
                0.01 * np.exp(0.3 * rng.normal(size=(B, HP))),
                -np.exp(0.1 * rng.normal(size=(HP,))), rng.normal(size=(HP,)),
                rng.normal(size=(B, N)), rng.normal(size=(B, N)))


def test_decode_kernel_equals_the_update():
    state, x, dt, a, d, b, c = _kernel_inputs()
    for layer in (0, 2):
        y, s = jax.jit(lambda *args: K.ssm_decode(*args, interpret=True))(
            state, jnp.int32(layer), x, dt, a, d, b, c)
        y_ref, s_ref = K.ssm_decode_reference(state, layer, x, dt, a, d, b, c)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=1e-6, rtol=1e-6)
        # the other layers' state is untouched
        others = [i for i in range(state.shape[0]) if i != layer]
        np.testing.assert_array_equal(np.asarray(s)[others], np.asarray(state)[others])


def test_decode_kernel_aliases_the_stacked_state():
    """The whole stack goes in and comes out as one buffer: the kernel's
    state operand is aliased to its state output, and the update is one
    call on the stack (no layer slice taken before it)."""
    state, x, dt, a, d, b, c = _kernel_inputs()
    jaxpr = jax.make_jaxpr(lambda s: K.ssm_decode(
        s, jnp.int32(1), x, dt, a, d, b, c, interpret=True))(state)
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert tuple(calls[0].params["input_output_aliases"]) == ((1, 1),)
    assert calls[0].invars[1] is jaxpr.jaxpr.invars[0]  # the stack itself
    assert [e.primitive.name for e in jaxpr.jaxpr.eqns].count("dynamic_slice") == 0


def test_mixer_decode_and_chunks_equal_the_whole_sequence():
    """The mixer over a sequence in one pass, in padded 16-wide spans from
    the carried state, and token by token through the kernel agree."""
    cfg = C.get_config("granite-4.0-h-micro", smoke=True)
    p = MB.init_mamba2(cfg, jax.random.key(3))
    p = {**p, "A_log": jnp.zeros_like(p["A_log"]),
         "dt_bias": jnp.full_like(p["dt_bias"], float(np.log(np.expm1(0.01))))}
    T = 37
    (h,) = _f32(np.random.default_rng(4).normal(size=(1, T, cfg.d_model)))
    whole, _ = jax.jit(lambda h: MB.apply_mamba2(cfg, p, h))(h)
    step = jax.jit(lambda h, cache, layer, valid: MB.apply_mamba2(
        cfg, p, h, cache, layer=layer, valid=valid))

    def fresh():
        conv = MB.init_conv_state(cfg, 1, jnp.float32)
        return {**conv, "ssm": MB.init_ssm_state(cfg, 2, 1)}

    cache, outs = fresh(), []
    for start in range(0, T, 16):
        n = min(16, T - start)
        span = jnp.pad(h[:, start:start + n], ((0, 0), (0, 16 - n), (0, 0)))
        valid = (jnp.arange(16) < n)[None]
        y, cache = step(span, cache, 1, valid)
        outs.append(y[:, :n])
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(whole), atol=2e-5, rtol=2e-5)
    cache, outs = fresh(), []
    for t in range(T):
        y, cache = step(h[:, t:t + 1], cache, 0, None)
        outs.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(whole), atol=2e-5, rtol=2e-5)
