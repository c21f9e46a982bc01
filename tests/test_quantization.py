"""Q7.8 fixed-point codec (paper Section 4.1/5.3) + int8 quantization."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import quantization as Q


class TestQ78:
    def test_representable_values_roundtrip(self):
        # every int16 value decodes and re-encodes to itself
        q = jnp.arange(-32768, 32768, 37, dtype=jnp.int16)
        assert bool(jnp.all(Q.q78_encode(Q.q78_decode(q)) == q))

    @given(st.floats(-127.0, 127.0))
    @settings(max_examples=100, deadline=None)
    def test_quantize_error_bound(self, x):
        # round-to-nearest: error <= 1/512 + float slack
        err = abs(float(Q.q78_quantize(jnp.float32(x))) - x)
        assert err <= (1.0 / 512.0) + 1e-6

    def test_saturation(self):
        assert int(Q.q78_encode(jnp.float32(1000.0))) == Q.Q78_MAX
        assert int(Q.q78_encode(jnp.float32(-1000.0))) == Q.Q78_MIN

    def test_matmul_is_integer_exact(self):
        rng = np.random.default_rng(0)
        a = Q.q78_encode(jnp.asarray(rng.normal(size=(5, 7)), jnp.float32))
        w = Q.q78_encode(jnp.asarray(rng.normal(size=(7, 3)), jnp.float32))
        acc = Q.q78_matmul(a, w)
        ref = np.asarray(a, np.int64) @ np.asarray(w, np.int64)
        assert np.array_equal(np.asarray(acc, np.int64), ref)

    def test_q1516_decode_scale(self):
        # 1.0 * 1.0 in Q7.8 -> 256*256 in the Q15.16 accumulator
        a = Q.q78_encode(jnp.ones((1, 1)))
        acc = Q.q78_matmul(a, a)
        assert float(Q.q1516_decode(acc)[0, 0]) == pytest.approx(1.0)

    def test_requantize_rounds(self):
        acc = jnp.asarray([[256 * 256]], jnp.int32)  # 1.0 in Q15.16
        assert int(Q.q78_requantize(acc)[0, 0]) == 256  # 1.0 in Q7.8

    def test_plan_sigmoid_matches_reference(self):
        # PLAN is a <=2% max-error approximation of sigmoid on [-8, 8]
        x = jnp.linspace(-8, 8, 201)
        y = Q.q78_decode(Q.q78_sigmoid_plan(Q.q78_encode(x)))
        ref = jax.nn.sigmoid(x)
        assert float(jnp.max(jnp.abs(y - ref))) < 0.025

    def test_plan_sigmoid_symmetry(self):
        # y(-x) = 1 - y(x) (the PLAN construction)
        x = jnp.linspace(0.0, 8.0, 33)
        yp = Q.q78_decode(Q.q78_sigmoid_plan(Q.q78_encode(x)))
        yn = Q.q78_decode(Q.q78_sigmoid_plan(Q.q78_encode(-x)))
        assert float(jnp.max(jnp.abs(yp + yn - 1.0))) < 2.0 / 256.0


class TestInt8:
    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=20, deadline=None)
    def test_roundtrip_error(self, seed):
        rng = np.random.default_rng(seed)
        w = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
        err = Q.quantization_error(w)
        assert err < 0.02  # int8 per-channel on gaussian data

    def test_int8_matmul_close_to_fp(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
        wq = Q.quantize_int8(w, axis=-1)
        y = Q.int8_matmul(x, wq)
        rel = float(jnp.linalg.norm(y - x @ w) / jnp.linalg.norm(x @ w))
        assert rel < 0.05

    def test_quantize_pytree_skips_small(self):
        tree = {"big": jnp.ones((128, 64)), "small": jnp.ones((4,))}
        out = Q.quantize_pytree(tree, min_size=1024)
        assert isinstance(out["big"], Q.QuantizedTensor)
        assert isinstance(out["small"], jnp.ndarray)

    def test_bytes_per_weight(self):
        assert Q.bytes_per_weight("q78") == 2.0
        assert Q.bytes_per_weight("int8") == 1.0
