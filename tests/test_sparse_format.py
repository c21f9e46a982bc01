"""(w, z)^3 stream codec (paper Section 5.6) + TPU block-sparse format."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import sparse_format as sf
from repro.core.pruning import BlockPruneConfig, sparsity_target_mask
from repro.core.quantization import q78_quantize


def _sparse_row(rng, n, q):
    row = rng.normal(size=n).astype(np.float32)
    row[rng.random(n) < q] = 0.0
    return row


class TestWZStream:
    @given(seed=st.integers(0, 10_000), q=st.floats(0.0, 0.98), n=st.integers(1, 300))
    @settings(max_examples=60, deadline=None)
    def test_row_roundtrip_bit_exact(self, seed, q, n):
        rng = np.random.default_rng(seed)
        row = _sparse_row(rng, n, q)
        words, nt = sf.encode_row(row)
        back = sf.decode_row(words, nt, n)
        expect = np.asarray(q78_quantize(jnp.asarray(row)))
        np.testing.assert_array_equal(back, expect)

    def test_long_zero_run_escape(self):
        # a zero run longer than Z_MAX=31 forces explicit zero-weight tuples
        row = np.zeros(100, np.float32)
        row[99] = 1.0
        words, nt = sf.encode_row(row)
        assert nt > 1  # escapes present
        back = sf.decode_row(words, nt, 100)
        assert back[99] == pytest.approx(1.0)
        assert np.all(back[:99] == 0)

    def test_paper_example_word_packing(self):
        # the paper's example row (Section 5.6) packs into 2 data words
        row = np.array([0, -1.5, 0, 0, 0.3, -0.17, 0, 0, 0, 1.1, 0, 0, -0.2, 0, 0.1], np.float32)
        s = sf.encode_matrix(row[None, :])
        assert len(s.words[0]) == 2
        np.testing.assert_allclose(
            sf.decode_matrix(s)[0], np.asarray(q78_quantize(jnp.asarray(row))), atol=1e-6
        )

    def test_q_overhead_converges_to_paper(self):
        # dense-ish long rows -> overhead -> 64/(3*16) = 1.333
        rng = np.random.default_rng(0)
        w = rng.normal(size=(8, 3000)).astype(np.float32) + 10.0  # no zeros
        s = sf.encode_matrix(w)
        assert s.q_overhead() == pytest.approx(64.0 / 48.0, rel=0.01)

    def test_stream_addresses_match_nonzeros(self):
        rng = np.random.default_rng(3)
        row = _sparse_row(rng, 200, 0.8)
        row = np.asarray(q78_quantize(jnp.asarray(row)))
        words, nt = sf.encode_row(row)
        addrs = sf.stream_addresses(words, nt)
        nz = np.nonzero(row)[0]
        # addresses must cover all nonzero positions (escape tuples add
        # zero-weight entries, so addrs is a superset)
        assert set(nz).issubset(set(addrs))


class TestBlockSparse:
    @given(seed=st.integers(0, 1000), q=st.sampled_from([0.0, 0.25, 0.5, 0.75]))
    @settings(max_examples=20, deadline=None)
    def test_pack_unpack_roundtrip(self, seed, q):
        rng = np.random.default_rng(seed)
        w = jnp.asarray(rng.normal(size=(256, 128)), jnp.float32)
        cfg = BlockPruneConfig(bk=64, bn=64)
        s = sf.to_block_sparse(w, q, cfg)
        dense = sf.block_sparse_to_dense(s)
        # surviving blocks bit-exact; pruned blocks zero
        from repro.core.pruning import block_mask, expand_block_mask
        m = expand_block_mask(block_mask(w, q, cfg), cfg)
        np.testing.assert_array_equal(np.asarray(dense), np.asarray(w * m))
        assert s.q_prune() == pytest.approx(q, abs=0.1)

    def test_block_overhead_tiny(self):
        w = jnp.ones((256, 256))
        s = sf.to_block_sparse(w, 0.0, BlockPruneConfig(bk=128, bn=128))
        assert s.q_overhead() < 1.001  # vs paper's 1.33
