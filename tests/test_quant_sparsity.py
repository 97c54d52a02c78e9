"""Quantization + sparsity profiling (Table V machinery)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # CI image has no hypothesis; use the local shim
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core import sparsity
from repro.core.quantization import fake_quant, quantize, vmax


class TestQuantization:
    @pytest.mark.parametrize("bits", [2, 4, 8])
    def test_range(self, rng, bits):
        x = jnp.asarray(rng.normal(0, 3, (32, 16)), jnp.float32)
        q = quantize(x, bits=bits)
        v = vmax(bits)
        assert int(jnp.max(q.values)) <= v and int(jnp.min(q.values)) >= -v

    @pytest.mark.parametrize("bits", [4, 8])
    def test_roundtrip_error_bounded(self, rng, bits):
        x = jnp.asarray(rng.normal(0, 1, (64, 64)), jnp.float32)
        err = jnp.max(jnp.abs(fake_quant(x, bits=bits) - x))
        # per-channel absmax: max error <= scale/2 = absmax/(2 Vmax)
        bound = float(jnp.max(jnp.abs(x))) / (2 * vmax(bits)) * 1.001
        assert float(err) <= bound

    @given(bits=st.sampled_from([2, 4, 8]), seed=st.integers(0, 10_000),
           scale=st.floats(1e-3, 1e3))
    @settings(max_examples=30, deadline=None)
    def test_property_scale_invariance(self, bits, seed, scale):
        r = np.random.default_rng(seed)
        x = jnp.asarray(r.normal(0, 1, (8, 8)), jnp.float32)
        q1 = quantize(x, bits=bits).values
        q2 = quantize(x * scale, bits=bits).values
        assert bool(jnp.all(q1 == q2))   # symmetric absmax is scale-invariant

    def test_zero_channel_safe(self):
        x = jnp.zeros((4, 4), jnp.float32)
        q = quantize(x, bits=8)
        assert bool(jnp.all(q.values == 0))
        assert bool(jnp.all(jnp.isfinite(q.scale)))


class TestSparsity:
    def test_word_sparsity_exact(self):
        q = jnp.asarray([[0, 1, 0, 2], [0, 0, 3, -1]], jnp.int8)
        assert float(sparsity.word_sparsity(q)) == pytest.approx(4 / 8)

    def test_bit_sparsity_blockmax_constant(self):
        # all values at magnitude Vmax -> the stream-length floor
        # 1 - Vmax/2^(w-1) (= the paper's Table V LLM values: 0.78% @ 8-bit)
        q = jnp.full((64, 64), vmax(8), jnp.int8)
        assert float(sparsity.bit_sparsity_blockmax(q, 8)) == \
            pytest.approx(1.0 - vmax(8) / 2 ** 7)
        # all zeros -> full sparsity
        q = jnp.zeros((64, 64), jnp.int8)
        assert float(sparsity.bit_sparsity_blockmax(q, 8)) == pytest.approx(1.0)

    @pytest.mark.parametrize("shape", [(64, 64), (70, 45), (3, 40, 96),
                                       (100,)])
    @pytest.mark.parametrize("bits", [2, 4, 8])
    def test_blockmax_bands_equal_whole_tensor(self, rng, shape, bits):
        """The band-wise block maxima (which keep a stacked full-width
        weight within device memory) reproduce the whole-tensor statistic
        bit for bit, ragged edges and stacked layers included."""
        def whole_tensor(q, bits, block=32):
            x = jnp.abs(q.astype(jnp.float32))
            x = x[None, :] if x.ndim == 1 else x.reshape(-1, x.shape[-1])
            r, c = x.shape
            x = jnp.pad(x, ((0, (-r) % block), (0, (-c) % block)))
            x = x.reshape(x.shape[0] // block, block, x.shape[1] // block,
                          block)
            blk = jnp.max(x, axis=(1, 3))[:-(-r // block), :-(-c // block)]
            return 1.0 - jnp.mean(blk) / 2 ** (bits - 1)

        x = jnp.asarray(rng.standard_t(3, shape), jnp.float32)
        q = quantize(x, bits=bits, per_channel=False).values
        assert float(sparsity.bit_sparsity_blockmax(q, bits)) == \
            float(jax.jit(whole_tensor, static_argnums=1)(q, bits))

    def test_blockmax_below_elementwise(self, rng):
        """Block-max sparsity (paper's latency-relevant stat) is a lower
        bound on element-wise bit sparsity."""
        x = jnp.asarray(rng.normal(0, 1, (128, 128)), jnp.float32)
        st_ = sparsity.profile_tensor(x, bits=8)
        assert st_.bit_blockmax <= st_.bit_elem + 1e-6

    def test_bit_subsumes_word(self, rng):
        """Paper: 'bit sparsity subsumes word sparsity' (elementwise)."""
        x = np.asarray(rng.normal(0, 1, (64, 64)), np.float32)
        x[rng.random(x.shape) < 0.3] = 0.0
        st_ = sparsity.profile_tensor(jnp.asarray(x), bits=8)
        assert st_.bit_elem >= st_.word - 1e-6

    def test_outlier_structure_raises_block_sparsity(self, rng):
        """Per-tensor quant + outlier rows -> most blocks far from Vmax."""
        x = np.asarray(rng.normal(0, 0.02, (256, 256)), np.float32)
        x[:32] *= 50.0   # outlier region pins the global scale
        st_ = sparsity.profile_tensor(jnp.asarray(x), bits=8)
        assert st_.bit_blockmax > 0.5

    def test_combine_stats_weighting(self):
        a = sparsity.SparsityStats(8, word=0.0, bit_elem=0.0, bit_blockmax=0.0,
                                   numel=100)
        b = sparsity.SparsityStats(8, word=1.0, bit_elem=1.0, bit_blockmax=1.0,
                                   numel=300)
        c = sparsity.combine_stats([a, b])
        assert c.word == pytest.approx(0.75)
        assert c.numel == 400

    def test_profile_tree_skips_vectors(self, rng):
        params = {"w": jnp.asarray(rng.normal(0, 1, (16, 16)), jnp.float32),
                  "b": jnp.zeros((16,), jnp.float32)}
        out = sparsity.profile_tree(params, bits=8)
        assert list(out) == ["w"]

    @given(seed=st.integers(0, 10_000), bits=st.sampled_from([2, 4, 8]))
    @settings(max_examples=20, deadline=None)
    def test_property_stats_in_unit_interval(self, seed, bits):
        r = np.random.default_rng(seed)
        x = jnp.asarray(r.normal(0, 1, (40, 40)), jnp.float32)
        st_ = sparsity.profile_tensor(x, bits=bits)
        for f in (st_.word, st_.bit_elem, st_.bit_blockmax):
            assert -1e-6 <= f <= 1.0 + 1e-6
