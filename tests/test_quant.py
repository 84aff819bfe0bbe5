"""Quantization unit + property tests (the paper's numerical contract)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import quant as Q


class TestQuantizeRoundTrip:
    def test_per_tensor_error_bound(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (64, 64))
        q = Q.quantize(x, bits=8, axis=None)
        err = jnp.abs(q.dequantize() - x)
        # symmetric rounding: |err| <= scale/2 everywhere
        assert float(jnp.max(err)) <= float(q.scale) * 0.5 + 1e-7

    def test_per_channel_tighter_than_per_tensor(self):
        key = jax.random.PRNGKey(1)
        # one channel with 100x the scale of the others
        x = jax.random.normal(key, (128, 16))
        x = x.at[:, 3].mul(100.0)
        q_t = Q.quantize(x, bits=8, axis=None)
        q_c = Q.quantize_weight(x, bits=8)
        err_t = float(jnp.mean(jnp.abs(q_t.dequantize() - x)[:, :3]))
        err_c = float(jnp.mean(jnp.abs(q_c.dequantize() - x)[:, :3]))
        assert err_c < err_t / 10

    def test_int_bounds_symmetric(self):
        lo, hi = Q.int_bounds(8)
        assert (lo, hi) == (-127, 127)

    def test_values_in_range(self):
        x = jax.random.normal(jax.random.PRNGKey(2), (32, 32)) * 50
        q = Q.quantize(x, bits=8)
        assert int(jnp.max(q.values)) <= 127
        assert int(jnp.min(q.values)) >= -127

    @given(st.integers(0, 2**31 - 1), st.floats(0.01, 100.0))
    @settings(max_examples=20, deadline=None)
    def test_roundtrip_error_bound_property(self, seed, scale):
        x = jax.random.normal(jax.random.PRNGKey(seed), (16, 8)) * scale
        q = Q.quantize(x, bits=8, axis=None)
        err = jnp.max(jnp.abs(q.dequantize() - x))
        assert float(err) <= float(q.scale) * 0.5 + 1e-6 * scale

    def test_fake_quant_gradient_straight_through(self):
        x = jnp.array([0.5, -1.0, 2.0])
        g = jax.grad(lambda v: jnp.sum(Q.fake_quant(v) * 3.0))(x)
        np.testing.assert_allclose(np.asarray(g), 3.0, rtol=1e-6)


class TestQuantizeTree:
    def _params(self):
        k = jax.random.PRNGKey(0)
        return {
            "layers": {
                "attn": {"wq": {"w": jax.random.normal(k, (4, 128, 128)),
                                "b": jnp.zeros((4, 128))}},
                "ln_attn": {"scale": jnp.ones((4, 128))},
            },
            "embed": {"table": jax.random.normal(k, (512, 64))},
        }

    def test_allowlist(self):
        qp = Q.quantize_tree(self._params(), min_size=1024)
        assert isinstance(qp["layers"]["attn"]["wq"]["w"], Q.QTensor)
        assert isinstance(qp["embed"]["table"], Q.QTensor)
        # biases and norm scales must stay fp
        assert not isinstance(qp["layers"]["attn"]["wq"]["b"], Q.QTensor)
        assert not isinstance(qp["layers"]["ln_attn"]["scale"], Q.QTensor)

    def test_stacked_scales_scannable(self):
        qp = Q.quantize_tree(self._params(), min_size=1024)
        w = qp["layers"]["attn"]["wq"]["w"]
        assert w.values.shape == (4, 128, 128)
        assert w.scale.shape == (4, 1, 128)   # per-layer, per-column

    def test_embedding_per_row(self):
        qp = Q.quantize_tree(self._params(), min_size=1024)
        t = qp["embed"]["table"]
        assert t.scale.shape == (512, 1)

    def test_weight_bytes_halve_vs_fp32(self):
        p = self._params()
        fp_bytes = Q.tree_weight_bytes(p)
        q_bytes = Q.tree_weight_bytes(Q.quantize_tree(p, min_size=1024))
        assert q_bytes < fp_bytes / 2.5   # int8 + small fp leaves


class TestGradientCompression:
    def test_unbiased(self):
        g = jax.random.normal(jax.random.PRNGKey(3), (256,))
        keys = jax.random.split(jax.random.PRNGKey(4), 300)
        acc = jnp.zeros_like(g)
        for k in keys:
            acc = acc + Q.compress_gradient(g, k).dequantize()
        mean = acc / len(keys)
        # stochastic rounding is unbiased: mean converges to g
        assert float(jnp.max(jnp.abs(mean - g))) < float(
            Q.compute_scale(g)) * 0.25

    def test_qtensor_is_pytree_with_keys(self):
        q = Q.quantize(jnp.ones((8, 8)), bits=8)
        flat = jax.tree_util.tree_flatten_with_path(q)[0]
        names = {str(p[-1]) for p, _ in flat}
        assert names == {".values", ".scale"}


def test_bits_speed_factor():
    assert Q.bits_speed_factor(8, 8) == 1.0
    assert Q.bits_speed_factor(8, 16) == 0.5
    assert Q.bits_speed_factor(16, 16) == 0.25


class TestInt4:
    """int4 weight-only quantization (stored in int8 containers, like
    XLA:TPU packs narrow ints) through the same kernel path."""

    def test_int4_bounds(self):
        assert Q.int_bounds(4) == (-7, 7)

    def test_int4_roundtrip_bound(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (64, 64))
        q = Q.quantize(x, bits=4, axis=None)
        assert int(jnp.max(jnp.abs(q.values))) <= 7
        err = jnp.max(jnp.abs(q.dequantize() - x))
        assert float(err) <= float(q.scale) * 0.5 + 1e-6

    def test_int4_matmul_through_kernel(self):
        from repro.kernels import ops
        keys = jax.random.split(jax.random.PRNGKey(1), 2)
        x = jax.random.normal(keys[0], (64, 128))
        w_fp = jax.random.normal(keys[1], (128, 64))
        w4 = Q.quantize_weight(w_fp, bits=4)
        got = ops.qmatmul(x, w4, None, interpret=True,
                          out_dtype=jnp.float32)
        rel = float(jnp.linalg.norm(got - x @ w_fp)
                    / jnp.linalg.norm(x @ w_fp))
        # symmetric int4 (±7 levels) on N(0,1) weights: expected rel err is
        # scale/sqrt(12) with scale = max|w|/7 ~ 3.2/7, i.e. ~0.13
        assert rel < 0.14   # 4-bit: ~16x coarser than int8

    def test_int4_weight_bytes(self):
        w = Q.quantize_weight(jnp.ones((256, 256)), bits=4)
        # nbytes_weights models the 4-bit wire format (packed)
        assert w.nbytes_weights < 256 * 256 * 1 + 256 * 8
