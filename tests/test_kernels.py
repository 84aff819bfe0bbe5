"""Pallas kernel validation: interpret-mode sweeps vs the jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.quant import quantize, quantize_weight
from repro.kernels import ops, qmatmul as K, ref


def _rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# exact block-multiple shapes exercise the kernel without the padding path;
# ragged shapes exercise ops.py padding.
SHAPES = [
    (128, 256, 128),
    (256, 512, 256),
    (128, 256, 384),
    (70, 300, 200),      # ragged
    (1, 256, 128),       # single row (decode-like)
    (257, 513, 129),     # all ragged
]


@pytest.mark.parametrize("m,k,n", SHAPES)
@pytest.mark.parametrize("activation", ["none", "relu", "gelu"])
def test_w8a16_matches_ref(m, k, n, activation):
    keys = jax.random.split(jax.random.PRNGKey(m * 7 + k + n), 3)
    x = _rand(keys[0], (m, k))
    w = quantize_weight(_rand(keys[1], (k, n)))
    b = _rand(keys[2], (n,))
    got = ops.qmatmul(x, w, b, activation=activation, interpret=True,
                      out_dtype=jnp.float32)
    want = ref.qmatmul_w8a16_ref(x, w.values, w.scale.reshape(-1), b,
                                 activation=activation,
                                 out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("m,k,n", SHAPES[:4])
@pytest.mark.parametrize("activation", ["none", "sigmoid", "tanh"])
def test_w8a8_matches_ref(m, k, n, activation):
    keys = jax.random.split(jax.random.PRNGKey(m + k * 3 + n), 3)
    x = _rand(keys[0], (m, k))
    xq = quantize(x, bits=8, axis=None)
    w = quantize_weight(_rand(keys[1], (k, n)))
    b = _rand(keys[2], (n,))
    got = ops.qmatmul(x, w, b, x_q=xq, activation=activation,
                      interpret=True, out_dtype=jnp.float32)
    want = ref.qmatmul_w8a8_ref(xq.values, w.values, xq.scale,
                                w.scale.reshape(-1), b,
                                activation=activation,
                                out_dtype=jnp.float32)
    # integer path: accumulation is exact; only the final fp ops differ
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_w8a8_integer_accumulate_exact():
    """With unit scales the kernel must be bit-exact integer arithmetic."""
    key = jax.random.PRNGKey(0)
    x = jax.random.randint(key, (128, 256), -127, 127, jnp.int8)
    w = jax.random.randint(jax.random.fold_in(key, 1), (256, 128),
                           -127, 127, jnp.int8)
    one = jnp.ones((), jnp.float32)
    got = K.qmatmul_w8a8(x, w, one, jnp.ones((128,)), None,
                         interpret=True, out_dtype=jnp.float32)
    want = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32).astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_w8a16_out_dtypes(dtype):
    keys = jax.random.split(jax.random.PRNGKey(5), 2)
    x = _rand(keys[0], (128, 256), dtype)
    w = quantize_weight(_rand(keys[1], (256, 128)))
    got = ops.qmatmul(x, w, None, interpret=True, out_dtype=dtype)
    assert got.dtype == dtype
    ref_out = ref.qmatmul_w8a16_ref(x, w.values, w.scale.reshape(-1), None,
                                    out_dtype=dtype)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref_out, np.float32),
        rtol=2e-2, atol=2e-2)


def test_nd_input_flattening():
    keys = jax.random.split(jax.random.PRNGKey(6), 2)
    x = _rand(keys[0], (2, 3, 5, 96))
    w = quantize_weight(_rand(keys[1], (96, 64)))
    got = ops.qmatmul(x, w, None, interpret=True, out_dtype=jnp.float32)
    assert got.shape == (2, 3, 5, 64)
    flat = ops.qmatmul(x.reshape(-1, 96), w, None, interpret=True,
                       out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got).reshape(-1, 64),
                               np.asarray(flat), rtol=1e-6)


@given(st.integers(0, 2**31 - 1),
       st.sampled_from([(64, 128, 64), (32, 256, 96)]),
       st.floats(0.1, 4.0))
@settings(max_examples=10, deadline=None)
def test_quantized_matmul_error_vs_fp_bounded(seed, shape, scale):
    """Property: w8a16 output error vs the fp matmul is bounded by the
    quantization step of the weights (relative error ~ 1/127)."""
    m, k, n = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = _rand(keys[0], (m, k), scale=scale)
    w_fp = _rand(keys[1], (k, n), scale=scale)
    w = quantize_weight(w_fp)
    got = ops.qmatmul(x, w, None, interpret=True, out_dtype=jnp.float32)
    want = x @ w_fp
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel < 0.02


def test_cpu_fallback_matches_interpret():
    """ops.py CPU fallback (oracle) and interpret-mode kernel agree."""
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    x = _rand(keys[0], (64, 128))
    w = quantize_weight(_rand(keys[1], (128, 64)))
    a = ops.qmatmul(x, w, None, interpret=True, out_dtype=jnp.float32)
    b = ops.qmatmul(x, w, None, interpret=False, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# flash attention kernel
# ---------------------------------------------------------------------------

FLASH_SHAPES = [
    (2, 256, 2, 128, True, None),
    (1, 128, 4, 64, True, None),       # hd padding path
    (2, 200, 2, 128, True, 64),        # ragged seq + sliding window
    (1, 384, 1, 128, False, None),     # non-causal (cross-attention)
]


@pytest.mark.parametrize("b,s,h,hd,causal,win", FLASH_SHAPES)
def test_flash_attention_matches_ref(b, s, h, hd, causal, win):
    keys = jax.random.split(jax.random.PRNGKey(s + hd), 3)
    q = _rand(keys[0], (b, s, h, hd))
    k = _rand(keys[1], (b, s, h, hd))
    v = _rand(keys[2], (b, s, h, hd))
    got = ops.flash_attention(q, k, v, causal=causal, window=win,
                              interpret=True, out_dtype=jnp.float32)
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    kr = k.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    want = ref.flash_attention_ref(
        qr, kr, vr, causal=causal, window=win, out_dtype=jnp.float32
    ).reshape(b, h, s, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_matches_model_chunked_attention():
    """The Pallas kernel and the pure-JAX chunked attention (the model's
    CPU/dry-run path) agree — they are interchangeable implementations."""
    from repro.models.layers import _chunked_attention
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    b, s, h, hd = 2, 160, 2, 64
    q = _rand(keys[0], (b, s, h, hd))
    k = _rand(keys[1], (b, s, h, hd))
    v = _rand(keys[2], (b, s, h, hd))
    a = ops.flash_attention(q, k, v, causal=True, interpret=True,
                            out_dtype=jnp.float32)
    c = _chunked_attention(q, k, v, causal=True, window=None, q_block=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(c, np.float32),
                               rtol=2e-5, atol=2e-5)


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=8, deadline=None)
def test_flash_attention_rows_sum_property(seed):
    """With v = ones, every output row must be exactly 1 (softmax rows
    sum to 1) regardless of masking pattern — catches denominator bugs."""
    key = jax.random.PRNGKey(seed)
    b, s, h, hd = 1, 128, 2, 128
    q = _rand(key, (b, s, h, hd))
    k = _rand(jax.random.fold_in(key, 1), (b, s, h, hd))
    v = jnp.ones((b, s, h, hd), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, interpret=True,
                              out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), 1.0, rtol=1e-5, atol=1e-5)
