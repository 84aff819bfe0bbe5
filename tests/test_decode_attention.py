"""Fused int8-KV decode-attention kernel: interpret-mode parity vs the
dense jnp oracle, plus structural properties (masking, scale folding)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


def _case(key, b, s, kv, g, hd, scale_lo=0.005, scale_hi=0.05):
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (b, kv, g, hd), jnp.float32)
    k = jax.random.randint(ks[1], (b, s, kv, hd), -127, 127, jnp.int8)
    v = jax.random.randint(ks[2], (b, s, kv, hd), -127, 127, jnp.int8)
    kscale = jax.random.uniform(ks[3], (b, s, kv, 1), jnp.float32,
                                scale_lo, scale_hi)
    vscale = jax.random.uniform(ks[4], (b, s, kv, 1), jnp.float32,
                                scale_lo, scale_hi)
    return q, k, v, kscale, vscale


# (B, S, KV, G, hd, valid_len): small-M GQA decode shapes — ragged head
# groups / head dims exercise the wrapper's padding, S=384 the multi-block
# online-softmax sweep, valid_len=1 the nearly-empty cache.
CASES = [
    (1, 128, 1, 1, 64, 37),
    (2, 256, 2, 4, 128, 256),
    (1, 128, 2, 3, 80, 1),
    (2, 384, 1, 8, 128, 200),
    (1, 256, 4, 2, 32, 100),
]


@pytest.mark.parametrize("b,s,kv,g,hd,vl", CASES)
def test_fused_matches_ref(b, s, kv, g, hd, vl):
    q, k, v, kscale, vscale = _case(
        jax.random.PRNGKey(b * s + kv + g + hd), b, s, kv, g, hd)
    got = ops.decode_attention(q, k, v, kscale, vscale, jnp.int32(vl),
                               interpret=True)
    want = ref.decode_attention_int8_ref(q, k, v, kscale, vscale,
                                         jnp.int32(vl))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_cpu_fallback_matches_interpret():
    """ops.decode_attention's CPU fallback (oracle) and the interpreted
    kernel body agree — interchangeable implementations."""
    q, k, v, kscale, vscale = _case(jax.random.PRNGKey(7), 2, 128, 2, 4, 64)
    a = ops.decode_attention(q, k, v, kscale, vscale, jnp.int32(77),
                             interpret=True)
    b = ops.decode_attention(q, k, v, kscale, vscale, jnp.int32(77),
                             interpret=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)


def test_empty_cache_returns_zeros():
    q, k, v, kscale, vscale = _case(jax.random.PRNGKey(1), 1, 128, 1, 2, 64)
    out = ops.decode_attention(q, k, v, kscale, vscale, jnp.int32(0),
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_masked_slots_do_not_leak():
    """Garbage in slots >= valid_len must not affect the output."""
    key = jax.random.PRNGKey(3)
    q, k, v, kscale, vscale = _case(key, 1, 256, 1, 4, 64)
    vl = 100
    k2 = k.at[:, vl:].set(127)
    v2 = v.at[:, vl:].set(-127)
    ks2 = kscale.at[:, vl:].set(1e3)
    vs2 = vscale.at[:, vl:].set(1e3)
    a = ops.decode_attention(q, k, v, kscale, vscale, jnp.int32(vl),
                             interpret=True)
    b = ops.decode_attention(q, k2, v2, ks2, vs2, jnp.int32(vl),
                             interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-6, atol=1e-6)


@given(st.integers(0, 2**31 - 1), st.sampled_from([17, 128, 200]))
@settings(max_examples=8, deadline=None)
def test_rows_sum_property(seed, vl):
    """With v = unit-dequant ones, every output element must be exactly 1
    (softmax rows sum to 1) regardless of mask — catches denominator and
    v-scale-folding bugs."""
    key = jax.random.PRNGKey(seed)
    b, s, kv, g, hd = 1, 256, 2, 2, 64
    q, k, _, kscale, _ = _case(key, b, s, kv, g, hd)
    v = jnp.ones((b, s, kv, hd), jnp.int8)
    vscale = jnp.ones((b, s, kv, 1), jnp.float32)
    out = ops.decode_attention(q, k, v, kscale, vscale, jnp.int32(vl),
                               interpret=True)
    np.testing.assert_allclose(np.asarray(out), 1.0, rtol=1e-5, atol=1e-5)


def _case_new(key, b, kv, hd):
    k1, k2 = jax.random.split(key)
    kn = jax.random.normal(k1, (b, 1, kv, hd), jnp.float32)
    vn = jax.random.normal(k2, (b, 1, kv, hd), jnp.float32)
    return kn, vn


@pytest.mark.parametrize("b,s,kv,g,hd,vl", CASES)
def test_append_path_matches_ref(b, s, kv, g, hd, vl):
    """Append path: the current token's k/v as an extra kernel operand
    folded into the online softmax at the final sweep step."""
    key = jax.random.PRNGKey(b * s + kv + g + hd + 1)
    q, k, v, kscale, vscale = _case(key, b, s, kv, g, hd)
    kn, vn = _case_new(jax.random.fold_in(key, 9), b, kv, hd)
    got = ops.decode_attention(q, k, v, kscale, vscale, jnp.int32(vl),
                               k_new=kn, v_new=vn, interpret=True)
    want = ref.decode_attention_int8_ref(q, k, v, kscale, vscale,
                                         jnp.int32(vl), k_new=kn, v_new=vn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_append_path_empty_cache_attends_only_to_self():
    """valid_len=0 + current-token operand: softmax collapses onto the new
    token, so out == v_new exactly (one column, prob 1)."""
    key = jax.random.PRNGKey(5)
    b, s, kv, g, hd = 1, 128, 2, 4, 64
    q, k, v, kscale, vscale = _case(key, b, s, kv, g, hd)
    kn, vn = _case_new(jax.random.fold_in(key, 1), b, kv, hd)
    out = ops.decode_attention(q, k, v, kscale, vscale, jnp.int32(0),
                               k_new=kn, v_new=vn, interpret=True)
    want = jnp.broadcast_to(vn[:, 0, :, None, :], (b, kv, g, hd))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_per_row_valid_len_matches_scalar_rows():
    """(B,) valid_len — the slot engine's per-request frontiers — equals
    running each row alone with its scalar valid_len."""
    q, k, v, kscale, vscale = _case(jax.random.PRNGKey(13), 3, 256, 2, 4, 64)
    kn, vn = _case_new(jax.random.PRNGKey(14), 3, 2, 64)
    vls = [0, 100, 256]
    got = ops.decode_attention(q, k, v, kscale, vscale,
                               jnp.array(vls, jnp.int32),
                               k_new=kn, v_new=vn, interpret=True)
    for i, vl in enumerate(vls):
        one = ops.decode_attention(
            q[i:i + 1], k[i:i + 1], v[i:i + 1], kscale[i:i + 1],
            vscale[i:i + 1], jnp.int32(vl), k_new=kn[i:i + 1],
            v_new=vn[i:i + 1], interpret=True)
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(one[0]),
                                   rtol=1e-6, atol=1e-6)


def test_append_path_matches_model_einsum():
    """The fused append path agrees with the model's einsum append branch
    (layers.attention append_only=True): cache scores + one self column,
    softmax over the concatenation, v-scale folded on cache probs only."""
    key = jax.random.PRNGKey(21)
    b, s, kv, g, hd = 2, 128, 2, 2, 64
    q, k, v, kscale, vscale = _case(key, b, s, kv, g, hd)
    kn, vn = _case_new(jax.random.fold_in(key, 2), b, kv, hd)
    vl = 90
    got = ops.decode_attention(q, k, v, kscale, vscale, jnp.int32(vl),
                               k_new=kn, v_new=vn, interpret=True)
    # the einsum append path as written in layers.attention, f32 contract
    q5 = q[:, None]                                  # (B, 1, KV, G, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q5.astype(jnp.float32),
                        k.astype(jnp.float32)) * hd ** -0.5
    scores = scores * kscale[..., 0].transpose(0, 2, 1)[:, :, None, None, :]
    valid = jnp.arange(s)[None, :] < vl
    scores = jnp.where(valid[None, None, None], scores, -1e30)
    s_self = jnp.einsum("bqkgd,btkd->bkgqt", q5.astype(jnp.float32),
                        kn.astype(jnp.float32)) * hd ** -0.5
    scores = jnp.concatenate([scores, s_self], axis=-1)
    probs = jax.nn.softmax(scores, axis=-1)
    pc, pn = probs[..., :s], probs[..., s:]
    pc = pc * vscale[..., 0].transpose(0, 2, 1)[:, :, None, None, :]
    want = jnp.einsum("bkgqs,bskd->bqkgd", pc, v.astype(jnp.float32)) \
        + jnp.einsum("bkgqt,btkd->bqkgd", pn, vn.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, 0]),
                               rtol=2e-4, atol=2e-4)


def test_matches_model_einsum_decode_path():
    """The fused kernel agrees with the model's XLA einsum decode path
    (layers.attention quantized branch) on a GQA-shaped case: the two are
    interchangeable implementations of the same math."""
    key = jax.random.PRNGKey(11)
    b, s, kv, g, hd = 2, 128, 2, 2, 64
    q, k, v, kscale, vscale = _case(key, b, s, kv, g, hd)
    vl = 90
    got = ops.decode_attention(q, k, v, kscale, vscale, jnp.int32(vl),
                               interpret=True)
    # the einsum path as written in layers.attention (scores/probs scale
    # folding, bf16 contractions) — rebuilt here with f32 contractions
    q5 = q[:, None]                                  # (B, 1, KV, G, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q5.astype(jnp.float32),
                        k.astype(jnp.float32)) * hd ** -0.5
    scores = scores * kscale[..., 0].transpose(0, 2, 1)[:, :, None, None, :]
    valid = jnp.arange(s)[None, :] < vl
    scores = jnp.where(valid[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = probs * vscale[..., 0].transpose(0, 2, 1)[:, :, None, None, :]
    want = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    want = want[:, 0]                                # (B, KV, G, hd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
