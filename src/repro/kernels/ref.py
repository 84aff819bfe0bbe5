"""Pure-jnp oracles for the Pallas kernels in this package.

Every kernel in kernels/ must agree with its oracle here (tests sweep shapes
and dtypes in interpret mode).  The oracles are also the CPU fallback used by
ops.py when not running on TPU and not asked for interpret-mode execution.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def _activate(x: jax.Array, activation: str) -> jax.Array:
    if activation == "none":
        return x
    if activation == "relu":
        return jnp.maximum(x, 0.0)
    if activation == "gelu":
        return jax.nn.gelu(x)
    if activation == "silu":
        return x * jax.nn.sigmoid(x)
    if activation == "tanh":
        return jnp.tanh(x)
    if activation == "sigmoid":
        return jax.nn.sigmoid(x)
    raise ValueError(f"unknown activation {activation!r}")


def qmatmul_w8a8_ref(x: jax.Array, w: jax.Array, x_scale: jax.Array,
                     w_scale: jax.Array, bias: Optional[jax.Array] = None, *,
                     activation: str = "none",
                     out_dtype=jnp.float32) -> jax.Array:
    """int8 x int8 -> int32 -> dequant -> bias -> act, bit-exact accumulate."""
    acc = jax.lax.dot_general(
        x, w, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * x_scale.astype(jnp.float32) \
        * w_scale.reshape(1, -1).astype(jnp.float32)
    if bias is not None:
        out = out + bias.reshape(1, -1).astype(jnp.float32)
    return _activate(out, activation).astype(out_dtype)


def qmatmul_w8a16_ref(x: jax.Array, w: jax.Array, w_scale: jax.Array,
                      bias: Optional[jax.Array] = None, *,
                      activation: str = "none",
                      out_dtype=jnp.bfloat16) -> jax.Array:
    """fp acts x dequantized int8 weights, fp32 accumulate."""
    w_fp = w.astype(jnp.float32) * w_scale.reshape(1, -1).astype(jnp.float32)
    acc = jax.lax.dot_general(
        x.astype(jnp.float32), w_fp,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if bias is not None:
        acc = acc + bias.reshape(1, -1).astype(jnp.float32)
    return _activate(acc, activation).astype(out_dtype)


def decode_attention_int8_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                              k_scale: jax.Array, v_scale: jax.Array,
                              valid_len, *, k_new=None, v_new=None,
                              sm_scale=None,
                              out_dtype=jnp.float32) -> jax.Array:
    """Dense one-token attention against an int8 KV cache.

    q: (B, KV, G, hd) fp; k, v: (B, S, KV, hd) int8; k_scale, v_scale:
    (B, S, KV) or (B, S, KV, 1) fp32; valid_len: () or (B,) int32 — slots
    with index < valid_len[b] participate.  ``k_new``/``v_new``
    (B, 1, KV, hd) or (B, KV, hd) fp: the append path's current-token
    k/v, one extra (always-valid) softmax column.  Dequantizes the cache
    densely (the thing the fused kernel avoids) and runs a masked softmax.
    """
    b, _, _, hd = q.shape
    kvh = k.shape[2]
    sm_scale = hd ** -0.5 if sm_scale is None else sm_scale
    ks = k_scale.reshape(k.shape[:3]).astype(jnp.float32)
    vs = v_scale.reshape(v.shape[:3]).astype(jnp.float32)
    kf = k.astype(jnp.float32) * ks[..., None]
    vf = v.astype(jnp.float32) * vs[..., None]
    scores = jnp.einsum("bkgd,bskd->bkgs", q.astype(jnp.float32),
                        kf) * sm_scale
    vl = jnp.asarray(valid_len).reshape(-1, 1)          # (1|B, 1)
    valid = jnp.arange(k.shape[1])[None, :] < vl        # (1|B, S)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    if k_new is None:
        probs = jax.nn.softmax(scores, axis=-1)
        probs = jnp.where(valid[:, None, None, :], probs, 0.0)
        return jnp.einsum("bkgs,bskd->bkgd", probs, vf).astype(out_dtype)
    kn = k_new.reshape(b, kvh, hd).astype(jnp.float32)
    vn = v_new.reshape(b, kvh, hd).astype(jnp.float32)
    s_new = jnp.einsum("bkgd,bkd->bkg", q.astype(jnp.float32),
                       kn) * sm_scale
    scores = jnp.concatenate([scores, s_new[..., None]], axis=-1)
    probs = jax.nn.softmax(scores, axis=-1)             # (B, KV, G, S+1)
    p_cache, p_new = probs[..., :-1], probs[..., -1]
    p_cache = jnp.where(valid[:, None, None, :], p_cache, 0.0)
    out = jnp.einsum("bkgs,bskd->bkgd", p_cache, vf) \
        + p_new[..., None] * vn[:, :, None, :]
    return out.astype(out_dtype)


def decode_attention_paged_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                               k_scale: jax.Array, v_scale: jax.Array,
                               valid_len, block_tables: jax.Array, *,
                               k_new=None, v_new=None, sm_scale=None,
                               out_dtype=jnp.float32) -> jax.Array:
    """Oracle for the paged (block-table) decode-attention kernel.

    k, v: (NB, bs, KV, hd) int8 physical blocks; k_scale, v_scale:
    (NB, bs, KV) or (NB, bs, KV, 1) fp32; block_tables: (B, MB) int32 —
    row b's logical position p lives at block ``block_tables[b, p // bs]``
    offset ``p % bs``.  Gathers the blocks into the contiguous
    (B, MB*bs, ...) layout and delegates to the dense oracle, so the
    paged kernel's contract IS the dense kernel's contract composed with
    the table gather.
    """
    def gather(c):
        g = c[block_tables]                   # (B, MB, bs, ...)
        return g.reshape((g.shape[0], g.shape[1] * g.shape[2])
                         + g.shape[3:])

    ks = k_scale.reshape(k.shape[:3])
    vs = v_scale.reshape(v.shape[:3])
    return decode_attention_int8_ref(
        q, gather(k), gather(v), gather(ks), gather(vs), valid_len,
        k_new=k_new, v_new=v_new, sm_scale=sm_scale, out_dtype=out_dtype)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window=None,
                        kv_len=None, sm_scale=None,
                        out_dtype=jnp.bfloat16) -> jax.Array:
    """Dense softmax attention oracle.  q: (BH, Sq, hd); k,v: (BH, Skv, hd)."""
    bh, sq, hd = q.shape
    skv = k.shape[1]
    kv_len = skv if kv_len is None else kv_len
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (
                       hd ** -0.5 if sm_scale is None else sm_scale)
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(skv)[None, :]
    mask = kpos < kv_len
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # rows with no valid keys (can happen under padding) -> zero output
    p = jnp.where(mask[None], p, 0.0)
    return jnp.einsum("bqk,bkd->bqd", p,
                      v.astype(jnp.float32)).astype(out_dtype)


def ssm_state_update_ref(h: jax.Array, layer, a: jax.Array, x: jax.Array,
                         dt: jax.Array, bm: jax.Array, cm: jax.Array,
                         fresh: jax.Array, active: jax.Array):
    """Oracle for the in-place SSD state update of layer ``layer``.

    h (L, B, H, hd, N) float32 stacked state; a (B, H) per-head decay;
    x (B, H, hd); dt (B, H); bm, cm (B, N); fresh, active (B,) bool.
    Rows with ``fresh`` start from a zero state; rows without ``active``
    keep theirs bitwise.  Returns the stacked state with layer ``layer``
    replaced, and the read-out y (B, H, hd) of the updated (unfrozen)
    state."""
    hl = jax.lax.dynamic_index_in_dim(h, layer, 0, keepdims=False)
    h_in = jnp.where(fresh[:, None, None, None], jnp.zeros_like(hl), hl)
    contrib = jnp.einsum("bn,bhd,bh->bhdn", bm, x, dt)
    new = a[..., None, None] * h_in + contrib
    y = jnp.einsum("bn,bhdn->bhd", cm, new)
    kept = jnp.where(active[:, None, None, None], new, hl)
    return jax.lax.dynamic_update_index_in_dim(h, kept, layer, 0), y


def moe_w8a16_ref(x: jax.Array, w: jax.Array, scale: jax.Array, layer,
                  counts: jax.Array, *, out_dtype=jnp.float32) -> jax.Array:
    """Oracle for the held-expert grouped matmul of layer ``layer``.

    x (M, K) rows shared by every expert, or (E, M, K) one set per
    expert (the kernel reads them as bf16); w (L, E, K, N) int8; scale
    (L, E, 1, N);
    counts (E,) rows routed to each expert.  Returns (E, M, N):
    ``x_e @ (w[l, e] * scale[l, e])``, zero for an expert with no rows."""
    wl = jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)
    sl = jax.lax.dynamic_index_in_dim(scale, layer, 0, keepdims=False)
    xf = x.astype(jnp.float32)
    spec = "mk,ekn->emn" if x.ndim == 2 else "emk,ekn->emn"
    acc = jnp.einsum(spec, xf, wl.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    out = jnp.where(counts[:, None, None] > 0, acc * sl, 0.0)
    return out.astype(out_dtype)
