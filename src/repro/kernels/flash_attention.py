"""Pallas TPU flash attention — fused scores/softmax/context.

The §Roofline analysis shows every prefill/train cell paying HBM traffic
for materialized (blk_q, Skv) probability tiles (the pure-XLA chunked
attention).  This kernel keeps the running max/sum/accumulator in VMEM
scratch across the KV-block sweep (online softmax), so HBM traffic is just
Q + K + V + O — the flash-attention memory discipline, which is also the
paper's Unified-Buffer philosophy: keep intermediates on chip, stream only
what must move.

Grid: (BH, n_q_blocks, n_kv_blocks), KV innermost ("arbitrary"); scratch
carries (acc[blk_q, hd] f32, m[blk_q] f32, l[blk_q] f32) across the KV
sweep, exactly like the int8 matmul kernel carries its accumulator tile.
Causal/window masking is applied per element; fully-masked KV blocks are
cheap (they still stream K/V — block-level skipping is a further TPU
optimization, noted in EXPERIMENTS).

Block shapes default to MXU-aligned (128) tiles; `ops.flash_attention`
pads ragged shapes and reshapes (B, S, H, hd) <-> (B*H, S, hd).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  nk: int, blk_q: int, blk_k: int, sm_scale: float,
                  causal: bool, window: Optional[int], kv_len: int,
                  out_dtype):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                  # (blk_q, hd)
    k = k_ref[0].astype(jnp.float32)                  # (blk_k, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale

    qpos = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32,
                                                 (blk_q, blk_k), 0)
    kpos = kj * blk_k + jax.lax.broadcasted_iota(jnp.int32,
                                                 (blk_q, blk_k), 1)
    mask = kpos < kv_len                              # padding
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                               # (blk_q,)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    v = v_ref[0].astype(jnp.float32)                  # (blk_k, hd)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _final():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=(
    "blk_q", "blk_k", "causal", "window", "kv_len", "sm_scale",
    "out_dtype", "interpret"))
def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         blk_q: int = 128, blk_k: int = 128,
                         causal: bool = True,
                         window: Optional[int] = None,
                         kv_len: Optional[int] = None,
                         sm_scale: Optional[float] = None,
                         out_dtype=jnp.bfloat16,
                         interpret: bool = False) -> jax.Array:
    """q: (BH, Sq, hd); k, v: (BH, Skv, hd) — padded to block multiples.

    ``kv_len``: number of valid KV positions (<= Skv, for padded inputs).
    ``sm_scale``: softmax scale — pass the ORIGINAL hd**-0.5 when the head
    dim was zero-padded to the 128 lane width.
    """
    bh, sq, hd = q.shape
    _, skv, _ = k.shape
    assert sq % blk_q == 0 and skv % blk_k == 0, (sq, skv)
    nq, nk = sq // blk_q, skv // blk_k
    kv_len = skv if kv_len is None else kv_len

    kernel = functools.partial(
        _flash_kernel, nk=nk, blk_q=blk_q, blk_k=blk_k,
        sm_scale=sm_scale if sm_scale is not None else hd ** -0.5,
        causal=causal, window=window,
        kv_len=kv_len, out_dtype=out_dtype)

    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, blk_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, hd), jnp.float32),   # running context acc
            pltpu.VMEM((blk_q,), jnp.float32),      # running max
            pltpu.VMEM((blk_q,), jnp.float32),      # running denominator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
