"""Pallas TPU fused decode attention over an int8 KV cache.

The dominant decode memory term today is the *materialized dequantized
cache*: the XLA einsum path streams the int8 cache, widens it to bf16/f32
in HBM-visible intermediates, and pays the traffic twice.  This kernel
reads the int8 cache tiles directly into VMEM and dequantizes tile-by-tile
on the way into the MXU — HBM traffic is exactly q + int8 K + int8 V +
fp32 scales + out, the paper's Unified-Buffer discipline applied to the
serving hot loop.

Shapes (native cache layout, no transposes):

  valid_len (B,)              int32, scalar-prefetched into SMEM — slots
                              < valid_len[b] participate (per-row: the
                              slot engine's requests each sit at their
                              own sequence frontier)
  q         (B, KV, G, hd)    fp — one query token, grouped per KV head
  k, v      (B, S, KV, hd)    int8 cache slots
  k_scale,  (B, S, KV)        fp32 per-(token, head) dequant scales
  v_scale
  k_new,    (B, 1, KV, hd)    fp — OPTIONAL: the current token's k/v
  v_new                       (append path: the cache holds only tokens
                              < valid_len; the new token rides along as
                              one extra operand instead of a cache
                              rewrite inside the layer scan)
  out       (B, KV, G, hd)    fp

Grid: (B, S/blk_s) with the slot sweep innermost ("arbitrary").  Every
block spans ALL KV heads: the TPU lowering only accepts blocks whose last
two axes are whole tiles or whole array axes, so a (blk_s, 1, hd) slice
of one head out of (S, KV, hd) is refused whenever KV > 1.  The kernel
walks the heads with a static loop over the (blk_s, KV, hd) tile instead.
Scratch carries the online-softmax state per head (acc[KV, G, hd],
m[KV, G, 1], l[KV, G, 1], all f32) across the sweep, like the flash
kernel.  Each head's (blk_s, hd) int8 tile is widened and scaled in VMEM
only, for the duration of one dot.  With ``k_new``/``v_new`` the final
sweep step folds the current token into the online softmax as one more
score column before normalizing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_attn_kernel(vl_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, *rest,
                        ns: int, blk_s: int, sm_scale: float, out_dtype,
                        has_new: bool):
    if has_new:
        kn_ref, vn_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    kvh = q_ref.shape[1]
    sb = pl.program_id(1)

    @pl.when(sb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    slot = sb * blk_s + jax.lax.broadcasted_iota(jnp.int32, (1, blk_s), 1)
    valid = slot < vl_ref[pl.program_id(0)]        # (1, blk_s)
    for h in range(kvh):
        q = q_ref[0, h].astype(jnp.float32)        # (G, hd)
        # int8 -> f32 tile, dequantized by its per-token (row) scales
        k = k_ref[0, :, h, :].astype(jnp.float32) * ks_ref[0, :, h:h + 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(valid, s * sm_scale, NEG_INF)    # (G, blk_s)

        m_prev = m_ref[h]                          # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, :, h, :].astype(jnp.float32) * vs_ref[0, :, h:h + 1]
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(sb == ns - 1)
    def _final():
        for h in range(kvh):
            acc, m_run, l_run = acc_ref[h], m_ref[h], l_ref[h]
            if has_new:
                # Append path: fold the current token's k/v (already fp —
                # the caller dequantized its own-step quantization) into
                # the online softmax as one extra column.  Also covers the
                # empty-cache tick: every slot masked -> m_run = -inf ->
                # alpha underflows to 0 and the output is exactly v_new.
                q = q_ref[0, h].astype(jnp.float32)            # (G, hd)
                kn = kn_ref[0, 0, h:h + 1, :].astype(jnp.float32)  # (1, hd)
                vn = vn_ref[0, 0, h:h + 1, :].astype(jnp.float32)
                s_new = jnp.sum(q * kn, axis=1, keepdims=True) * sm_scale
                m_fin = jnp.maximum(m_run, s_new)
                alpha_f = jnp.exp(m_run - m_fin)
                p_new = jnp.exp(s_new - m_fin)
                l_run = l_run * alpha_f + p_new
                acc = acc * alpha_f + p_new * vn
            o_ref[0, h] = (acc / jnp.maximum(l_run, 1e-30)).astype(out_dtype)


def _scratch(kvh: int, g: int, hd: int):
    return [pltpu.VMEM((kvh, g, hd), jnp.float32),    # running context acc
            pltpu.VMEM((kvh, g, 1), jnp.float32),     # running max
            pltpu.VMEM((kvh, g, 1), jnp.float32)]     # running denominator


def _valid_len(valid_len, b: int) -> jax.Array:
    return jnp.broadcast_to(jnp.asarray(valid_len, jnp.int32).reshape(-1),
                            (b,))


@functools.partial(jax.jit, static_argnames=(
    "blk_s", "sm_scale", "out_dtype", "interpret"))
def decode_attention_int8(q: jax.Array, k: jax.Array, ks: jax.Array,
                          v: jax.Array, vs: jax.Array,
                          valid_len: jax.Array,
                          k_new=None, v_new=None, *, blk_s: int = 128,
                          sm_scale: float, out_dtype=jnp.float32,
                          interpret: bool = False) -> jax.Array:
    """One-token attention against an int8 KV cache (padded shapes).

    q (B, KV, G, hd) fp; k/v (B, S, KV, hd) int8; ks/vs (B, S, KV) f32;
    valid_len () or (B,) int32.  ``k_new``/``v_new`` (B, 1, KV, hd) fp:
    the append path's current-token k/v, folded in at the final sweep
    step.  G must be sublane-aligned (>= 8), hd lane-aligned (128
    multiple), S a multiple of blk_s (itself a multiple of 8) —
    `ops.decode_attention` pads.
    """
    b, kvh, g, hd = q.shape
    s_slots = k.shape[1]
    assert s_slots % blk_s == 0 and blk_s % 8 == 0, (s_slots, blk_s)
    assert (k_new is None) == (v_new is None)
    ns = s_slots // blk_s
    has_new = k_new is not None

    kernel = functools.partial(
        _decode_attn_kernel, ns=ns, blk_s=blk_s, sm_scale=sm_scale,
        out_dtype=out_dtype, has_new=has_new)
    cache_blk = pl.BlockSpec((1, blk_s, kvh, hd),
                             lambda bi, si, vl: (bi, si, 0, 0))
    scale_blk = pl.BlockSpec((1, blk_s, kvh), lambda bi, si, vl: (bi, si, 0))
    row_blk = pl.BlockSpec((1, kvh, g, hd), lambda bi, si, vl: (bi, 0, 0, 0))
    in_specs = [row_blk, cache_blk, scale_blk, cache_blk, scale_blk]
    operands = [q, k, ks, v, vs]
    if has_new:
        new_blk = pl.BlockSpec((1, 1, kvh, hd),
                               lambda bi, si, vl: (bi, 0, 0, 0))
        in_specs += [new_blk, new_blk]
        operands += [k_new, v_new]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(b, ns), in_specs=in_specs,
        out_specs=row_blk, scratch_shapes=_scratch(kvh, g, hd))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, hd), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(_valid_len(valid_len, b), *operands)


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "out_dtype", "interpret"))
def decode_attention_int8_paged(q: jax.Array, k: jax.Array, ks: jax.Array,
                                v: jax.Array, vs: jax.Array,
                                valid_len: jax.Array,
                                block_tables: jax.Array,
                                k_new=None, v_new=None, *,
                                sm_scale: float, out_dtype=jnp.float32,
                                interpret: bool = False) -> jax.Array:
    """Paged variant: the same online-softmax sweep, but K/V tiles are
    physical KV blocks gathered through a per-row block table.

    q (B, KV, G, hd) fp; k/v (NB, bs, KV, hd) int8 physical blocks;
    ks/vs (NB, bs, KV) f32; block_tables (B, MB) int32; valid_len () or
    (B,) int32 counts LOGICAL positions.  ``k_new``/``v_new``
    (B, 1, KV, hd) fp: the current token's k/v (the engine scatters the
    new entry into its block after attention, so the cache holds tokens
    < valid_len and the new token rides as the append column).

    The table and the frontiers are scalar-prefetch operands: grid step
    (bi, si) streams block ``block_tables[bi, si]`` — the sweep that
    walks contiguous slot tiles now walks table entries, so the kernel
    body is reused unchanged with ns=MB, blk_s=bs (its position mask
    ``si*bs + i < valid_len`` is logical-position math either way).
    Entries past a row's frontier point at the reserved trash block 0;
    their finite garbage is masked exactly like padding.
    """
    b, kvh, g, hd = q.shape
    bs = k.shape[1]
    mb = block_tables.shape[1]
    assert (k_new is None) == (v_new is None)
    has_new = k_new is not None

    def kernel(tbl_ref, *refs):
        del tbl_ref    # consumed by the index maps below
        _decode_attn_kernel(*refs, ns=mb, blk_s=bs, sm_scale=sm_scale,
                            out_dtype=out_dtype, has_new=has_new)

    cache_blk = pl.BlockSpec((1, bs, kvh, hd),
                             lambda bi, si, tbl, vl: (tbl[bi, si], 0, 0, 0))
    scale_blk = pl.BlockSpec((1, bs, kvh),
                             lambda bi, si, tbl, vl: (tbl[bi, si], 0, 0))
    row_blk = pl.BlockSpec((1, kvh, g, hd),
                           lambda bi, si, tbl, vl: (bi, 0, 0, 0))
    in_specs = [row_blk, cache_blk, scale_blk, cache_blk, scale_blk]
    operands = [q, k, ks, v, vs]
    if has_new:
        new_blk = pl.BlockSpec((1, 1, kvh, hd),
                               lambda bi, si, tbl, vl: (bi, 0, 0, 0))
        in_specs += [new_blk, new_blk]
        operands += [k_new, v_new]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b, mb), in_specs=in_specs,
        out_specs=row_blk, scratch_shapes=_scratch(kvh, g, hd))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, hd), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32), _valid_len(valid_len, b),
      *operands)
