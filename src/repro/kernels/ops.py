"""Public jit'd wrappers around the Pallas quantized-matmul kernels.

Responsibilities:
- accept ND activations (leading dims flattened to M),
- resolve tile shapes: explicit (bm, bn, bk) overrides win, otherwise
  `kernels.autotune.best_config` picks tuned tiles per (M, K, N, mode) —
  small-M decode problems get GEMV-style bm ∈ {8, 16, 32} row tiles
  instead of padding to 128 rows,
- pad M up to the chosen row block and slice the result back; weights,
  scales and bias are never padded (a ragged last column block, and a
  ``bk`` that divides K),
- hand a layer-stacked int8 weight to the kernel whole, with the index of
  the layer to read,
- dispatch: TPU backend -> compiled Pallas kernel; CPU -> the jnp oracle
  (numerically identical contract) unless ``interpret=True`` is forced, which
  runs the actual kernel body through the Pallas interpreter for validation,
- integrate with `repro.core.quant.QTensor`.

This is the only module model code should import from kernels/.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quant import QTensor, quantize
from repro.kernels import autotune as _at
from repro.kernels import qmatmul as _k
from repro.kernels import ref as _ref


def on_tpu() -> bool:
    """The one backend predicate of the model and kernel code: True when
    the default backend is a TPU, so Pallas kernels compile for the chip.
    A compile rehearsal for a described (unattached) TPU patches this one
    function to steer every TPU branch while JAX itself runs on the CPU."""
    return jax.default_backend() == "tpu"


def _pad_to(x: jax.Array, mult: int, axis: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _dtype_name(dtype) -> str:
    return "f32" if dtype == jnp.float32 else "bf16"


def _resolve_blocks(m: int, k: int, n: int, *, mode: str, x_dtype: str,
                    out_dtype: str, has_bias: bool, bm: Optional[int],
                    bn: Optional[int], bk: Optional[int]):
    """Fill unspecified block dims from the autotuner (explicit args win).

    Runs at trace time on static shapes: the analytic choice is a
    Python int baked into the compiled kernel, the same on every run.
    """
    if bm is not None and bn is not None and bk is not None:
        return bm, bn, bk
    tc = _at.best_config(m, k, n, mode=mode, x_dtype=x_dtype,
                         out_dtype=out_dtype, has_bias=has_bias)
    return bm or tc.bm, bn or tc.bn, bk or tc.bk


@functools.partial(jax.jit, static_argnames=(
    "activation", "out_dtype", "interpret", "bm", "bn", "bk"))
def qmatmul(x, w: QTensor, bias: Optional[jax.Array] = None, *,
            layer=None, x_q: Optional[QTensor] = None,
            activation: str = "none", out_dtype=jnp.bfloat16,
            interpret: bool = False, bm: Optional[int] = None,
            bn: Optional[int] = None, bk: Optional[int] = None) -> jax.Array:
    """act((x @ dequant(w)) + bias) with int8 weights.

    ``x`` fp array of shape (..., K).  ``w`` a QTensor: one (K, N) matrix
    with per-column scales, or, with ``layer`` () int32, a layer stack
    (L, K, N) with (L, 1, N) scales of which layer ``layer`` is read, in
    place.  If ``x_q`` is given (pre-quantized activations, per-tensor
    scale), the full w8a8 integer path runs; otherwise weight-only w8a16.
    ``bm``/``bn``/``bk`` override the autotuned tile shape when given.
    """
    if not isinstance(w, QTensor):
        raise TypeError("w must be a QTensor; quantize with quantize_weight()")
    lead = x.shape[:-1]
    kdim = x.shape[-1]
    n = w.shape[-1]
    x2 = x.reshape(-1, kdim)
    m = x2.shape[0]
    w8a8 = x_q is not None
    if w8a8:
        xq2 = x_q.values.reshape(-1, kdim)
        xs = x_q.scale.reshape(())

    if not (on_tpu() or interpret):
        values, scale = w.values, w.scale
        if layer is not None:
            values, scale = (jax.lax.dynamic_index_in_dim(a, layer, 0, False)
                             for a in (values, scale))
        if w8a8:
            out = _ref.qmatmul_w8a8_ref(
                xq2, values, xs, scale.reshape(-1), bias,
                activation=activation, out_dtype=out_dtype)
        else:
            out = _ref.qmatmul_w8a16_ref(
                x2, values, scale.reshape(-1), bias,
                activation=activation, out_dtype=out_dtype)
        return out.reshape(*lead, n)

    # one matrix is a stack of one: reshapes, no copies
    stack = w.values.reshape(-1, kdim, n)
    scales = w.scale.reshape(stack.shape[0], 1, n)
    layer = jnp.zeros((), jnp.int32) if layer is None else layer
    bm, bn, bk = _resolve_blocks(
        m, kdim, n, mode="w8a8" if w8a8 else "w8a16",
        x_dtype="int8" if w8a8 else _dtype_name(x.dtype),
        out_dtype=_dtype_name(out_dtype),
        has_bias=bias is not None, bm=bm, bn=bn, bk=bk)
    run_interp = interpret and not on_tpu()
    if w8a8:
        out = _k.qmatmul_w8a8(
            _pad_to(xq2, bm, 0), stack, xs, scales, layer, bias,
            bm=bm, bn=bn, bk=bk, activation=activation,
            out_dtype=out_dtype, interpret=run_interp)
    else:
        out = _k.qmatmul_w8a16(
            _pad_to(x2, bm, 0), stack, scales, layer, bias,
            bm=bm, bn=bn, bk=bk, activation=activation,
            out_dtype=out_dtype, interpret=run_interp)
    return out[:m].reshape(*lead, n)


def qmatmul_dynamic(x, w: QTensor, bias=None, *, layer=None,
                    activation: str = "none", out_dtype=jnp.bfloat16,
                    interpret: bool = False):
    """w8a8 with on-the-fly per-tensor activation quantization (the TPU's
    quantize-on-entry-to-UB behaviour)."""
    x_q = quantize(x.astype(jnp.float32), bits=8, axis=None)
    return qmatmul(x, w, bias, layer=layer, x_q=x_q, activation=activation,
                   out_dtype=out_dtype, interpret=interpret)


def decode_attention(q, k, v, k_scale, v_scale, valid_len, *,
                     block_tables=None, k_new=None, v_new=None,
                     blk_s: int = 128, out_dtype=jnp.float32,
                     interpret: bool = False):
    """Fused one-token attention against an int8 KV cache.

    q: (B, KV, G, hd) fp — current token's queries grouped per KV head;
    k, v: (B, S, KV, hd) int8 cache; k_scale, v_scale: (B, S, KV) or
    (B, S, KV, 1) fp32 per-(token, head) scales; valid_len: () or (B,)
    int32 (per-row frontiers for the slot engine).

    ``k_new``/``v_new`` (B, 1, KV, hd) or (B, KV, hd) fp: the current
    token's k/v for the append path — the cache then holds only tokens
    < valid_len and the new token joins the softmax as one extra operand
    column inside the kernel (no cache rewrite inside the layer scan).

    ``block_tables`` (B, MB) int32 switches to the PAGED cache layout:
    k/v become physical blocks (NB, bs, KV, hd) (scales (NB, bs, KV) or
    (NB, bs, KV, 1)) and the kernel gathers each row's tiles through its
    table via scalar prefetch; valid_len still counts logical positions.

    TPU (or ``interpret=True``) -> the Pallas kernel, which dequantizes
    tile-by-tile in VMEM; CPU -> the dense jnp oracle (identical math).
    Padding: G to the 8-sublane floor, hd to the 128 lane width, S to a
    blk_s multiple (padded slots are masked by ``valid_len``).
    """
    b, kvh, g, hd = q.shape
    sm_scale = hd ** -0.5
    if (k_new is None) != (v_new is None):
        raise ValueError("k_new and v_new must be passed together")
    if k_new is not None:
        k_new = k_new.reshape(b, 1, kvh, hd)
        v_new = v_new.reshape(b, 1, kvh, hd)
    use_pallas = on_tpu() or interpret
    if block_tables is not None:
        nb, bs = k.shape[0], k.shape[1]
        ks = k_scale.reshape(nb, bs, kvh)
        vs = v_scale.reshape(nb, bs, kvh)
        if not use_pallas:
            return _ref.decode_attention_paged_ref(
                q, k, v, ks, vs, valid_len, block_tables,
                k_new=k_new, v_new=v_new, sm_scale=sm_scale,
                out_dtype=out_dtype)
        sub = 8 if q.dtype == jnp.float32 else 16
        gp = max(sub, -(-g // sub) * sub)
        qp = _pad_to(_pad_to(q, gp, 2), 128, 3)
        kp = _pad_to(k, 128, 3)
        vp = _pad_to(v, 128, 3)
        knp = _pad_to(k_new, 128, 3) if k_new is not None else None
        vnp = _pad_to(v_new, 128, 3) if v_new is not None else None
        from repro.kernels import decode_attention as _da
        out = _da.decode_attention_int8_paged(
            qp, kp, ks, vp, vs, jnp.asarray(valid_len),
            jnp.asarray(block_tables, jnp.int32), knp, vnp,
            sm_scale=sm_scale, out_dtype=out_dtype,
            interpret=interpret and not on_tpu())
        return out[:, :, :g, :hd]
    s_slots = k.shape[1]
    ks = k_scale.reshape(b, s_slots, kvh)
    vs = v_scale.reshape(b, s_slots, kvh)
    if not use_pallas:
        out = _ref.decode_attention_int8_ref(
            q, k, v, ks, vs, valid_len, k_new=k_new, v_new=v_new,
            sm_scale=sm_scale, out_dtype=out_dtype)
        return out
    # query-group rows padded to the sublane floor of q's dtype (f32 8,
    # bf16 16) — the (1, 1, G, hd) query block must be a legal tile
    sub = 8 if q.dtype == jnp.float32 else 16
    gp = max(sub, -(-g // sub) * sub)
    qp = _pad_to(_pad_to(q, gp, 2), 128, 3)
    kp = _pad_to(_pad_to(k, blk_s, 1), 128, 3)
    vp = _pad_to(_pad_to(v, blk_s, 1), 128, 3)
    ksp = _pad_to(ks, blk_s, 1)
    vsp = _pad_to(vs, blk_s, 1)
    knp = _pad_to(k_new, 128, 3) if k_new is not None else None
    vnp = _pad_to(v_new, 128, 3) if v_new is not None else None
    from repro.kernels import decode_attention as _da
    out = _da.decode_attention_int8(
        qp, kp, ksp, vp, vsp, jnp.asarray(valid_len), knp, vnp,
        blk_s=blk_s, sm_scale=sm_scale, out_dtype=out_dtype,
        interpret=interpret and not on_tpu())
    return out[:, :, :g, :hd]


def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    blk_q: int = 128, blk_k: int = 128,
                    out_dtype=None, sm_scale=None, interpret: bool = False):
    """Fused flash attention.  q: (B, Sq, H, hd); k, v: (B, Skv, H, hd)
    (KV already expanded to H heads); scores scaled by ``sm_scale``,
    default hd ** -0.5.  TPU -> Pallas kernel; CPU -> dense oracle unless
    ``interpret=True`` (kernel body under the interpreter).
    """
    from repro.kernels import flash_attention as _fa
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    out_dtype = out_dtype or q.dtype
    use_pallas = on_tpu() or interpret
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kr = k.transpose(0, 2, 1, 3).reshape(b * h, skv, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(b * h, skv, hd)
    if use_pallas:
        bq = min(blk_q, max(8, sq))
        bk = min(blk_k, max(8, skv))
        qp = _pad_to(_pad_to(qr, bq, 1), 128, 2)
        kp = _pad_to(_pad_to(kr, bk, 1), 128, 2)
        vp = _pad_to(_pad_to(vr, bk, 1), 128, 2)
        out = _fa.flash_attention_bhsd(
            qp, kp, vp, blk_q=bq, blk_k=bk, causal=causal, window=window,
            kv_len=skv, sm_scale=hd ** -0.5 if sm_scale is None else sm_scale,
            out_dtype=out_dtype,
            interpret=interpret and not on_tpu())
        out = out[:, :sq, :hd]
    else:
        out = _ref.flash_attention_ref(qr, kr, vr, causal=causal,
                                       window=window, sm_scale=sm_scale,
                                       out_dtype=out_dtype)
    return out.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)


# Largest state tile one grid step of the SSD state kernel moves each way.
# Double-buffered in and out it takes four times this in VMEM, inside the
# default scoped limit.  On a v5e at mamba2-1.3b's widths and 32 slots a
# call read 235 us with 2 MiB tiles (64 heads), 243 us with 1 MiB and
# 264 us with 512 KiB: fewer grid steps, less fixed cost.
SSM_TILE_BYTES = 2 << 20


def ssm_head_block(nh: int, hd: int, n: int) -> int:
    """Heads per grid step of the SSD state kernel: the largest divisor of
    ``nh`` whose float32 (hb, hd, n) tile fits ``SSM_TILE_BYTES``."""
    fits = [d for d in range(1, nh + 1)
            if nh % d == 0 and d * hd * n * 4 <= SSM_TILE_BYTES]
    return max(fits, default=1)


def ssm_state_update(h, layer, a, x, dt, bm, cm, fresh, active, *,
                     hb: Optional[int] = None, interpret: bool = False):
    """One SSD decode step on layer ``layer`` of the stacked state.

    h (L, B, H, hd, N) float32; layer () int32; a (B, H) per-head decay;
    x (B, H, hd); dt (B, H); bm, cm (B, N); fresh, active (B,) bool.
    Rows with ``fresh`` start from zero state, rows without ``active``
    keep their state bitwise.  Returns ``(h, y)`` with y (B, H, hd)
    float32 the read-out of the updated state.

    TPU (or ``interpret=True``) -> the Pallas kernel, which updates ``h``
    in place in one pass; ``hb`` overrides the head block.  CPU -> the
    jnp oracle.
    """
    a, x, dt, bm, cm = (t.astype(jnp.float32) for t in (a, x, dt, bm, cm))
    if not (on_tpu() or interpret):
        return _ref.ssm_state_update_ref(h, layer, a, x, dt, bm, cm,
                                         fresh, active)
    from repro.kernels import ssm_state as _ss
    b, nh, hd = x.shape
    hb = hb or ssm_head_block(nh, hd, h.shape[-1])

    def head_cols(t):                  # (B, H, r) -> (B, H/hb, r, hb)
        return t.reshape(b, nh // hb, hb, -1).swapaxes(-1, -2)

    h, y = _ss.ssm_state_update(
        h, layer, head_cols(a[..., None]), head_cols(dt[..., None] * x),
        bm[:, None], cm[:, None], fresh, active,
        interpret=interpret and not on_tpu())
    return h, y.swapaxes(-1, -2).reshape(b, nh, hd)


def _lane_block(dim: int, cap: int = 1024) -> int:
    """The largest multiple of 128 that divides ``dim`` and is at most
    ``cap``; ``dim`` itself where it is not a multiple of 128."""
    if dim % 128:
        return dim
    return max(b for b in range(128, min(dim, cap) + 1, 128)
               if dim % b == 0)


def moe_w8a16(x, w: QTensor, layer, counts, *,
              out_dtype=jnp.float32, interpret: bool = False):
    """Every held expert's int8 weights times its rows, W8A16.

    ``x`` (M, K) rows every expert reads, or (E, M, K) one set per
    expert; ``w`` a QTensor of (L, E, K, N) int8 values with
    (L, E, 1, N) column scales, stacked over layers, and ``layer`` the
    one to read; ``counts`` (E,) rows routed to each expert, an expert
    with none gives zeros.  Returns (E, M, N) in ``out_dtype``.

    TPU (or ``interpret=True``) -> the Pallas kernel
    (``kernels/moe_w8a16.py``), which reads each expert's int8 weights
    and scales once and converts them in VMEM; CPU -> the jnp oracle.
    """
    values, scale = w.values, w.scale
    k, n = values.shape[2:]
    m = x.shape[-2]
    counts = jnp.asarray(counts, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    if not (on_tpu() or interpret):
        return _ref.moe_w8a16_ref(x, values, scale, layer, counts,
                                  out_dtype=out_dtype)
    from repro.kernels import moe_w8a16 as _moe
    bk, bn = _lane_block(k), _lane_block(n)
    bm = min(256, -(-m // 16) * 16)             # bf16 rows pack 16 a tile
    xp = _pad_to(x.astype(jnp.bfloat16), bm, x.ndim - 2)
    out = _moe.moe_w8a16(xp, values, scale, layer, counts, bm=bm, bn=bn,
                         bk=bk, out_dtype=out_dtype,
                         interpret=interpret and not on_tpu())
    return out[:, :m]
