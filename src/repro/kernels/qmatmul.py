"""Pallas TPU kernels for quantized matmul — the MXU-era analogue of the
TPU v1 MatrixMultiply → Accumulators → Activate pipeline.

Two kernels:

``qmatmul_w8a8``  int8 × int8 → int32 accumulate → fused dequant + bias +
                  activation → fp out.  The paper-faithful path: both operands
                  8-bit, products accumulated at 32 bit ("4 MiB of 32-bit
                  Accumulators"), nonlinearity applied on the way out of the
                  accumulators ("Activate ... inputs are the Accumulators").

``qmatmul_w8a16`` bf16/f32 activations × int8 weights, dequantized inside the
                  kernel tile-by-tile, fp32 accumulate.  The modern
                  weight-only-quant serving mode; memory-roofline-wise it is
                  the paper's TPU' insight (halve weight bytes → move the
                  memory term) applied at the kernel level.

Dataflow / BlockSpec design (HW adaptation notes):

- The weights are a layer stack, (L, K, N) int8 with (L, 1, N) column
  scales, and the layer to read is a scalar prefetched into the index
  maps: a layer scan hands the kernel the whole stack, so no layer's
  weights are sliced, relaid out or padded before the kernel reads them.
  One matrix is a stack of one (``ops.qmatmul`` reshapes it, a bitcast).
- Grid is (M/bm, cdiv(N, bn), K/bk) with K innermost ("arbitrary"); an
  int32/f32 accumulator tile lives in VMEM scratch across the K sweep —
  this is the Accumulator bank.  The last column block may be ragged: its
  out-of-bounds columns read whatever lies past N and are never written
  back.  ``bk`` divides K, since a ragged K block would add out-of-bounds
  products into real outputs; only the rows (M) are padded, by
  ``ops.qmatmul``.  Pallas's automatic pipelining double-buffers the incoming
  weight tiles, playing the role of the 4-tile-deep Weight FIFO.
- Activations stream from the Unified Buffer analogue (VMEM blocks of x);
  weights stream from HBM (Weight Memory).  Ops/weight-byte of one call is
  2·M — matching the paper's operational-intensity definition.
- Block shapes default to MXU-aligned multiples of 128; int8 K-tiles are 256
  wide since 8-bit operands pack 2× per register lane.  Small-M decode
  problems (M = batch, often 8–64) pass bm ∈ {8, 16, 32} GEMV-style row
  tiles instead of padding to 128 rows; `kernels/autotune.py` picks the
  tile per (M, K, N, mode) under the VMEM budget and `ops.py` threads the
  choice through.
- The bias tile is only streamed when a bias exists: the in_specs/operand
  list is built conditionally, so the bias-free path (most serving
  matmuls) saves one VMEM stream per tile.
- Per-output-channel weight scales (1, bn) and a per-tensor (or per-row)
  activation scale are fused into the accumulator drain, together with bias
  and the Activate-unit nonlinearity (ReLU / sigmoid / tanh of the paper, plus
  gelu / silu for the modern archs).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ACTIVATIONS = ("none", "relu", "gelu", "silu", "tanh", "sigmoid")


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


def _linear_call(kernel, x, w, w_scale, layer, bias, extra, *, bm, bn, bk,
                 acc_dtype, out_dtype, interpret, name):
    """One pallas_call of an int8 linear kernel on layer ``layer`` of the
    stack ``w`` (L, K, N) with column scales ``w_scale`` (L, 1, N).
    ``extra`` are (operand, block shape) pairs read whole by every grid
    step, between the weight scales and the bias."""
    m, k = x.shape
    nl, k2, n = w.shape
    assert k == k2 and w_scale.shape == (nl, 1, n), (
        x.shape, w.shape, w_scale.shape)
    assert m % bm == 0 and k % bk == 0, \
        f"rows {m} or depth {k} not a multiple of blocks {(bm, bk)}"
    has_bias = bias is not None
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk, lr: (i, kk)),     # acts (UB)
        pl.BlockSpec((None, bk, bn),                              # weights
                     lambda i, j, kk, lr: (lr[0], kk, j)),        # (FIFO)
        pl.BlockSpec((None, 1, bn),                               # col scales
                     lambda i, j, kk, lr: (lr[0], 0, j)),
    ]
    operands = [x, w, w_scale.astype(jnp.float32)]
    for operand, block in extra:
        in_specs.append(pl.BlockSpec(block, lambda i, j, kk, lr: (0, 0)))
        operands.append(operand)
    if has_bias:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk, lr: (0, j)))
        operands.append(bias.reshape(1, n).astype(jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(m // bm, pl.cdiv(n, bn), k // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, lr: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)])       # Accumulators
    return pl.pallas_call(
        functools.partial(kernel, nk=k // bk, has_bias=has_bias),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(jnp.asarray(layer, jnp.int32).reshape(1), *operands)


# ---------------------------------------------------------------------------
# w8a8: int8 x int8 -> int32 accumulate -> dequant -> act
# ---------------------------------------------------------------------------

def _w8a8_kernel(layer_ref, x_ref, w_ref, ws_ref, xs_ref, *rest,
                 nk: int, activation: str, out_dtype, has_bias: bool):
    del layer_ref                               # consumed by the index maps
    if has_bias:
        b_ref, o_ref, acc_ref = rest
    else:
        b_ref, (o_ref, acc_ref) = None, rest
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 x int8 -> int32 on the MXU (preferred_element_type drives the
    # 32-bit accumulate, exactly the paper's 16-bit products -> 32-bit acc).
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == nk - 1)
    def _drain():
        acc = acc_ref[...].astype(jnp.float32)
        # dequant: per-tensor act scale (scalar) x per-column weight scale.
        out = acc * xs_ref[0, 0] * ws_ref[...]
        if b_ref is not None:
            out = out + b_ref[...]
        o_ref[...] = _activate(out, activation).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "activation", "out_dtype", "interpret"))
def qmatmul_w8a8(x: jax.Array, w: jax.Array, x_scale: jax.Array,
                 w_scale: jax.Array, layer: jax.Array,
                 bias: Optional[jax.Array] = None, *,
                 bm: int = 128, bn: int = 128, bk: int = 256,
                 activation: str = "none", out_dtype=jnp.float32,
                 interpret: bool = False) -> jax.Array:
    """out = act((x_int8 @ w_int8[layer]) * x_scale * w_scale[layer] + bias).

    x: (M, K) int8, M a multiple of bm.  w: (L, K, N) int8.  x_scale:
    scalar ().  w_scale: (L, 1, N).  layer: () or (1,) int32.  bias: (N,)
    fp or None.  bk divides K; N needs no multiple of bn.
    """
    kernel = functools.partial(_w8a8_kernel, activation=activation,
                               out_dtype=out_dtype)
    xs = x_scale.reshape(1, 1).astype(jnp.float32)
    return _linear_call(kernel, x, w, w_scale, layer, bias, [(xs, (1, 1))],
                        bm=bm, bn=bn, bk=bk, acc_dtype=jnp.int32,
                        out_dtype=out_dtype, interpret=interpret,
                        name="qmatmul_w8a8")


# ---------------------------------------------------------------------------
# w8a16: fp acts x int8 weights (dequant in-kernel), fp32 accumulate
# ---------------------------------------------------------------------------

def _w8a16_kernel(layer_ref, x_ref, w_ref, ws_ref, *rest,
                  nk: int, activation: str, out_dtype, has_bias: bool):
    del layer_ref                               # consumed by the index maps
    if has_bias:
        b_ref, o_ref, acc_ref = rest
    else:
        b_ref, (o_ref, acc_ref) = None, rest
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Dequantize the resident weight tile once per (j, kk) visit; fp32 MACs.
    w_tile = w_ref[...].astype(jnp.float32) * ws_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w_tile,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _drain():
        out = acc_ref[...]
        if b_ref is not None:
            out = out + b_ref[...]
        o_ref[...] = _activate(out, activation).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "activation", "out_dtype", "interpret"))
def qmatmul_w8a16(x: jax.Array, w: jax.Array, w_scale: jax.Array,
                  layer: jax.Array, bias: Optional[jax.Array] = None, *,
                  bm: int = 128, bn: int = 128, bk: int = 256,
                  activation: str = "none", out_dtype=jnp.bfloat16,
                  interpret: bool = False) -> jax.Array:
    """out = act((x_fp @ dequant(w_int8[layer])) + bias); weight-only
    quantization.

    x: (M, K) bf16/f32, M a multiple of bm.  w: (L, K, N) int8.  w_scale:
    (L, 1, N).  layer: () or (1,) int32.  bias: (N,) fp or None.  bk
    divides K; N needs no multiple of bn.
    """
    kernel = functools.partial(_w8a16_kernel, activation=activation,
                               out_dtype=out_dtype)
    return _linear_call(kernel, x, w, w_scale, layer, bias, [],
                        bm=bm, bn=bn, bk=bk, acc_dtype=jnp.float32,
                        out_dtype=out_dtype, interpret=interpret,
                        name="qmatmul_w8a16")
