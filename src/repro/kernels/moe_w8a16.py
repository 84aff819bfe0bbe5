"""Pallas TPU grouped matmul over the routed experts this chip holds.

One call multiplies rows by every held expert's int8 weight matrix of
one layer, W8A16: the weights stream from HBM as int8 with their float32
column scales and are converted tile by tile in VMEM, so no bf16 copy of
an expert's weights is ever written to HBM.  The experts' weights stay
stacked over layers, ``(L, E, K, N)``, and the layer is a scalar
prefetched into the index maps: a layer scan hands the kernel the whole
stack and no layer's weights are sliced out first.

Shapes:

  layer   (1,)          int32, scalar-prefetched: the layer to read
  counts  (E,)          int32, scalar-prefetched: rows routed to each
                        expert; an expert with none skips its MXU work
                        and writes zeros
  x       (M, K)        bf16 rows every expert reads (the up-projection)
          or (E, M, K)  one set of rows per expert (the down-projection)
  w       (L, E, K, N)  int8
  scale   (L, E, 1, N)  float32, per expert and output column

  out     (E, M, N)     ``x_e @ (w[l, e] * scale[l, e])``

Grid: (E, M/bm, N/bn, K/bk), K innermost; a float32 accumulator holds
one (bm, bn) tile across the K sweep.  The tile body is
``qmatmul_w8a16``'s with the column scale moved to the drain: int8 values
are exact in bf16, so the MXU multiplies bf16 by bf16 into float32 and
the scale multiplies the accumulated tile once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(layer_ref, counts_ref, x_ref, w_ref, s_ref, o_ref, acc_ref, *,
            nk: int):
    del layer_ref                               # consumed by the index maps
    e, kk = pl.program_id(0), pl.program_id(3)

    @pl.when(kk == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(counts_ref[e] > 0)
    def _accumulate():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...].astype(jnp.bfloat16), w_ref[...].astype(jnp.bfloat16),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _drain():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "out_dtype",
                                    "interpret"))
def moe_w8a16(x: jax.Array, w: jax.Array, scale: jax.Array,
              layer: jax.Array, counts: jax.Array, *, bm: int, bn: int,
              bk: int, out_dtype=jnp.float32,
              interpret: bool = False) -> jax.Array:
    """Every held expert's product with its rows, for layer ``layer``.

    Operands as the module docstring lays them out; M, K and N must be
    multiples of (bm, bk, bn) (``ops.moe_w8a16`` picks the tiles and pads
    the rows)."""
    _, e, k, n = w.shape
    m = x.shape[-2]
    assert x.shape[-1] == k and scale.shape == (w.shape[0], e, 1, n), (
        x.shape, w.shape, scale.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"unpadded shapes {(m, n, k)} vs blocks {(bm, bn, bk)}")
    nk = k // bk
    if x.ndim == 2:                             # rows shared by all experts
        x_spec = pl.BlockSpec((bm, bk),
                              lambda ei, i, j, kk, lr, cr: (i, kk))
    else:
        x_spec = pl.BlockSpec((None, bm, bk),
                              lambda ei, i, j, kk, lr, cr: (ei, i, kk))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(e, m // bm, n // bn, nk),
        in_specs=[
            x_spec,
            pl.BlockSpec((None, None, bk, bn),
                         lambda ei, i, j, kk, lr, cr: (lr[0], ei, kk, j)),
            pl.BlockSpec((None, None, 1, bn),
                         lambda ei, i, j, kk, lr, cr: (lr[0], ei, 0, j)),
        ],
        out_specs=pl.BlockSpec((None, bm, bn),
                               lambda ei, i, j, kk, lr, cr: (ei, i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((e, m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="moe_w8a16",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      counts.astype(jnp.int32), x, w, scale)
