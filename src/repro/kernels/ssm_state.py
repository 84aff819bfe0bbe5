"""Pallas TPU in-place update of one layer of the stacked SSD state.

The Mamba-2 decode step advances every slot's recurrent state by one
token per layer:

    h_in = 0 if fresh[b] else h                 (reset at position 0)
    new  = a[b,h] * h_in + (dt x)[b,h,:,None] * B[b,None,:]
    h'   = new if active[b] else h              (slot freeze)
    y    = sum_n new * C[b,n]                   (read-out)

At serving widths the state dwarfs everything else the step touches
(mamba2-1.3b, 32 slots: 48 x 32 x 64 x 64 x 128 float32, 3.2 GB), so the
step's cost is the number of passes over it.  Written as XLA selects and
a scan over layers, the reset, the update, the read-out and the freeze
each stream it again.  This kernel makes one pass: it reads each
(row, head block) tile of layer ``l`` once, and writes the result into
the same buffer (the output aliases ``h``), computing the read-out from
the tile while it is in VMEM.  The layer scan carries the stacked state
through the kernel, so no layer's state is ever copied.

Shapes:

  layer   (1,)                int32, scalar-prefetched: the layer whose
                              blocks the grid visits
  fresh,  (B,)                int32, scalar-prefetched: row flags
  active
  h       (L, B, H, hd, N)    float32, aliased to the first output
  a       (B, H/hb, 1, hb)    float32 per-head decay, head on lanes
  dtx     (B, H/hb, hd, hb)   float32 dt * x, one column per head
  bm, cm  (B, 1, N)           float32 input and output projections

  out h   (L, B, H, hd, N)    the same buffer; layer ``l`` updated
  out y   (B, H/hb, hd, hb)   float32, one column per head

Grid: (B, H/hb).  A step moves one (hb, hd, N) tile in and out: at
hd 64 and N 128 a head is 32 KiB, so hb 64 moves 2 MiB each way
(``ops.ssm_head_block`` picks hb).  ``a``, ``dtx`` and ``y`` come
head-on-lanes so that a head's values are one column: the kernel
broadcasts that column across the state's N lanes, and reduces the
read-out into it, with no relayout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(layer_ref, fresh_ref, active_ref, h_ref, a_ref, dtx_ref,
            bm_ref, cm_ref, o_ref, y_ref):
    del layer_ref                              # consumed by the index maps
    row = pl.program_id(0)
    fresh = fresh_ref[row] != 0
    active = active_ref[row] != 0
    bm = bm_ref[...]                           # (1, N)
    cm = cm_ref[...]
    for j in range(h_ref.shape[0]):
        h = h_ref[j]                           # (hd, N)
        h_in = jnp.where(fresh, jnp.zeros_like(h), h)
        new = a_ref[:, j:j + 1] * h_in + dtx_ref[:, j:j + 1] * bm
        o_ref[j] = jnp.where(active, new, h)
        y_ref[:, j:j + 1] = jnp.sum(new * cm, axis=1, keepdims=True)


def ssm_state_update(h: jax.Array, layer: jax.Array, a: jax.Array,
                     dtx: jax.Array, bm: jax.Array, cm: jax.Array,
                     fresh: jax.Array, active: jax.Array, *,
                     interpret: bool = False):
    """Update layer ``layer`` of the stacked state ``h`` in place.

    Operands as the module docstring lays them out (``ops.ssm_state_update``
    builds them); the head block is ``a.shape[-1]``.  Returns
    ``(h, y)``: ``h`` aliases the input buffer, so under a donated jit
    or a scan carry the update costs one read and one write of layer
    ``l``'s state and nothing else."""
    _, b, nh, hd, n = h.shape
    hb = a.shape[-1]
    assert nh % hb == 0 and a.shape == (b, nh // hb, 1, hb), (h.shape,
                                                              a.shape)
    state = pl.BlockSpec((None, None, hb, hd, n),
                         lambda r, j, lr, fr, ar: (lr[0], r, j, 0, 0))
    heads = lambda rows: pl.BlockSpec(
        (None, None, rows, hb), lambda r, j, lr, fr, ar: (r, j, 0, 0))
    row = pl.BlockSpec((None, 1, n), lambda r, j, lr, fr, ar: (r, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(b, nh // hb),
        in_specs=[state, heads(1), heads(hd), row, row],
        out_specs=[state, heads(hd)])
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(h.shape, h.dtype),
                   jax.ShapeDtypeStruct(dtx.shape, jnp.float32)],
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ssm_state_update",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      fresh.astype(jnp.int32), active.astype(jnp.int32),
      h, a, dtx, bm, cm)
