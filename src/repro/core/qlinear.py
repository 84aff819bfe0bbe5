"""Quantization-aware linear layers.

The framework's models are functional (param pytrees + apply fns).  Every
matmul in the model zoo goes through :func:`linear` so that post-training
quantization (`quant.quantize_tree`) transparently switches a model from the
bf16 training path to the paper's int8 serving path:

- fp weight (jnp array)      -> jnp dot in bf16 (training / baseline serving)
- QTensor weight             -> kernels.ops.qmatmul (w8a16 weight-only quant)
- QTensor weight + act_bits8 -> kernels.ops.qmatmul_dynamic (full w8a8 path)

The execution mode is carried in a `QuantMode` (static, hashable) so jitted
step functions specialize on it.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quant import QTensor
from repro.kernels import ops


@dataclasses.dataclass(frozen=True)
class QuantMode:
    """Static quantization mode threaded through model apply fns."""
    enabled: bool = False          # weights are QTensors
    act_bits: int = 16             # 8 -> w8a8 integer path, else w8a16
    interpret: bool = False        # force Pallas interpreter (CPU validation)

    @property
    def w8a8(self) -> bool:
        return self.enabled and self.act_bits == 8


FP = QuantMode(enabled=False)
W8A16 = QuantMode(enabled=True, act_bits=16)
W8A8 = QuantMode(enabled=True, act_bits=8)


def layer_view(stack, layer):
    """Layer ``layer`` of a layer-stacked parameter tree, as the int8 path
    reads it: every float leaf sliced to the layer, every QTensor left
    whole, for :func:`linear` to read at ``layer`` in place."""
    return jax.tree_util.tree_map(
        lambda a: a if isinstance(a, QTensor)
        else jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
        stack, is_leaf=lambda a: isinstance(a, QTensor))


def init_linear(key, d_in: int, d_out: int, *, bias: bool = True,
                dtype=jnp.float32, scale: Optional[float] = None) -> dict:
    """Truncated-normal init, std = 1/sqrt(d_in) unless overridden."""
    std = scale if scale is not None else d_in ** -0.5
    p = {"w": (jax.random.truncated_normal(key, -2, 2, (d_in, d_out),
                                           jnp.float32) * std).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(params: dict, x: jax.Array, *, activation: str = "none",
           mode: QuantMode = FP, compute_dtype=jnp.bfloat16,
           layer=None) -> jax.Array:
    """y = act(x @ w + b), dispatching on the weight's quantization state.

    ``layer`` () int32: ``w`` is a QTensor layer stack (L, K, N), read in
    place at that layer by the int8 kernel (see :func:`layer_view`); a
    float weight comes already sliced, and ``layer`` is not used."""
    w = params["w"]
    b = params.get("b")
    if isinstance(w, QTensor):
        fn = ops.qmatmul_dynamic if mode.w8a8 else ops.qmatmul
        return fn(x, w, b, layer=layer, activation=activation,
                  out_dtype=x.dtype, interpret=mode.interpret)
    # fp path: bf16 compute, fp32 accumulate (XLA default on MXU)
    y = jnp.dot(x.astype(compute_dtype), w.astype(compute_dtype),
                preferred_element_type=jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    if activation == "relu":
        y = jnp.maximum(y, 0.0)
    elif activation == "gelu":
        y = jax.nn.gelu(y)
    elif activation == "silu":
        y = y * jax.nn.sigmoid(y)
    elif activation == "tanh":
        y = jnp.tanh(y)
    elif activation == "sigmoid":
        y = jax.nn.sigmoid(y)
    elif activation != "none":
        raise ValueError(activation)
    return y.astype(x.dtype)
