"""Sharding rules: logical activation/param axes -> mesh axes.

Mesh axes (launch/mesh.py):
    single pod:  ("data", "model")           = (16, 16)   256 chips
    multi-pod:   ("pod", "data", "model")    = (2, 16, 16) 512 chips

Parallelism scheme (DESIGN.md §4):
- batch ("dp")    over ("pod", "data")  — pure DP across pods, so the only
  cross-pod collective is the gradient all-reduce (cheapest to overlap, and
  the one gradient compression applies to);
- FSDP ("fsdp")   over "data" — parameter/optimizer sharding within a pod;
- TP   ("tp")     over "model" — head/FFN sharding, all-reduce per block;
- SP   ("sp")     over "model" — sequence dim for long-context decode caches
  and (optional rule set) norm/elementwise sections.

Rules are data, not code: the §Perf hillclimb swaps rule sets without
touching model code.  ``constrain`` is a no-op unless a rule set is active,
so models run unsharded on CPU tests unchanged.
"""
from __future__ import annotations

import dataclasses
import re
import threading
from typing import Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical -> physical mesh axis (resolved per-mesh; "dp" expands to the
# batch axes present in the mesh)
_LOGICAL = {"fsdp": "data", "tp": "model", "sp": "model"}


def _dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Activation rules: logical name -> tuple of logical axes per dim.

    Entries use logical axis names: "dp", "fsdp", "tp", "sp" or None.
    """
    act: Tuple = ("dp", None, None)            # (B, S, D)
    act_heads: Tuple = ("dp", None, "tp", None)  # (B, S, H, hd)
    act_heads_decode: Tuple = ("dp", None, "tp", None)  # decode q (B,1,H,hd)
    act_ff: Tuple = ("dp", None, "tp")         # (B, S, F)
    kv_cache: Tuple = ("dp", "sp", None, None)  # (B, S_max, KV, hd)
    logits: Tuple = ("dp", None, "tp")         # (B, S, V)
    ssm_state: Tuple = ("dp", "tp", None, None)  # (B, H, hd, N)
    rnn_state: Tuple = ("dp", "tp")            # (B, D_rnn)
    conv_state: Tuple = ("dp", None, "tp")     # (B, width-1, C)
    moe_inter: Tuple = (None, "dp", None)      # (E, C, D) legacy dispatch
    moe_disp: Tuple = ("dp", None, None, None)  # (B, E, C_row, D) dispatch

    # param rules: regex over the param path -> per-dim logical axes.
    # Matched in order; first hit wins.  Leading L (scan) dim is implicit
    # (prepend None when the leaf has one more dim than the rule).
    params: Tuple = (
        (r"embed.*table", ("tp", "fsdp")),               # (V, D)
        (r"(wq|wk|wv|w_up|w_gate)\.w$", ("fsdp", "tp")),  # (D, F/Hhd)
        (r"(wo|w_down)\.w$", ("tp", "fsdp")),            # (F/Hhd, D)
        (r"(router|w_router)\.w$", (None, None)),        # tiny, replicated
        (r"experts.*(w_up|w_gate)", (None, "fsdp", "tp")),  # (E, D, F)
        (r"experts.*w_down", (None, "tp", "fsdp")),      # (E, F, D)
        (r"(wq|wk|wv|wo|w_up|w_gate|w_down)\.b$", ("tp",)),
        (r"conv.*\.w$", (None, None, "tp")),             # (width, 1, D)
        (r"(in_proj|x_proj|dt_proj)\.w$", ("fsdp", "tp")),
        (r"out_proj\.w$", ("tp", "fsdp")),
        (r"(a_log|dt_bias|D|Lambda|rg_.*)$", ("tp",)),   # per-channel ssm/rnn
        (r".*", ()),                                     # default: replicate
    )


BASELINE_RULES = ShardingRules()

# Sequence-parallel variant: shard the sequence dim of (B, S, D) activations
# over "model" in the elementwise/norm sections (perf-iteration candidate).
SEQPAR_RULES = dataclasses.replace(
    BASELINE_RULES, act=("dp", "sp", None))

# §Perf iteration A1 (refuted): decode KV cache sharded on head_dim over
# "model" instead of the sequence dim.
KVHD_RULES = dataclasses.replace(
    BASELINE_RULES, kv_cache=("dp", None, None, "tp"))

# §Perf iteration A2: keep the cache S-sharded, but leave the decode query
# REPLICATED over "model".  The measured collective term came from GSPMD
# resharding the (expanded, f32) cache to match the head-sharded q; with q
# replicated, scores are computed against the local S-shard and softmax /
# context need only tiny stat all-reduces.
DECODE_V2_RULES = dataclasses.replace(
    BASELINE_RULES, act_heads_decode=("dp", None, None, None))

# §Perf iteration B1: MoE dispatch buffer (E, C, D) sharded over experts.
MOE_EP_RULES = dataclasses.replace(
    DECODE_V2_RULES, moe_inter=("tp", "dp", None))

# §Perf iteration A5: batch-only cache sharding for small-KV (GQA) archs —
# the int8 cache of a kv<=8 model fits replicated over "model"
# (starcoder2 decode_32k: 30 GB / 16 data-rows = 1.9 GB/chip), making both
# the post-scan append and every attention read purely local.
DECODE_V3_RULES = dataclasses.replace(
    DECODE_V2_RULES, kv_cache=("dp", None, None, None))

RULE_SETS = {
    "baseline": BASELINE_RULES,
    "seqpar": SEQPAR_RULES,
    "kvhd": KVHD_RULES,
    "decode_v2": DECODE_V2_RULES,
    "decode_v3": DECODE_V3_RULES,
    "moe_ep": MOE_EP_RULES,
}


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: Optional[ShardingRules] = None
        self.manual: frozenset = frozenset()


_CTX = _Ctx()


class manual_axes:
    """Declare mesh axes as manual (shard_map) for constrain(): the step
    wrapper enters it around a shard_map body, and constrain() leaves
    the declared axes out of its sharding constraints.
    """

    def __init__(self, axes):
        self.axes = frozenset(axes)

    def __enter__(self):
        self._prev = _CTX.manual
        _CTX.manual = _CTX.manual | self.axes
        return self

    def __exit__(self, *exc):
        _CTX.manual = self._prev
        return False


class use_rules:
    """Context manager activating (mesh, rules) for constrain()/specs."""

    def __init__(self, mesh: Optional[Mesh], rules: ShardingRules):
        self.mesh, self.rules = mesh, rules

    def __enter__(self):
        self._prev = (_CTX.mesh, _CTX.rules)
        _CTX.mesh, _CTX.rules = self.mesh, self.rules
        return self

    def __exit__(self, *exc):
        _CTX.mesh, _CTX.rules = self._prev
        return False


def _resolve(axes: Tuple, mesh: Mesh,
             shape: Optional[Tuple[int, ...]] = None) -> P:
    """Logical axes tuple -> PartitionSpec for this mesh.

    When ``shape`` is given, any dim not divisible by its mesh-axis extent
    falls back to replicated — jit *argument* shardings (unlike internal
    constraints) require exact divisibility (e.g. vocab 50280 on a 16-way
    axis, or the batch-1 long_500k cell).
    """
    out = []
    for i, a in enumerate(axes):
        phys = None
        if a == "dp":
            dp = _dp_axes(mesh)
            phys = dp if len(dp) > 1 else (dp[0] if dp else None)
        elif a is not None:
            cand = _LOGICAL[a]
            phys = cand if cand in mesh.axis_names else None
        if phys is not None and shape is not None and i < len(shape):
            extent = 1
            for ax in (phys if isinstance(phys, tuple) else (phys,)):
                extent *= mesh.shape[ax]
            if shape[i] % extent != 0:
                phys = None
        out.append(phys)
    return P(*out)


def _manual_axes() -> frozenset:
    """Axes currently under manual (shard_map) control in this trace:
    the explicitly declared set (manual_axes), plus whatever the abstract
    mesh reports on JAX versions that expose it."""
    traced = frozenset()
    try:
        amesh = jax.sharding.get_abstract_mesh()
        if amesh is not None and not amesh.empty:
            traced = frozenset(
                n for n, t in zip(amesh.axis_names, amesh.axis_types)
                if t == jax.sharding.AxisType.Manual)
    except Exception:
        pass
    return _CTX.manual | traced


def constrain(x: jax.Array, logical_name: str) -> jax.Array:
    """with_sharding_constraint by logical name; no-op without active rules.

    Axes that are Manual in the current trace (inside a partial-manual
    shard_map, e.g. the int8 cross-pod gradient exchange) are dropped from
    the spec — they are already fixed by the enclosing shard_map.
    """
    if _CTX.rules is None or _CTX.mesh is None:
        return x
    if _manual_axes():
        # Inside a partial-manual shard_map (int8 cross-pod gradient
        # exchange): rely on GSPMD propagation from the in/out shardings.
        # Mixing explicit constraints with partial-manual trips an XLA SPMD
        # partitioner CHECK in this XLA version (verified on CPU backend).
        return x
    axes = getattr(_CTX.rules, logical_name, None)
    if axes is None:
        return x
    axes = axes[:x.ndim] if len(axes) >= x.ndim else \
        tuple(axes) + (None,) * (x.ndim - len(axes))
    spec = _resolve(axes, _CTX.mesh)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_CTX.mesh, spec))


def spec_for(logical_name: str, ndim: int, mesh: Mesh,
             rules: ShardingRules, shape=None) -> P:
    axes = getattr(rules, logical_name)
    axes = tuple(axes)[:ndim] + (None,) * max(0, ndim - len(axes))
    return _resolve(axes, mesh, shape)


def param_spec(path: str, ndim: int, mesh: Mesh,
               rules: ShardingRules, shape=None) -> P:
    """PartitionSpec for a parameter leaf given its tree path string."""
    for pattern, axes in rules.params:
        if re.search(pattern, path):
            axes = tuple(axes)
            if len(axes) < ndim:   # leading scan (L) / group dims: replicate
                axes = (None,) * (ndim - len(axes)) + axes
            elif len(axes) > ndim:
                axes = axes[-ndim:]
            return _resolve(axes, mesh, shape)
    return P()


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):          # DictKey
            parts.append(str(p.key))
        elif hasattr(p, "name"):       # GetAttrKey (e.g. QTensor fields)
            parts.append(str(p.name))
        elif hasattr(p, "idx"):        # SequenceKey
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return ".".join(parts)


def tree_shardings(tree, mesh: Mesh, rules: ShardingRules):
    """NamedSharding pytree for a parameter / optimizer-state pytree.

    QTensor leaves: `.values` shards by the enclosing weight's rule;
    `.scale` is tiny and replicated.
    """
    def leaf_spec(path, leaf):
        ndim = getattr(leaf, "ndim", 0)
        shape = getattr(leaf, "shape", None)
        ps = _path_str(path)
        if ps.endswith(".scale"):
            return NamedSharding(mesh, P())
        if ps.endswith(".values"):
            ps = ps[: -len(".values")]
        return NamedSharding(mesh, param_spec(ps, ndim, mesh, rules, shape))
    return jax.tree_util.tree_map_with_path(leaf_spec, tree)


# decode-cache leaves: regex on path -> logical activation rule,
# right-aligned over the trailing dims (leading scan/group dims replicate).
_CACHE_RULES = (
    (r"(^|\.)(k|v|lo_k|lo_v|xk|xv)$", "kv_cache"),
    (r"(k|v)_scale$", "kv_cache"),
    (r"rnn_h", "rnn_state"),
    (r"(^|\.)h$", "ssm_state"),
    (r"conv", "conv_state"),
    (r"enc_out|vision", "act"),
)


def cache_shardings(cache_tree, mesh: Mesh, rules: ShardingRules):
    def leaf_spec(path, leaf):
        ps = _path_str(path)
        ndim = getattr(leaf, "ndim", 0)
        shape = getattr(leaf, "shape", None)
        for pattern, logical in _CACHE_RULES:
            if re.search(pattern, ps):
                axes = tuple(getattr(rules, logical))
                if len(axes) < ndim:
                    axes = (None,) * (ndim - len(axes)) + axes
                else:
                    axes = axes[-ndim:] if ndim else ()
                return NamedSharding(mesh, _resolve(axes, mesh, shape))
        return NamedSharding(mesh, P())
    return jax.tree_util.tree_map_with_path(leaf_spec, cache_tree)


def batch_spec(mesh: Mesh, ndim: int = 2, shape=None) -> P:
    """(B, S, ...) input batch: batch over all dp axes (replicated when the
    batch dim is not divisible, e.g. the batch-1 long_500k cell)."""
    dp = _dp_axes(mesh)
    first = dp if len(dp) > 1 else (dp[0] if dp else None)
    if first is not None and shape:
        extent = 1
        for ax in (first if isinstance(first, tuple) else (first,)):
            extent *= mesh.shape[ax]
        if shape[0] % extent != 0:
            first = None
    return P(first, *([None] * (ndim - 1)))
