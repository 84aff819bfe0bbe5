"""Mamba-2 and attention mixers, each layer ending in a MoE block
(granite-4.0-h, ``model_type`` granitemoehybrid).

Every layer, with m = ``cfg.residual_multiplier``:

    h = x + m * mixer(rmsnorm(x))
    x = h + m * (routed(rmsnorm(h)) + shared(rmsnorm(h)))

The mixer is the layer's entry of ``cfg.layer_types``: a Mamba-2 block
(``ssm._mix_in`` / ``_mix_out``, with the gated RMSNorm before its
out-projection) or GQA attention with no positional embedding and scores
scaled by ``cfg.attention_multiplier``.  The MoE block is
``moe.expert_share``: the router over all experts, this chip's held
experts and the shared MLP.  Embeddings are scaled by
``cfg.embedding_multiplier`` and logits divided by ``cfg.logits_scaling``.

Parameters stack per kind: ``mamba`` over the Mamba layers, ``attn`` over
the attention layers, ``moe`` over every layer.  The layers run as a scan
over periods of the layer pattern; inside a period each run of one mixer
kind is a scan of its own, so the program holds one body per run of the
period (three for granite's 5 Mamba, 1 attention, 4 Mamba), not one per
layer.  Each body indexes the stacks at its layer's place; in the decode
step the int8 linears are not sliced but read in place at that index
(``qlinear.layer_view``).

Decode state: the stacked float32 SSD state ``h`` (L_mamba, B, H, hd, N)
and conv tails, updated in place by ``ops.ssm_state_update`` as in
``ssm.decode_step`` (reset at position 0, frozen outside ``active``), and
a contiguous K/V cache (L_attn, B, S, KV, hd) written at each row's index
only where the row is active, read below it.
"""
from __future__ import annotations

from typing import Callable, List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, layer_period
from repro.core.qlinear import FP, QuantMode, layer_view
from repro.models import layers as L
from repro.models import moe, ssm

Array = jax.Array


def attn_config(cfg: ArchConfig) -> L.AttnConfig:
    return L.AttnConfig(
        d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, use_rope=cfg.use_rope,
        sm_scale=cfg.attention_multiplier or None)


def _runs(cfg: ArchConfig):
    """(period length, periods, runs, per-period count of each kind);
    a run is (kind, first layer in the period, length, first index of
    its kind in the period)."""
    period = layer_period(cfg.layer_types)
    runs: List[Tuple[str, int, int, int]] = []
    seen = {"mamba": 0, "attention": 0}
    for i, kind in enumerate(period):
        if kind not in seen:
            raise ValueError(f"unknown layer type {kind!r}")
        if runs and runs[-1][0] == kind:
            k, start, n, off = runs[-1]
            runs[-1] = (k, start, n + 1, off)
        else:
            runs.append((kind, i, 1, seen[kind]))
        seen[kind] += 1
    return len(period), cfg.n_layers // len(period), runs, seen


def _counts(cfg: ArchConfig) -> Tuple[int, int]:
    return (sum(t == "mamba" for t in cfg.layer_types),
            sum(t == "attention" for t in cfg.layer_types))


def _at(tree, i):
    """Entry ``i`` of every leaf of a layer-stacked tree."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        tree)


def _stack(cfg: ArchConfig, carry, mamba: Callable, attention: Callable):
    """Run every layer: ``mamba(carry, m, l)`` / ``attention(carry, a, l)``
    with ``m`` / ``a`` the layer's index in its kind's stack and ``l`` its
    index among all layers."""
    plen, periods, runs, per = _runs(cfg)

    def period(c, p):
        for kind, start, n, off in runs:
            fn = mamba if kind == "mamba" else attention

            def body(c, j, fn=fn, start=start, off=off, kind=kind):
                return fn(c, p * per[kind] + off + j, p * plen + start + j), None

            c, _ = jax.lax.scan(body, c, jnp.arange(n))
        return c, None

    carry, _ = jax.lax.scan(period, carry, jnp.arange(periods))
    return carry


def _moe_residual(params, l, x, cfg, mode):
    mp = params["moe"]
    h = L.rmsnorm(_at(mp["norm"], l), x, cfg.norm_eps)
    return x + _scaled(moe.expert_share(mp, l, h, cfg, mode=mode), cfg)


def _scaled(out, cfg):
    """A residual branch times ``cfg.residual_multiplier``, in float32."""
    return (out.astype(jnp.float32) * cfg.residual_multiplier
            ).astype(out.dtype)


def _embed(params, tokens, cfg):
    x = L.embed(params["embed"], tokens, compute_dtype=jnp.float32)
    return (x * cfg.embedding_multiplier).astype(jnp.bfloat16)


def _head(params, x, cfg):
    x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
    return L.unembed(params["embed"], x) / cfg.logits_scaling


# ---------------------------------------------------------------------------
# parameters and cache
# ---------------------------------------------------------------------------

def init(key, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    ke, km, ka, kx = jax.random.split(key, 4)
    n_m, n_a = _counts(cfg)
    acfg = attn_config(cfg)
    return {
        "embed": L.init_embedding(ke, cfg.vocab, cfg.d_model, dtype),
        "mamba": jax.vmap(lambda k: ssm.init_ssd_layer(k, cfg, dtype))(
            jax.random.split(km, n_m)),
        "attn": jax.vmap(lambda k: {
            "norm": L.init_rmsnorm(cfg.d_model, dtype),
            "attn": L.init_attention(k, acfg, dtype)})(
                jax.random.split(ka, n_a)),
        "moe": jax.vmap(lambda k: {
            "norm": L.init_rmsnorm(cfg.d_model, dtype),
            **moe.init_expert_share(k, cfg, dtype)})(
                jax.random.split(kx, cfg.n_layers)),
        "ln_f": L.init_rmsnorm(cfg.d_model, dtype),
    }


def serving_params(params: dict) -> dict:
    """The parameters as the engine serves them (``ssm.pad_in_proj``)."""
    return {**params, "mamba": ssm.pad_in_proj(params["mamba"])}


def init_cache(cfg: ArchConfig, batch: int, s_max: int,
               dtype=jnp.bfloat16) -> dict:
    """SSD state (float32) and conv tails of the Mamba layers, K/V of the
    attention layers (contiguous, ``s_max`` positions a row); conv tails
    and K/V in ``dtype``."""
    n_m, n_a = _counts(cfg)
    kv = (n_a, batch, s_max, cfg.n_kv_heads, cfg.head_dim)
    return {
        "h": jnp.zeros((n_m, batch, cfg.ssm_heads, cfg.ssm_headdim,
                        cfg.ssm_state), jnp.float32),
        "conv": jnp.zeros((n_m, batch, cfg.conv_width - 1,
                           cfg.d_inner + 2 * cfg.ssm_state), dtype),
        "k": jnp.zeros(kv, dtype),
        "v": jnp.zeros(kv, dtype),
    }


# ---------------------------------------------------------------------------
# forward over whole sequences and the one-token decode
# ---------------------------------------------------------------------------

def forward(params: dict, tokens: Array, cfg: ArchConfig, *,
            mode: QuantMode = FP, remat: bool = True) -> Array:
    x = _embed(params, tokens, cfg)
    acfg = attn_config(cfg)
    positions = jnp.arange(tokens.shape[1])[None, :]

    def mamba(x, m, l):
        with jax.named_scope("mamba_mixer"):
            x = ssm.ssd_layer(_at(params["mamba"], m), x, cfg, mode=mode)
        return _moe_residual(params, l, x, cfg, mode)

    def attention(x, a, l):
        ap = _at(params["attn"], a)
        with jax.named_scope("attention_mixer"):
            out, _ = L.attention(ap["attn"],
                                 L.rmsnorm(ap["norm"], x, cfg.norm_eps),
                                 acfg, mode=mode, positions=positions)
            x = x + _scaled(out, cfg)
        return _moe_residual(params, l, x, cfg, mode)

    if remat:
        policy = jax.checkpoint_policies.nothing_saveable
        mamba = jax.checkpoint(mamba, policy=policy)
        attention = jax.checkpoint(attention, policy=policy)
    return _head(params, _stack(cfg, x, mamba, attention), cfg)


def decode_step(params: dict, tokens: Array, cache: dict, cache_index: Array,
                cfg: ArchConfig, *, mode: QuantMode = FP,
                active: Array = None) -> Tuple[Array, dict]:
    """One-token decode.  ``cache_index`` () or (B,) per-row positions.
    A row at position 0 starts from zero SSD state and conv tail and
    reads no K/V; a row outside ``active`` (default every row) keeps its
    SSD state, conv tail and K/V bitwise."""
    b, s = tokens.shape
    if s != 1:
        raise ValueError(f"decode_step takes one token a row, got {s}")
    idx = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32), (b,))
    fresh = idx == 0
    active = (jnp.ones((b,), bool) if active is None
              else jnp.asarray(active, bool))
    acfg = attn_config(cfg)
    rows = jnp.arange(b)

    def mamba(c, m, l):
        x, h, conv, k, v = c
        with jax.named_scope("mamba_mixer"):
            x, h, conv = ssm.ssd_decode_layer(
                layer_view(params["mamba"], m), x, h, conv, m, fresh,
                active, cfg, mode=mode)
        return (_moe_residual(params, l, x, cfg, mode), h, conv, k, v)

    def attention(c, a, l):
        x, h, conv, k, v = c
        ap = layer_view(params["attn"], a)
        with jax.named_scope("attention_mixer"):
            kl = jax.lax.dynamic_index_in_dim(k, a, 0, keepdims=False)
            vl = jax.lax.dynamic_index_in_dim(v, a, 0, keepdims=False)
            out, (kn, vn) = L.attention(
                ap["attn"], L.rmsnorm(ap["norm"], x, cfg.norm_eps), acfg,
                mode=mode, positions=idx[:, None], kv_cache=(kl, vl),
                cache_index=idx, append_only=True, layer=a)
            x = x + _scaled(out, cfg)

            def write(c, new):                 # the new token, active rows
                old = c[a, rows, idx]
                new = jnp.where(active[:, None, None],
                                new[:, 0].astype(c.dtype), old)
                return c.at[a, rows, idx].set(new)
            k, v = write(k, kn), write(v, vn)
        return (_moe_residual(params, l, x, cfg, mode), h, conv, k, v)

    x, h, conv, k, v = _stack(
        cfg, (_embed(params, tokens, cfg), cache["h"], cache["conv"],
              cache["k"], cache["v"]), mamba, attention)
    return _head(params, x, cfg), {"h": h, "conv": conv, "k": k, "v": v}
