"""RecurrentGemma / Griffin hybrid [arXiv:2402.19427].

Layer pattern: (rec, rec, attn) repeating — two RG-LRU recurrent blocks per
local-attention block (window 2048, MQA kv=1).  Every layer is
norm -> temporal-mixing -> residual; norm -> gated-MLP -> residual.

Recurrent block: two branches from x —
  a: linear(D->W) -> causal conv1d(4) -> RG-LRU
  b: linear(D->W) -> GeLU
merged a*b -> linear(W->D).

RG-LRU:  r_t = sigmoid(W_a x + b_a)        (recurrence gate)
         i_t = sigmoid(W_x x + b_x)        (input gate)
         log a_t = -c * softplus(Lambda) * r_t          (c = 8)
         h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)

Training/prefill evaluate the linear recurrence with
``jax.lax.associative_scan`` (parallel prefix — O(log S) depth, TPU-native
replacement for the GPU kernel the Griffin paper uses); decode is the O(1)
update.  Fixed-size state -> long_500k runnable.  Gates/state in fp32 (the
32-bit-accumulator argument); projections quantize like all matmuls.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.qlinear import FP, QuantMode, init_linear, linear
from repro.models import layers as L
from repro.models import transformer as TF
from repro.runtime.sharding import constrain

Array = jax.Array
_C = 8.0   # RG-LRU decay sharpness constant


# ---------------------------------------------------------------------------
# RG-LRU core
# ---------------------------------------------------------------------------

def init_rglru(key, width: int) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "w_a": init_linear(k1, width, width, bias=True, dtype=jnp.float32),
        "w_x": init_linear(k2, width, width, bias=True, dtype=jnp.float32),
        # Lambda init so a^c spans ~[0.9, 0.999] (paper's init range)
        "Lambda": jnp.linspace(-4.3, -1.5, width).astype(jnp.float32),
    }


def _rglru_gates(p: dict, x: Array, mode: QuantMode):
    r = jax.nn.sigmoid(linear(p["w_a"], x, mode=FP,
                              compute_dtype=jnp.float32))
    i = jax.nn.sigmoid(linear(p["w_x"], x, mode=FP,
                              compute_dtype=jnp.float32))
    log_a = -_C * jax.nn.softplus(p["Lambda"])[None, None] * r
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.clip(1.0 - a * a, 1e-12)) * (i * x.astype(jnp.float32))
    return a, b


def rglru(p: dict, x: Array, *, mode: QuantMode = FP,
          state: Array = None) -> Tuple[Array, Array]:
    """x: (B, S, W).  Returns (y, last_state)."""
    a, b = _rglru_gates(p, x, mode)
    if state is None:
        def combine(l, r):
            al, bl = l
            ar, br = r
            return al * ar, bl * ar + br
        _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    else:
        # decode: single step (S == 1)
        h = a * state[:, None] + b
    return h.astype(x.dtype), h[:, -1]


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def init_rec_block(key, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    w = cfg.rnn_width or cfg.d_model
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    return {
        "ln": L.init_rmsnorm(cfg.d_model, dtype),
        "w_in_a": init_linear(k1, cfg.d_model, w, bias=False, dtype=dtype),
        "w_in_b": init_linear(k2, cfg.d_model, w, bias=False, dtype=dtype),
        "conv_w": (jax.random.truncated_normal(
            k3, -2, 2, (cfg.conv_width, w), jnp.float32) * 0.3).astype(dtype),
        "conv_b": jnp.zeros((w,), dtype),
        "lru": init_rglru(k4, w),
        "w_out": init_linear(k5, w, cfg.d_model, bias=False, dtype=dtype,
                             scale=w ** -0.5),
        "ln_mlp": L.init_rmsnorm(cfg.d_model, dtype),
        "mlp": L.init_mlp(jax.random.fold_in(key, 7), cfg.d_model, cfg.d_ff,
                          gated=cfg.gated_mlp, activation=cfg.activation,
                          dtype=dtype),
    }


def rec_block(p: dict, x: Array, cfg: ArchConfig, *, mode: QuantMode = FP,
              state: dict = None) -> Tuple[Array, dict]:
    from repro.models.ssm import _causal_conv
    h = L.rmsnorm(p["ln"], x)
    a = linear(p["w_in_a"], h, mode=mode)
    b = linear(p["w_in_b"], h, activation="gelu", mode=mode)
    conv_state = None if state is None else state["conv"]
    a, new_conv = _causal_conv(a, p["conv_w"], p["conv_b"], conv_state)
    lru_state = None if state is None else state["h"]
    a, new_h = rglru(p["lru"], a, mode=mode, state=lru_state)
    y = linear(p["w_out"], (a * b).astype(x.dtype), mode=mode)
    x = x + constrain(y, "act")
    h = L.rmsnorm(p["ln_mlp"], x)
    x = x + L.mlp(p["mlp"], h, gated=cfg.gated_mlp,
                  activation=cfg.activation, mode=mode)
    new_state = None if state is None else {"h": new_h, "conv": new_conv}
    return constrain(x, "act"), new_state


def _attn_cfg(cfg: ArchConfig) -> L.AttnConfig:
    return L.AttnConfig(
        d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
        window=cfg.local_window)


def init_attn_block(key, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "ln": L.init_rmsnorm(cfg.d_model, dtype),
        "attn": L.init_attention(k1, _attn_cfg(cfg), dtype),
        "ln_mlp": L.init_rmsnorm(cfg.d_model, dtype),
        "mlp": L.init_mlp(k2, cfg.d_model, cfg.d_ff, gated=cfg.gated_mlp,
                          activation=cfg.activation, dtype=dtype),
    }


def attn_block(p: dict, x: Array, cfg: ArchConfig, *, mode: QuantMode = FP,
               positions=None, kv_cache=None, cache_index=None,
               valid_len=None) -> Tuple[Array, object]:
    acfg = _attn_cfg(cfg)
    h = L.rmsnorm(p["ln"], x)
    attn_out, new_kv = L.attention(
        p["attn"], h, acfg, mode=mode, positions=positions,
        kv_cache=kv_cache, cache_index=cache_index, valid_len=valid_len,
        positions_k=positions)
    x = x + attn_out
    h = L.rmsnorm(p["ln_mlp"], x)
    x = x + L.mlp(p["mlp"], h, gated=cfg.gated_mlp,
                  activation=cfg.activation, mode=mode)
    return constrain(x, "act"), new_kv


# ---------------------------------------------------------------------------
# full model: scan over (rec, rec, attn) groups + leftover rec blocks
# ---------------------------------------------------------------------------

def _layout(cfg: ArchConfig) -> Tuple[int, int]:
    pat = cfg.block_pattern or ("rec", "rec", "attn")
    assert tuple(pat) == ("rec", "rec", "attn"), \
        "only the Griffin 2:1 pattern is implemented"
    n_groups = cfg.n_layers // 3
    leftover = cfg.n_layers - 3 * n_groups   # leading rec blocks
    assert leftover <= 2
    return n_groups, leftover


def init(key, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    n_groups, leftover = _layout(cfg)
    ke, kg, kl = jax.random.split(key, 3)

    def group_init(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"rec0": init_rec_block(k1, cfg, dtype),
                "rec1": init_rec_block(k2, cfg, dtype),
                "attn": init_attn_block(k3, cfg, dtype)}

    params = {
        "embed": L.init_embedding(ke, cfg.vocab, cfg.d_model, dtype),
        "groups": jax.vmap(group_init)(jax.random.split(kg, n_groups)),
        "ln_f": L.init_rmsnorm(cfg.d_model, dtype),
    }
    if leftover:
        params["leftover"] = jax.vmap(
            lambda k: init_rec_block(k, cfg, dtype))(
                jax.random.split(kl, leftover))
    return params


def forward(params: dict, tokens: Array, cfg: ArchConfig, *,
            mode: QuantMode = FP, remat: bool = True) -> Array:
    x = L.embed(params["embed"], tokens)
    positions = jnp.arange(tokens.shape[1])[None, :]

    def group_body(x, gp):
        x, _ = rec_block(gp["rec0"], x, cfg, mode=mode)
        x, _ = rec_block(gp["rec1"], x, cfg, mode=mode)
        x, _ = attn_block(gp["attn"], x, cfg, mode=mode, positions=positions)
        return x, None

    if remat:
        group_body = jax.checkpoint(
            group_body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(group_body, x, params["groups"])
    if "leftover" in params:
        def rec_body(x, lp):
            out, _ = rec_block(lp, x, cfg, mode=mode)
            return out, None
        x, _ = jax.lax.scan(rec_body, x, params["leftover"])
    x = L.rmsnorm(params["ln_f"], x)
    return L.unembed(params["embed"], x)


def init_cache(cfg: ArchConfig, batch: int, s_max: int,
               dtype=jnp.bfloat16) -> dict:
    """Fixed-size: RG-LRU states + conv tails + local-window ring KV."""
    n_groups, leftover = _layout(cfg)
    w = cfg.rnn_width or cfg.d_model
    win = min(cfg.local_window, s_max)
    cache = {
        "rnn_h": jnp.zeros((n_groups, 2, batch, w), jnp.float32),
        "conv": jnp.zeros((n_groups, 2, batch, cfg.conv_width - 1, w), dtype),
        "k": jnp.zeros((n_groups, batch, win, cfg.n_kv_heads, cfg.head_dim),
                       dtype),
        "v": jnp.zeros((n_groups, batch, win, cfg.n_kv_heads, cfg.head_dim),
                       dtype),
    }
    if leftover:
        cache["lo_rnn_h"] = jnp.zeros((leftover, batch, w), jnp.float32)
        cache["lo_conv"] = jnp.zeros(
            (leftover, batch, cfg.conv_width - 1, w), dtype)
    return cache


def cache_batch_axes(cache: dict) -> dict:
    """Batch axis per cache leaf (the slot engine slices slots with it).
    Grouped recurrent state stacks (group, block) ahead of batch."""
    axes = {"rnn_h": 2, "conv": 2, "k": 1, "v": 1}
    if "lo_rnn_h" in cache:
        axes["lo_rnn_h"] = 1
        axes["lo_conv"] = 1
    return axes


def decode_step(params: dict, tokens: Array, cache: dict, cache_index: Array,
                cfg: ArchConfig, *, mode: QuantMode = FP,
                active: Array = None) -> Tuple[Array, dict]:
    """One-token decode.  ``cache_index`` is scalar () (lockstep batch) or
    (B,) per-row for the slot engine: RoPE positions, ring write indices
    and window masks all become per-row, and — like the SSM — a row at
    position 0 has its recurrent/conv state zeroed before the update (the
    reset-at-zero scrub that makes slot reuse safe).

    ``active`` (B,) bool, default every row: rows outside it keep their
    RG-LRU ``rnn_h`` and conv tails bitwise (the slot engine's freeze).
    The local-attention KV ring is positional (reads masked by
    ``valid_len``) and needs no freeze."""
    b, s = tokens.shape
    old = cache
    x = L.embed(params["embed"], tokens)
    ci = jnp.asarray(cache_index)
    if ci.ndim:                             # (B,): per-slot positions
        positions = ci[:, None] + jnp.arange(s)[None, :]
    else:
        positions = ci + jnp.arange(s)[None, :]
    win = cache["k"].shape[2]
    write_idx = ci % win
    valid_len = jnp.minimum(ci + s, win)
    fresh = jnp.broadcast_to(ci == 0, (b,))
    cache = dict(
        cache,
        rnn_h=jnp.where(fresh[None, None, :, None],
                        jnp.zeros_like(cache["rnn_h"]), cache["rnn_h"]),
        conv=jnp.where(fresh[None, None, :, None, None],
                       jnp.zeros_like(cache["conv"]), cache["conv"]))
    if "lo_rnn_h" in cache:
        cache = dict(
            cache,
            lo_rnn_h=jnp.where(fresh[None, :, None],
                               jnp.zeros_like(cache["lo_rnn_h"]),
                               cache["lo_rnn_h"]),
            lo_conv=jnp.where(fresh[None, :, None, None],
                              jnp.zeros_like(cache["lo_conv"]),
                              cache["lo_conv"]))

    def group_body(x, inp):
        gp, h2, conv2, ck, cv = inp
        x, st0 = rec_block(gp["rec0"], x, cfg, mode=mode,
                           state={"h": h2[0], "conv": conv2[0]})
        x, st1 = rec_block(gp["rec1"], x, cfg, mode=mode,
                           state={"h": h2[1], "conv": conv2[1]})
        x, new_kv = attn_block(gp["attn"], x, cfg, mode=mode,
                               positions=positions, kv_cache=(ck, cv),
                               cache_index=write_idx, valid_len=valid_len)
        new_h = jnp.stack([st0["h"], st1["h"]])
        new_conv = jnp.stack([st0["conv"], st1["conv"]])
        return x, (new_h, new_conv, new_kv[0], new_kv[1])

    x, (nh, nc, nk, nv) = jax.lax.scan(
        group_body, x,
        (params["groups"], cache["rnn_h"], cache["conv"],
         cache["k"], cache["v"]))
    new_cache = dict(cache, rnn_h=nh, conv=nc, k=nk, v=nv)

    if "leftover" in params:
        def rec_body(x, inp):
            lp, h, conv = inp
            x, st = rec_block(lp, x, cfg, mode=mode,
                              state={"h": h, "conv": conv})
            return x, (st["h"], st["conv"])
        x, (lh, lc) = jax.lax.scan(
            rec_body, x,
            (params["leftover"], cache["lo_rnn_h"], cache["lo_conv"]))
        new_cache["lo_rnn_h"] = lh
        new_cache["lo_conv"] = lc

    if active is not None:
        keep = {"rnn_h": active[None, None, :, None],
                "conv": active[None, None, :, None, None],
                "lo_rnn_h": active[None, :, None],
                "lo_conv": active[None, :, None, None]}
        for k, row in keep.items():
            if k in new_cache:
                new_cache[k] = jnp.where(row, new_cache[k], old[k])

    x = L.rmsnorm(params["ln_f"], x)
    return L.unembed(params["embed"], x), new_cache
