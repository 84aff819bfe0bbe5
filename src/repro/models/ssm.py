"""Mamba-2 (SSD, state-space duality) — attention-free LM [arXiv:2405.21060].

The SSD layer computes y_t = C_t^T h_t,  h_t = a_t h_{t-1} + dt_t B_t x_t^T
with scalar-per-head decay a_t = exp(dt_t * A).  The *chunked* algorithm
(the paper's contribution) splits the sequence into chunks of Q tokens:

  intra-chunk: a masked (C_q B_k^T)-style "attention" matmul — MXU-friendly;
  inter-chunk: a small recurrence over per-chunk states (B, H, hd, N),
               carried by lax.scan.

This gives O(S*Q) work instead of O(S^2) -> the long_500k cell is runnable.
Training/prefill use the chunked path; decode is the O(1) state update.

Numerics note (paper tie-in): the recurrent state h accumulates in fp32 —
the same reasoning as the TPU's 32-bit accumulators; in/out projections run
through the quantized `linear` like every other matmul.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.qlinear import FP, QuantMode, init_linear, layer_view, linear
from repro.core.quant import QTensor
from repro.kernels import ops
from repro.models import layers as L
from repro.runtime.sharding import constrain

Array = jax.Array


def _segsum(log_a: Array) -> Array:
    """Stable segment-sum: out[..., i, j] = sum_{k=j+1..i} log_a[..., k]
    for j < i (lower-triangular), -inf above the diagonal."""
    q = log_a.shape[-1]
    cs = jnp.cumsum(log_a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]          # sum_(j..i]
    i = jnp.arange(q)
    mask = i[:, None] >= i[None, :]
    return jnp.where(mask, diff, -jnp.inf)


def init_ssd_layer(key, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    d, din, n, nh = (cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    # fused in_proj: [x (din), z (din), B (n), C (n), dt (nh)]
    p = {
        "norm": L.init_rmsnorm(d, dtype),
        "in_proj": init_linear(k1, d, 2 * din + 2 * n + nh, bias=False,
                               dtype=dtype),
        "conv_w": (jax.random.truncated_normal(
            k2, -2, 2, (cfg.conv_width, din + 2 * n), jnp.float32)
            * 0.3).astype(dtype),
        "conv_b": jnp.zeros((din + 2 * n,), dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, nh)).astype(jnp.float32),
        "dt_bias": jnp.full((nh,), -2.0, jnp.float32),
        "D": jnp.ones((nh,), jnp.float32),
        "out_proj": init_linear(k3, din, d, bias=False, dtype=dtype,
                                scale=din ** -0.5),
    }
    if cfg.ssm_gated_norm:
        p["gate_norm"] = L.init_rmsnorm(din, dtype)
    return p


def _causal_conv(x: Array, w: Array, b: Array,
                 state: Array = None) -> Tuple[Array, Array]:
    """Depthwise causal conv1d.  x: (B, S, C); w: (width, C).

    Returns (out, new_state) where state is the last (width-1) inputs
    (decode carries it)."""
    width = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * w[i][None, None].astype(x.dtype)
              for i in range(width))
    new_state = xp[:, -(width - 1):] if width > 1 else \
        jnp.zeros((x.shape[0], 0, x.shape[2]), x.dtype)
    return jax.nn.silu(out + b[None, None].astype(x.dtype)), new_state


def _ssd_chunked(xh: Array, dt: Array, a_log: Array, Bm: Array, Cm: Array,
                 chunk: int) -> Array:
    """Chunked SSD scan.

    xh: (B, S, H, hd); dt: (B, S, H); Bm, Cm: (B, S, N).
    Returns y: (B, S, H, hd).  fp32 state.
    """
    b, s, h, hd = xh.shape
    n = Bm.shape[-1]
    q = min(chunk, s)
    pad = (-s) % q
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    nc = xh.shape[1] // q

    # chunk views: (nc, B, q, ...)
    def chunked(t):
        return t.reshape(b, nc, q, *t.shape[2:]).swapaxes(0, 1)

    xc, dtc, Bc, Cc = map(chunked, (xh, dt, Bm, Cm))
    A = -jnp.exp(a_log)                                   # (H,)
    log_a = dtc.astype(jnp.float32) * A[None, None, None]  # (nc,B,q,H)<=0

    def per_chunk(state, inp):
        xq, dtq, bq, cq, la = inp          # (B,q,H,hd) (B,q,H) (B,q,N) ...
        la_h = la.transpose(0, 2, 1)                       # (B,H,q)
        seg = _segsum(la_h)                                # (B,H,q,q)
        decay = jnp.exp(seg)                               # lower-tri
        # intra-chunk: scores (B,H,q,q) = C_i . B_j * decay * dt_j
        scores = jnp.einsum("bin,bjn->bij", cq.astype(jnp.float32),
                            bq.astype(jnp.float32))
        scores = scores[:, None] * decay * \
            dtq.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, :]
        y_intra = jnp.einsum("bhij,bjhd->bihd", scores,
                             xq.astype(jnp.float32))
        # inter-chunk: contribution of carried state
        decay_in = jnp.exp(jnp.cumsum(la_h, axis=-1))      # (B,H,q)
        y_inter = jnp.einsum("bin,bhdn,bhi->bihd",
                             cq.astype(jnp.float32), state, decay_in)
        # state update: h' = a_total * h + sum_j decay_rest_j dt_j B_j x_j
        a_total = jnp.exp(jnp.sum(la_h, axis=-1))          # (B,H)
        decay_rest = jnp.exp(jnp.sum(la_h, axis=-1, keepdims=True)
                             - jnp.cumsum(la_h, axis=-1))  # (B,H,q)
        contrib = jnp.einsum("bjn,bjhd,bhj,bjh->bhdn",
                             bq.astype(jnp.float32), xq.astype(jnp.float32),
                             decay_rest, dtq.astype(jnp.float32))
        new_state = a_total[..., None, None] * state + contrib
        return new_state, (y_intra + y_inter).astype(xh.dtype)

    state0 = jnp.zeros((b, h, hd, n), jnp.float32)
    state0 = constrain(state0, "ssm_state")
    _, ys = jax.lax.scan(per_chunk, state0, (xc, dtc, Bc, Cc, log_a))
    y = ys.swapaxes(0, 1).reshape(b, nc * q, h, hd)
    return y[:, :s]


def _mix_in(p: dict, x: Array, cfg: ArchConfig, mode: QuantMode,
            conv_state: Array = None, layer: Array = None):
    """Norm, in-projection and causal conv of one block (``layer``: the
    int8 linears are stacks read at that layer, see ``linear``).  Returns
    (xh (B,S,H,hd), z, Bm, Cm, dt (B,S,H) float32, new conv tail).  The
    in-projection's pieces are taken by their widths: columns past them
    (``pad_in_proj``'s zeros) are dropped."""
    b, s, _ = x.shape
    din, n, nh, hd = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_headdim
    h_in = L.rmsnorm(p["norm"], x, cfg.norm_eps)
    proj = linear(p["in_proj"], h_in, mode=mode, layer=layer)
    xz, z, Bm, Cm, dt, _ = jnp.split(
        proj, [din, 2 * din, 2 * din + n, 2 * din + 2 * n,
               2 * din + 2 * n + nh], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"][None, None])       # (B,S,H)

    conv_in = jnp.concatenate([xz, Bm, Cm], axis=-1)
    conv_out, new_conv = _causal_conv(conv_in, p["conv_w"], p["conv_b"],
                                      conv_state)
    xz, Bm, Cm = jnp.split(conv_out, [din, din + n], axis=-1)
    return xz.reshape(b, s, nh, hd), z, Bm, Cm, dt, new_conv


def _mix_out(p: dict, x: Array, y: Array, xh: Array, z: Array,
             cfg: ArchConfig, mode: QuantMode, layer: Array = None) -> Array:
    """Skip term, gate (with the gated RMSNorm where ``cfg.ssm_gated_norm``)
    and out-projection; residual added, scaled by
    ``cfg.residual_multiplier``."""
    b, s, nh, hd = xh.shape
    y = y + p["D"][None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(b, s, nh * hd) * jax.nn.silu(z.astype(jnp.float32))
    if cfg.ssm_gated_norm:
        y = L.rmsnorm(p["gate_norm"], y, cfg.norm_eps)
    out = linear(p["out_proj"], y.astype(x.dtype), mode=mode, layer=layer)
    if cfg.residual_multiplier != 1.0:
        out = (out.astype(jnp.float32) * cfg.residual_multiplier
               ).astype(out.dtype)
    return x + constrain(out, "act")


def ssd_layer(p: dict, x: Array, cfg: ArchConfig, *,
              mode: QuantMode = FP) -> Array:
    """One Mamba-2 block over a full sequence (chunked SSD)."""
    xh, z, Bm, Cm, dt, _ = _mix_in(p, x, cfg, mode)
    y = _ssd_chunked(xh, dt, p["a_log"], Bm, Cm, cfg.ssm_chunk)
    return _mix_out(p, x, y, xh, z, cfg, mode)


def ssd_decode_layer(p: dict, x: Array, h: Array, conv: Array, layer: Array,
                     fresh: Array, active: Array, cfg: ArchConfig, *,
                     mode: QuantMode = FP) -> Tuple[Array, Array, Array]:
    """One Mamba-2 block, one token, on layer ``layer`` of the stacked
    state: h (L, B, H, hd, N) float32 and conv (L, B, width-1, C).  ``p``
    is the block's stack at ``layer`` as ``qlinear.layer_view`` gives it:
    float leaves sliced, int8 linears whole, read in place at ``layer``.

    O(1) decode: h' = a h + dt B x, y = C.h'.  Rows with ``fresh`` start
    from zero state and rows without ``active`` keep theirs bitwise; both
    happen inside ``ops.ssm_state_update``, which on a TPU rewrites the
    layer's state in place in one pass.  Returns (x, h, conv)."""
    conv_l = jax.lax.dynamic_index_in_dim(conv, layer, 0, keepdims=False)
    conv_in = jnp.where(fresh[:, None, None], jnp.zeros_like(conv_l), conv_l)
    xh, z, Bm, Cm, dt, new_conv = _mix_in(p, x, cfg, mode, conv_in, layer)
    new_conv = jnp.where(active[:, None, None], new_conv, conv_l)
    conv = jax.lax.dynamic_update_index_in_dim(conv, new_conv, layer, 0)
    a_step = jnp.exp(dt[:, 0] * (-jnp.exp(p["a_log"]))[None])   # (B,H)
    h, y = ops.ssm_state_update(h, layer, a_step, xh[:, 0], dt[:, 0],
                                Bm[:, 0], Cm[:, 0], fresh, active)
    return _mix_out(p, x, y[:, None], xh, z, cfg, mode, layer), h, conv


def pad_in_proj(block: dict) -> dict:
    """``block`` (SSD blocks stacked over layers) with its int8
    in-projection's columns padded with zeros to a multiple of 128, as
    served.  XLA stores an int8 stack whose last dimension is not a
    multiple of 128 with K minor, and the int8 kernel reads it row-major,
    so every step would relayout the whole stack.  ``_mix_in`` drops the
    extra columns.  Float weights and widths already aligned are returned
    as they are, so a second call changes nothing."""
    w = block["in_proj"]["w"]
    pad = -w.shape[-1] % 128
    if not isinstance(w, QTensor) or pad == 0:
        return block
    cols = lambda a: jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
    w = QTensor(values=cols(w.values), scale=cols(w.scale), bits=w.bits)
    return {**block, "in_proj": {**block["in_proj"], "w": w}}


def serving_params(params: dict) -> dict:
    """The parameters as the engine serves them (``pad_in_proj``)."""
    return {**params, "layers": pad_in_proj(params["layers"])}


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------

def init(key, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    ke, kl = jax.random.split(key)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    stacked = jax.vmap(lambda k: init_ssd_layer(k, cfg, dtype))(layer_keys)
    return {
        "embed": L.init_embedding(ke, cfg.vocab, cfg.d_model, dtype),
        "layers": stacked,
        "ln_f": L.init_rmsnorm(cfg.d_model, dtype),
    }


def forward(params: dict, tokens: Array, cfg: ArchConfig, *,
            mode: QuantMode = FP, remat: bool = True) -> Array:
    x = L.embed(params["embed"], tokens)

    def body(x, lp):
        return ssd_layer(lp, x, cfg, mode=mode), None

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = L.rmsnorm(params["ln_f"], x)
    return L.unembed(params["embed"], x)


def init_cache(cfg: ArchConfig, batch: int, s_max: int,
               dtype=jnp.bfloat16) -> dict:
    """Fixed-size state: (L, B, H, hd, N) fp32 + conv tail — independent of
    context length (the whole point for long_500k)."""
    nh, hd, n = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    return {
        "h": jnp.zeros((cfg.n_layers, batch, nh, hd, n), jnp.float32),
        "conv": jnp.zeros((cfg.n_layers, batch, cfg.conv_width - 1,
                           cfg.d_inner + 2 * n), dtype),
    }


def decode_step(params: dict, tokens: Array, cache: dict, cache_index: Array,
                cfg: ArchConfig, *, mode: QuantMode = FP,
                active: Array = None) -> Tuple[Array, dict]:
    """One-token decode.  ``cache_index`` is scalar () (lockstep batch) or
    (B,) per-row for the slot engine.  The state is position-free, so the
    index's only job here is the *reset-at-zero scrub*: a row decoding its
    position-0 token by definition has no history, so its carried
    ``h``/conv state is zeroed before the update — that is what makes a
    reused slot's previous tenant invisible without scrubbing the pool.

    ``active`` (B,) bool, default every row: rows outside it keep their
    ``h`` and conv state bitwise (the slot engine's freeze).  Unlike a KV
    cache the state has no ``valid_len`` frontier to hide a clobbered row
    behind, so the freeze is part of the update.  Their logits are
    computed and discarded by the caller."""
    b, s = tokens.shape
    x = L.embed(params["embed"], tokens)
    fresh = jnp.broadcast_to(jnp.asarray(cache_index) == 0, (b,))
    active = (jnp.ones((b,), bool) if active is None
              else jnp.asarray(active, bool))

    def body(carry, layer):
        x, h, conv = carry
        lp = layer_view(params["layers"], layer)
        return ssd_decode_layer(lp, x, h, conv, layer, fresh, active, cfg,
                                mode=mode), None

    (x, h, conv), _ = jax.lax.scan(
        body, (x, cache["h"], cache["conv"]), jnp.arange(cfg.n_layers))
    x = L.rmsnorm(params["ln_f"], x)
    return L.unembed(params["embed"], x), {"h": h, "conv": conv}
