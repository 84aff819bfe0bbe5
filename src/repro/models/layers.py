"""Shared model components: norms, RoPE, GQA attention, MLPs, embeddings.

Conventions
-----------
- Functional: ``init_*`` returns a param dict; apply fns are pure.
- Per-layer params are stacked on a leading L axis by the model builders and
  consumed through ``lax.scan`` (keeps HLO size and compile time independent
  of depth — essential for the 100-layer dry-run cells).
- Every matmul routes through :func:`repro.core.qlinear.linear`, so
  post-training int8 quantization (the paper's technique) switches the whole
  model without touching model code.
- Sharding is expressed with ``with_sharding_constraint`` through
  :func:`repro.runtime.sharding.constrain` (a no-op outside a mesh), using
  logical axis names resolved by the active sharding rules.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.qlinear import QuantMode, FP, init_linear, linear
from repro.core.quant import QTensor
from repro.kernels import ops as kops
from repro.runtime.sharding import constrain

Array = jax.Array


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: dict, x: Array, eps: float = 1e-6) -> Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return out.astype(dt)


def init_layernorm(d: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p: dict, x: Array, eps: float = 1e-5) -> Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float = 10000.0) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: Array, positions: Array, theta: float = 10000.0) -> Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                      # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                            # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, optional sliding window, chunked for long sequences)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    window: Optional[int] = None     # sliding-window size (None = full)
    qkv_bias: bool = False           # qwen1.5-style QKV bias
    causal: bool = True
    use_rope: bool = True
    q_block: int = 512               # chunked-attention query block


def init_attention(key, cfg: AttnConfig, dtype=jnp.float32) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        "wq": init_linear(kq, d, h * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wk": init_linear(kk, d, kvh * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wv": init_linear(kv, d, kvh * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wo": init_linear(ko, h * hd, d, bias=False, dtype=dtype,
                          scale=(h * hd) ** -0.5),
    }


def _expand_kv(k: Array, n_heads: int) -> Array:
    """(B, S, KV, hd) -> (B, S, H, hd) by repeating each KV group."""
    b, s, kvh, hd = k.shape
    if kvh == n_heads:
        return k
    return jnp.repeat(k, n_heads // kvh, axis=2)


def _cache_write(c: Array, new: Array, idx: Array) -> Array:
    """Write ``new`` (B, s, KV, hd) into ``c`` (B, S_slots, KV, hd) at
    sequence position ``idx`` — scalar () for lockstep decode, or (B,) for
    the slot engine, where every row scatters at its own position."""
    idx = jnp.asarray(idx)
    if idx.ndim == 0:
        return jax.lax.dynamic_update_slice(c, new, (0, idx, 0, 0))
    return jax.vmap(
        lambda cb, nb, ib: jax.lax.dynamic_update_slice(cb, nb, (ib, 0, 0))
    )(c, new, idx)


def paged_gather(c: Array, block_tables: Array) -> Array:
    """Gather physical KV blocks (NB, bs, ...) through per-row tables
    (B, MB) into the contiguous (B, MB*bs, ...) logical layout the dense
    decode path uses — identical bytes in, identical einsums out, so the
    paged path stays bit-for-bit with the contiguous reference."""
    g = c[block_tables]                       # (B, MB, bs, ...)
    return g.reshape((g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:])


def paged_append(c: Array, new: Array, block_tables: Array, idx: Array, *,
                 block_axis: int) -> Array:
    """Scatter each row's new-token entry (..., B, 1, KV, hd) into the
    physical block pool (..., NB, bs, KV, hd) through its table at
    logical position ``idx`` (() or (B,)).  ``block_axis`` is the NB axis
    of ``c`` (the batch axis of ``new``).  Rows whose table entry is the
    reserved trash block 0 write there harmlessly — trash is never read
    because attention masks past each row's frontier."""
    blk = c.shape[block_axis + 1]
    batch = new.shape[block_axis]
    idx = jnp.asarray(idx)
    if idx.ndim == 0:
        idx = jnp.full((batch,), idx, jnp.int32)
    phys = block_tables[jnp.arange(batch), idx // blk]    # (B,)
    off = idx % blk                                       # (B,)
    pre = (slice(None),) * block_axis
    return c.at[pre + (phys, off)].set(new[pre + (slice(None), 0)])


def _chunked_attention(q: Array, k: Array, v: Array, *, causal: bool,
                       window: Optional[int], q_block: int,
                       q_offset: int = 0,
                       kv_valid_len: Optional[Array] = None) -> Array:
    """Memory-bounded attention: scan over query blocks, masked scores.

    q: (B, Sq, H, hd); k, v: (B, Sk, H, hd).  Keeps the live score tensor at
    (B, H, q_block, Sk) — the JAX-level analogue of streaming activations
    through the Unified Buffer instead of materializing the full S^2 matrix.

    Note (§Perf, refuted experiment): a pure-JAX online-softmax variant
    (nested scan over KV blocks carrying m/l/acc) measured WORSE on the
    dry-run byte model (+17-24% memory term) — the scan-carried state and
    per-pair remat replay outweigh the probs it avoids.  The fused
    `kernels/flash_attention.py` (used on TPU) gets the win without the
    JAX-level state traffic; this path stays the CPU/dry-run baseline.
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    scale = hd ** -0.5
    blk = min(q_block, sq)
    pad = (-sq) % blk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nblk = q.shape[1] // blk
    qb = q.reshape(b, nblk, blk, h, hd).transpose(1, 0, 3, 2, 4)  # (n,B,H,blk,hd)
    kt = k.transpose(0, 2, 3, 1)   # (B, H, hd, Sk)
    vt = v.transpose(0, 2, 1, 3)   # (B, H, Sk, hd)
    kpos = jnp.arange(sk)

    def one_block(carry, inp):
        qi, idx = inp
        scores = jnp.einsum("bhqd,bhdk->bhqk", qi.astype(jnp.float32),
                            kt.astype(jnp.float32)) * scale
        qpos = q_offset + idx * blk + jnp.arange(blk)
        mask = jnp.ones((blk, sk), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        scores = jnp.where(mask[None, None], scores, -1e30)
        if kv_valid_len is not None:
            # per-row KV frontier (slot engine: each row's primed source
            # has its own valid length)
            vmask = kpos[None, :] < kv_valid_len.reshape(-1, 1)   # (B, Sk)
            scores = jnp.where(vmask[:, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt.astype(jnp.float32))
        return carry, out.astype(q.dtype)

    # flash-attention memory discipline: recompute scores/probs per block in
    # the backward instead of saving (B, H, blk, Sk) f32 per block.
    one_block = jax.checkpoint(
        one_block, policy=jax.checkpoint_policies.nothing_saveable)
    _, outs = jax.lax.scan(one_block, None, (qb, jnp.arange(nblk)))
    out = outs.transpose(1, 0, 3, 2, 4).reshape(b, nblk * blk, h, hd)
    return out[:, :sq]


def attention(p: dict, x: Array, cfg: AttnConfig, *,
              mode: QuantMode = FP,
              positions: Optional[Array] = None,
              kv_cache: Optional[Tuple[Array, Array]] = None,
              cache_index: Optional[Array] = None,
              valid_len: Optional[Array] = None,
              positions_k: Optional[Array] = None,
              xattn_kv: Optional[Array] = None,
              xattn_precomputed: Optional[Tuple[Array, Array]] = None,
              xattn_valid_len: Optional[Array] = None,
              append_only: bool = False,
              block_tables: Optional[Array] = None,
              ) -> Tuple[Array, Optional[Tuple[Array, Array]]]:
    """GQA attention with three modes:

    - training / prefill: kv_cache=None -> chunked causal self-attention.
    - decode: kv_cache=(K, V) of shape (B, S_slots, KV, hd); cache_index =
      write slot; valid_len = number of valid slots; x is (B, 1, D).
      ``cache_index`` / ``valid_len`` may be scalars () — the whole batch
      advances in lockstep — or vectors (B,) for the slot-based serving
      engine, where every batch row is an independent request at its own
      sequence position (writes become per-row scatters, masks and RoPE
      positions per-row).
      Sliding-window archs use a ring buffer (S_slots = window): RoPE is
      applied at absolute positions before caching, so slot order does not
      affect scores, and masking is just `slot < valid_len`.
    - cross-attention: xattn_kv = encoder/vision states (B, S_src, D);
      non-causal over the source (cache unused; K/V recomputed — static
      source states make this a pure matmul, MXU-friendly).
      ``xattn_precomputed`` = (K, V) projected once at prime time (the
      slot engine's per-slot primed cross operand); ``xattn_valid_len``
      () or (B,) masks each row's source reads at its own primed length,
      so a slot row holding a shorter source (or a previous tenant's
      stale tail) contributes nothing past the frontier.

    ``block_tables`` (B, MB) int32 switches decode to the paged KV cache:
    ``kv_cache`` leaves are physical blocks (NB, bs, KV, hd) and each
    row's logical position p lives at block ``table[b, p // bs]``, offset
    ``p % bs``.  The einsum path gathers the blocks into the SAME
    contiguous layout as above and writes the new token into the gathered
    view, so the math (and its rounding) is bit-identical to the
    contiguous non-append path; ``new_cache`` returns just the new-token
    entries for the caller to scatter through the table outside the layer
    scan (see :func:`paged_append`).
    """
    b, s, d = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = linear(p["wq"], x, mode=mode).reshape(b, s, h, hd)
    if xattn_precomputed is not None:
        # §Perf iteration D: source K/V were projected ONCE at prime time
        # (encoder frames / vision patches are static across decode steps)
        k, v = xattn_precomputed
        xattn_kv = k    # flags the non-causal source-attention path below
    else:
        kv_src = xattn_kv if xattn_kv is not None else x
        k = linear(p["wk"], kv_src, mode=mode).reshape(
            b, kv_src.shape[1], kvh, hd)
        v = linear(p["wv"], kv_src, mode=mode).reshape(
            b, kv_src.shape[1], kvh, hd)

    if positions is None:
        positions = jnp.arange(s)[None, :]
    if cfg.use_rope and xattn_kv is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        # k is rotated at its *absolute* position before caching, so ring
        # storage order does not affect the scores.
        kpos = positions if positions_k is None else positions_k
        k = apply_rope(k, kpos, cfg.rope_theta)

    new_cache = None
    if kv_cache is not None:
        # GQA-aware decode: contract against the cache in its native
        # (B, S, KV, hd) layout — materializing the KV->H repeat would cost
        # G x the cache traffic and force GSPMD to reshard the whole cache
        # (measured: the dominant collective term of the decode baseline).
        q = constrain(q, "act_heads_decode")
        quantized = len(kv_cache) == 4          # (k, v, k_scale, v_scale)
        paged = block_tables is not None

        def q8(t):                              # (B, s, KV, hd) -> int8
            tf = t.astype(jnp.float32)
            amax = jnp.maximum(jnp.max(jnp.abs(tf), axis=-1,
                                       keepdims=True), 1e-6)
            sc = amax / 127.0
            return (jnp.round(tf / sc).astype(jnp.int8),
                    sc.astype(jnp.float32))

        if quantized:
            # int8 cache with per-(token, head) scales — the paper's 8-bit
            # discipline applied to the KV cache (halves cache HBM traffic
            # and footprint vs bf16; §Perf iteration C1).
            ck, cv, cks, cvs = kv_cache
            kq, ks = q8(k)
            vq, vs = q8(v)
            if paged:
                # Paged: gather physical blocks into the contiguous layout
                # and write the new token into the gathered VIEW — the
                # einsum below then sees byte-identical inputs to the
                # contiguous non-append path (bit parity).  Only the
                # new-token entries return; the caller scatters them
                # through the table post-scan.
                pck, pcv, pcks, pcvs = ck, cv, cks, cvs
                ck = _cache_write(paged_gather(pck, block_tables), kq,
                                  cache_index)
                cv = _cache_write(paged_gather(pcv, block_tables), vq,
                                  cache_index)
                cks = _cache_write(paged_gather(pcks, block_tables), ks,
                                   cache_index)
                cvs = _cache_write(paged_gather(pcvs, block_tables), vs,
                                   cache_index)
                new_cache = (kq, vq, ks, vs)
            elif append_only:
                # §Perf iteration A4/C3: do NOT rewrite the cache slice
                # inside the layer scan (that costs a full slice write+read
                # per layer per step); return just the new token's entry —
                # the caller appends once, outside the scan.
                new_cache = (kq, vq, ks, vs)
            else:
                ck = _cache_write(ck, kq, cache_index)
                cv = _cache_write(cv, vq, cache_index)
                cks = _cache_write(cks, ks, cache_index)
                cvs = _cache_write(cvs, vs, cache_index)
                ck = constrain(ck, "kv_cache")
                cv = constrain(cv, "kv_cache")
                cks = constrain(cks, "kv_cache")
                cvs = constrain(cvs, "kv_cache")
                new_cache = (ck, cv, cks, cvs)
            k_self, v_self = kq.astype(jnp.float32) * ks, \
                vq.astype(jnp.float32) * vs
        else:
            ck, cv = kv_cache                   # (B, S_slots, KV, hd)
            if paged:
                pck, pcv = ck, cv
                ck = _cache_write(paged_gather(pck, block_tables),
                                  k.astype(pck.dtype), cache_index)
                cv = _cache_write(paged_gather(pcv, block_tables),
                                  v.astype(pcv.dtype), cache_index)
                new_cache = (k.astype(pck.dtype), v.astype(pcv.dtype))
            elif append_only:
                new_cache = (k.astype(ck.dtype), v.astype(cv.dtype))
            else:
                ck = _cache_write(ck, k.astype(ck.dtype), cache_index)
                cv = _cache_write(cv, v.astype(cv.dtype), cache_index)
                ck = constrain(ck, "kv_cache")
                cv = constrain(cv, "kv_cache")
                new_cache = (ck, cv)
            k_self, v_self = k, v
        smax = ck.shape[1]
        g = h // kvh                            # heads per KV group
        if valid_len is None:
            valid_len = cache_index + s
        if quantized and s == 1 and kops.on_tpu():
            # Fused Pallas decode attention: streams the int8 cache and
            # dequantizes tile-by-tile in VMEM (each tile's rows scaled
            # by their per-token scales), removing the decode path's
            # dominant memory term — the materialized dequantized cache.
            # Append path: the cache holds tokens < cache_index and the
            # current token's k/v ride along as an extra kernel operand,
            # so the fused kernel now serves ALL quantized decode, not
            # only the in-scan-update (non-append) variant.
            if paged:
                # physical blocks stream through the per-row table inside
                # the kernel (scalar-prefetch grid); the cache holds tokens
                # < cache_index, the current token's k/v ride along as the
                # append column.  The gathered view above is dead code on
                # this branch and gets DCE'd.
                out = kops.decode_attention(
                    q.reshape(b, kvh, g, hd), pck, pcv, pcks, pcvs,
                    cache_index, block_tables=block_tables,
                    k_new=k_self, v_new=v_self, out_dtype=jnp.float32)
            elif append_only:
                out = kops.decode_attention(
                    q.reshape(b, kvh, g, hd), ck, cv, cks, cvs,
                    cache_index, k_new=k_self, v_new=v_self,
                    out_dtype=jnp.float32)
            else:
                out = kops.decode_attention(
                    q.reshape(b, kvh, g, hd), ck, cv, cks, cvs, valid_len,
                    out_dtype=jnp.float32)
            out = out.astype(x.dtype).reshape(b, s, h, hd)
            out = constrain(out, "act_heads")
            out = linear(p["wo"], out.reshape(b, s, h * hd), mode=mode)
            return constrain(out, "act"), new_cache
        q5 = q.reshape(b, s, kvh, g, hd)
        scale = hd ** -0.5
        # bf16-native contractions with f32 accumulate; per-token dequant
        # scales are independent of the contracted hd axis, so they fold
        # into the scores/probs instead of materializing a dequantized
        # cache copy (§Perf iteration A3/C2).
        scores = jnp.einsum("bqkgd,bskd->bkgqs",
                            q5.astype(jnp.bfloat16),
                            ck.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32) * scale
        if quantized:
            scores = scores * cks[..., 0].transpose(0, 2, 1)[:, :, None,
                                                             None, :]
        kpos_idx = jnp.arange(smax)
        if append_only:
            # cache holds tokens < cache_index; the current token's k/v are
            # handled as an extra score column below.
            bound = cache_index
        else:
            bound = valid_len
        # (1, S) lockstep, or (B, S) when the slot engine passes per-row
        # indices — every request masks at its own sequence frontier.
        valid = kpos_idx[None, :] < jnp.asarray(bound).reshape(-1, 1)
        scores = jnp.where(valid[:, None, None, None, :], scores, -1e30)
        if append_only:
            s_self = jnp.einsum("bqkgd,btkd->bkgqt",
                                q5.astype(jnp.float32),
                                k_self.astype(jnp.float32)) * scale
            scores = jnp.concatenate([scores, s_self], axis=-1)
        probs = jax.nn.softmax(scores, axis=-1)
        if append_only:
            probs, p_self = probs[..., :smax], probs[..., smax:]
        if quantized:
            probs = probs * cvs[..., 0].transpose(0, 2, 1)[:, :, None,
                                                           None, :]
        out = jnp.einsum("bkgqs,bskd->bqkgd",
                         probs.astype(jnp.bfloat16),
                         cv.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
        if append_only:
            out = out + jnp.einsum("bkgqt,btkd->bqkgd",
                                   p_self.astype(jnp.float32),
                                   v_self.astype(jnp.float32))
        out = out.astype(x.dtype).reshape(b, s, h, hd)
    else:
        q = constrain(q, "act_heads")  # (B, S, H, hd): H over model axis
        kfull = _expand_kv(k, h)
        vfull = _expand_kv(v, h)
        causal = cfg.causal and xattn_kv is None
        window = cfg.window if xattn_kv is None else None
        if kops.on_tpu() and xattn_valid_len is None:
            # Pallas fused flash kernel: probs never leave VMEM (the
            # Unified-Buffer discipline); HBM traffic = Q+K+V+O.  The
            # kernel carries no per-row KV frontier, so a primed source
            # with per-row valid lengths takes the masked chunked path.
            out = kops.flash_attention(q, kfull, vfull, causal=causal,
                                       window=window)
        else:
            # pure-JAX chunked path: identical math (tests assert so),
            # used on CPU and in the dry-run.
            out = _chunked_attention(q, kfull, vfull, causal=causal,
                                     window=window, q_block=cfg.q_block,
                                     kv_valid_len=xattn_valid_len)
    out = constrain(out, "act_heads")
    out = linear(p["wo"], out.reshape(b, s, h * hd), mode=mode)
    return constrain(out, "act"), new_cache


def init_kv_cache(batch: int, s_max: int, n_kv: int, head_dim: int,
                  dtype=jnp.bfloat16) -> Tuple[Array, Array]:
    shape = (batch, s_max, n_kv, head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


# ---------------------------------------------------------------------------
# MLP blocks
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, *, gated: bool = True,
             activation: str = "silu", bias: bool = False,
             dtype=jnp.float32) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"w_up": init_linear(k1, d_model, d_ff, bias=bias, dtype=dtype),
         "w_down": init_linear(k2, d_ff, d_model, bias=bias, dtype=dtype,
                               scale=d_ff ** -0.5)}
    if gated:
        p["w_gate"] = init_linear(k3, d_model, d_ff, bias=bias, dtype=dtype)
    return p


def mlp(p: dict, x: Array, *, gated: bool, activation: str,
        mode: QuantMode = FP) -> Array:
    if gated:
        g = linear(p["w_gate"], x, activation=activation, mode=mode)
        u = linear(p["w_up"], x, mode=mode)
        h = constrain(g * u, "act_ff")
    else:
        h = linear(p["w_up"], x, activation=activation, mode=mode)
        h = constrain(h, "act_ff")
    return constrain(linear(p["w_down"], h, mode=mode), "act")


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d_model: int, dtype=jnp.float32) -> dict:
    return {"table": (jax.random.normal(key, (vocab, d_model), jnp.float32)
                      * d_model ** -0.5).astype(dtype)}


def embed(p: dict, tokens: Array, compute_dtype=jnp.bfloat16) -> Array:
    table = p["table"]
    if isinstance(table, QTensor):
        # per-row scales: gather int8 rows, dequantize the gathered slice
        rows = table.values[tokens].astype(compute_dtype)
        scale = table.scale.reshape(-1)[tokens][..., None]
        return constrain(rows * scale.astype(compute_dtype), "act")
    return constrain(table.astype(compute_dtype)[tokens], "act")


def unembed(p: dict, x: Array, compute_dtype=jnp.bfloat16) -> Array:
    """(Tied) LM head: logits = x @ table.T, fp32 accumulate.  Quantized
    tables have per-row scales, folded per output column of the head."""
    table = p["table"]
    if isinstance(table, QTensor):
        logits = jnp.einsum("bsd,vd->bsv", x.astype(compute_dtype),
                            table.values.astype(compute_dtype),
                            preferred_element_type=jnp.float32)
        logits = logits * table.scale.reshape(1, 1, -1)
    else:
        logits = jnp.einsum("bsd,vd->bsv", x.astype(compute_dtype),
                            table.astype(compute_dtype),
                            preferred_element_type=jnp.float32)
    return constrain(logits, "logits")
