"""Mixture-of-Experts LM (qwen2-moe-a2.7b, mixtral-8x22b).

Decoder layer = GQA attention + MoE FFN.  The MoE FFN uses capacity-based
top-k routing with a grouped matmul formulation:

  router (fp32, tiny — kept unquantized, mirroring the paper keeping control
  logic out of the quantized datapath) -> top-k experts per token ->
  scatter tokens into an (E, C, D) dispatch buffer (C = capacity) ->
  one batched einsum per FFN matmul over all experts -> weighted combine.

This keeps HLO FLOPs proportional to *active* experts (top_k/E of dense),
which is what MODEL_FLOPS=6*N_active*D in the roofline expects, and the
dispatch/combine are pure data movement (gather/scatter), not matmul.

Sharding: expert weights (E, D, F) are TP-sharded on F over "model" and
FSDP on D over "data" — identical collective structure to the dense FFN.
True expert-parallel placement (E over "model") is a rule-set swap; the
default avoids it because 60 and 8 don't divide the 16-wide model axis
(DESIGN.md §6).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.qlinear import FP, QuantMode, init_linear, layer_view, linear
from repro.core.quant import QTensor
from repro.kernels import ops as kops
from repro.models import layers as L
from repro.models import transformer as TF
from repro.runtime.sharding import constrain

Array = jax.Array


def _expert_matmul(w, x_ecd: Array, mode: QuantMode) -> Array:
    """(E, C, D) x (E, D, F) -> (E, C, F); QTensor-aware."""
    if isinstance(w, QTensor):
        wf = w.values.astype(jnp.bfloat16) * w.scale.astype(jnp.bfloat16)
    else:
        wf = w.astype(jnp.bfloat16)
    return jnp.einsum("ecd,edf->ecf", x_ecd.astype(jnp.bfloat16), wf,
                      preferred_element_type=jnp.float32
                      ).astype(x_ecd.dtype)


def init_moe_ffn(key, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    std = d ** -0.5
    def w(k, shape, s=std):
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32)
                * s).astype(dtype)
    p = {
        "router": init_linear(kr, d, e, bias=False, dtype=jnp.float32),
        "experts": {
            "w_gate": w(kg, (e, d, f)),
            "w_up": w(ku, (e, d, f)),
            "w_down": w(kd, (e, f, d), s=f ** -0.5),
        },
    }
    if cfg.n_shared_experts:
        p["shared"] = L.init_mlp(
            ks, d, f * cfg.n_shared_experts, gated=cfg.gated_mlp,
            activation=cfg.activation, dtype=dtype)
    return p


def moe_ffn(p: dict, x: Array, cfg: ArchConfig, *,
            mode: QuantMode = FP) -> Array:
    """x: (B, S, D) -> (B, S, D).

    Dispatch is LOCAL per batch row (vmapped): each row routes its own S
    tokens into an (E, C_row, D) buffer, so the scatter/cumsum never
    crosses the dp sharding of the batch.  The original global-scatter
    formulation made GSPMD all-reduce the full (E, C, D) dispatch buffer
    per layer — measured as the dominant collective term of the MoE train
    baseline (§Perf iteration B1: 23.0 s -> see EXPERIMENTS.md).
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = int(math.ceil(s * k / e * cfg.capacity_factor))

    def route_row(xt):                                    # (S, D)
        logits = linear(p["router"], xt.astype(jnp.float32), mode=FP,
                        compute_dtype=jnp.float32)        # (S, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)            # (S, k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        flat_e = top_e.reshape(-1)                        # (S*k,)
        onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - 1
        my_pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
        keep = my_pos < cap
        tok_idx = jnp.repeat(jnp.arange(s), k)
        safe_pos = jnp.where(keep, my_pos, 0)
        disp = jnp.zeros((e, cap, d), x.dtype)
        disp = disp.at[flat_e, safe_pos].add(
            jnp.where(keep[:, None], xt[tok_idx], 0.0))
        return disp, flat_e, safe_pos, keep, top_p

    xt = x                                                 # (B, S, D)
    disp, flat_e, safe_pos, keep, top_p = jax.vmap(route_row)(xt)
    disp = constrain(disp, "moe_disp")                     # (B, E, C, D)

    # expert FFNs as grouped matmuls over all rows at once.  bf16 operands
    # on TPU (MXU-native); f32 on CPU, whose dot runtime lacks the batched
    # BF16xBF16=F32 thunk.
    cdt = jnp.bfloat16 if kops.on_tpu() else jnp.float32

    def emm(w, t):                                         # (B,E,C,D)x(E,D,F)
        if isinstance(w, QTensor):
            wf = w.values.astype(cdt) * w.scale.astype(cdt)
        else:
            wf = w.astype(cdt)
        return jnp.einsum("becd,edf->becf", t.astype(cdt), wf,
                          preferred_element_type=jnp.float32
                          ).astype(t.dtype)

    g = emm(p["experts"]["w_gate"], disp)
    if cfg.activation == "silu":
        g = g * jax.nn.sigmoid(g)
    else:
        g = jax.nn.gelu(g)
    u = emm(p["experts"]["w_up"], disp)
    h = constrain(g * u, "moe_disp")
    out_becd = emm(p["experts"]["w_down"], h)
    out_becd = constrain(out_becd, "moe_disp")

    # combine: per-row gather back, weight by router prob
    def combine_row(o, fe, sp, kp, tp):
        gathered = o[fe, sp]                               # (S*k, D)
        w = (tp.reshape(-1, 1) * kp[:, None]).astype(gathered.dtype)
        return jnp.sum((gathered * w).reshape(s, k, d), axis=1)

    out = jax.vmap(combine_row)(out_becd, flat_e, safe_pos, keep, top_p)

    if "shared" in p:
        out = out + L.mlp(p["shared"], xt, gated=cfg.gated_mlp,
                          activation=cfg.activation, mode=mode)
    return constrain(out, "act")


def aux_load_balance_loss(p: dict, x: Array, cfg: ArchConfig) -> Array:
    """Switch-style load-balance loss: E * sum_e f_e * P_e."""
    b, s, d = x.shape
    xt = x.reshape(-1, d).astype(jnp.float32)
    logits = linear(p["router"], xt, mode=FP, compute_dtype=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_e = jnp.argmax(probs, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(top_e, cfg.n_experts), axis=0)
    mean_p = jnp.mean(probs, axis=0)
    return cfg.n_experts * jnp.sum(frac * mean_p)


# ---------------------------------------------------------------------------
# expert share: the routed experts this chip holds, dropless
# ---------------------------------------------------------------------------

def init_expert_share(key, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    """The router over all ``cfg.n_experts``, the ``cfg.n_held`` experts
    held here (gate and up fused, gate first) and the shared MLP."""
    e, d, f, sf = cfg.n_experts, cfg.d_model, cfg.d_ff, cfg.shared_width
    kr, ku, kd, ksu, ksd = jax.random.split(key, 5)

    def w(k, shape, fan_in):
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    return {
        # float32 and outside quantize_tree's paths: routing stays exact
        "router": w(kr, (d, e), d).astype(jnp.float32),
        "experts": {"gate_up": {"w": w(ku, (cfg.n_held, d, 2 * f), d)},
                    "down": {"w": w(kd, (cfg.n_held, f, d), f)}},
        "shared": {"gate_up": {"w": w(ksu, (d, 2 * sf), d)},
                   "down": {"w": w(ksd, (sf, d), sf)}},
    }


def route_held(router, x: Array, cfg: ArchConfig) -> Array:
    """Combine weights (T, held) of the held experts for rows x (T, D):
    the router scores all ``n_experts``, the softmax runs over each row's
    top-k scores (equal to softmax-then-renormalise over the top k), and
    each held expert takes its row's weight where it is among the k, 0
    elsewhere."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    top_v, top_i = jax.lax.top_k(logits, cfg.top_k)
    gates = jax.nn.softmax(top_v, axis=-1)                 # (T, k)
    held = cfg.expert_offset + jnp.arange(cfg.n_held)
    return jnp.einsum("tk,tke->te", gates,
                      (top_i[..., None] == held).astype(jnp.float32))


def held_experts(experts: dict, layer, x: Array, comb: Array,
                 cfg: ArchConfig, mode: QuantMode) -> Array:
    """sum_e comb[:, e] * FFN_e(x) over the held experts of layer
    ``layer``: rows x (T, D), combine weights comb (T, held).  Every row
    goes through every held expert (capacity = rows), so no routed token
    is dropped at any length; ``comb`` zeroes the rows an expert was not
    routed.  Int8 weights (stacked over layers, read whole) go through
    ``ops.moe_w8a16``; float ones through an einsum."""
    gu_w, dn_w = experts["gate_up"]["w"], experts["down"]["w"]
    counts = jnp.sum(comb > 0, axis=0).astype(jnp.int32)   # (held,)
    if mode.w8a8:
        # the program's W8A8 path: the rows rounded to int8 on entry
        x = _fake_quant(x)
    if isinstance(gu_w, QTensor):
        gu = kops.moe_w8a16(x, gu_w, layer, counts, interpret=mode.interpret)
    else:
        gu = jnp.einsum("td,edf->etf", x.astype(jnp.float32),
                        gu_w[layer].astype(jnp.float32))
    g, u = jnp.split(gu, 2, axis=-1)                       # (held, T, F)
    act = jax.nn.silu(g) * u * comb.T[..., None]
    act = act.astype(x.dtype)
    if mode.w8a8:
        act = _fake_quant(act)
    if isinstance(dn_w, QTensor):
        out = kops.moe_w8a16(act, dn_w, layer, counts,
                             interpret=mode.interpret)
    else:
        out = jnp.einsum("etf,efd->etd", act.astype(jnp.float32),
                         dn_w[layer].astype(jnp.float32))
    return jnp.sum(out, axis=0)


def _fake_quant(x: Array) -> Array:
    """Round to int8 with one scale for the tensor, back in x's dtype."""
    xf = x.astype(jnp.float32)
    sc = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-6) / 127.0
    return (jnp.round(xf / sc) * sc).astype(x.dtype)


def shared_mlp(p: dict, x: Array, mode: QuantMode, layer=None) -> Array:
    """The shared SiLU-gated MLP, gate and up fused (gate first); with
    ``layer``, int8 weights are stacks read at that layer."""
    g, u = jnp.split(linear(p["gate_up"], x, mode=mode, layer=layer), 2,
                     axis=-1)
    return linear(p["down"], (jax.nn.silu(g) * u).astype(x.dtype),
                  mode=mode, layer=layer)


def expert_share(p: dict, layer, x: Array, cfg: ArchConfig, *,
                 mode: QuantMode = FP) -> Array:
    """One MoE block as this chip computes it: the held experts' part of
    the routed output plus the shared MLP, counted once.  ``p`` holds the
    layer-stacked ``router``, ``experts`` and ``shared`` leaves; x
    (..., D).  What the experts held elsewhere would add is left out."""
    lead, d = x.shape[:-1], x.shape[-1]
    xt = x.reshape(-1, d)
    router = jax.lax.dynamic_index_in_dim(p["router"], layer, 0, False)
    shared = layer_view(p["shared"], layer)
    with jax.named_scope("moe_router"):
        comb = route_held(router, xt, cfg)
    with jax.named_scope("moe_experts"):
        routed = held_experts(p["experts"], layer, xt, comb, cfg, mode)
    with jax.named_scope("moe_shared"):
        out = routed.astype(jnp.float32) + shared_mlp(
            shared, xt, mode, layer).astype(jnp.float32)
    return out.astype(x.dtype).reshape(*lead, d)


# ---------------------------------------------------------------------------
# full model: attention from transformer.py + MoE FFN
# ---------------------------------------------------------------------------

def init_layer(key, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "ln_attn": TF._norm_init(cfg)(cfg.d_model, dtype),
        "attn": L.init_attention(k1, TF.attn_config(cfg), dtype),
        "ln_mlp": TF._norm_init(cfg)(cfg.d_model, dtype),
        "moe": init_moe_ffn(k2, cfg, dtype),
    }


def init(key, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    ke, kl, ku = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    stacked = jax.vmap(lambda k: init_layer(k, cfg, dtype))(layer_keys)
    params = {
        "embed": L.init_embedding(ke, cfg.vocab, cfg.d_model, dtype),
        "layers": stacked,
        "ln_f": TF._norm_init(cfg)(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = L.init_embedding(ku, cfg.vocab, cfg.d_model,
                                             dtype)
    return params


# truncated-layer self-draft: the moe param tree has the same
# {embed, layers (vmap-stacked), ln_f[, unembed]} shape as the dense one,
# so the slice-the-stack view applies verbatim (expert weights ride the
# same leading layer axis)
draft_params = TF.draft_params


def forward(params: dict, tokens: Array, cfg: ArchConfig, *,
            mode: QuantMode = FP, remat: bool = True) -> Array:
    x = L.embed(params["embed"], tokens)
    positions = jnp.arange(tokens.shape[1])[None, :]
    acfg = TF.attn_config(cfg)

    def body(x, lp):
        h = TF.norm_apply(cfg, lp["ln_attn"], x)
        attn_out, _ = L.attention(lp["attn"], h, acfg, mode=mode,
                                  positions=positions)
        x = x + attn_out
        h = TF.norm_apply(cfg, lp["ln_mlp"], x)
        x = x + moe_ffn(lp["moe"], h, cfg, mode=mode)
        return constrain(x, "act"), None

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = TF.norm_apply(cfg, params["ln_f"], x)
    head = params.get("unembed", params["embed"])
    return L.unembed(head, x)


init_cache = TF.init_cache
init_paged_cache = TF.init_paged_cache
paged_block_axes = TF.paged_block_axes


def decode_step(params: dict, tokens: Array, cache: dict, cache_index: Array,
                cfg: ArchConfig, *, mode: QuantMode = FP
                ) -> Tuple[Array, dict]:
    """One decode step; ``cache_index`` scalar () (lockstep) or (B,)
    per-row for the slot engine, exactly as in the dense family.  Expert
    routing needs no extra per-row plumbing: dispatch/combine are already
    vmapped per batch row, so each slot routes its own token against its
    own position-independent router state."""
    b, s = tokens.shape
    x = L.embed(params["embed"], tokens)
    cache_index = jnp.asarray(cache_index)
    if cache_index.ndim:                    # (B,): per-slot positions
        positions = cache_index[:, None] + jnp.arange(s)[None, :]
    else:
        positions = cache_index + jnp.arange(s)[None, :]
    acfg = TF.attn_config(cfg)
    tables = cache.get("block_tables")      # (B, MB) int32: paged mode
    if tables is not None:
        s_alloc = tables.shape[1] * cache["k"].shape[2]   # MB * bs
    else:
        s_alloc = cache["k"].shape[2]
    write_idx = cache_index % s_alloc if cfg.window else cache_index
    valid_len = jnp.minimum(cache_index + s, s_alloc)
    quant = "k_scale" in cache
    append = (tables is None and cfg.window is None
              and cfg.n_kv_heads >= 16)     # see TF.decode_step

    def body(x, lp_and_cache):
        if quant:
            lp, ck, cv, cks, cvs = lp_and_cache
            kv = (ck, cv, cks, cvs)
        else:
            lp, ck, cv = lp_and_cache
            kv = (ck, cv)
        h = TF.norm_apply(cfg, lp["ln_attn"], x)
        attn_out, new_kv = L.attention(
            lp["attn"], h, acfg, mode=mode, positions=positions,
            kv_cache=kv, cache_index=write_idx,
            valid_len=valid_len, positions_k=positions,
            append_only=append, block_tables=tables)
        x = x + attn_out
        h = TF.norm_apply(cfg, lp["ln_mlp"], x)
        x = x + moe_ffn(lp["moe"], h, cfg, mode=mode)
        return constrain(x, "act"), new_kv

    w = TF._stacked_cache_write            # scalar () or per-row (B,) idx
    if quant:
        xs = (params["layers"], cache["k"], cache["v"],
              cache["k_scale"], cache["v_scale"])
        x, (nk, nv, nks, nvs) = jax.lax.scan(body, x, xs)
        if tables is not None:
            new_cache = dict(cache)
            for key, new in (("k", nk), ("v", nv),
                             ("k_scale", nks), ("v_scale", nvs)):
                new_cache[key] = L.paged_append(cache[key], new, tables,
                                                write_idx, block_axis=1)
        elif append:
            new_cache = {
                "k": w(cache["k"], nk, write_idx),
                "v": w(cache["v"], nv, write_idx),
                "k_scale": w(cache["k_scale"], nks, write_idx),
                "v_scale": w(cache["v_scale"], nvs, write_idx)}
        else:
            new_cache = {"k": nk, "v": nv, "k_scale": nks, "v_scale": nvs}
    else:
        x, (nk, nv) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"]))
        if tables is not None:
            new_cache = dict(cache)
            new_cache["k"] = L.paged_append(cache["k"], nk, tables,
                                            write_idx, block_axis=1)
            new_cache["v"] = L.paged_append(cache["v"], nv, tables,
                                            write_idx, block_axis=1)
        elif append:
            new_cache = {"k": w(cache["k"], nk, write_idx),
                         "v": w(cache["v"], nv, write_idx)}
        else:
            new_cache = {"k": nk, "v": nv}
    x = TF.norm_apply(cfg, params["ln_f"], x)
    head = params.get("unembed", params["embed"])
    return L.unembed(head, x), new_cache
