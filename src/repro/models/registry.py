"""Family -> model module dispatch.

Uniform API per family:
    init(key, cfg, dtype) -> params
    forward(params, tokens, [extra_embeds,] cfg, *, mode, remat) -> logits
    init_cache(cfg, batch, s_max, dtype) -> cache
    decode_step(params, tokens, cache, cache_index, cfg, *, mode[, active])
        -> (logits, cache)

``apply_forward`` / ``apply_decode`` normalize the extra-input plumbing
(encoder frames / vision patches) so the runtime treats all ten archs
identically.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.configs.base import ArchConfig
from repro.core.qlinear import FP, QuantMode
from repro.models import (encdec, hybrid_moe, moe, rglru, ssm, transformer,
                          vision)

_MODULES = {
    "dense": transformer,
    "moe": moe,
    "ssm": ssm,
    "hybrid": rglru,
    "hybrid_moe": hybrid_moe,
    "encdec": encdec,
    "vlm": vision,
}


# families whose decode state is recurrent: it freezes for inactive rows
_RECURRENT = ("ssm", "hybrid", "hybrid_moe")


def module_for(cfg: ArchConfig):
    return _MODULES[cfg.family]


def init(key, cfg: ArchConfig, dtype=None):
    import jax.numpy as jnp
    return module_for(cfg).init(key, cfg, dtype or jnp.float32)


def init_quantized(key, cfg: ArchConfig):
    """``serving_params(quantize_tree(init(key, cfg), min_size=2048))``
    under ONE jit whose only output is the int8 tree, so the float32 tree
    never exists whole on the device.  At starcoder2-3b's widths that tree
    is 12.1 GB of a 16 GB chip; compiled for a v5e, this program's
    temporaries peak at the largest float32 leaf (4.5 GB) beside the
    3.0 GB int8 output."""
    from repro.core.quant import quantize_tree
    return jax.jit(lambda k: serving_params(
        cfg, quantize_tree(init(k, cfg), min_size=2048)))(key)


def serving_params(cfg: ArchConfig, params):
    """``params`` laid out as the engine serves them: the family's
    ``serving_params`` hook where it has one (ssm and hybrid_moe pad the
    int8 SSD in-projection to a multiple of 128 columns), else ``params``
    itself.  Applying it twice changes nothing."""
    hook = getattr(module_for(cfg), "serving_params", None)
    return params if hook is None else hook(params)


def apply_forward(params, cfg: ArchConfig, batch: dict, *,
                  mode: QuantMode = FP, remat: bool = True):
    """batch: dict from input_specs (tokens + optional modality embeds)."""
    m = module_for(cfg)
    if cfg.family == "encdec":
        return m.forward(params, batch["tokens"], batch["encoder_embeds"],
                         cfg, mode=mode, remat=remat)
    if cfg.family == "vlm":
        return m.forward(params, batch["tokens"], batch["vision_embeds"],
                         cfg, mode=mode, remat=remat)
    return m.forward(params, batch["tokens"], cfg, mode=mode, remat=remat)


def init_cache(cfg: ArchConfig, batch: int, s_max: int, dtype=None):
    import jax.numpy as jnp
    return module_for(cfg).init_cache(cfg, batch, s_max,
                                      dtype or jnp.bfloat16)


def apply_decode(params, cfg: ArchConfig, batch: dict, cache, *,
                 mode: QuantMode = FP, active=None):
    """One decode step.  ``active`` (B,) bool, default every row: the
    slot engine's freeze.  The recurrent families (_RECURRENT) take it
    into their decode and keep inactive rows' state bitwise; their state
    has no ``valid_len`` frontier to hide a clobbered row behind.  The
    other families' caches are positional (stale entries are masked on
    read), so they are never given it and their steps do not change."""
    m = module_for(cfg)
    kw = {} if active is None or cfg.family not in _RECURRENT \
        else {"active": active}
    return m.decode_step(params, batch["tokens"], cache,
                         batch["cache_index"], cfg, mode=mode, **kw)


def supports_paging(cfg: ArchConfig) -> bool:
    """True when the family can serve from a paged (block-table) KV
    cache: it must have a growing positional KV frontier (excludes the
    recurrent ssm/hybrid state) and full attention (a sliding window's
    ring overwrite has no stable position -> block mapping)."""
    return (cfg.window is None
            and hasattr(module_for(cfg), "init_paged_cache"))


def init_paged_cache(cfg: ArchConfig, num_slots: int, s_max: int,
                     block_size: int, num_blocks: int, dtype=None):
    """Paged KV cache: positional leaves become physical blocks
    (..., num_blocks, block_size, KV, hd) shared by all slots through the
    per-slot ``cache["block_tables"]`` (num_slots, s_max // block_size)
    int32 leaf; block 0 is the reserved trash block.  Non-positional
    leaves (primed cross K/V, xlen) stay slot-resident."""
    import jax.numpy as jnp
    if not supports_paging(cfg):
        raise ValueError(f"family {cfg.family!r} (window={cfg.window}) "
                         f"does not support the paged KV cache")
    return module_for(cfg).init_paged_cache(cfg, num_slots, s_max,
                                            block_size, num_blocks,
                                            dtype or jnp.bfloat16)


def paged_block_axes(cfg: ArchConfig, cache: dict) -> dict:
    """Physical-block (NB) axis per PAGED cache leaf — the axis a block
    table entry indexes.  Leaves absent from this dict (cross K/V, xlen,
    the table itself) are slot-resident and keep cache_batch_axes
    semantics."""
    return module_for(cfg).paged_block_axes(cache)


# ---------------------------------------------------------------------------
# slot-engine contract (per-row decode state; see docs/serving.md)
# ---------------------------------------------------------------------------

def needs_prime(cfg: ArchConfig) -> bool:
    """True when the family decodes against per-request primed state
    (encoder frames / vision patches) that must be written into a slot
    row at admission by a prime dispatch (encdec/vlm)."""
    return hasattr(module_for(cfg), "prime_slot")


def source_len(cfg: ArchConfig) -> int:
    """Static source length of a prime dispatch: how many frames/patches
    one slot row's primed cross-K/V holds (0 for token-only families)."""
    if cfg.family == "encdec":
        return cfg.enc_seq
    if cfg.family == "vlm":
        return cfg.n_patches
    return 0


def source_shape(cfg: ArchConfig) -> Optional[tuple]:
    """(source_len, d_model) of one request's source embeddings, or None
    for token-only families — the single contract request generators
    (serve CLI, benches, tests) build per-request sources against."""
    if not needs_prime(cfg):
        return None
    return (source_len(cfg), cfg.d_model)


def prime_slot(cfg: ArchConfig, params, source, n_valid, *,
               mode: QuantMode = FP) -> dict:
    """Run one request's encoder / vision tower and return the
    slot-resident primed leaves (pre-projected cross K/V + the row's
    ``xlen`` frontier) that a prime dispatch scatters into the pooled
    cache at the slot's row.  ``source`` is (1, source_len(cfg), D)
    padded to the static length; ``n_valid`` () is how many positions
    are real (decode masks reads past it)."""
    return module_for(cfg).prime_slot(params, source, n_valid, cfg,
                                      mode=mode)


def supports_speculation(cfg: ArchConfig) -> bool:
    """True when the family can serve as the TARGET (or the draft) of
    draft-and-verify speculative decoding: its entire decode state must
    be positional KV behind a ``valid_len`` frontier, so a rejected
    speculative tail can be *rewound* by resetting ``cache_index`` — the
    stale writes die by overwrite-before-read (decode-contract rule 7,
    docs/architecture.md).  That excludes the recurrent families
    (ssm/hybrid: ``h``/conv state advances irreversibly through rejected
    tokens), sliding-window attention (the ring overwrite destroys the
    positions a rewind must restore), and the prime families (their
    cross-attention plumbing is not wired through the verify scan)."""
    return (cfg.window is None and not needs_prime(cfg)
            and hasattr(module_for(cfg), "draft_params"))


def supports_self_draft(cfg: ArchConfig) -> bool:
    """True when the family can draft for itself with a truncated-layer
    view of its own params (no second checkpoint): it must be
    speculation-capable AND expose ``draft_params`` — a module-level
    slice of the vmap-stacked ``layers`` leaves."""
    return supports_speculation(cfg)


def draft_config(cfg: ArchConfig, n_layers: int) -> ArchConfig:
    """The self-draft model's config: the target's, truncated to its
    first ``n_layers`` layers."""
    import dataclasses
    if not 1 <= n_layers <= cfg.n_layers:
        raise ValueError(
            f"draft_layers must be in [1, n_layers={cfg.n_layers}], "
            f"got {n_layers}")
    return dataclasses.replace(cfg, name=f"{cfg.name}-draft{n_layers}",
                               n_layers=n_layers)


def draft_params(cfg: ArchConfig, params, n_layers: int):
    """The self-draft model's params: the target's, with the stacked
    ``layers`` leaves sliced to ``[:n_layers]`` (embed/norm/unembed
    shared by reference — zero extra weight memory)."""
    if not supports_self_draft(cfg):
        raise ValueError(f"family {cfg.family!r} (window={cfg.window}) "
                         f"does not support self-draft speculation")
    return module_for(cfg).draft_params(params, n_layers)


def cache_batch_axes(cfg: ArchConfig, cache: dict) -> dict:
    """Batch (slot) axis per cache leaf.  Families whose cache stacks
    extra leading dims (hybrid groups) override ``cache_batch_axes`` in
    their module; everyone else keeps batch right behind the layer axis."""
    m = module_for(cfg)
    if hasattr(m, "cache_batch_axes"):
        return m.cache_batch_axes(cache)
    return {k: 1 for k in cache}


# the kind of each decode-cache leaf, by its name
_CACHE_KINDS = {"h": "ssd_state", "rnn_h": "rnn_state", "lo_rnn_h": "rnn_state",
                "conv": "conv", "lo_conv": "conv", "k": "kv", "v": "kv",
                "k_scale": "kv", "v_scale": "kv"}


def cache_bytes(cache: dict) -> dict:
    """Bytes of a decode cache by kind: ``ssd_state``, ``rnn_state``,
    ``conv`` and ``kv``; any other leaf under its own name."""
    out: dict = {}
    for name, leaf in cache.items():
        kind = _CACHE_KINDS.get(name, name)
        out[kind] = out.get(kind, 0) + sum(
            x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(leaf))
    return out
