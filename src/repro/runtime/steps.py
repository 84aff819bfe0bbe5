"""Train / serve step builders: the jit boundary of the framework.

``make_train_step``: cross-entropy LM loss -> grads -> clip -> optimizer.
Distribution is GSPMD: params/opt-state shardings come from the rules
(FSDP x TP), the batch is dp-sharded, and XLA's latency-hiding scheduler
overlaps the gradient reduce with the backward pass.

Cross-pod **gradient compression** (``grad_compression="int8"``): the only
cross-pod traffic in the hierarchical scheme is the gradient all-reduce.
With compression on, the step runs under ``shard_map`` manual over the
"pod" axis only (data/model stay auto/GSPMD): per-pod gradients are
stochastically rounded to int8 (unbiased — core.quant), all-gathered over
"pod" as int8 (half the bytes of a bf16 all-reduce), and dequant-summed
locally.  This is the paper's 8-bit insight applied to the interconnect,
and it shows up directly in the dry-run's collective-bytes term.

``make_serve_step``: prefill (full forward) and decode (one token against
the KV cache) with static shapes — the TPU's deterministic-execution
argument applied to the serving runtime (predictable p99, Table 4).

``make_decode_loop``: the fused serving hot loop — ``lax.scan`` over N
decode steps inside ONE jit boundary (one dispatch per *sequence* instead
of one per token), with the KV cache donated so XLA updates it in place,
and ``bucket_batch`` rounding request batches to a fixed ladder of shapes
so the jit cache stays small and recompiles never land on the hot path.

The engine's five step kinds each jit a function named after the kind —
``slot_decode``, ``prefill_chunk``, ``prime``, ``verify``,
``draft_propose``, single-device and sharded alike — so a profiler trace
names every dispatch: ``PjitFunction(slot_decode)`` on the host,
``jit_slot_decode(<hash>)`` on the device's ``XLA Modules`` line.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeSpec
from repro.core.qlinear import FP, QuantMode
from repro.core.quant import compute_scale, int_bounds
from repro.models import registry as R
from repro.optim import Optimizer, clip_by_global_norm
from repro.runtime import sharding as S


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  z_loss: float = 1e-4) -> jax.Array:
    """Mean CE in fp32 + z-loss (logit-norm stabilizer, production recipe)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ce = jnp.mean(lse - ll)
    return ce + z_loss * jnp.mean(jnp.square(lse))


def make_loss_fn(cfg: ArchConfig, *, mode: QuantMode = FP,
                 remat: bool = True) -> Callable:
    def loss_fn(params, batch):
        logits = R.apply_forward(params, cfg, batch, mode=mode, remat=remat)
        return cross_entropy(logits, batch["labels"])
    return loss_fn


# ---------------------------------------------------------------------------
# int8 cross-pod gradient exchange
# ---------------------------------------------------------------------------

def _int8_allreduce_pod(g: jax.Array, key: jax.Array) -> jax.Array:
    """Unbiased int8 all-reduce over the manual "pod" axis.

    quantize (stochastic) -> all_gather int8 (+ scalar scales) -> local
    dequant-sum.  Wire bytes: 1B/elem vs 2-4B for a raw all-reduce.
    """
    scale = compute_scale(g, bits=8)
    qmin, qmax = int_bounds(8)
    noise = jax.random.uniform(key, g.shape, jnp.float32) - 0.5
    q = jnp.clip(jnp.floor(g.astype(jnp.float32) / scale + 0.5 + noise),
                 qmin, qmax).astype(jnp.int8)
    qs = jax.lax.all_gather(q, "pod")                  # (npod, ...)
    ss = jax.lax.all_gather(scale, "pod")              # (npod, 1...)
    total = jnp.sum(qs.astype(jnp.float32)
                    * ss.reshape((qs.shape[0],) + (1,) * g.ndim), axis=0)
    return (total / qs.shape[0]).astype(g.dtype)


def make_train_step(cfg: ArchConfig, optimizer: Optimizer, *,
                    mode: QuantMode = FP, remat: bool = True,
                    max_grad_norm: float = 1.0,
                    grad_compression: Optional[str] = None,
                    mesh=None) -> Callable:
    """Returns train_step(params, opt_state, batch, step_rng) ->
    (params, opt_state, metrics)."""
    loss_fn = make_loss_fn(cfg, mode=mode, remat=remat)

    use_int8 = (grad_compression == "int8" and mesh is not None
                and "pod" in mesh.axis_names)

    def _core(params, opt_state, batch, rng):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        if use_int8:
            keys = jax.random.split(rng, len(jax.tree.leaves(grads)))
            keys_tree = jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(grads), list(keys))
            grads = jax.tree_util.tree_map(
                _int8_allreduce_pod, grads, keys_tree)
            loss = jax.lax.pmean(loss, "pod")
        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        new_params, new_state = optimizer.update(params, grads, opt_state)
        metrics = {"loss": loss, "grad_norm": gnorm}
        return new_params, new_state, metrics

    if use_int8:
        from jax.sharding import PartitionSpec as P
        # partial-manual shard_map: only "pod" is manual; data/model stay
        # under GSPMD auto-sharding inside.
        pspec = P()            # params: pod-replicated (FSDP is on "data")
        bspec = jax.tree_util.tree_map(lambda _: P("pod"),
                                       {"tokens": 0, "labels": 0})
        def _core_manual(*args):
            # declare "pod" manual for constrain()
            with S.manual_axes({"pod"}):
                return _core(*args)

        return jax.shard_map(_core_manual, mesh=mesh, axis_names={"pod"},
                             check_vma=False,
                             in_specs=(pspec, pspec, bspec, P()),
                             out_specs=(pspec, pspec, pspec))
    return _core


# ---------------------------------------------------------------------------
# serving steps
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ArchConfig, *, mode: QuantMode = FP) -> Callable:
    def prefill_step(params, batch):
        # inference: no remat needed (no backward pass)
        return R.apply_forward(params, cfg, batch, mode=mode, remat=False)
    return prefill_step


def make_decode_step(cfg: ArchConfig, *, mode: QuantMode = FP) -> Callable:
    """``decode_step(params, batch, cache, active=None) -> (logits,
    cache)``; ``active`` (B,) bool freezes the other rows' recurrent
    state (``registry.apply_decode``)."""
    def decode_step(params, batch, cache, active=None):
        return R.apply_decode(params, cfg, batch, cache, mode=mode,
                              active=active)
    return decode_step


# Static batch-shape ladder: every request batch is padded up to one of
# these, so at most len(BATCH_BUCKETS) + log2(MAX_BUCKET / BATCH_BUCKETS[-1])
# decode-loop compilations ever exist (the deterministic-shapes discipline
# that makes p99 predictable).
BATCH_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256)

# Hard ceiling of the power-of-two extension past the ladder's end.  An
# unbounded doubling would silently mint new compiled shapes for any batch
# a caller throws at us — precisely the recompile-on-the-hot-path failure
# the ladder exists to rule out.  Batches beyond MAX_BUCKET are a config
# error: raise, don't compile.
MAX_BUCKET = 2048


def bucket_batch(b: int, buckets=BATCH_BUCKETS,
                 max_bucket: int = MAX_BUCKET) -> int:
    """Smallest bucket >= b (powers of two beyond the ladder's end, capped
    at ``max_bucket``).  Raises ValueError past the cap: the bounded shape
    set is the invariant, so oversized batches must be split upstream, not
    absorbed by a fresh compilation."""
    if b <= 0:
        raise ValueError(f"batch must be positive, got {b}")
    for c in buckets:
        if b <= c:
            return c
    c = buckets[-1]
    while c < b and c < max_bucket:
        c *= 2
    if c < b:
        raise ValueError(
            f"batch {b} exceeds MAX_BUCKET={max_bucket}: the static shape "
            f"ladder is bounded by design — split the batch or raise "
            f"MAX_BUCKET deliberately")
    return c


def make_decode_loop(cfg: ArchConfig, *, mode: QuantMode = FP,
                     num_tokens: int, temperature: float = 0.0) -> Callable:
    """Fused multi-token decode: one jit'd ``lax.scan`` over steps.

    Returns ``loop(params, tokens, cache, cache_index, rng=None) ->
    (out, cache)`` with ``tokens`` (B, 1) int32 seed, ``cache_index`` ()
    int32, and ``out`` (B, num_tokens) int32 generated tokens.  With the
    default ``temperature=0.0`` sampling is greedy (``rng`` ignored);
    ``temperature > 0`` draws from :func:`temperature_sample` with a
    per-step key ``fold_in(rng, cache_index + step)`` — the same key
    schedule a per-token Python loop would use, so the fused loop is
    sampling-parity-testable against it.  Compile once per (bucketed
    batch, num_tokens); wrap with :func:`jit_decode_loop` to get the
    cache donated (in-place update, no per-step host round-trip).
    """
    decode = make_decode_step(cfg, mode=mode)

    def loop(params, tokens, cache, cache_index, rng=None):
        if temperature > 0.0 and rng is None:
            raise ValueError(
                "temperature sampling needs an rng key: "
                "loop(params, tokens, cache, cache_index, rng)")

        def step(carry, _):
            tok, cache, idx = carry
            logits, cache = decode(
                params, {"tokens": tok, "cache_index": idx}, cache)
            if temperature > 0.0:
                nxt = temperature_sample(
                    logits, jax.random.fold_in(rng, idx), temperature)
            else:
                nxt = greedy_sample(logits)
            return (nxt[:, None], cache, idx + 1), nxt

        cache_index = jnp.asarray(cache_index, jnp.int32)
        (_, cache, _), toks = jax.lax.scan(
            step, (tokens, cache, cache_index), None, length=num_tokens)
        return jnp.swapaxes(toks, 0, 1), cache

    return loop


def jit_decode_loop(loop: Callable) -> Callable:
    """jit a decode loop with the KV cache donated (argument 2)."""
    return jax.jit(loop, donate_argnums=(2,))


def make_slot_decode_step(cfg: ArchConfig, *, mode: QuantMode = FP,
                          temperature: float = 0.0) -> Callable:
    """One tick of the continuous-batching engine: advance EVERY slot of
    the fixed pool by one token, in one fused step of static shape.

    Returns ``step(params, tokens, cache, slot_index, active) ->
    (next_tokens, cache, slot_index)`` with ``tokens`` (S_slots, 1) int32,
    ``slot_index`` (S_slots,) int32 per-slot sequence positions, and
    ``active`` (S_slots,) bool; with ``temperature > 0`` the step takes a
    trailing ``rng`` key and samples row ``r`` with
    ``fold_in(rng, slot_index[r])`` — the per-row analogue of
    :func:`make_decode_loop`'s key schedule, so engine sampling is
    parity-testable against the fused loop and the per-token reference.

    The active mask folds into sampling (inactive rows emit 0), into
    the index advance (inactive rows freeze) and into the decode itself.
    Works for every registry family with token-only decode
    (dense/moe/ssm/hybrid): positional KV writes are row-local scatters at
    each slot's own frontier with reads masked by ``slot_index``, while
    the recurrent families (ssm/hybrid) freeze inactive rows' state and
    scrub a reused slot's by the reset-at-position-0 rule inside their
    own decode, in the same pass that updates it (the engine's isolation
    property test poisons dead rows to prove both).
    Wrap with :func:`jit_slot_decode_step` to donate the cache.
    """
    decode = make_decode_step(cfg, mode=mode)

    def _advance(params, tokens, cache, slot_index, active):
        return decode(params, {"tokens": tokens, "cache_index": slot_index},
                      cache, active)

    def _guard(nxt, logits, active):
        # In-graph finite guard: a row whose last-position logits contain
        # NaN/Inf (corrupted cache, overflowed activation) emits the
        # sentinel -1 instead of a garbage sample, so the host can retire
        # or rebuild the poisoned slot without an extra device round-trip.
        # Valid tokens are >= 0 and inactive rows still emit 0, so the
        # sentinel is unambiguous; with all-finite logits this is the
        # identity and the step stays bit-for-bit what it was.
        finite = jnp.all(jnp.isfinite(logits[:, -1].astype(jnp.float32)),
                         axis=-1)
        nxt = jnp.where(finite, nxt, jnp.full_like(nxt, -1))
        return jnp.where(active, nxt, jnp.zeros_like(nxt))

    if temperature > 0.0:
        def slot_decode(params, tokens, cache, slot_index, active, rng):
            logits, cache = _advance(params, tokens, cache, slot_index,
                                     active)
            keys = jax.vmap(lambda p: jax.random.fold_in(rng, p))(slot_index)
            nxt = temperature_sample_rows(logits, keys, temperature)
            nxt = _guard(nxt, logits, active)
            return nxt, cache, slot_index + active.astype(slot_index.dtype)
    else:
        def slot_decode(params, tokens, cache, slot_index, active):
            logits, cache = _advance(params, tokens, cache, slot_index,
                                     active)
            nxt = greedy_sample(logits)
            nxt = _guard(nxt, logits, active)
            return nxt, cache, slot_index + active.astype(slot_index.dtype)

    return slot_decode


def jit_slot_decode_step(step: Callable) -> Callable:
    """jit a slot decode step with the KV cache donated (argument 2)."""
    return jax.jit(step, donate_argnums=(2,))


def make_verify_step(cfg: ArchConfig, *, mode: QuantMode = FP,
                     k: int, temperature: float = 0.0) -> Callable:
    """One speculative *verify* tick: teacher-force up to ``k + 1`` tokens
    per slot through the target model in ONE fused dispatch, sampling at
    every position — the wide step draft-and-verify acceptance scores
    against.

    Returns ``step(params, tokens, cache, slot_index, n_tokens, active)
    -> (samples, cache, slot_index)`` with ``tokens`` (S, k+1) int32 (per
    row: the slot's next input followed by its draft proposals),
    ``n_tokens`` (S,) int32 how many leading tokens each row really feeds
    (1 for a non-speculating row, up to k+1 for a generating one — one
    compiled shape whatever the mix), and ``samples`` (S, k+1) int32 the
    sample drawn after each fed position.  With ``temperature > 0`` the
    step takes a trailing ``rng`` and samples position ``p`` of row ``r``
    with ``fold_in(rng, slot_index[r] + p)`` — the position-derived key
    schedule of :func:`make_slot_decode_step`, which is what makes
    *sampled* speculative acceptance bitwise, not just greedy.

    Internally this is a ``lax.scan`` of the SAME per-token slot decode
    step (per-row masking, paged block tables, recurrent freeze — all
    inherited), so ``samples[r, j]`` is bit-for-bit what ``j + 1``
    non-speculative ticks would have produced given the same fed tokens.
    Positions past ``n_tokens[r]`` keep row ``r``'s index frozen; their
    writes land at the row's frozen frontier and are overwritten by the
    next real feed before any read can see them (the engine's rewind
    rule — see docs/serving.md).  Rows with non-finite logits at any fed
    position emit the -1 sentinel there, and the engine treats the whole
    round as uncommitted.  Wrap with :func:`jit_verify_step` to donate
    the cache.
    """
    decode = make_decode_step(cfg, mode=mode)

    def _scan(params, tokens, cache, slot_index, n_tokens, active, rng):
        def body(carry, inp):
            cache, idx = carry
            tok, j = inp                        # tok (S,), j ()
            act = active & (j < n_tokens)
            logits, new_cache = decode(
                params, {"tokens": tok[:, None], "cache_index": idx}, cache,
                act)
            if temperature > 0.0:
                keys = jax.vmap(lambda p: jax.random.fold_in(rng, p))(idx)
                nxt = temperature_sample_rows(logits, keys, temperature)
            else:
                nxt = greedy_sample(logits)
            finite = jnp.all(
                jnp.isfinite(logits[:, -1].astype(jnp.float32)), axis=-1)
            nxt = jnp.where(finite, nxt, jnp.full_like(nxt, -1))
            nxt = jnp.where(act, nxt, jnp.zeros_like(nxt))
            return (new_cache, idx + act.astype(idx.dtype)), nxt

        (cache, idx), samples = jax.lax.scan(
            body, (cache, slot_index),
            (jnp.swapaxes(tokens, 0, 1), jnp.arange(k + 1)))
        return jnp.swapaxes(samples, 0, 1), cache, idx

    if temperature > 0.0:
        def verify(params, tokens, cache, slot_index, n_tokens, active, rng):
            return _scan(params, tokens, cache, slot_index, n_tokens,
                         active, rng)
    else:
        def verify(params, tokens, cache, slot_index, n_tokens, active):
            return _scan(params, tokens, cache, slot_index, n_tokens,
                         active, None)
    return verify


def jit_verify_step(step: Callable) -> Callable:
    """jit a verify step with the KV cache donated (argument 2)."""
    return jax.jit(step, donate_argnums=(2,))


def make_draft_propose_step(cfg: ArchConfig, *, mode: QuantMode = FP,
                            k: int) -> Callable:
    """One speculative *propose* tick: the draft model extends every
    speculating slot by ``k`` greedy tokens in one fused dispatch.

    Returns ``step(params, tokens, cache, slot_index, active) ->
    (proposals, cache, slot_index)`` with ``tokens`` (S, 1) int32 each
    row's committed next input and ``proposals`` (S, k) int32 the draft's
    greedy continuations ``d_1..d_k`` (fed back token by token).  The
    draft is always greedy whatever the target's sampling mode: its
    proposals are *guesses* the verify step scores, so they affect only
    the acceptance rate, never the committed output.  A draft row whose
    logits go non-finite proposes token 0 instead of the -1 sentinel —
    a wrong-but-harmless guess (it can only be rejected), which is why
    draft dispatches need none of the engine's fault recovery.  Wrap
    with :func:`jit_draft_propose_step` to donate the draft cache.
    """
    decode = make_decode_step(cfg, mode=mode)

    def draft_propose(params, tokens, cache, slot_index, active):
        def body(carry, _):
            tok, cache, idx = carry
            logits, new_cache = decode(
                params, {"tokens": tok, "cache_index": idx}, cache, active)
            nxt = greedy_sample(logits)
            finite = jnp.all(
                jnp.isfinite(logits[:, -1].astype(jnp.float32)), axis=-1)
            nxt = jnp.where(finite & active, nxt, jnp.zeros_like(nxt))
            return (nxt[:, None], new_cache,
                    idx + active.astype(idx.dtype)), nxt

        (_, cache, idx), props = jax.lax.scan(
            body, (tokens, cache, slot_index), None, length=k)
        return jnp.swapaxes(props, 0, 1), cache, idx

    return draft_propose


def jit_draft_propose_step(step: Callable) -> Callable:
    """jit a propose step with the draft cache donated (argument 2)."""
    return jax.jit(step, donate_argnums=(2,))


def make_prefill_chunk_step(cfg: ArchConfig, *, mode: QuantMode = FP,
                            chunk: int) -> Callable:
    """Chunked prefill for ONE slot of the engine's pool: write ``chunk``
    teacher-forced prompt tokens of KV/recurrent state in a single
    dispatch, instead of one engine tick per token.

    Returns ``step(params, tokens, cache, sid, start, n_valid) -> cache``
    with ``tokens`` (chunk,) int32 prompt tokens, ``sid`` () int32 the
    slot row, ``start`` () int32 the slot's current frontier, and
    ``n_valid`` () int32 how many of the ``chunk`` tokens are real (the
    rest is bucket padding whose state updates are reverted, so one
    compilation per bucket on :func:`bucket_batch`'s power-of-two ladder
    serves every prompt length).

    Internally this slices the slot's row out of the pooled cache
    (``registry.cache_batch_axes`` names the slot axis per leaf), runs a
    ``lax.scan`` of the SAME per-token decode step the engine and the
    sequential reference use — so the written state is bit-for-bit what
    per-token prefill would have written — and scatters the row back.
    Logits are discarded: the engine feeds the LAST prompt token through
    the fused slot step, whose sample is the request's first output.
    Wrap with :func:`jit_prefill_chunk_step` to donate the cache.
    """
    decode = make_decode_step(cfg, mode=mode)
    # prime families decode with a (1,)-vector index inside the chunk
    # scan: the per-row path is where their xlen frontier masks the
    # padded source, and the fused slot step takes exactly that path —
    # token-only families keep the scalar (lockstep) variant bit-for-bit
    vec_index = R.needs_prime(cfg)

    def _scan_slot(params, tokens, slot, start, n_valid):
        def body(carry, inp):
            slot, idx = carry
            tok, i = inp
            _, new_slot = decode(
                params, {"tokens": tok.reshape(1, 1),
                         "cache_index": (idx.reshape(1) if vec_index
                                         else idx)},
                slot)
            keep = i < n_valid
            slot = jax.tree_util.tree_map(
                lambda n, o: jnp.where(keep, n, o), new_slot, slot)
            return (slot, jnp.where(keep, idx + 1, idx)), None

        (slot, _), _ = jax.lax.scan(
            body, (slot, jnp.asarray(start, jnp.int32)),
            (tokens, jnp.arange(chunk)))
        return slot

    def prefill_chunk(params, tokens, cache, sid, start, n_valid):
        if "block_tables" in cache:
            return _paged_step(params, tokens, cache, sid, start, n_valid)
        axes = R.cache_batch_axes(cfg, cache)
        slot = {k: jax.lax.dynamic_slice_in_dim(v, sid, 1, axis=axes[k])
                for k, v in cache.items()}
        slot = _scan_slot(params, tokens, slot, start, n_valid)
        return {k: jax.lax.dynamic_update_slice_in_dim(
                    cache[k], slot[k], sid, axis=axes[k])
                for k in cache}

    def _paged_step(params, tokens, cache, sid, start, n_valid):
        # Paged cache: gather the slot's logical row through its block
        # table into a CONTIGUOUS one-slot view (bit-identical bytes to
        # what the contiguous engine would hold), run the same per-token
        # scan on it, then scatter the row's blocks back at the table's
        # physical entries.  Unwritten/shared table entries are rewritten
        # with the bytes just gathered — byte-identical, so shared blocks
        # are never mutated and duplicate trash entries (block 0) all
        # write the same block-0 content back.
        axes = R.cache_batch_axes(cfg, cache)
        paxes = R.paged_block_axes(cfg, cache)
        trow = jax.lax.dynamic_slice_in_dim(
            cache["block_tables"], sid, 1, axis=0)[0]       # (MB,) int32
        slot = {}
        for k, v in cache.items():
            if k == "block_tables":
                continue
            a = paxes.get(k)
            if a is None:                    # slot-resident leaf (xk/xv/xlen)
                slot[k] = jax.lax.dynamic_slice_in_dim(v, sid, 1,
                                                       axis=axes[k])
            else:                            # paged leaf: gather via table
                gat = jnp.take(v, trow, axis=a)     # (..., MB, bs, ...)
                shp = (gat.shape[:a] + (gat.shape[a] * gat.shape[a + 1],)
                       + gat.shape[a + 2:])
                slot[k] = jnp.expand_dims(gat.reshape(shp), axis=a)
        # the inner decode sees a contiguous (no-table) slot view, so it
        # takes the exact same write/mask path as the contiguous engine
        slot = _scan_slot(params, tokens, slot, start, n_valid)
        out = dict(cache)
        for k, v in cache.items():
            if k == "block_tables":
                continue
            a = paxes.get(k)
            if a is None:
                out[k] = jax.lax.dynamic_update_slice_in_dim(
                    v, slot[k], sid, axis=axes[k])
            else:
                row = jnp.squeeze(slot[k], axis=a)
                shp = (row.shape[:a] + (trow.shape[0], v.shape[a + 1])
                       + row.shape[a + 1:])
                blocks = row.reshape(shp)
                out[k] = v.at[(slice(None),) * a + (trow,)].set(blocks)
        return out

    return prefill_chunk


def jit_prefill_chunk_step(step: Callable) -> Callable:
    """jit a prefill chunk step with the KV cache donated (argument 2)."""
    return jax.jit(step, donate_argnums=(2,))


def make_prime_step(cfg: ArchConfig, *, mode: QuantMode = FP) -> Callable:
    """Prime dispatch for ONE slot of the engine's pool: run the request's
    encoder / vision tower once and scatter the pre-projected cross-K/V
    (plus the row's ``xlen`` frontier) into the slot's row of the pooled
    cache — the second slot-resident static operand that lets encdec/vlm
    decode through the same fused slot step as every other family.

    Returns ``step(params, source, cache, sid, n_valid) -> cache`` with
    ``source`` (1, source_len(cfg), D) the request's frame/patch
    embeddings padded to the static source length, ``sid`` () int32 the
    slot row, and ``n_valid`` () int32 how many source positions are
    real.  Decode masks cross reads at the frontier, so K/V past
    ``n_valid`` — pad projections, or a previous tenant's stale tail —
    is never read.  The pad itself is deterministic zero frames: the
    vlm's position-wise projections are pad-exact, while the encdec
    encoder attends over the padded input like Whisper encodes its
    pad-to-30s silence (both the engine and the sequential reference
    prime with byte-identical padded sources, so the semantics is one
    and parity is exact).  One static shape, one compilation, like every
    other engine dispatch.  Wrap with :func:`jit_prime_step` to donate
    the cache.
    """

    def prime(params, source, cache, sid, n_valid):
        leaves = R.prime_slot(cfg, params, source, n_valid, mode=mode)
        axes = R.cache_batch_axes(cfg, cache)
        out = dict(cache)
        for k, v in leaves.items():
            out[k] = jax.lax.dynamic_update_slice_in_dim(
                cache[k], v.astype(cache[k].dtype), sid, axis=axes[k])
        return out

    return prime


def jit_prime_step(step: Callable) -> Callable:
    """jit a prime step with the pooled cache donated (argument 2)."""
    return jax.jit(step, donate_argnums=(2,))


# ---------------------------------------------------------------------------
# per-model compiled step cache (the multiplexed engine's step registry)
# ---------------------------------------------------------------------------
#
# A multiplexed engine holds one compiled step SET per admitted model, and
# the differential test harness additionally builds dedicated single-model
# engines over the very same configs.  Memoizing the jitted builders on
# their full specialization key — (kind, cfg, mode, static shape args);
# both ArchConfig and QuantMode are frozen dataclasses, hence hashable —
# means each (model, shape) pair compiles exactly once per process however
# many Engine instances reference it.  Params stay call arguments, so
# sharing a compiled step between engines shares no model state.

_STEP_CACHE: dict = {}


def _cached(key, build):
    fn = _STEP_CACHE.get(key)
    if fn is None:
        fn = _STEP_CACHE[key] = build()
    return fn


def cached_slot_decode_step(cfg: ArchConfig, *, mode: QuantMode = FP,
                            temperature: float = 0.0) -> Callable:
    """Memoized ``jit_slot_decode_step(make_slot_decode_step(...))``."""
    return _cached(("slot_decode", cfg, mode, temperature),
                   lambda: jit_slot_decode_step(make_slot_decode_step(
                       cfg, mode=mode, temperature=temperature)))


def cached_prefill_chunk_step(cfg: ArchConfig, *, mode: QuantMode = FP,
                              chunk: int) -> Callable:
    """Memoized ``jit_prefill_chunk_step(make_prefill_chunk_step(...))``."""
    return _cached(("prefill_chunk", cfg, mode, chunk),
                   lambda: jit_prefill_chunk_step(make_prefill_chunk_step(
                       cfg, mode=mode, chunk=chunk)))


def cached_prime_step(cfg: ArchConfig, *, mode: QuantMode = FP) -> Callable:
    """Memoized ``jit_prime_step(make_prime_step(...))``."""
    return _cached(("prime", cfg, mode),
                   lambda: jit_prime_step(make_prime_step(cfg, mode=mode)))


def cached_verify_step(cfg: ArchConfig, *, mode: QuantMode = FP,
                       k: int, temperature: float = 0.0) -> Callable:
    """Memoized ``jit_verify_step(make_verify_step(...))``."""
    return _cached(("verify", cfg, mode, k, temperature),
                   lambda: jit_verify_step(make_verify_step(
                       cfg, mode=mode, k=k, temperature=temperature)))


def cached_draft_propose_step(cfg: ArchConfig, *, mode: QuantMode = FP,
                              k: int) -> Callable:
    """Memoized ``jit_draft_propose_step(make_draft_propose_step(...))``."""
    return _cached(("draft_propose", cfg, mode, k),
                   lambda: jit_draft_propose_step(make_draft_propose_step(
                       cfg, mode=mode, k=k)))


# ---------------------------------------------------------------------------
# tensor-parallel (sharded) serving steps
# ---------------------------------------------------------------------------
#
# The engine's second ExecutorBackend: the SAME make_*_step builders run
# under full-manual shard_map on a ("model",) host mesh, sharded along
# the SLOT axis.  Every per-row float op of the fused steps is
# batch-size-independent (no op ever crosses rows), so a shard advancing
# its num_slots/tp rows computes bit-for-bit what the single-device step
# computes for those rows — which is the whole point: head/expert tensor
# parallelism needs a cross-shard psum whose float adds reassociate, and
# bit parity with the single-device engine (the repo's gating currency)
# would be lost.  Slot sharding needs no collective inside the step;
# the paged merge below is the one cross-shard exchange.  Pallas kernels
# inside the steps carry no varying-manual-axes annotations, so the
# shard_map runs with ``check_vma=False``.
#
# Paged leaves are the one wrinkle: physical KV blocks are shared across
# slots (hence across shards), so each shard gets a replicated COPY,
# diverges it with its own rows' writes, and the merge outside the
# shard_map folds the copies back by "who changed it" — sound because
# every real block has at most one writing slot per tick (block tables
# partition real blocks; only reserved trash block 0 takes multi-shard
# garbage writes, and block 0 is never read).

@functools.lru_cache(maxsize=None)
def _sharded_mesh(tp: int):
    from repro.launch.mesh import make_host_mesh
    return make_host_mesh((tp,), ("model",))


def _shard_map(tp: int):
    return functools.partial(jax.shard_map, mesh=_sharded_mesh(tp),
                             check_vma=False)


def _sharded_cache_specs(cfg: ArchConfig, cache: dict):
    """Per-leaf shard_map specs for a pooled cache: slot-resident leaves
    shard on their slot axis, the block table on its slot axis 0, paged
    block leaves replicate in (each shard diverges a private copy) and
    come back STACKED (leading shard axis) for the host-side merge.

    Returns ``(in_specs, out_specs, paged_keys, axes)``."""
    from jax.sharding import PartitionSpec as P
    axes = R.cache_batch_axes(cfg, cache)
    paxes = R.paged_block_axes(cfg, cache) if "block_tables" in cache \
        else {}
    in_s, out_s, paged = {}, {}, []
    for k in cache:
        if k == "block_tables":
            in_s[k] = out_s[k] = P("model")
        elif paxes.get(k) is not None:
            in_s[k] = P()
            out_s[k] = P("model")          # leaf[None] per shard
            paged.append(k)
        else:
            sp = P(*([None] * axes[k] + ["model"]))
            in_s[k] = out_s[k] = sp
    return in_s, out_s, paged, axes


def _bitwise_neq(a: jax.Array, b: jax.Array) -> jax.Array:
    """Elementwise "did the bytes change": float leaves compare as
    integer bit patterns so a write of 0.0 over -0.0 (equal under IEEE
    ``!=``) still counts as a write — the merge below must be exact to
    the BIT, not to float equality (NaN != NaN would also misfire)."""
    if jnp.issubdtype(a.dtype, jnp.floating):
        w = {2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}[a.dtype.itemsize]
        return (jax.lax.bitcast_convert_type(a, w)
                != jax.lax.bitcast_convert_type(b, w))
    return a != b


def _merge_shard_writes(stacked: jax.Array, old: jax.Array) -> jax.Array:
    """Fold per-shard copies of a replicated paged leaf: wherever shard
    i's bytes differ from the pre-step bytes, shard i wrote there.  At
    most one shard writes any real block per tick (block tables
    partition real blocks across slots), so the fold order only decides
    who wins the reserved trash block 0 — which is never read."""
    acc = old
    for i in range(stacked.shape[0]):
        si = stacked[i]
        acc = jnp.where(_bitwise_neq(si, old), si, acc)
    return acc


def _local_slots(cache: dict, axes: dict, paged_keys) -> int:
    """This shard's slot count, read off a slot-resident leaf's shape
    (inside shard_map every leaf is already the local block)."""
    if "block_tables" in cache:
        return cache["block_tables"].shape[0]
    for k, v in cache.items():
        if k not in paged_keys:
            return v.shape[axes[k]]
    raise ValueError("cache has no slot-resident leaf")


class _StructMemo:
    """jit-compiled sharded step per cache STRUCTURE (leaf names + slot
    axes): the engine's cache structure is fixed per lane, so this holds
    one entry per (lane family, paged-ness) — the same bounded-compile
    discipline as the batch ladder."""

    def __init__(self, build):
        self.build = build
        self.fns: dict = {}

    def __call__(self, cfg, cache):
        axes = R.cache_batch_axes(cfg, cache)
        key = (tuple(sorted(cache)), tuple(sorted(axes.items())))
        fn = self.fns.get(key)
        if fn is None:
            fn = self.fns[key] = self.build(cfg, cache)
        return fn


def _rep_and_row(tp: int):
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _sharded_mesh(tp)
    return NamedSharding(mesh, P()), NamedSharding(mesh, P("model"))


def place_sharded_cache(cfg: ArchConfig, cache: dict, tp: int) -> dict:
    """Lay a pooled cache out the way the sharded steps take it (slot
    leaves split on their slot axis, paged blocks replicated), so a fresh
    cache and a step's output cache present one input signature."""
    from jax.sharding import NamedSharding
    in_c, _, _, _ = _sharded_cache_specs(cfg, cache)
    mesh = _sharded_mesh(tp)
    return {k: jax.device_put(v, NamedSharding(mesh, in_c[k]))
            for k, v in cache.items()}


def place_replicated(tree, tp: int):
    """Copy ``tree`` onto every device of the tp-way mesh once, in the
    replicated layout the sharded steps take their params in — so a
    step call never re-broadcasts them from one device."""
    return jax.device_put(tree, _rep_and_row(tp)[0])


def make_sharded_slot_decode_step(cfg: ArchConfig, *, mode: QuantMode = FP,
                                  temperature: float = 0.0,
                                  tp: int = 1) -> Callable:
    """Tensor-parallel :func:`make_slot_decode_step`: same signature,
    bit-identical outputs, each shard advancing ``num_slots / tp`` rows
    with the params replicated.  The pool size must divide by ``tp``
    (``ShardedExecutor.validate`` enforces it)."""
    base = make_slot_decode_step(cfg, mode=mode, temperature=temperature)
    has_rng = temperature > 0.0

    def build(cfg_, cache0):
        from jax.sharding import NamedSharding, PartitionSpec as P
        in_c, out_c, paged, _ = _sharded_cache_specs(cfg_, cache0)
        row = P("model")

        def inner(params, tokens, cache, slot_index, active, *rng):
            with S.manual_axes({"model"}):
                nxt, new_cache, idx = base(params, tokens, cache,
                                           slot_index, active, *rng)
            new_cache = {k: (v[None] if k in paged else v)
                         for k, v in new_cache.items()}
            return nxt, new_cache, idx

        in_specs = (P(), row, in_c, row, row) + ((P(),) if has_rng else ())
        fn = _shard_map(tp)(inner, in_specs=in_specs,
                            out_specs=(row, out_c, row))

        if has_rng:
            def slot_decode(params, tokens, cache, slot_index, active, rng):
                nxt, nc, idx = fn(params, tokens, cache, slot_index,
                                  active, rng)
                for k in paged:
                    nc[k] = _merge_shard_writes(nc[k], cache[k])
                return nxt, nc, idx
        else:
            def slot_decode(params, tokens, cache, slot_index, active):
                nxt, nc, idx = fn(params, tokens, cache, slot_index,
                                  active)
                for k in paged:
                    nc[k] = _merge_shard_writes(nc[k], cache[k])
                return nxt, nc, idx

        rep, rowsh = _rep_and_row(tp)
        mesh = _sharded_mesh(tp)
        csh_in = {k: NamedSharding(mesh, s) for k, s in in_c.items()}
        csh_out = {k: (rep if k in paged else NamedSharding(mesh, out_c[k]))
                   for k in out_c}
        in_sh = (rep, rowsh, csh_in, rowsh, rowsh) \
            + ((rep,) if has_rng else ())
        # no donation: the paged merge reads the pre-step cache bytes,
        # so the buffer cannot be reused in place (and the non-paged
        # case keeps the same policy for one uniform compile path)
        return jax.jit(slot_decode, in_shardings=in_sh,
                       out_shardings=(rowsh, csh_out, rowsh))

    memo = _StructMemo(build)

    if has_rng:
        def step(params, tokens, cache, slot_index, active, rng):
            return memo(cfg, cache)(params, tokens, cache, slot_index,
                                    active, rng)
    else:
        def step(params, tokens, cache, slot_index, active):
            return memo(cfg, cache)(params, tokens, cache, slot_index,
                                    active)
    return step


def make_sharded_verify_step(cfg: ArchConfig, *, mode: QuantMode = FP,
                             k: int, temperature: float = 0.0,
                             tp: int = 1) -> Callable:
    """Tensor-parallel :func:`make_verify_step` — the wide speculative
    verify scan, slot-axis sharded, forward-only and collective-free."""
    base = make_verify_step(cfg, mode=mode, k=k, temperature=temperature)
    has_rng = temperature > 0.0

    def build(cfg_, cache0):
        from jax.sharding import NamedSharding, PartitionSpec as P
        in_c, out_c, paged, _ = _sharded_cache_specs(cfg_, cache0)
        row = P("model")

        def inner(params, tokens, cache, slot_index, n_tokens, active,
                  *rng):
            with S.manual_axes({"model"}):
                samples, new_cache, idx = base(params, tokens, cache,
                                               slot_index, n_tokens,
                                               active, *rng)
            new_cache = {kk: (v[None] if kk in paged else v)
                         for kk, v in new_cache.items()}
            return samples, new_cache, idx

        in_specs = (P(), row, in_c, row, row, row) \
            + ((P(),) if has_rng else ())
        fn = _shard_map(tp)(inner, in_specs=in_specs,
                            out_specs=(row, out_c, row))

        if has_rng:
            def verify(params, tokens, cache, slot_index, n_tokens,
                      active, rng):
                samples, nc, idx = fn(params, tokens, cache, slot_index,
                                      n_tokens, active, rng)
                for kk in paged:
                    nc[kk] = _merge_shard_writes(nc[kk], cache[kk])
                return samples, nc, idx
        else:
            def verify(params, tokens, cache, slot_index, n_tokens,
                      active):
                samples, nc, idx = fn(params, tokens, cache, slot_index,
                                      n_tokens, active)
                for kk in paged:
                    nc[kk] = _merge_shard_writes(nc[kk], cache[kk])
                return samples, nc, idx

        rep, rowsh = _rep_and_row(tp)
        mesh = _sharded_mesh(tp)
        csh_in = {kk: NamedSharding(mesh, s) for kk, s in in_c.items()}
        csh_out = {kk: (rep if kk in paged
                        else NamedSharding(mesh, out_c[kk]))
                   for kk in out_c}
        in_sh = (rep, rowsh, csh_in, rowsh, rowsh, rowsh) \
            + ((rep,) if has_rng else ())
        return jax.jit(verify, in_shardings=in_sh,
                       out_shardings=(rowsh, csh_out, rowsh))

    memo = _StructMemo(build)

    if has_rng:
        def step(params, tokens, cache, slot_index, n_tokens, active, rng):
            return memo(cfg, cache)(params, tokens, cache, slot_index,
                                    n_tokens, active, rng)
    else:
        def step(params, tokens, cache, slot_index, n_tokens, active):
            return memo(cfg, cache)(params, tokens, cache, slot_index,
                                    n_tokens, active)
    return step


def make_sharded_draft_propose_step(cfg: ArchConfig, *,
                                    mode: QuantMode = FP, k: int,
                                    tp: int = 1) -> Callable:
    """Tensor-parallel :func:`make_draft_propose_step`.  The draft cache
    is always contiguous (never paged), so this is pure slot sharding
    with no merge."""
    base = make_draft_propose_step(cfg, mode=mode, k=k)

    def build(cfg_, cache0):
        from jax.sharding import NamedSharding, PartitionSpec as P
        in_c, out_c, paged, _ = _sharded_cache_specs(cfg_, cache0)
        row = P("model")

        def inner(params, tokens, cache, slot_index, active):
            with S.manual_axes({"model"}):
                props, new_cache, idx = base(params, tokens, cache,
                                             slot_index, active)
            new_cache = {kk: (v[None] if kk in paged else v)
                         for kk, v in new_cache.items()}
            return props, new_cache, idx

        fn = _shard_map(tp)(inner, in_specs=(P(), row, in_c, row, row),
                            out_specs=(row, out_c, row))

        def draft_propose(params, tokens, cache, slot_index, active):
            props, nc, idx = fn(params, tokens, cache, slot_index, active)
            for kk in paged:
                nc[kk] = _merge_shard_writes(nc[kk], cache[kk])
            return props, nc, idx

        rep, rowsh = _rep_and_row(tp)
        mesh = _sharded_mesh(tp)
        csh_in = {kk: NamedSharding(mesh, s) for kk, s in in_c.items()}
        csh_out = {kk: (rep if kk in paged
                        else NamedSharding(mesh, out_c[kk]))
                   for kk in out_c}
        return jax.jit(draft_propose,
                       in_shardings=(rep, rowsh, csh_in, rowsh, rowsh),
                       out_shardings=(rowsh, csh_out, rowsh))

    memo = _StructMemo(build)

    def step(params, tokens, cache, slot_index, active):
        return memo(cfg, cache)(params, tokens, cache, slot_index, active)
    return step


def make_sharded_prefill_chunk_step(cfg: ArchConfig, *,
                                    mode: QuantMode = FP, chunk: int,
                                    tp: int = 1) -> Callable:
    """Tensor-parallel :func:`make_prefill_chunk_step`: a single-slot
    dispatch, so exactly ONE shard owns the target row.  Every shard
    runs the base step on its clamped local row (static shapes — no
    shard may skip work); the owner's writes are kept via the in-range
    mask, and for paged leaves the owner's whole diverged copy is
    selected outside the shard_map (non-owners corrupted a wrong local
    row's blocks in their private copies, which are discarded)."""
    base = make_prefill_chunk_step(cfg, mode=mode, chunk=chunk)

    def build(cfg_, cache0):
        from jax.sharding import NamedSharding, PartitionSpec as P
        in_c, out_c, paged, axes = _sharded_cache_specs(cfg_, cache0)

        def inner(params, tokens, cache, sid, start, n_valid):
            local_S = _local_slots(cache, axes, paged)
            off = jax.lax.axis_index("model") * local_S
            lsid = sid - off
            in_r = (lsid >= 0) & (lsid < local_S)
            lsid_c = jnp.clip(lsid, 0, local_S - 1)
            with S.manual_axes({"model"}):
                new_cache = base(params, tokens, cache, lsid_c, start,
                                 n_valid)
            out = {}
            for kk, v in new_cache.items():
                if kk in paged:
                    out[kk] = v[None]
                else:
                    out[kk] = jnp.where(in_r, v, cache[kk])
            return out

        fn = _shard_map(tp)(inner,
                            in_specs=(P(), P(), in_c, P(), P(), P()),
                            out_specs=out_c)

        def prefill_chunk(params, tokens, cache, sid, start, n_valid):
            nc = fn(params, tokens, cache, sid, start, n_valid)
            if paged:
                local_S = _global_slots(cfg_, cache, axes, paged) // tp
                owner = jnp.asarray(sid, jnp.int32) // local_S
                for kk in paged:
                    nc[kk] = jax.lax.dynamic_index_in_dim(
                        nc[kk], owner, 0, keepdims=False)
            return nc

        rep, _ = _rep_and_row(tp)
        mesh = _sharded_mesh(tp)
        csh_in = {kk: NamedSharding(mesh, s) for kk, s in in_c.items()}
        csh_out = {kk: (rep if kk in paged
                        else NamedSharding(mesh, out_c[kk]))
                   for kk in out_c}
        return jax.jit(prefill_chunk,
                       in_shardings=(rep, rep, csh_in, rep, rep, rep),
                       out_shardings=csh_out)

    memo = _StructMemo(build)

    def step(params, tokens, cache, sid, start, n_valid):
        return memo(cfg, cache)(params, tokens, cache, sid, start, n_valid)
    return step


def _global_slots(cfg: ArchConfig, cache: dict, axes: dict,
                  paged_keys) -> int:
    """Global pool size, read off an UNsharded cache (host side)."""
    if "block_tables" in cache:
        return cache["block_tables"].shape[0]
    for k, v in cache.items():
        if k not in paged_keys:
            return v.shape[axes[k]]
    raise ValueError("cache has no slot-resident leaf")


def make_sharded_prime_step(cfg: ArchConfig, *, mode: QuantMode = FP,
                            tp: int = 1) -> Callable:
    """Tensor-parallel :func:`make_prime_step`.  Prime writes only
    slot-resident leaves (cross K/V + xlen), so each shard runs the
    encoder replicated, scatters into its clamped local row, and the
    in-range mask keeps the owner's write — no paged merge needed."""
    base = make_prime_step(cfg, mode=mode)

    def build(cfg_, cache0):
        from jax.sharding import NamedSharding, PartitionSpec as P
        in_c, out_c_all, paged, axes = _sharded_cache_specs(cfg_, cache0)
        # prime never touches paged leaves: pass them through untouched
        # and REPLICATED (every shard returns identical bytes, so the
        # unchecked-replication out_spec is valid) — no stack, no merge
        out_c = {k: (P() if k in paged else out_c_all[k])
                 for k in out_c_all}
        in_cp = {k: (P() if k in paged else in_c[k]) for k in in_c}

        def inner(params, source, cache, sid, n_valid):
            local_S = _local_slots(cache, axes, paged)
            off = jax.lax.axis_index("model") * local_S
            lsid = sid - off
            in_r = (lsid >= 0) & (lsid < local_S)
            lsid_c = jnp.clip(lsid, 0, local_S - 1)
            with S.manual_axes({"model"}):
                new_cache = base(params, source, cache, lsid_c, n_valid)
            return {k: (v if k in paged
                        else jnp.where(in_r, v, cache[k]))
                    for k, v in new_cache.items()}

        fn = _shard_map(tp)(inner, in_specs=(P(), P(), in_cp, P(), P()),
                            out_specs=out_c)

        def prime(params, source, cache, sid, n_valid):
            return fn(params, source, cache, sid, n_valid)

        rep, _ = _rep_and_row(tp)
        mesh = _sharded_mesh(tp)
        csh_in = {k: NamedSharding(mesh, s) for k, s in in_cp.items()}
        csh_out = {k: NamedSharding(mesh, s) for k, s in out_c.items()}
        return jax.jit(prime,
                       in_shardings=(rep, rep, csh_in, rep, rep),
                       out_shardings=csh_out)

    memo = _StructMemo(build)

    def step(params, source, cache, sid, n_valid):
        return memo(cfg, cache)(params, source, cache, sid, n_valid)
    return step


def cached_sharded_slot_decode_step(cfg: ArchConfig, *,
                                    mode: QuantMode = FP,
                                    temperature: float = 0.0,
                                    tp: int = 1) -> Callable:
    """Memoized :func:`make_sharded_slot_decode_step` (key includes tp)."""
    return _cached(("sharded_slot_decode", cfg, mode, temperature, tp),
                   lambda: make_sharded_slot_decode_step(
                       cfg, mode=mode, temperature=temperature, tp=tp))


def cached_sharded_prefill_chunk_step(cfg: ArchConfig, *,
                                      mode: QuantMode = FP, chunk: int,
                                      tp: int = 1) -> Callable:
    """Memoized :func:`make_sharded_prefill_chunk_step`."""
    return _cached(("sharded_prefill_chunk", cfg, mode, chunk, tp),
                   lambda: make_sharded_prefill_chunk_step(
                       cfg, mode=mode, chunk=chunk, tp=tp))


def cached_sharded_prime_step(cfg: ArchConfig, *, mode: QuantMode = FP,
                              tp: int = 1) -> Callable:
    """Memoized :func:`make_sharded_prime_step`."""
    return _cached(("sharded_prime", cfg, mode, tp),
                   lambda: make_sharded_prime_step(cfg, mode=mode, tp=tp))


def cached_sharded_verify_step(cfg: ArchConfig, *, mode: QuantMode = FP,
                               k: int, temperature: float = 0.0,
                               tp: int = 1) -> Callable:
    """Memoized :func:`make_sharded_verify_step`."""
    return _cached(("sharded_verify", cfg, mode, k, temperature, tp),
                   lambda: make_sharded_verify_step(
                       cfg, mode=mode, k=k, temperature=temperature, tp=tp))


def cached_sharded_draft_propose_step(cfg: ArchConfig, *,
                                      mode: QuantMode = FP, k: int,
                                      tp: int = 1) -> Callable:
    """Memoized :func:`make_sharded_draft_propose_step`."""
    return _cached(("sharded_draft_propose", cfg, mode, k, tp),
                   lambda: make_sharded_draft_propose_step(
                       cfg, mode=mode, k=k, tp=tp))


def greedy_sample(logits: jax.Array) -> jax.Array:
    return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)


def temperature_sample(logits: jax.Array, rng: jax.Array,
                       temperature: float = 1.0) -> jax.Array:
    return jax.random.categorical(
        rng, logits[:, -1].astype(jnp.float32) / temperature
    ).astype(jnp.int32)


def temperature_sample_rows(logits: jax.Array, keys: jax.Array,
                            temperature: float = 1.0) -> jax.Array:
    """Per-row temperature sampling: row ``r`` draws with ``keys[r]``.

    This is the slot engine's schedule — every row is an independent
    request at its own position, so each gets its own
    ``fold_in(rng, position)`` key.  A single row's draw is bitwise equal
    to :func:`temperature_sample` at batch 1 with the same key (the
    categorical consumes the same random bits), which is what makes
    engine sampling parity-testable against the sequential reference."""
    last = logits[:, -1].astype(jnp.float32) / temperature
    return jax.vmap(jax.random.categorical)(keys, last).astype(jnp.int32)


# ---------------------------------------------------------------------------
# jit + sharding assembly (used by launch/ and the dry-run)
# ---------------------------------------------------------------------------

def shard_train_fn(train_step, params_like, opt_like, batch_like, mesh,
                   rules):
    """jit with in/out shardings resolved from the rules."""
    p_sh = S.tree_shardings(params_like, mesh, rules)
    o_sh = S.tree_shardings(opt_like, mesh, rules)
    from jax.sharding import NamedSharding
    b_sh = jax.tree_util.tree_map(
        lambda x: NamedSharding(mesh, S.batch_spec(mesh, max(1, x.ndim))),
        batch_like)
    r_sh = NamedSharding(mesh, S.batch_spec(mesh, 1))
    return jax.jit(
        train_step,
        in_shardings=(p_sh, o_sh, b_sh, r_sh),
        out_shardings=(p_sh, o_sh, None),
        donate_argnums=(0, 1),
    )
