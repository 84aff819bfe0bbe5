"""The continuous-batching engine: one fused slot-masked step per tick.

Execution model (Orca-style iteration-level scheduling, specialized to
the paper's static-shape discipline):

- The KV cache is a fixed pool of ``num_slots`` rows of ``max_seq``
  positions — ONE compiled decode step ever exists, whatever the request
  mix, so per-tick latency is deterministic (the Table 4 argument).
- Every tick advances EVERY ready slot by one token in one fused
  ``make_slot_decode_step`` call (active mask folded into sampling and
  index advance, cache donated).  A slot mid-prefill is teacher-forced
  its next prompt token; a slot mid-generation feeds back its last
  sample; the first sample after the final prompt token is the request's
  first output token.
- EVERY registry family serves through the same step: positional KV
  state isolates per row behind each slot's ``valid_len`` frontier,
  recurrent state (ssm/hybrid) is frozen for inactive rows and scrubbed
  on reuse by the families' reset-at-position-0 rule, and the
  encoder-conditioned families (encdec/vlm) decode against a second
  slot-resident static operand — per-request primed cross-attention K/V,
  written once at admission by a *prime dispatch* that runs the encoder
  or vision tower and scatters the pre-projected cross K/V (plus the
  row's ``xlen`` frontier) into the slot's row (docs/serving.md).
- With ``prefill_chunk=c``, a newly admitted slot's prompt (all but the
  last token) is written by a chunked prefill step — one dispatch per
  bucketed chunk, concurrent with other slots' decoding — so
  admission-to-first-token drops from ``P`` ticks to ``ceil((P-1)/c)``
  (the final chunk tick doubles as the slot's first fused tick).  The
  chunk step scans the SAME per-token decode step, so outputs stay
  bit-for-bit equal to the per-token path.
- ``temperature > 0`` samples per row with ``fold_in(rng, position)`` —
  the fused decode loop's key schedule made per-row, so sampling parity
  holds against the sequential reference beyond greedy.
- ``spec_k > 0`` turns every tick into draft-and-verify speculative
  decoding: a small draft model (a second checkpoint, or a truncated-
  layer view of the target's own params) proposes up to ``k`` greedy
  tokens per generating slot, and ONE wide verify dispatch
  (``make_verify_step``) scores all proposals across the pool at a
  single compiled shape, committing the accepted prefix plus the bonus
  sample and rewinding each row's index past the rejected tail.  The
  committed stream is bit-for-bit the non-speculative stream, greedy or
  sampled (the verify scan reuses the per-position key schedule).
- Admission consults the same ``core.batching.AdmissionPolicy`` as the
  virtual-time simulator; admitted requests take over free slots
  immediately — there is NO drain barrier: new requests prefill while
  older ones are mid-generation (``admissions_while_busy`` counts the
  overlap, and the engine test asserts it is nonzero).
- Retired slots return to the pool the same tick they finish; stale
  cache contents need no scrub because every read is masked at the
  slot's own frontier.

Since the dispatch-core split (docs/architecture.md), this module is
the POLICY + REPORTING layer: request validation, admission policy and
lane configuration, and ``EngineReport`` assembly.  The tick loop,
slot/block accounting, stash/exact-resume, and fault plumbing live in
``engine.dispatch.DispatchCore``; compiled steps reach the core
through an ``ExecutorBackend`` — the single-device step set by
default, or ``ShardedExecutor(tp=...)`` to run the same steps
tensor-parallel under ``shard_map`` (bit-identical, slot-axis
sharding).  ``engine.router.ReplicaRouter`` scales this out across N
engine replicas.

``reference_outputs`` is the sequential per-token loop (batch=1, same
decode math) the engine must match bit-for-bit under greedy sampling.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import (Callable, Dict, List, Mapping, Optional, Sequence,
                    Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import batching as bt
from repro.core.qlinear import FP, QuantMode
from repro.engine.dispatch import (DispatchCore, EngineRequest,
                                   ExecutorBackend, RequestResult,
                                   ShardedExecutor, SingleDeviceExecutor,
                                   _Lane, _padded_source, _validate_source)
from repro.engine.faults import FaultPlan
from repro.engine.slots import RequestTooLong
from repro.models import registry as R
from repro.runtime import steps as ST


@dataclasses.dataclass
class EngineReport:
    results: List[RequestResult]
    ticks: int
    generated_tokens: int
    duration_s: float                 # engine-clock time (virtual or wall)
    wall_s: float                     # measured host time, always
    p99_latency_s: float
    tokens_per_s: float
    occupancy: List[int]              # active slots per tick
    mean_occupancy: float             # fraction of the pool in use
    admissions_while_busy: int        # requests admitted while some older
                                      # request was mid-generation
    num_slots: int
    mean_ttft_s: float = 0.0          # admission-to-first-token, mean
    p99_ttft_s: float = 0.0           # admission-to-first-token, p99
    prefill_chunk: Optional[int] = None
    dropped: int = 0                  # requests retired on deadline miss
    # paged KV cache (Engine(block_size=...)) memory accounting — all
    # defaults when the engine runs contiguous rows
    block_size: Optional[int] = None
    num_blocks: int = 0               # physical blocks incl. reserved trash
    kv_hbm_bytes: int = 0             # resident KV-cache bytes (all leaves)
    peak_blocks_used: int = 0         # high-water mark of held blocks
    mean_block_util: float = 0.0      # mean held / usable blocks, per tick
    shared_block_hits: int = 0        # prefix blocks reused at admission
    shared_hit_rate: float = 0.0      # hits / worst-case blocks demanded
    prefill_tokens_skipped: int = 0   # prompt tokens served from shared blocks
    effective_concurrency: float = 0.0  # mean active requests per tick
    # overload robustness (serve(preemption=..., fault_plan=...)):
    preempted: int = 0                # eviction events (exact resume each)
    failed: int = 0                   # requests retired by fault recovery
    unfinished: int = 0               # requests retired by the tick cap
    dispatch_retries: int = 0         # failed fused-step dispatch attempts
    nonfinite_samples: int = 0        # sentinel tokens caught by the guard
    torn_rows_repaired: int = 0       # block-table rows audited + rebuilt
    stuck_ticks: int = 0              # wall-clock stragglers (watchdog)
    leaked_blocks: int = 0            # pool deficit at drain (must be 0)
    # hot-swap (Engine.retire_model / serve(control=...)): requests whose
    # lane was retired (or never admitted) before they could enter it
    refused: int = 0
    # per-SLO-class tails + the honest metric at scale: goodput counts
    # only completed requests that met their deadline
    class_p99_latency_s: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    class_mean_ttft_s: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    class_p99_ttft_s: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    goodput_tokens_per_s: float = 0.0
    slo_attainment: float = 0.0       # ok-and-on-time / all requests
    # speculative decoding (Engine(spec_k=..., draft=...|draft_layers=...)):
    spec_k: int = 0                   # proposal depth (0 = not speculating)
    accepted_per_dispatch: float = 0.0  # committed tokens per emitting
                                        # row-tick — exactly 1.0 without
                                        # speculation, the mean accepted+
                                        # bonus run length with it
    latency_per_token_s: float = 0.0  # mean over ok requests of
                                      # latency_s / emitted tokens
    # multi-model multiplexing (Engine(models={...})): per-model tails,
    # goodput and occupancy.  Empty on a single-model engine.  Per-model
    # occupancy is each lane's active slots over the SHARED lease budget
    # (num_slots), so the per-model fractions sum to mean_occupancy.
    model_p99_latency_s: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    model_mean_ttft_s: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    model_p99_ttft_s: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    model_goodput_tokens_per_s: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    model_mean_occupancy: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    model_occupancy: Dict[str, List[int]] = dataclasses.field(
        default_factory=dict)        # per-tick active slots per lane
    # XLA programs compiled (or loaded from the persistent compile cache)
    # while serving: 0 once warmup() has compiled every shape the serve
    # dispatches (docs/serving.md, "Profiling a serve")
    compiles: int = 0

    def outputs(self) -> Dict[int, List[int]]:
        return {r.rid: r.tokens for r in self.results}

    def outputs_for(self, model: Optional[str]) -> Dict[int, List[int]]:
        """One model lane's outputs — what the differential harness
        compares against a dedicated single-model engine."""
        return {r.rid: r.tokens for r in self.results if r.model == model}


@dataclasses.dataclass
class WarmupReport:
    """What :meth:`Engine.warmup` cost: its wall time, the XLA programs
    it compiled or loaded from the persistent compile cache
    (``compiles``; ``cache_hits`` of them were loaded), and the seconds
    spent in the backend's compile step.  ``cache_bytes`` gives each
    lane's decode cache in bytes by kind (``registry.cache_bytes``: SSD
    or RG-LRU state, conv tails, K/V, ...)."""
    seconds: float
    compiles: int
    cache_hits: int
    compile_s: float
    cache_bytes: Dict[str, Dict[str, int]] = dataclasses.field(
        default_factory=dict)


class CompileCounter:
    """Counts the XLA compiles of a stretch of the program through
    ``jax.monitoring``: ``with CompileCounter() as cc: ...`` registers
    its two listeners on entry and unregisters them on exit, so nothing
    stays registered after the block.  A backend compile event is
    recorded for every program built, and for every program loaded from
    the persistent compile cache, which also counts a cache hit."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.compiles = self.cache_hits = 0
        self.compile_s = 0.0

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == self.COMPILE:
            self.compiles += 1
            self.compile_s += secs

    def _on_event(self, event: str, **_) -> None:
        if event == self.CACHE_HIT:
            self.cache_hits += 1

    def __enter__(self) -> "CompileCounter":
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


class Engine:
    """Continuous-batching serving engine over a slot-based KV cache.

    Single-model (the legacy form): ``Engine(cfg, params, ...)`` — one
    model lane tagged ``None``, every request untagged.

    Multi-model multiplexing: ``Engine(models={tag: (cfg, params)},
    ...)`` — one lane per admitted model, each with its own compiled
    step set, device cache, slot pool, and (paged mode) block pool.
    Requests carry ``EngineRequest.model`` naming their lane; the tick
    loop interleaves per-lane fused dispatches, and the ``num_slots``
    lease budget caps TOTAL active slots across lanes (each lane's pool
    holds ``num_slots`` rows so any lane may hold the whole budget —
    one compiled batch shape per lane, dynamic leasing between them).
    Admission meters ``(model, class)`` quota keys through the same
    ``AdmissionPolicy``; see docs/serving.md, multi-model multiplexing.

    ``backend`` selects the executor the dispatch core runs compiled
    steps through: the default :class:`SingleDeviceExecutor`, or
    :class:`ShardedExecutor` for tensor-parallel slot-axis sharding
    (bit-identical outputs; docs/serving.md, "Scaling out").  ``name``
    labels this engine in straggler warnings and router rollups.
    """

    def __init__(self, cfg: Optional[ArchConfig] = None, params=None, *,
                 models: Optional[Dict[str, Tuple[ArchConfig, dict]]] = None,
                 mode: QuantMode = FP,
                 num_slots: int = 8, max_seq: int = 64,
                 policy: Optional[bt.AdmissionPolicy] = None,
                 prefill_chunk: Optional[int] = None,
                 block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 temperature: float = 0.0, rng=None,
                 spec_k: int = 0,
                 draft: Optional[Tuple[ArchConfig, dict]] = None,
                 draft_layers: Optional[int] = None,
                 backend: Optional[ExecutorBackend] = None,
                 name: Optional[str] = None):
        if (models is None) == (cfg is None):
            raise ValueError("exactly one of Engine(cfg, params) or "
                             "Engine(models={tag: (cfg, params)})")
        if temperature > 0.0 and rng is None:
            raise ValueError("temperature sampling needs an rng key: "
                             "Engine(..., temperature=t, rng=key)")
        # speculative decoding: spec_k > 0 turns every generation tick
        # into draft-propose (k greedy tokens from the draft model) +
        # one wide verify dispatch on the target; the committed output
        # is bit-for-bit the non-speculative output (docs/serving.md)
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if spec_k == 0 and (draft is not None or draft_layers is not None):
            raise ValueError("a draft model needs spec_k >= 1: "
                             "Engine(..., spec_k=k, draft=... or "
                             "draft_layers=...)")
        self.multi = models is not None
        if self.multi:
            if not models:
                raise ValueError("models must name at least one "
                                 "(cfg, params) lane")
            for tag in models:
                if not isinstance(tag, str) or not tag:
                    raise ValueError(
                        f"model tag must be a non-empty string, got {tag!r}")
            if spec_k > 0 and draft is not None:
                raise ValueError(
                    "a multiplexed engine cannot take one explicit "
                    "draft=(cfg, params) for every lane (vocabs differ); "
                    "use draft_layers=n — each supporting lane self-drafts")
            lane_cfgs = {t: cp for t, cp in models.items()}
        else:
            lane_cfgs = {None: (cfg, params)}
        if spec_k > 0 and not self.multi:
            if (draft is None) == (draft_layers is None):
                raise ValueError(
                    "speculative decoding needs exactly one of "
                    "draft=(cfg, params) or draft_layers=n "
                    "(truncated-layer self-draft)")
            if not R.supports_speculation(cfg):
                raise ValueError(
                    f"family {cfg.family!r} (window={cfg.window}) does not "
                    f"support speculative decoding: the target's decode "
                    f"state must be rewindable positional KV")
            if draft is not None:
                dcfg, dparams = draft
                if not R.supports_speculation(dcfg):
                    raise ValueError(
                        f"draft family {dcfg.family!r} "
                        f"(window={dcfg.window}) cannot draft: its decode "
                        f"state must be rewindable positional KV")
                if dcfg.vocab != cfg.vocab:
                    raise ValueError(
                        f"draft vocab {dcfg.vocab} != target vocab "
                        f"{cfg.vocab}: proposals would not be token-"
                        f"compatible")
        if spec_k > 0 and self.multi and draft_layers is None:
            raise ValueError("multiplexed speculation needs draft_layers=n")
        self.spec_k = spec_k
        self.mode = mode
        self.temperature, self.rng = temperature, rng
        self.name = name
        # the pool size IS the compiled batch shape: bucket it so the
        # engine's one decode step per lane sits on the static ladder;
        # the cache length rounds up to 16 so the slot dimension tiles
        # cleanly (paged mode additionally rounds to whole blocks)
        if num_blocks is not None and block_size is None:
            raise ValueError("num_blocks needs block_size: paged mode is "
                             "enabled by Engine(..., block_size=...)")
        if block_size is not None:
            if block_size < 1 or block_size & (block_size - 1):
                raise ValueError(
                    f"block_size must be a power of two, got {block_size}")
            for tag, (mcfg, _) in lane_cfgs.items():
                if not R.supports_paging(mcfg):
                    raise ValueError(
                        f"family {mcfg.family!r} (window={mcfg.window}"
                        f"{'' if tag is None else f', model {tag!r}'}) does "
                        f"not support the paged KV cache")
        self.num_slots = ST.bucket_batch(num_slots)
        align = max(16, block_size) if block_size else 16
        self.max_seq = max_seq + (-max_seq) % align
        self.block_size = block_size
        if block_size:
            self.max_blocks = self.max_seq // block_size
            # default pool (PER LANE): every slot can hold a full row
            # privately, +1 for the reserved trash block — byte-parity
            # with contiguous rows; pass a smaller num_blocks for
            # memory-bound admission
            self.num_blocks = (num_blocks if num_blocks is not None
                               else self.num_slots * self.max_blocks + 1)
            if self.num_blocks < 2:
                raise ValueError(f"num_blocks must be >= 2, "
                                 f"got {self.num_blocks}")
        else:
            self.max_blocks = 0
            self.num_blocks = 0
        # chunked prefill: cap rounds up to the same power-of-two ladder,
        # so chunk shapes and pool shapes share one bounded compile set
        self.prefill_chunk = (ST.bucket_batch(prefill_chunk)
                              if prefill_chunk else None)
        self.policy = policy or bt.AdmissionPolicy(
            lambda b: 0.0, max_batch=self.num_slots, max_wait_s=0.0)
        # the executor seam: every compiled step a lane holds comes from
        # this backend — swap it for ShardedExecutor(tp=...) and the
        # same engine serves tensor-parallel, bit-identically
        self.backend = backend if backend is not None \
            else SingleDeviceExecutor()
        self.backend.validate(self)
        # draft catch-up dispatch cap: per-tick gaps are <= 1 (a full
        # accept), but admission/resume rebuilds feed whole prompts
        self._draft_cap = (self.prefill_chunk or 16) if spec_k > 0 else 0
        self._draft_layers = draft_layers
        self._epoch = 0                  # bumps on every hot-swap admit
        # build the lanes: per-lane speculative resolution — a
        # multiplexed lane whose family cannot draft serves
        # non-speculatively ("where supported"), the single-model path
        # keeps its hard error above
        self.lanes: Dict[Optional[str], _Lane] = {}
        for order, (tag, (mcfg, mparams)) in enumerate(lane_cfgs.items()):
            lk = spec_k
            dcfg = dparams = None
            if spec_k > 0:
                if self.multi and not R.supports_speculation(mcfg):
                    lk = 0
                elif draft_layers is not None:
                    dcfg = R.draft_config(mcfg, draft_layers)
                    dparams = R.draft_params(mcfg, mparams, draft_layers)
                else:
                    dcfg, dparams = draft
            self.lanes[tag] = _Lane(self, tag, order, mcfg, mparams,
                                    lk, dcfg, dparams)
        # legacy aliases: the single-model engine's config/params (and
        # draft pair) remain reachable where old code expects them
        lane0 = next(iter(self.lanes.values()))
        self.cfg, self.params = lane0.cfg, lane0.params
        self.dcfg, self.dparams = lane0.dcfg, lane0.dparams

    # -- hot-swap: admit / retire a lane on a live engine ---------------

    def admit_model(self, tag: str, cfg: ArchConfig, params) -> None:
        """Admit a new model lane.  Legal mid-serve (through
        ``serve(control=...)``): the lane appends to the lane list with
        ``order = len(lanes)`` so fault gids and dispatch interleaving
        of existing lanes are untouched, and its fresh pools start
        empty — no other lane drains, stalls, or recompiles."""
        if not self.multi:
            raise ValueError("hot-swap needs a multiplexed engine: "
                             "Engine(models={...})")
        if not isinstance(tag, str) or not tag:
            raise ValueError(f"model tag must be a non-empty string, "
                             f"got {tag!r}")
        if tag in self.lanes:
            raise ValueError(f"model {tag!r} is already admitted")
        if self.block_size is not None and not R.supports_paging(cfg):
            raise ValueError(
                f"family {cfg.family!r} (window={cfg.window}, model "
                f"{tag!r}) does not support the paged KV cache")
        lk = self.spec_k
        dcfg = dparams = None
        if lk > 0:
            if not R.supports_speculation(cfg):
                lk = 0
            else:
                dcfg = R.draft_config(cfg, self._draft_layers)
                dparams = R.draft_params(cfg, params, self._draft_layers)
        lane = _Lane(self, tag, len(self.lanes), cfg, params,
                     lk, dcfg, dparams)
        self._epoch += 1
        lane.epoch = self._epoch
        self.lanes[tag] = lane

    def retire_model(self, tag: str) -> None:
        """Mark a lane retiring: its in-flight slots finish normally
        (their outputs stay bitwise what they would have been) but the
        lane-epoch check in admission refuses every NEW request for it
        with the typed ``refused`` status.  The drained lane is removed
        when the serve ends."""
        if tag not in self.lanes:
            raise ValueError(
                f"model {tag!r} is not admitted on this engine "
                f"(lanes: {[t for t in self.lanes]})")
        self.lanes[tag].retiring = True

    def warmup(self) -> WarmupReport:
        """Trace + compile every lane's slot step (and, when chunked
        prefill is on, every reachable chunk bucket) on throwaway caches
        so a wall-clock ``serve`` charges its first tick to serving, not
        to compilation.  Returns what that cost (:class:`WarmupReport`)."""
        t0 = time.perf_counter()
        with warnings.catch_warnings(), CompileCounter() as cc:
            warnings.filterwarnings("ignore", message=".*[Dd]onat.*")
            S = self.num_slots
            cache_bytes = {}
            for tag, ln in self.lanes.items():
                cache = ln._init_cache()
                cache_bytes[tag] = R.cache_bytes(cache)
                if ln._prime_step is not None:
                    cache = ln._prime_step(
                        ln.params,
                        jnp.zeros((1, R.source_len(ln.cfg),
                                   ln.cfg.d_model), jnp.bfloat16),
                        cache, jnp.zeros((), jnp.int32),
                        jnp.zeros((), jnp.int32))
                if ln.spec_k > 0:
                    # speculative serve never dispatches the 1-token
                    # fused step: warm what it DOES run — verify,
                    # propose, and the draft's catch-up chunk buckets
                    _, cache, _ = ln._verify(
                        jnp.zeros((S, ln.spec_k + 1), jnp.int32), cache,
                        jnp.zeros((S,), jnp.int32),
                        jnp.zeros((S,), jnp.int32),
                        jnp.zeros((S,), bool))
                    dcache = self.backend.place_cache(
                        ln.dcfg, R.init_cache(ln.dcfg, S, self.max_seq))
                    _, dcache, _ = ln._propose_step(
                        ln.dparams, jnp.zeros((S, 1), jnp.int32), dcache,
                        jnp.zeros((S,), jnp.int32), jnp.zeros((S,), bool))
                    c = 1
                    while c <= self._draft_cap:
                        dcache = ln._draft_chunk_step(c)(
                            ln.dparams, jnp.zeros((c,), jnp.int32), dcache,
                            jnp.zeros((), jnp.int32),
                            jnp.zeros((), jnp.int32),
                            jnp.zeros((), jnp.int32))
                        c *= 2
                else:
                    _, cache, _ = ln._fused(
                        jnp.zeros((S, 1), jnp.int32), cache,
                        jnp.zeros((S,), jnp.int32),
                        jnp.zeros((S,), bool))
                if self.prefill_chunk:
                    # every reachable bucket: remainder chunks bucket to
                    # the smaller powers of two, and a cold compile
                    # mid-serve is exactly what this warmup exists to
                    # keep off the clock
                    c = 1
                    while c <= self.prefill_chunk:
                        cache = ln._chunk_step(c)(
                            ln.params, jnp.zeros((c,), jnp.int32), cache,
                            jnp.zeros((), jnp.int32),
                            jnp.zeros((), jnp.int32),
                            jnp.zeros((), jnp.int32))
                        c *= 2
                jax.block_until_ready(cache)
        return WarmupReport(seconds=time.perf_counter() - t0,
                            compiles=cc.compiles,
                            cache_hits=cc.cache_hits,
                            compile_s=cc.compile_s,
                            cache_bytes=cache_bytes)

    def serve(self, requests: Sequence[EngineRequest], *,
              clock: str = "virtual",
              tick_s: Union[float, Mapping,
                            Callable[[int], float]] = 1e-3,
              max_ticks: Optional[int] = None,
              drop_missed_deadlines: bool = False,
              preemption: bool = False,
              fault_plan: Optional[FaultPlan] = None,
              max_retries: int = 3,
              control: Sequence[Tuple[float, Callable]] = ()
              ) -> EngineReport:
        """Serve a whole request trace; return per-request outputs and
        achieved latency/throughput/occupancy metrics.

        ``clock="virtual"``: time advances ``tick_s`` per tick (or
        ``tick_s(active_count)`` when callable) — fully deterministic,
        used by tests and the offline benchmark.  A *Mapping* ``tick_s``
        ({lane tag: seconds}) prices each tick as the SUM of the
        dispatched lanes' per-lane service times, so a multiplexed tick
        that dispatches a heavy lane costs honestly more than one that
        only advances a light lane.  ``clock="wall"``: time is the
        measured host clock — the live mode, where arrivals interleave
        with real step latency and a rolling-median watchdog flags
        stuck ticks (``EngineReport.stuck_ticks``).

        ``drop_missed_deadlines=True`` retires a slot the tick its
        deadline passes (possibly mid-prefill, before any token): its
        result is recorded with ``dropped=True``, whatever it generated,
        and — crucially — the ``first_token_s = -1.0`` sentinel, which
        the ttft aggregates below exclude.

        ``preemption=True`` lets admission-time pressure (no free slot,
        or a paged block claim the pool cannot cover) evict the active
        slot of strictly lower SLO class than the pending head — latest
        deadline first.  The victim's blocks are released, its host
        progress stashed, and it re-enters the pending queue; on
        re-admission its ``prompt + generated-so-far`` is teacher-forced
        through the chunked-prefill path, so the resumed output is
        bit-for-bit the never-preempted output (docs/serving.md).

        ``fault_plan`` injects a seeded :class:`FaultPlan`'s failures at
        their scheduled ticks; the recovery machinery (always on)
        retries failed dispatches, rebuilds slots that sample the
        non-finite sentinel or lose a torn block-table row, and retires
        a slot still faulting after ``max_retries`` recovery attempts
        with the typed ``failed`` status — one poisoned slot never takes
        down the cohort.

        ``control`` schedules hot-swap operations on the live serve: a
        sequence of ``(time_s, fn)`` pairs, each ``fn(engine)`` run at
        the first tick boundary past its time — typically closures over
        :meth:`admit_model` / :meth:`retire_model`.  Requests whose
        ``model`` tag is unknown at validation time are allowed through
        when a control schedule is present (a control op may admit the
        lane before they arrive); a request whose lane is retiring or
        still unknown when admission reaches it is refused with the
        typed ``refused`` status.

        On a multiplexed engine (``Engine(models={...})``) every
        request's ``model`` tag must name an admitted lane; the tick
        loop then interleaves one fused dispatch per lane with live
        slots, ``num_slots`` caps TOTAL active slots across lanes
        (dynamic leasing), and fault injection sees dense global slot
        ids (``lane.order * num_slots + sid``) so one seeded plan
        strikes across models deterministically.  All per-model state —
        cache, block pool, draft state, table mirror — stays
        lane-private (decode-contract rule 8).
        """
        if clock not in ("virtual", "wall"):
            raise ValueError(f"clock must be 'virtual' or 'wall': {clock!r}")
        if isinstance(tick_s, Mapping):
            if clock != "virtual":
                raise ValueError("per-lane tick_s mapping needs the "
                                 "virtual clock")
            missing = [t for t in self.lanes if t not in tick_s]
            if missing:
                raise ValueError(
                    f"per-lane tick_s must price every lane; missing "
                    f"{missing} (keys: {sorted(tick_s, key=repr)})")
        for t_ctl, fn_ctl in control:
            if not callable(fn_ctl):
                raise ValueError(
                    f"control entries must be (time_s, callable), got "
                    f"({t_ctl!r}, {fn_ctl!r})")
        for r in requests:
            mtag = getattr(r, "model", None)
            lane_r = self.lanes.get(mtag)
            if lane_r is None and not control:
                raise ValueError(
                    f"request {r.rid}: model {mtag!r} is not admitted on "
                    f"this engine (lanes: "
                    f"{[t for t in self.lanes]})")
            if r.max_new_tokens <= 0:
                raise ValueError(
                    f"request {r.rid}: max_new_tokens must be positive "
                    f"(got {r.max_new_tokens})")
            need = len(r.prompt) + r.max_new_tokens
            if need > self.max_seq:
                raise RequestTooLong(
                    f"request {r.rid} needs {need} cache positions > "
                    f"max_seq={self.max_seq}")
            if self.block_size:
                nb = -(-need // self.block_size)
                if nb > self.num_blocks - 1:
                    # would wait forever even against an empty pool
                    raise RequestTooLong(
                        f"request {r.rid} needs {nb} KV blocks > "
                        f"{self.num_blocks - 1} usable in the pool")
            if lane_r is not None and lane_r._prime_step is not None:
                _validate_source(lane_r.cfg, r)
        reqs = sorted(requests, key=lambda r: r.arrival_s)
        S = self.num_slots

        core = DispatchCore(self)
        with CompileCounter() as cc:
            out = core.run(reqs, clock=clock, tick_s=tick_s,
                           max_ticks=max_ticks,
                           drop_missed_deadlines=drop_missed_deadlines,
                           preemption=preemption, fault_plan=fault_plan,
                           max_retries=max_retries, control=control)
        lanes = out.lanes
        # hot-swap epilogue: a retired lane that has drained leaves the
        # engine now (its device cache is released with the lane); the
        # report below still covers it via the serve's lane snapshot
        for tag in [t for t, ln in self.lanes.items()
                    if ln.retiring and ln.pool.active_count == 0]:
            del self.lanes[tag]

        results = out.results
        results.sort(key=lambda r: r.rid)
        now, ticks = out.now, out.ticks
        occupancy = out.occupancy
        paged = self.block_size is not None
        lat = [r.latency_s for r in results if r.status == "ok"]
        # a request retired before emitting a token still carries the
        # first_token_s = -1.0 sentinel: it must never leak a negative
        # ttft into the aggregates
        ttft = [r.ttft_s for r in results if r.emitted]
        dur = max(now, 1e-12)
        kv_bytes = int(sum(x.size * x.dtype.itemsize
                           for ln in lanes
                           for x in jax.tree_util.tree_leaves(ln.cache)))
        # per-SLO-class tails + goodput: only a completed request that
        # met its deadline counts toward the honest metric at scale
        by_class: Dict[str, List[RequestResult]] = {}
        for r in results:
            by_class.setdefault(r.priority, []).append(r)
        cls_lat = {c: bt.p99([r.latency_s for r in rs if r.status == "ok"])
                   for c, rs in sorted(by_class.items())}
        cls_ttft = {c: [r.ttft_s for r in rs if r.emitted]
                    for c, rs in sorted(by_class.items())}
        good = [r for r in results
                if r.status == "ok" and r.finish_s <= r.deadline_s]
        good_tokens = sum(len(r.tokens) for r in good)
        lat_tok = [r.latency_s / len(r.tokens) for r in results
                   if r.status == "ok" and r.tokens]
        # per-model aggregates (multiplexed engines only; empty dicts on a
        # single-model engine keep its report byte-identical)
        mdl_lat: Dict[str, float] = {}
        mdl_ttft_mean: Dict[str, float] = {}
        mdl_ttft_p99: Dict[str, float] = {}
        mdl_goodput: Dict[str, float] = {}
        if self.multi:
            by_model: Dict[str, List[RequestResult]] = \
                {ln.tag: [] for ln in lanes}
            for r in results:
                by_model.setdefault(r.model, []).append(r)
            for m, rs in by_model.items():
                mdl_lat[m] = bt.p99(
                    [r.latency_s for r in rs if r.status == "ok"])
                ts = [r.ttft_s for r in rs if r.emitted]
                mdl_ttft_mean[m] = float(np.mean(ts)) if ts else 0.0
                mdl_ttft_p99[m] = bt.p99(ts)
                mdl_goodput[m] = sum(
                    len(r.tokens) for r in rs
                    if r.status == "ok" and r.finish_s <= r.deadline_s
                ) / dur
        return EngineReport(
            results=results, ticks=ticks,
            generated_tokens=out.gen_tokens,
            duration_s=now, wall_s=out.wall,
            p99_latency_s=bt.p99(lat),
            tokens_per_s=out.gen_tokens / dur,
            occupancy=occupancy,
            mean_occupancy=(sum(occupancy) / (len(occupancy) * S)
                            if occupancy else 0.0),
            admissions_while_busy=out.admissions_while_busy,
            num_slots=S,
            mean_ttft_s=float(np.mean(ttft)) if ttft else 0.0,
            p99_ttft_s=bt.p99(ttft),
            prefill_chunk=self.prefill_chunk,
            dropped=out.dropped,
            block_size=self.block_size,
            num_blocks=self.num_blocks,
            kv_hbm_bytes=kv_bytes,
            peak_blocks_used=out.peak_used,
            mean_block_util=(out.util_sum / ticks
                             if paged and ticks else 0.0),
            shared_block_hits=out.shared_hits,
            shared_hit_rate=(out.shared_hits / out.blocks_demanded
                             if out.blocks_demanded else 0.0),
            prefill_tokens_skipped=out.skipped_tokens,
            effective_concurrency=(sum(occupancy) / len(occupancy)
                                   if occupancy else 0.0),
            preempted=out.preempted,
            failed=out.failed,
            unfinished=out.unfinished,
            dispatch_retries=out.dispatch_retries,
            nonfinite_samples=out.nonfinite,
            torn_rows_repaired=out.torn_repaired,
            stuck_ticks=out.stuck_ticks,
            leaked_blocks=(sum((self.num_blocks - 1) - ln.bpool.free_blocks
                               for ln in lanes) if paged else 0),
            refused=out.refused,
            class_p99_latency_s=cls_lat,
            class_mean_ttft_s={c: (float(np.mean(ts)) if ts else 0.0)
                               for c, ts in cls_ttft.items()},
            class_p99_ttft_s={c: bt.p99(ts) for c, ts in cls_ttft.items()},
            goodput_tokens_per_s=good_tokens / dur,
            slo_attainment=(len(good) / len(results) if results else 0.0),
            spec_k=self.spec_k,
            accepted_per_dispatch=(out.gen_tokens / out.emit_dispatches
                                   if out.emit_dispatches else 0.0),
            latency_per_token_s=(float(np.mean(lat_tok))
                                 if lat_tok else 0.0),
            model_p99_latency_s=mdl_lat,
            model_mean_ttft_s=mdl_ttft_mean,
            model_p99_ttft_s=mdl_ttft_p99,
            model_goodput_tokens_per_s=mdl_goodput,
            model_mean_occupancy={
                t: (sum(v) / (len(v) * S) if v else 0.0)
                for t, v in out.occ_by_lane.items()},
            model_occupancy={t: list(v)
                             for t, v in out.occ_by_lane.items()},
            compiles=cc.compiles)


# ---------------------------------------------------------------------------
# sequential reference + trace synthesis (shared by tests / serve / bench)
# ---------------------------------------------------------------------------

def reference_outputs(cfg: ArchConfig, params,
                      requests: Sequence[EngineRequest], *,
                      mode: QuantMode = FP, max_seq: int = 64,
                      temperature: float = 0.0, rng=None
                      ) -> Dict[int, List[int]]:
    """The sequential per-token reference loop: each request alone at
    batch=1, prompt teacher-forced a token at a time, then greedy
    generation — the bit-for-bit baseline the engine must reproduce.

    With ``temperature > 0`` sampling draws with the
    ``fold_in(rng, position)`` key schedule — the same schedule
    :func:`repro.runtime.steps.make_decode_loop` and the slot engine use
    (per-row there), so sampled outputs stay engine-comparable."""
    if temperature > 0.0 and rng is None:
        raise ValueError("temperature sampling needs an rng key")
    decode = jax.jit(ST.make_decode_step(cfg, mode=mode))
    # encdec/vlm: the same prime computation the engine dispatches, at a
    # pool of one slot (no donation: the reference is not a hot path)
    prime = (jax.jit(ST.make_prime_step(cfg, mode=mode))
             if R.needs_prime(cfg) else None)
    out: Dict[int, List[int]] = {}
    for r in sorted(requests, key=lambda x: x.rid):
        cache = R.init_cache(cfg, 1, max_seq)
        if prime is not None:
            src, n_valid = _padded_source(cfg, r)
            cache = prime(params, src, cache,
                          jnp.zeros((), jnp.int32), n_valid)
        tok = None
        gen: List[int] = []
        feed = list(r.prompt)
        pos = 0
        while len(gen) < r.max_new_tokens:
            cur = feed[pos] if pos < len(feed) else tok
            # prime families decode with a (1,)-vector index: the per-row
            # path is where the xlen frontier masks the padded source, and
            # the engine's slot rows take exactly that path
            idx = (jnp.asarray([pos], jnp.int32) if prime is not None
                   else jnp.asarray(pos, jnp.int32))
            logits, cache = decode(
                params,
                {"tokens": jnp.asarray([[cur]], jnp.int32),
                 "cache_index": idx}, cache)
            pos += 1
            if pos >= len(feed):
                if temperature > 0.0:
                    key = jax.random.fold_in(
                        rng, jnp.asarray(pos - 1, jnp.int32))
                    tok = int(ST.temperature_sample(logits, key,
                                                    temperature)[0])
                else:
                    tok = int(ST.greedy_sample(logits)[0])
                gen.append(tok)
        out[r.rid] = gen
    return out


def synthetic_requests(n: int, *, rate_per_s: float, vocab: int,
                       prompt_len: int = 4, max_new_tokens: int = 8,
                       deadline_s: float = float("inf"),
                       seed: int = 0,
                       shared_prefix_len: int = 0,
                       source_shape: Optional[Tuple[int, int]] = None,
                       priority: Union[str, Callable[[int], str]]
                       = "interactive",
                       arrival_process: Optional[
                           Callable[[int, float, int], Sequence[float]]]
                       = None,
                       model: Union[None, str, Callable[[int], str]]
                       = None) -> List[EngineRequest]:
    """Deterministic pseudo-Poisson request trace with synthetic prompts
    (derived from the rid, so any two runs see identical streams).

    ``shared_prefix_len=k`` makes the first ``k`` prompt tokens identical
    across ALL requests (a seed-derived "system prompt") with rid-seeded
    suffixes after it — the workload shape the paged engine's
    shared-prefix block reuse exists for.  The default 0 reproduces the
    fully rid-derived prompts exactly.

    ``source_shape=(source_len, d_model)`` additionally attaches
    per-request source embeddings for the prime families (encdec/vlm):
    rid-seeded gaussian frames/patches whose length varies across
    requests (full, -1, -2 cyclically), so a shared slot pool holds rows
    of different xlen frontiers at once.

    ``priority`` tags every request with an SLO class (a string) or a
    per-request one (a ``rid -> class`` callable).  ``arrival_process``
    replaces the pseudo-Poisson arrivals with a custom process — a
    callable ``(n, rate_per_s, seed) -> arrival times`` (sorted,
    seconds), e.g. the MMPP/bursty builders in ``benchmarks/traces.py``.

    ``model`` tags every request with a multiplexed engine's lane tag (a
    string) or a per-request one (a ``rid -> tag`` callable); the
    default ``None`` leaves requests untagged for single-model engines.
    The defaults reproduce today's traces byte-identically."""
    if not 0 <= shared_prefix_len <= prompt_len:
        raise ValueError(
            f"shared_prefix_len must be in [0, prompt_len={prompt_len}], "
            f"got {shared_prefix_len}")
    if arrival_process is None:
        arr = bt.poisson_arrivals(rate_per_s, n, 0.0, seed)
    else:
        times = list(arrival_process(n, rate_per_s, seed))
        if len(times) != n or any(b < a for a, b in zip(times, times[1:])):
            raise ValueError(
                f"arrival_process must return {n} sorted arrival times, "
                f"got {len(times)}")
        arr = [bt.Request(arrival_s=t, deadline_s=t, rid=rid)
               for rid, t in enumerate(times)]
    cls_of = priority if callable(priority) else (lambda rid: priority)
    mdl_of = model if callable(model) else (lambda rid: model)
    reqs = []
    for a in arr:
        prompt = tuple(
            (1 + (11 * j + 13 * seed) % (vocab - 1))
            if j < shared_prefix_len
            else (1 + (a.rid * 7 + 3 * j) % (vocab - 1))
            for j in range(prompt_len))
        source = None
        if source_shape is not None:
            smax, d = source_shape
            src_len = max(1, smax - a.rid % 3)
            g = np.random.default_rng((seed + 1) * 1_000_003 + a.rid)
            source = g.standard_normal((src_len, d)).astype(np.float32)
        reqs.append(EngineRequest(
            rid=a.rid, prompt=prompt, max_new_tokens=max_new_tokens,
            arrival_s=a.arrival_s,
            deadline_s=(a.arrival_s + deadline_s
                        if deadline_s != float("inf") else float("inf")),
            source=source, priority=cls_of(a.rid), model=mdl_of(a.rid)))
    return reqs
