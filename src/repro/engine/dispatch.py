"""Dispatch core: the per-lane tick loop behind the Engine's policy face.

The engine splits into three layers (docs/architecture.md):

- ``Engine`` (engine.py) — policy + reporting: request validation,
  admission policy configuration, and ``EngineReport`` assembly.
- ``DispatchCore`` (this module) — mechanism: the per-lane tick loop,
  slot/block accounting, stash/exact-resume, and fault plumbing.  It
  consumes an engine's lanes and returns raw counters
  (:class:`DispatchOutcome`); it never computes aggregates.
- ``ExecutorBackend`` — the narrow seam the dispatch core runs compiled
  steps through: the five step builders behind the process-wide
  ``runtime.steps.cached_*`` memos.  :class:`SingleDeviceExecutor` is
  the single-device step set; :class:`ShardedExecutor` runs the same
  builders under ``jax.shard_map`` on a tensor-parallel mesh axis
  (slot-axis sharding — bit-identical to the single-device backend by
  construction, see runtime/steps.py).

Everything host-side a request leaves behind between dispatches —
``SlotState`` progress, block tables, the preemption stash — lives
here, so an ``Engine`` is exactly "an admission policy and a report
assembler wired to a dispatch core".
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
from repro.engine.faults import FaultPlan
from repro.engine.scheduler import SlotScheduler
from repro.engine.slots import BlockPool, SlotPool
from repro.models import registry as R
from repro.runtime import steps as ST
from repro.runtime.watchdog import StepWatchdog


@dataclasses.dataclass(frozen=True)
class EngineRequest:
    rid: int
    prompt: Tuple[int, ...]
    max_new_tokens: int
    arrival_s: float = 0.0
    deadline_s: float = float("inf")
    # encdec/vlm: the request's source embeddings (src_len, d_model) —
    # encoder frames / vision patches a prime dispatch turns into the
    # slot's cross-K/V row at admission.  src_len may be shorter than the
    # static source length; the pad is masked behind the row's xlen.
    source: Optional[np.ndarray] = dataclasses.field(
        default=None, compare=False, repr=False)
    # SLO class (see core.batching.PRIORITY_CLASSES): admission orders
    # and sheds cohorts class-first, per-class slot quotas cap how many
    # slots a class may hold, and preemption only ever evicts a slot of
    # strictly lower class than the request it makes room for
    priority: str = "interactive"
    # multi-model multiplexing: which admitted model lane serves this
    # request (must name a tag of Engine(models={...}); None on a
    # single-model engine).  Quotas then meter (model, class) keys —
    # see docs/serving.md, multi-model multiplexing.
    model: Optional[str] = None


@dataclasses.dataclass
class RequestResult:
    rid: int
    tokens: List[int]
    arrival_s: float
    admit_s: float
    first_token_s: float
    finish_s: float
    slot: int
    dropped: bool = False             # retired before completing (deadline)
    # typed outcome: "ok" (completed), "dropped" (deadline miss, mirrors
    # the bool), "failed" (retired by fault recovery after max_retries),
    # "unfinished" (still in flight when the tick cap hit), "refused"
    # (its model lane was retired or never admitted — hot-swap)
    status: str = "ok"
    priority: str = "interactive"
    preemptions: int = 0              # times evicted + exactly resumed
    deadline_s: float = float("inf")
    model: Optional[str] = None       # serving model lane (None = single)

    @property
    def latency_s(self) -> float:
        return self.finish_s - self.arrival_s

    @property
    def emitted(self) -> bool:
        """True once the request produced at least one token; ``ttft_s``
        is meaningless (the -1.0 sentinel) until then."""
        return self.first_token_s >= 0

    @property
    def ttft_s(self) -> float:
        """Admission-to-first-token: what chunked prefill shrinks.  Only
        defined when ``emitted`` — a request retired mid-prefill still
        carries the -1.0 sentinel, which aggregates must exclude."""
        return self.first_token_s - self.admit_s


@dataclasses.dataclass
class _Stash:
    """A preempted request's host-side progress, held between eviction
    and re-admission.  Device state is deliberately NOT kept: resume
    reconstructs every cache byte by teacher-forcing ``prompt +
    generated`` through the chunked-prefill path (decode is
    deterministic and the sampling key schedule is position-based, so
    the rebuilt run is bit-for-bit the never-preempted run) —
    "preempted state is reconstructed, never trusted"."""
    generated: List[int]
    first_token_s: float
    admit_s: float
    preemptions: int
    retries: int


# ---------------------------------------------------------------------------
# executor backends: the compiled step set behind the dispatch core
# ---------------------------------------------------------------------------

class ExecutorBackend:
    """The narrow interface the dispatch core runs device work through:
    five step providers, each returning a compiled callable with the
    exact signature of the corresponding ``runtime.steps.make_*_step``.

    Backends provide STEPS, not state — every device buffer (cache,
    tokens, index, block tables) is owned by the lane that calls the
    step, so two backends over the same config are interchangeable
    mid-process and comparable bit-for-bit (the conformance test in
    tests/test_dispatch.py pins the signatures)."""

    kind: str = "abstract"
    tp: int = 1                        # tensor-parallel width (1 = none)

    def validate(self, eng) -> None:
        """Reject engine shapes this backend cannot serve (called once
        at Engine construction, before any lane compiles a step)."""

    def place_params(self, params):
        """Put a lane's params where this backend's steps run, once, at
        lane construction — so no step call copies them."""
        return params

    def place_cache(self, cfg: ArchConfig, cache):
        """Lay a cache out exactly as this backend's steps take it, so
        the warmup compiles the signature every later tick reuses."""
        return cache

    def slot_step(self, cfg: ArchConfig, *, mode: QuantMode,
                  temperature: float) -> Callable:
        raise NotImplementedError

    def chunk_step(self, cfg: ArchConfig, *, mode: QuantMode,
                   chunk: int) -> Callable:
        raise NotImplementedError

    def prime_step(self, cfg: ArchConfig, *, mode: QuantMode) -> Callable:
        raise NotImplementedError

    def verify_step(self, cfg: ArchConfig, *, mode: QuantMode, k: int,
                    temperature: float) -> Callable:
        raise NotImplementedError

    def propose_step(self, dcfg: ArchConfig, *, mode: QuantMode,
                     k: int) -> Callable:
        raise NotImplementedError


class SingleDeviceExecutor(ExecutorBackend):
    """The single-device step set: one compiled step per (config, shape)
    from the process-wide ``cached_*`` memos — a dedicated engine and a
    multiplexed lane over the same config share one compilation.

    ``device`` pins the engine's params and caches to one device (each
    replica behind a ``ReplicaRouter`` on its own chip); the steps then
    run where their committed inputs live.  ``None`` leaves state on
    JAX's default device."""

    kind = "single"

    def __init__(self, device=None):
        self.device = device

    def place_params(self, params):
        if self.device is None:
            return params
        return jax.device_put(params, self.device)

    def place_cache(self, cfg, cache):
        return self.place_params(cache)

    def slot_step(self, cfg, *, mode, temperature):
        return ST.cached_slot_decode_step(cfg, mode=mode,
                                          temperature=temperature)

    def chunk_step(self, cfg, *, mode, chunk):
        return ST.cached_prefill_chunk_step(cfg, mode=mode, chunk=chunk)

    def prime_step(self, cfg, *, mode):
        return ST.cached_prime_step(cfg, mode=mode)

    def verify_step(self, cfg, *, mode, k, temperature):
        return ST.cached_verify_step(cfg, mode=mode, k=k,
                                     temperature=temperature)

    def propose_step(self, dcfg, *, mode, k):
        return ST.cached_draft_propose_step(dcfg, mode=mode, k=k)


class ShardedExecutor(ExecutorBackend):
    """Tensor-parallel step set: the same ``make_*_step`` builders run
    under ``jax.shard_map`` on the ``"model"`` axis of a
    host mesh (``launch.mesh.make_host_mesh``), sharded along the SLOT
    axis — each shard advances ``num_slots / tp`` rows with the full
    model replicated, which keeps every per-row float op in the exact
    order of the single-device step, so outputs are bit-for-bit
    identical (the parity gate in tests/test_sharded.py).  Attention
    heads and MoE experts could shard instead, but cross-shard psum
    reassociates float adds and loses bit parity — the slot axis is the
    sharding that costs nothing (per-row ``cache_index`` is batch-local
    already).

    Params are replicated onto the mesh once, when a lane is built.
    Sharded state is replica-private (decode-contract rule 9): the mesh
    lives inside this backend and never crosses an engine boundary."""

    kind = "sharded"

    def __init__(self, tp: Optional[int] = None):
        ndev = len(jax.devices())
        self.tp = int(tp) if tp is not None else ndev
        if self.tp < 1:
            raise ValueError(f"tp must be >= 1, got {self.tp}")
        if self.tp > ndev:
            raise ValueError(
                f"tp={self.tp} exceeds the {ndev} visible device(s); "
                f"force a CPU mesh with "
                f"XLA_FLAGS=--xla_force_host_platform_device_count=N")

    def validate(self, eng) -> None:
        if eng.num_slots % self.tp:
            raise ValueError(
                f"num_slots={eng.num_slots} must divide by tp={self.tp} "
                f"(the pool shards along the slot axis)")

    def place_params(self, params):
        return ST.place_replicated(params, self.tp)

    def place_cache(self, cfg, cache):
        return ST.place_sharded_cache(cfg, cache, self.tp)

    def slot_step(self, cfg, *, mode, temperature):
        return ST.cached_sharded_slot_decode_step(
            cfg, mode=mode, temperature=temperature, tp=self.tp)

    def chunk_step(self, cfg, *, mode, chunk):
        return ST.cached_sharded_prefill_chunk_step(
            cfg, mode=mode, chunk=chunk, tp=self.tp)

    def prime_step(self, cfg, *, mode):
        return ST.cached_sharded_prime_step(cfg, mode=mode, tp=self.tp)

    def verify_step(self, cfg, *, mode, k, temperature):
        return ST.cached_sharded_verify_step(
            cfg, mode=mode, k=k, temperature=temperature, tp=self.tp)

    def propose_step(self, dcfg, *, mode, k):
        return ST.cached_sharded_draft_propose_step(
            dcfg, mode=mode, k=k, tp=self.tp)


class _Lane:
    """One admitted model on the engine: its compiled step set, its
    device cache(s), and its model-scoped host accounting (SlotPool,
    BlockPool, block-table mirror, dispatch buffers).

    A single-model engine is exactly one lane with ``tag=None`` — every
    legacy code path routes through it unchanged.  The multiplexed
    engine holds one lane per entry of ``Engine(models={...})``; no
    leaf of one lane's cache, block pool, or draft state is ever read
    by another lane's dispatches (decode-contract rule 8: per-lane
    pools make cross-model sharing structurally impossible, and the
    prefix hash chain is additionally seeded with the lane tag).

    Compiled steps come from the engine's :class:`ExecutorBackend`
    (whose providers sit on the process-wide memo in
    ``runtime.steps``), so a dedicated single-model engine and a
    multiplexed lane over the same config share one compilation —
    which is what keeps the differential test harness cheap."""

    def __init__(self, eng, tag: Optional[str], order: int,
                 cfg: ArchConfig, params, spec_k: int,
                 dcfg: Optional[ArchConfig], dparams):
        self.eng = eng
        self.tag = tag
        self.order = order                 # dense gid = order * S + sid
        be = eng.backend
        self.cfg, self.params = cfg, be.place_params(params)
        self.spec_k = spec_k               # 0 on lanes that can't draft
        self.dcfg = dcfg
        self.dparams = None if dparams is None else be.place_params(dparams)
        # hot-swap state: a retiring lane finishes its in-flight slots
        # but admission refuses new requests for it; epoch stamps when
        # the lane joined (0 = at engine construction)
        self.retiring = False
        self.epoch = 0
        mode, temp = eng.mode, eng.temperature
        self.step = be.slot_step(cfg, mode=mode, temperature=temp)
        # encdec/vlm: the prime dispatch that writes a slot's cross-K/V
        # row (second slot-resident static operand) at admission, run
        # concurrently with other slots' decoding like chunked prefill
        self._prime_step = (be.prime_step(cfg, mode=mode)
                            if R.needs_prime(cfg) else None)
        # speculative steps: the target's wide verify step replaces the
        # fused 1-token step on every tick, the draft's propose step and
        # its own chunked catch-up steps feed it (draft state is a plain
        # contiguous cache — the draft never pages or shares blocks)
        if spec_k > 0:
            self._verify_step = be.verify_step(
                cfg, mode=mode, k=spec_k, temperature=temp)
            self._propose_step = be.propose_step(dcfg, mode=mode, k=spec_k)
        else:
            self._verify_step = self._propose_step = None
        self.reset()

    # -- per-serve runtime state ---------------------------------------

    def reset(self) -> None:
        """Fresh serving state: called at Engine construction and at the
        top of every ``serve`` (a serve never trusts a previous serve's
        device or host state)."""
        eng = self.eng
        S = eng.num_slots
        self.pool = SlotPool(S, max_seq=eng.max_seq, model=self.tag)
        self.cache = self._init_cache()
        self.tokens = np.zeros((S, 1), np.int32)
        self.index = np.zeros((S,), np.int32)
        self.spec = self.spec_k > 0
        self.draft_cache = (eng.backend.place_cache(
            self.dcfg, R.init_cache(self.dcfg, S, eng.max_seq))
            if self.spec else None)
        self.krow = np.zeros((S,), np.int32)
        self.props = self.tok_mat = self.n_tok = None
        paged = eng.block_size is not None
        self.bpool = (BlockPool(eng.num_blocks, eng.block_size,
                                model=self.tag) if paged else None)
        self.tables_np = (np.zeros((S, eng.max_blocks), np.int32)
                          if paged else None)
        self.tables_dirty = False
        # per-tick dispatch scratch (rebuilt each tick by the core)
        self.active_mask = np.zeros((S,), bool)
        self.ready: List[int] = []
        self.torn: List[int] = []
        self.nxt = None

    # -- compiled-step plumbing ----------------------------------------

    def _init_cache(self):
        """The pooled device cache: contiguous slot rows, or (paged mode)
        physical KV blocks behind an all-trash block table."""
        eng = self.eng
        if eng.block_size:
            cache = R.init_paged_cache(self.cfg, eng.num_slots, eng.max_seq,
                                       eng.block_size, eng.num_blocks)
        else:
            cache = R.init_cache(self.cfg, eng.num_slots, eng.max_seq)
        return eng.backend.place_cache(self.cfg, cache)

    def set_tables(self, tables: np.ndarray) -> None:
        """Push a block-table mirror into the device cache, laid out like
        the rest of the cache (no new step signature, no recompile)."""
        self.cache = self.eng.backend.place_cache(
            self.cfg, dict(self.cache, block_tables=jnp.asarray(tables)))

    def _chunk_step(self, chunk: int) -> Callable:
        """The compiled prefill step for one bucket size (memoized in
        ``runtime.steps`` — at most one compilation per (config, bucket)
        ever exists in the process)."""
        return self.eng.backend.chunk_step(self.cfg, mode=self.eng.mode,
                                           chunk=chunk)

    def _draft_chunk_step(self, chunk: int) -> Callable:
        """The draft model's compiled prefill step for one bucket size —
        how the engine teacher-forces committed tokens the draft cache
        has not consumed yet (admission, exact resume, full accepts)."""
        return self.eng.backend.chunk_step(self.dcfg, mode=self.eng.mode,
                                           chunk=chunk)

    def _fused(self, tokens, cache, index, active):
        args = (self.params, jnp.asarray(tokens), cache,
                jnp.asarray(index), jnp.asarray(active))
        if self.eng.temperature > 0.0:
            return self.step(*args, self.eng.rng)
        return self.step(*args)

    def _verify(self, tok_mat, cache, index, n_tok, active):
        args = (self.params, jnp.asarray(tok_mat), cache,
                jnp.asarray(index), jnp.asarray(n_tok),
                jnp.asarray(active))
        if self.eng.temperature > 0.0:
            return self._verify_step(*args, self.eng.rng)
        return self._verify_step(*args)

    # -- paged-mode admission helpers (host-side; docs/serving.md) -----

    def _prefix_keys(self, req: EngineRequest) -> Tuple:
        """Exact prefix hash chain, one key per FULL prompt block:
        ``key_j = (key_{j-1}, block_j_tokens)`` — nested tuples compared
        by value, so equal keys mean equal token prefixes (no hash
        collisions by construction).  Prime families seed the chain with
        the request's source bytes: their self-KV at any position depends
        on the cross-attended source, so two prefixes only share when
        source AND tokens match.  A tagged lane additionally seeds the
        chain with its model tag — the explicit fingerprint behind the
        no-cross-model-sharing rule (each lane's BlockPool is private
        anyway, so this is defense in depth, not the only wall)."""
        bs = self.eng.block_size
        key: Tuple = ()
        if self._prime_step is not None:
            src = np.asarray(req.source, np.float32)
            key = (src.shape, src.tobytes())
        if self.tag is not None:
            key = (("model", self.tag), key)
        keys = []
        for j in range(len(req.prompt) // bs):
            key = (key, tuple(req.prompt[j * bs:(j + 1) * bs]))
            keys.append(key)
        return tuple(keys)

    def _usable_hits(self, req: EngineRequest,
                     keys: Optional[Tuple] = None) -> int:
        """Leading prompt blocks already resident (registered by an
        earlier tenant).  Capped at ``(prompt-1) // bs``: the LAST prompt
        token always rides the fused step, and its KV write must land in
        a privately owned block, never a shared one."""
        if keys is None:
            keys = self._prefix_keys(req)
        cap = (len(req.prompt) - 1) // self.eng.block_size
        hits = 0
        for j in range(min(cap, len(keys))):
            if self.bpool.lookup(keys[j]) is None:
                break
            hits += 1
        return hits

    def _block_cost(self, req: EngineRequest) -> int:
        """Worst-case FRESH blocks this request claims if admitted now:
        ceil((prompt + max_new) / bs) minus currently shareable prefix
        blocks — what memory-aware admission prices against the pool."""
        bs = self.eng.block_size
        need = -(-(len(req.prompt) + req.max_new_tokens) // bs)
        return need - self._usable_hits(req)


@dataclasses.dataclass
class DispatchOutcome:
    """Raw counters out of one :meth:`DispatchCore.run` — everything
    ``Engine.serve`` needs to assemble an ``EngineReport``, nothing
    aggregated (the core mechanizes; the engine reports)."""
    results: List[RequestResult]
    lanes: List["_Lane"]              # the serve's lane snapshot
    occupancy: List[int]
    occ_by_lane: Dict[str, List[int]]
    ticks: int = 0
    gen_tokens: int = 0
    emit_dispatches: int = 0
    admissions_while_busy: int = 0
    dropped: int = 0
    refused: int = 0
    preempted: int = 0
    failed: int = 0
    unfinished: int = 0
    dispatch_retries: int = 0
    nonfinite: int = 0
    torn_repaired: int = 0
    stuck_ticks: int = 0
    shared_hits: int = 0
    skipped_tokens: int = 0
    blocks_demanded: int = 0
    peak_used: int = 0
    util_sum: float = 0.0
    now: float = 0.0                  # engine-clock duration
    wall: float = 0.0                 # measured host time


class DispatchCore:
    """The tick loop: ingest -> (preempt) -> admit -> chunk prefill ->
    draft/propose -> fused or verify dispatch per lane -> host
    bookkeeping, repeated until the trace drains.  One instance per
    ``serve`` call; all cross-tick host state (stash, counters, clocks)
    is local to :meth:`run`.

    The core reads engine CONFIG (num_slots, block_size, policy, lanes,
    ...) but owns the serve-time MECHANISM — Engine never touches a
    slot, block, or stash directly."""

    def __init__(self, eng):
        self.eng = eng

    def run(self, reqs: List[EngineRequest], *,
            clock: str,
            tick_s: Union[float, Mapping, Callable[[int], float]],
            max_ticks: Optional[int],
            drop_missed_deadlines: bool,
            preemption: bool,
            fault_plan: Optional[FaultPlan],
            max_retries: int,
            control: Sequence[Tuple[float, Callable]] = ()
            ) -> DispatchOutcome:
        eng = self.eng
        by_rid = {r.rid: r for r in reqs}
        S = eng.num_slots
        lanes = list(eng.lanes.values())      # index == lane.order
        for ln in lanes:
            ln.reset()
        # hot-swap control schedule: (time_s, fn(engine)) ops executed at
        # tick boundaries once the clock passes their time — how a live
        # serve admits or retires a lane (engine.admit_model /
        # engine.retire_model) without draining the others
        ctl = sorted(control, key=lambda c: c[0])
        ctl_i = 0
        sched = SlotScheduler(eng.policy)
        results: List[RequestResult] = []
        occupancy: List[int] = []
        occ_by_lane: Dict[str, List[int]] = (
            {ln.tag: [] for ln in lanes} if eng.multi else {})
        admissions_while_busy = 0
        dropped = 0
        refused = 0
        ticks = 0
        gen_tokens = 0
        # a row-tick that commits >= 1 token is one "emitting dispatch":
        # accepted_per_dispatch = gen_tokens / emit_dispatches is exactly
        # 1.0 without speculation and the mean accepted+bonus run length
        # with it — the honest denominator for speculative throughput
        emit_dispatches = 0
        # overload robustness state: stashed progress of preempted
        # requests (rid -> _Stash) and the fault/recovery counters
        stash: Dict[int, _Stash] = {}
        preempted = failed = unfinished = 0
        dispatch_retries = nonfinite = torn_repaired = 0
        wd = StepWatchdog(name=eng.name) if clock == "wall" else None
        # paged-mode state lives per lane (lane.bpool / lane.tables_np);
        # the aggregate counters below span lanes
        paged = eng.block_size is not None
        shared_hits = 0
        skipped_tokens = 0
        blocks_demanded = 0
        peak_used = 0
        util_sum = 0.0
        # per-lane tick pricing: a Mapping tick_s charges each tick the
        # sum of its DISPATCHED lanes' per-lane service times, so a
        # heavy lane's dispatch is priced honestly when lanes differ
        lane_priced = isinstance(tick_s, Mapping)

        def total_active() -> int:
            return sum(ln.pool.active_count for ln in lanes)

        def _register_blocks(ln, st) -> None:
            # publish each prompt block for prefix sharing the moment the
            # slot's frontier passes its end (its KV writes are already
            # issued in dispatch order, so any later gather sees them)
            while (st.registered < len(st.prompt_keys)
                   and st.pos >= (st.registered + 1) * eng.block_size):
                ln.bpool.register(st.prompt_keys[st.registered],
                                  st.block_table[st.registered])
                st.registered += 1

        def _release_blocks(ln, st) -> None:
            for bid in st.block_table:
                ln.bpool.release(bid)
            st.block_table, st.prompt_keys, st.registered = None, (), 0
            ln.tables_np[st.sid, :] = 0       # retired row scatters to trash
            ln.tables_dirty = True

        def _eff_req(req: EngineRequest) -> EngineRequest:
            """The request as (re-)admission sees it: a preempted request
            resumes with its stashed tokens appended to the prompt
            (teacher-forced through prefill — the exact-resume mechanism)
            and its token budget reduced by the same count, so its total
            cache claim is invariant under preemption."""
            s = stash.get(req.rid)
            if s is None or not s.generated:
                return req
            return dataclasses.replace(
                req, prompt=req.prompt + tuple(s.generated),
                max_new_tokens=req.max_new_tokens - len(s.generated))

        def _block_cost(req: EngineRequest) -> int:
            ln_c = eng.lanes.get(getattr(req, "model", None))
            return (ln_c._block_cost(_eff_req(req))
                    if ln_c is not None else 0)

        def _preempt(ln, st) -> None:
            """Evict a live slot with exact-resume semantics: release its
            blocks, stash host progress, requeue the original request.
            No device state survives — resume rebuilds it all."""
            nonlocal preempted
            preempted += 1
            rid = st.rid                  # pool.free() scrubs it to -1
            stash[rid] = _Stash(
                generated=list(st.generated or []),
                first_token_s=st.first_token_s, admit_s=st.admit_s,
                preemptions=st.preemptions + 1, retries=st.retries)
            if paged and st.block_table is not None:
                _release_blocks(ln, st)
            ln.pool.free(st.sid)
            ln.index[st.sid] = 0
            ln.tokens[st.sid, 0] = 0
            sched.push(by_rid[rid])

        def _fail(ln, st) -> None:
            """Retire a slot fault recovery gave up on (typed status)."""
            nonlocal failed
            failed += 1
            results.append(RequestResult(
                rid=st.rid, tokens=list(st.generated or []),
                arrival_s=st.arrival_s, admit_s=st.admit_s,
                first_token_s=st.first_token_s, finish_s=now,
                slot=st.sid, status="failed", priority=st.priority,
                preemptions=st.preemptions, deadline_s=st.deadline_s,
                model=ln.tag))
            if paged and st.block_table is not None:
                _release_blocks(ln, st)
            ln.pool.free(st.sid)
            ln.index[st.sid] = 0
            ln.tokens[st.sid, 0] = 0

        i, now = 0, 0.0
        t0 = time.perf_counter()
        limit = max_ticks if max_ticks is not None else \
            (sum(len(r.prompt) + r.max_new_tokens for r in reqs) + 16) * 4

        with warnings.catch_warnings():
            # CPU backends warn that donated buffers were not usable
            warnings.filterwarnings("ignore", message=".*[Dd]onat.*")
            while (i < len(reqs) or sched.pending or total_active()
                   or ctl_i < len(ctl)):
                # 0) hot-swap control: run every op the clock has passed;
                #    each may admit or retire a lane, so refresh the lane
                #    snapshot (append-only during a serve — gid mapping
                #    `lanes[g // S]` stays index == order)
                while ctl_i < len(ctl) and ctl[ctl_i][0] <= now:
                    ctl[ctl_i][1](eng)
                    ctl_i += 1
                    lanes = list(eng.lanes.values())
                    if eng.multi:
                        for ln in lanes:
                            occ_by_lane.setdefault(
                                ln.tag, [0] * len(occupancy))
                if (i >= len(reqs) and not sched.pending
                        and not total_active()):
                    if ctl_i >= len(ctl):
                        break
                    now = max(now, ctl[ctl_i][0])
                    continue
                # 1) ingest everything that has arrived by `now`
                while i < len(reqs) and reqs[i].arrival_s <= now:
                    sched.push(reqs[i])
                    i += 1
                next_arrival = reqs[i].arrival_s if i < len(reqs) else None
                # lanes dispatched this tick (Mapping tick_s pricing)
                tick_lanes = set()
                # 2) admit into free slot leases — mid-flight, no drain
                #    barrier; `num_slots` caps the TOTAL across lanes
                generating = any(s.active and not s.in_prefill
                                 for ln in lanes for s in ln.pool.slots)
                if preemption and sched.pending:
                    # resource pressure + a strictly-higher-class head:
                    # evict the lowest-class generating slot (latest
                    # deadline first) until the head fits or no victim of
                    # lower class remains — equal class never preempts,
                    # so batch can't thrash batch.  Slot pressure frees a
                    # LEASE, so victims come from any lane; pure block
                    # pressure only helps if the victim is in the head's
                    # own lane (block pools are lane-private, rule 8).
                    head = sched.pending[0]
                    lane_h = eng.lanes.get(getattr(head, "model", None))
                    if lane_h is not None and not lane_h.retiring:
                        hrank = bt.priority_rank(
                            getattr(head, "priority",
                                    bt.PRIORITY_CLASSES[0]))
                        for _ in range(S * len(lanes)):
                            slot_pressed = total_active() >= S
                            block_pressed = (
                                paged and lane_h._block_cost(_eff_req(head))
                                > lane_h.bpool.free_blocks)
                            if not (slot_pressed or block_pressed):
                                break
                            vlanes = lanes if slot_pressed else [lane_h]
                            victims = [(ln, s) for ln in vlanes
                                       for s in ln.pool.active_slots()
                                       if bt.priority_rank(s.priority)
                                       > hrank]
                            if not victims:
                                break
                            ln_v, st_v = max(victims, key=lambda t: (
                                bt.priority_rank(t[1].priority),
                                t[1].deadline_s, t[0].order, t[1].sid))
                            _preempt(ln_v, st_v)
                quotas_on = bool(eng.policy.class_quotas)
                abc = None
                if quotas_on or eng.multi:
                    # quota denominators: on a multiplexed engine each
                    # active slot charges its (model, class) tuple AND the
                    # bare model and class keys, so quotas configured at
                    # any granularity meter correctly
                    abc = {}
                    for ln in lanes:
                        for s in ln.pool.active_slots():
                            if eng.multi:
                                for k in ((ln.tag, s.priority), ln.tag,
                                          s.priority):
                                    abc[k] = abc.get(k, 0) + 1
                            else:
                                abc[s.priority] = abc.get(s.priority, 0) + 1
                if paged:
                    budget = ({ln.tag: ln.bpool.free_blocks for ln in lanes}
                              if eng.multi else lanes[0].bpool.free_blocks)
                else:
                    budget = None
                cohort = sched.admit(
                    now, S - total_active(), next_arrival,
                    cost_fn=_block_cost if paged else None,
                    budget=budget,
                    active_by_class=abc,
                    key_fn=((lambda r: (getattr(r, "model", None),
                                        getattr(r, "priority",
                                                bt.PRIORITY_CLASSES[0])))
                            if eng.multi else None))
                admitted = 0
                for req in cohort:
                    ln = eng.lanes.get(getattr(req, "model", None))
                    s_res = stash.get(req.rid)
                    if ln is None or ln.retiring:
                        # hot-swap refusal: the lane was retired (or not
                        # yet admitted) — in-flight slots of a retiring
                        # lane keep running, but the lane-epoch check
                        # stops anything NEW from entering it
                        results.append(RequestResult(
                            rid=req.rid,
                            tokens=list(s_res.generated) if s_res else [],
                            arrival_s=req.arrival_s,
                            admit_s=s_res.admit_s if s_res else -1.0,
                            first_token_s=(s_res.first_token_s if s_res
                                           else -1.0),
                            finish_s=now, slot=-1, status="refused",
                            priority=req.priority,
                            preemptions=s_res.preemptions if s_res else 0,
                            deadline_s=req.deadline_s,
                            model=getattr(req, "model", None)))
                        stash.pop(req.rid, None)
                        refused += 1
                        continue
                    if drop_missed_deadlines and now > req.deadline_s:
                        # expired while queued: retire WITHOUT taking a
                        # slot — no prime or prefill dispatch is wasted
                        # on a request that is already dead (a preempted
                        # request keeps what it had generated)
                        results.append(RequestResult(
                            rid=req.rid,
                            tokens=list(s_res.generated) if s_res else [],
                            arrival_s=req.arrival_s,
                            admit_s=s_res.admit_s if s_res else now,
                            first_token_s=(s_res.first_token_s if s_res
                                           else -1.0),
                            finish_s=now, slot=-1, dropped=True,
                            status="dropped", priority=req.priority,
                            preemptions=s_res.preemptions if s_res else 0,
                            deadline_s=req.deadline_s, model=ln.tag))
                        stash.pop(req.rid, None)
                        dropped += 1
                        continue
                    admitted += 1
                    eff = _eff_req(req)
                    st = ln.pool.alloc(req.rid, eff.prompt,
                                       eff.max_new_tokens,
                                       now=now, arrival_s=req.arrival_s,
                                       deadline_s=req.deadline_s,
                                       priority=req.priority)
                    if s_res is not None:
                        # exact resume: the stashed tokens ride the prompt
                        # (teacher-forced), the generated list starts from
                        # them, and ttft/admit bookkeeping survives the
                        # eviction — alloc validated the INVARIANT claim
                        # eff.prompt + eff.max_new == original total
                        st.generated = list(s_res.generated)
                        st.max_new = req.max_new_tokens
                        st.first_token_s = s_res.first_token_s
                        st.admit_s = s_res.admit_s
                        st.preemptions = s_res.preemptions
                        st.retries = s_res.retries
                        del stash[req.rid]
                    ln.index[st.sid] = 0
                    if paged:
                        # build the slot's block table: ref every shared
                        # prefix block (their prefill chunks are skipped
                        # entirely), alloc the rest privately — the
                        # admission decision priced exactly this claim.
                        # Keys are model-fingerprinted (lane._prefix_keys)
                        # and looked up in the lane's OWN pool, so a hit
                        # can never cross models.
                        keys = ln._prefix_keys(eff)
                        hits = ln._usable_hits(eff, keys)
                        need = -(-(len(eff.prompt) + eff.max_new_tokens)
                                 // eng.block_size)
                        table = []
                        for j in range(hits):
                            bid = ln.bpool.lookup(keys[j])
                            ln.bpool.ref(bid)
                            table.append(bid)
                        for _ in range(need - hits):
                            table.append(ln.bpool.alloc())
                        st.block_table = table
                        st.prompt_keys = keys
                        st.registered = hits
                        st.pos = hits * eng.block_size
                        ln.index[st.sid] = st.pos
                        ln.tables_np[st.sid, :] = 0
                        ln.tables_np[st.sid, :len(table)] = table
                        ln.tables_dirty = True
                        shared_hits += hits
                        skipped_tokens += hits * eng.block_size
                        blocks_demanded += need
                    if ln._prime_step is not None:
                        # prime dispatch: write this slot's cross-K/V row
                        # (and its xlen frontier) once, concurrently with
                        # other slots' decoding — like a prefill chunk,
                        # its cost lands on this tick's clock (resume
                        # re-primes: reconstructed, never trusted)
                        src, n_valid = _padded_source(ln.cfg, req)
                        ln.cache = ln._prime_step(
                            ln.params, src, ln.cache,
                            jnp.asarray(st.sid, jnp.int32), n_valid)
                        tick_lanes.add(ln.tag)
                    left = len(st.prompt) - 1 - st.pos
                    if eng.prefill_chunk and left > 0:
                        # remaining prompt (all but the last token, minus
                        # any shared-prefix positions already resident)
                        # goes through the chunked prefill step; the last
                        # token rides the fused step (its sample = first
                        # output token)
                        st.chunk_left = left
                    else:
                        ln.tokens[st.sid, 0] = st.next_input()
                if generating:
                    admissions_while_busy += admitted
                if paged:
                    # push each dirty host table mirror before any
                    # dispatch this tick gathers or scatters through it
                    for ln in lanes:
                        if ln.tables_dirty:
                            ln.set_tables(ln.tables_np)
                            ln.tables_dirty = False
                # 3) idle: nothing active -> jump to the next event
                if total_active() == 0:
                    nxt_ctl = ctl[ctl_i][0] if ctl_i < len(ctl) else None
                    if (next_arrival is None and not sched.pending
                            and nxt_ctl is None):
                        break
                    if (next_arrival is None and not cohort
                            and nxt_ctl is None and sched.pending):
                        # this round consumed nothing from a non-empty
                        # queue, the pool is idle, and nothing is left to
                        # arrive: no future round can differ — surface
                        # the policy bug instead of spinning (the
                        # virtual-time twin of the run_virtual guard)
                        raise RuntimeError(
                            "admission declined a non-empty pending queue "
                            f"({len(sched.pending)} requests) with an idle "
                            "pool and no future arrival; check the policy "
                            "/ class_quotas configuration")
                    target = next_arrival if next_arrival is not None else now
                    if nxt_ctl is not None:
                        # a scheduled control op is an event too: never
                        # jump the idle clock past a pending hot-swap
                        target = (min(target, nxt_ctl)
                                  if next_arrival is not None else nxt_ctl)
                    if clock == "wall":
                        gap = target - (time.perf_counter() - t0)
                        if gap > 0:
                            time.sleep(min(gap, 0.05))
                        now = time.perf_counter() - t0
                    else:
                        now = max(now, target)
                    continue
                # 4) chunked prefill: each mid-prefill slot writes one
                #    bucketed chunk of teacher-forced prompt state in a
                #    single dispatch (admission-to-first-token shrinks
                #    from prompt_len ticks to ceil(prompt_len/chunk))
                for ln in lanes:
                    for st in ln.pool.active_slots():
                        if st.chunk_left <= 0:
                            continue
                        n = min(st.chunk_left, eng.prefill_chunk)
                        c = ST.bucket_batch(n)
                        buf = np.zeros((c,), np.int32)
                        buf[:n] = st.prompt[st.pos:st.pos + n]
                        ln.cache = ln._chunk_step(c)(
                            ln.params, jnp.asarray(buf), ln.cache,
                            jnp.asarray(st.sid, jnp.int32),
                            jnp.asarray(st.pos, jnp.int32),
                            jnp.asarray(n, jnp.int32))
                        st.pos += n
                        st.chunk_left -= n
                        ln.index[st.sid] = st.pos
                        tick_lanes.add(ln.tag)
                        if paged:
                            _register_blocks(ln, st)
                        if st.chunk_left == 0:
                            ln.tokens[st.sid, 0] = st.prompt[st.pos]
                # 4.5) speculative draft: catch each generating slot's
                #      draft cache up to its committed frontier (teacher-
                #      forced — this is also what rebuilds the draft after
                #      admission, preemption/resume, or slot reuse), then
                #      propose k greedy tokens per slot in ONE fused
                #      dispatch per speculating lane.  Draft dispatches
                #      see no fault injection: a wrong proposal can only
                #      be rejected.
                for ln in lanes:
                    if not ln.spec:
                        continue
                    ln.krow = np.zeros((S,), np.int32)
                    for st in ln.pool.active_slots():
                        if st.chunk_left > 0 or st.pos < len(st.prompt) - 1:
                            continue
                        k_row = min(ln.spec_k,
                                    st.max_new - len(st.generated) - 1,
                                    eng.max_seq - 1 - st.pos)
                        if k_row <= 0:
                            continue
                        ln.krow[st.sid] = k_row
                        P = len(st.prompt)
                        while st.draft_pos < st.pos:
                            n = min(st.pos - st.draft_pos, eng._draft_cap)
                            c = ST.bucket_batch(n)
                            buf = np.zeros((c,), np.int32)
                            for t in range(n):
                                p = st.draft_pos + t
                                buf[t] = (st.prompt[p] if p < P
                                          else st.generated[p - P])
                            ln.draft_cache = ln._draft_chunk_step(c)(
                                ln.dparams, jnp.asarray(buf),
                                ln.draft_cache,
                                jnp.asarray(st.sid, jnp.int32),
                                jnp.asarray(st.draft_pos, jnp.int32),
                                jnp.asarray(n, jnp.int32))
                            st.draft_pos += n
                    d_active = ln.krow > 0
                    if d_active.any():
                        d_index = np.array(
                            [s.draft_pos for s in ln.pool.slots], np.int32)
                        props, ln.draft_cache, _ = ln._propose_step(
                            ln.dparams, jnp.asarray(ln.tokens),
                            ln.draft_cache,
                            jnp.asarray(d_index), jnp.asarray(d_active))
                        ln.props = np.asarray(props)
                        tick_lanes.add(ln.tag)
                    else:
                        ln.props = np.zeros((S, ln.spec_k), np.int32)
                # 5) one fused slot-masked step PER LANE with live slots:
                #    every ready slot (not mid-chunk), one token — or,
                #    speculating, one wide verify dispatch scoring 1..k+1
                #    tokens per ready slot (same single compiled shape per
                #    lane whatever the mix).  Fault injection addresses
                #    slots by dense GLOBAL id (lane.order * S + sid) so a
                #    single-lane engine sees byte-identical sid streams.
                all_ready: List[int] = []      # global ids, lane-major
                for ln in lanes:
                    ln.active_mask = np.array(
                        [s.active and s.chunk_left == 0
                         for s in ln.pool.slots], bool)
                    ln.ready = [int(s) for s in np.where(ln.active_mask)[0]]
                    ln.torn = []
                    ln.nxt = None
                    all_ready.extend(ln.order * S + sid for sid in ln.ready)
                if fault_plan is not None and paged and all_ready:
                    # fault: tear the victim's DEVICE table row (zero ->
                    # all-trash) just before dispatch; the host mirror
                    # stays clean, which is exactly how the post-step
                    # audit knows what to rebuild
                    for g in fault_plan.torn_rows(ticks, all_ready):
                        lanes[g // S].torn.append(g % S)
                    for ln in lanes:
                        if ln.torn:
                            torn = ln.tables_np.copy()
                            for sid in ln.torn:
                                torn[sid, :] = 0
                            ln.set_tables(torn)
                            ln.tables_dirty = True  # clean mirror repushed
                if all_ready:
                    # resolve dispatch faults FIRST, over the union of
                    # ready global ids (the injected fault strikes the
                    # tick's dispatch sequence, whichever lane the culprit
                    # sits in), then run each lane's step exactly once
                    attempt = 0
                    while all_ready:
                        culprit = (fault_plan.dispatch_fault(
                            ticks, attempt, all_ready)
                            if fault_plan is not None else None)
                        if culprit is None:
                            break
                        # dispatch failed: charge the culprit's retry
                        # budget; past max_retries the request is retired
                        # as `failed` and the retry goes on without it —
                        # one poisoned slot never takes down the cohort
                        dispatch_retries += 1
                        attempt += 1
                        ln = lanes[culprit // S]
                        sid = culprit % S
                        st = ln.pool.slots[sid]
                        st.retries += 1
                        if st.retries > max_retries:
                            _fail(ln, st)
                            ln.active_mask[sid] = False
                            ln.ready.remove(sid)
                            all_ready.remove(culprit)
                for ln in lanes:
                    if not ln.ready:
                        continue
                    tick_lanes.add(ln.tag)
                    if ln.spec:
                        # per-row verify payload: the committed next input
                        # in column 0, the row's usable proposals after it
                        ln.tok_mat = np.zeros((S, ln.spec_k + 1), np.int32)
                        ln.tok_mat[:, 0] = ln.tokens[:, 0]
                        for sid in ln.ready:
                            kr = int(ln.krow[sid])
                            if kr > 0:
                                ln.tok_mat[sid, 1:1 + kr] = \
                                    ln.props[sid, :kr]
                        ln.n_tok = np.where(ln.active_mask, 1 + ln.krow,
                                            0).astype(np.int32)
                        nxt, ln.cache, new_index = ln._verify(
                            ln.tok_mat, ln.cache, ln.index, ln.n_tok,
                            ln.active_mask)
                    else:
                        nxt, ln.cache, new_index = ln._fused(
                            ln.tokens, ln.cache, ln.index, ln.active_mask)
                    ln.nxt = np.asarray(nxt)
                    ln.index = np.array(new_index)   # writable host copy
                if not all_ready and clock == "wall":
                    # charge chunk/prime time here
                    jax.block_until_ready([ln.cache for ln in lanes])
                if fault_plan is not None and all_ready:
                    # fault: poison chosen slots' logits — modelled at the
                    # guard's observable surface, the -1 sentinel the
                    # in-graph finite check emits for NaN/Inf rows
                    for g in fault_plan.nonfinite_slots(ticks, all_ready):
                        ln = lanes[g // S]
                        ln.nxt = np.array(ln.nxt)    # writable copy
                        ln.nxt[g % S] = -1
                ticks += 1
                tact = total_active()
                occupancy.append(tact)
                for t in occ_by_lane:
                    occ_by_lane[t].append(eng.lanes[t].pool.active_count)
                if paged:
                    used = sum(ln.bpool.used_blocks for ln in lanes)
                    peak_used = max(peak_used, used)
                    util_sum += used / max(
                        1, (eng.num_blocks - 1) * len(lanes))
                if clock == "wall":
                    # np.asarray(nxt) above already blocked on the step
                    prev = now
                    now = time.perf_counter() - t0
                    # stuck-tick watchdog: with static shapes, per-tick
                    # wall time is tight — a straggler means a sick
                    # host, not workload variance
                    msg = wd.record(now - prev)
                    if msg:
                        warnings.warn(f"engine tick {ticks}: {msg}",
                                      RuntimeWarning)
                elif lane_priced:
                    # every lane that dispatched anything this tick
                    # (chunk, prime, draft, fused or verify) contributes
                    # its configured service time; an admission-only tick
                    # with no dispatch charges the cheapest lane's time
                    # (the clock must still advance)
                    vals = [float(tick_s[t]) for t in sorted(
                        tick_lanes, key=lambda x: (x is None, x))]
                    now += (sum(vals) if vals
                            else min(float(v) for v in tick_s.values()))
                else:
                    dt = tick_s(tact) if callable(tick_s) else tick_s
                    now += dt
                # 6) host bookkeeping, lane by lane: teacher-force
                #    prefill, collect samples, retire finished slots for
                #    immediate lease reuse (by any lane)
                for ln in lanes:
                  for sid in ln.torn:
                    # the torn row sent this tick's K/V write to trash
                    # and sampled through garbage gathers: the slot's
                    # device state can no longer be trusted, so the
                    # audit repairs the table (clean mirror repush) and
                    # rebuilds the tenant from scratch via preemption —
                    # its output stays bit-for-bit (exact resume)
                    st = ln.pool.slots[sid]
                    if not st.active:
                        continue          # already retired by _fail
                    torn_repaired += 1
                    _preempt(ln, st)
                  for st in ln.pool.active_slots():
                    if st.sid in ln.torn:
                        continue
                    if drop_missed_deadlines and now > st.deadline_s:
                        # deadline miss — possibly mid-prefill, before
                        # any token: record with the first_token_s
                        # sentinel intact (ttft aggregates exclude it)
                        results.append(RequestResult(
                            rid=st.rid, tokens=list(st.generated),
                            arrival_s=st.arrival_s, admit_s=st.admit_s,
                            first_token_s=st.first_token_s, finish_s=now,
                            slot=st.sid, dropped=True, status="dropped",
                            priority=st.priority,
                            preemptions=st.preemptions,
                            deadline_s=st.deadline_s, model=ln.tag))
                        dropped += 1
                        if paged:
                            _release_blocks(ln, st)
                        ln.pool.free(st.sid)
                        continue
                    if st.chunk_left > 0:          # mid-chunk: no sample
                        continue
                    if not ln.spec:
                        st.pos += 1
                        if paged:
                            _register_blocks(ln, st)
                        if st.pos < len(st.prompt):    # still prefilling
                            ln.tokens[st.sid, 0] = st.prompt[st.pos]
                            continue
                        tok = int(ln.nxt[st.sid])
                        if tok < 0:
                            # the in-graph finite guard's sentinel: this
                            # slot's logits went NaN/Inf.  The sample is
                            # garbage and the cache row suspect — rebuild
                            # deterministically via preemption (a transient
                            # fault recomputes clean, bit-for-bit); a slot
                            # that keeps faulting exhausts its retry budget
                            # and is retired as `failed`
                            nonfinite += 1
                            st.retries += 1
                            if st.retries > max_retries:
                                _fail(ln, st)
                            else:
                                _preempt(ln, st)
                            continue
                        st.generated.append(tok)
                        gen_tokens += 1
                        emit_dispatches += 1
                        if st.first_token_s < 0:
                            st.first_token_s = now
                        if st.done():
                            results.append(RequestResult(
                                rid=st.rid, tokens=list(st.generated),
                                arrival_s=st.arrival_s, admit_s=st.admit_s,
                                first_token_s=st.first_token_s,
                                finish_s=now,
                                slot=st.sid, priority=st.priority,
                                preemptions=st.preemptions,
                                deadline_s=st.deadline_s, model=ln.tag))
                            if paged:
                                _release_blocks(ln, st)
                            ln.pool.free(st.sid)
                        else:
                            ln.tokens[st.sid, 0] = tok
                        continue
                    # speculative commit: walk the verified row, keeping
                    # the accepted prefix + the bonus sample, then REWIND
                    # the device index to the committed frontier — the
                    # rejected tail's KV writes die by overwrite-before-
                    # read (decode-contract rule 7)
                    nt = int(ln.n_tok[st.sid])
                    row = ln.nxt[st.sid]
                    if np.any(row[:nt] < 0):
                        # any sentinel in the fed range poisons the whole
                        # round: in-flight proposals are uncommitted state,
                        # so fault recovery rebuilds from the last COMMITTED
                        # token exactly as in the non-speculative engine
                        nonfinite += 1
                        st.retries += 1
                        if st.retries > max_retries:
                            _fail(ln, st)
                        else:
                            _preempt(ln, st)
                        continue
                    pos0 = st.pos
                    committed = 0
                    for j in range(nt):
                        st.pos += 1
                        if paged:
                            _register_blocks(ln, st)
                        if st.pos < len(st.prompt):    # still prefilling
                            ln.tokens[st.sid, 0] = st.prompt[st.pos]
                            break
                        tok = int(row[j])
                        st.generated.append(tok)
                        gen_tokens += 1
                        committed += 1
                        if st.first_token_s < 0:
                            st.first_token_s = now
                        if st.done() or (j + 1 < nt
                                         and tok != int(ln.tok_mat[st.sid,
                                                                   j + 1])):
                            break
                    ln.index[st.sid] = st.pos  # the rewind past rejections
                    if committed:
                        emit_dispatches += 1
                        if ln.krow[st.sid] > 0:
                            # the draft consumed [f, d_1..d_{k-1}]; the
                            # committed-valid prefix of that is 1 + the
                            # accepted count (capped at k-1): gap 0 after
                            # a partial accept, 1 after a full accept
                            st.draft_pos = pos0 + 1 + min(
                                committed - 1, ln.spec_k - 1)
                    if st.done():
                        results.append(RequestResult(
                            rid=st.rid, tokens=list(st.generated),
                            arrival_s=st.arrival_s, admit_s=st.admit_s,
                            first_token_s=st.first_token_s, finish_s=now,
                            slot=st.sid, priority=st.priority,
                            preemptions=st.preemptions,
                            deadline_s=st.deadline_s, model=ln.tag))
                        if paged:
                            _release_blocks(ln, st)
                        ln.pool.free(st.sid)
                    elif committed:
                        ln.tokens[st.sid, 0] = st.generated[-1]
                if ticks > limit:
                    # the cap exists to bound a stuck run; hitting it is
                    # an overload outcome, not a crash — retire everything
                    # still in flight (and everything that never got in)
                    # with the typed `unfinished` status and report it
                    warnings.warn(
                        f"engine hit the {limit}-tick cap with "
                        f"{total_active()} active, "
                        f"{len(sched.pending)} pending and "
                        f"{len(reqs) - i} unarrived requests; retiring "
                        "them as 'unfinished'", RuntimeWarning)
                    for ln in lanes:
                        for st in ln.pool.active_slots():
                            unfinished += 1
                            results.append(RequestResult(
                                rid=st.rid, tokens=list(st.generated or []),
                                arrival_s=st.arrival_s, admit_s=st.admit_s,
                                first_token_s=st.first_token_s,
                                finish_s=now,
                                slot=st.sid, status="unfinished",
                                priority=st.priority,
                                preemptions=st.preemptions,
                                deadline_s=st.deadline_s, model=ln.tag))
                            if paged:
                                _release_blocks(ln, st)
                            ln.pool.free(st.sid)
                    for req in list(sched.pending) + reqs[i:]:
                        s_res = stash.pop(req.rid, None)
                        unfinished += 1
                        results.append(RequestResult(
                            rid=req.rid,
                            tokens=list(s_res.generated) if s_res else [],
                            arrival_s=req.arrival_s,
                            admit_s=s_res.admit_s if s_res else -1.0,
                            first_token_s=(s_res.first_token_s if s_res
                                           else -1.0),
                            finish_s=now, slot=-1, status="unfinished",
                            priority=req.priority,
                            preemptions=s_res.preemptions if s_res else 0,
                            deadline_s=req.deadline_s,
                            model=getattr(req, "model", None)))
                    sched.pending.clear()
                    i = len(reqs)
                    break

        return DispatchOutcome(
            results=results, lanes=lanes, occupancy=occupancy,
            occ_by_lane=occ_by_lane, ticks=ticks, gen_tokens=gen_tokens,
            emit_dispatches=emit_dispatches,
            admissions_while_busy=admissions_while_busy,
            dropped=dropped, refused=refused, preempted=preempted,
            failed=failed, unfinished=unfinished,
            dispatch_retries=dispatch_retries, nonfinite=nonfinite,
            torn_repaired=torn_repaired,
            stuck_ticks=wd.slow_steps if wd is not None else 0,
            shared_hits=shared_hits, skipped_tokens=skipped_tokens,
            blocks_demanded=blocks_demanded, peak_used=peak_used,
            util_sum=util_sum, now=now,
            wall=time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# source-embedding validation / padding (prime families)
# ---------------------------------------------------------------------------

def _validate_source(cfg: ArchConfig, req: EngineRequest) -> np.ndarray:
    """Host-side shape/length checks only (no device array is built —
    ``serve`` validates the whole trace up front before admitting
    anything, and builds the padded array once, at admission)."""
    smax = R.source_len(cfg)
    if req.source is None:
        raise ValueError(
            f"request {req.rid}: {cfg.family!r} serves against per-request "
            f"source embeddings; EngineRequest.source must be "
            f"(src_len <= {smax}, {cfg.d_model})")
    src = np.asarray(req.source, np.float32)
    if src.ndim != 2 or src.shape[1] != cfg.d_model:
        raise ValueError(
            f"request {req.rid}: source must be (src_len, {cfg.d_model}), "
            f"got {src.shape}")
    n = src.shape[0]
    if not 0 < n <= smax:
        raise ValueError(
            f"request {req.rid}: source length {n} outside (0, {smax}]")
    return src


def _padded_source(cfg: ArchConfig, req: EngineRequest):
    """One request's source embeddings padded to the static prime shape:
    (1, source_len(cfg), d_model) bf16 plus the () int32 count of real
    positions.  Shared by the engine's prime dispatch and the sequential
    reference, so both prime with byte-identical inputs — the pad is
    masked behind the row's xlen frontier at decode time."""
    src = _validate_source(cfg, req)
    n = src.shape[0]
    buf = np.zeros((1, R.source_len(cfg), cfg.d_model), np.float32)
    buf[0, :n] = src
    return (jnp.asarray(buf, jnp.bfloat16),
            jnp.asarray(n, jnp.int32))
