"""Kernel tile autotuner — pick (bm, bn, bk) per problem shape.

The paper's performance story is that *fit*, not peak TOPS, decides achieved
throughput: the 256x256 matrix unit runs at 80% of peak only when the
software keeps its pipelines full.  Our Pallas kernels are the same story at
MXU scale — a decode-sized matmul (M = batch, often 8-64) padded up to a
bm=128 tile wastes >75% of every MXU pass, while an over-large K tile blows
the VMEM (Unified-Buffer analogue) budget and stalls the pipeline on
spills.  This module makes the tile choice explicit, modelled, and cached:

1. ``enumerate_candidates`` — every legal (bm, bn, bk) for an (M, K, N,
   quant-mode) problem under hard alignment rules (lane = 128, dtype
   sublane minima) and an explicit VMEM budget: double-buffered x-tile +
   w-tile + scale/bias tiles + output tile, plus the accumulator scratch,
   must fit in ``DEFAULT_VMEM_BUDGET``.
2. ``predicted_cost`` — an analytic roofline of one kernel launch: padded
   flops vs streamed bytes (x is re-streamed per N-tile, w per M-tile —
   the same flops/bytes accounting ``core.hlo_cost`` does structurally),
   plus a per-grid-step dispatch overhead.  Padding waste is penalized
   naturally because the padded problem is what gets executed.
3. ``best_config`` — rank candidates and keep the analytic winner, or,
   as an explicit tuning step, re-rank the top few with a timing backend
   the caller passes (``measure_config`` on a chip); winners go to a
   cache keyed by (shape, mode, x-dtype, backend).  The serving path
   never times anything, so its tile choice is the same on every run.

Cache file format (``autotune.json``)::

    {"schema_version": 1,
     "entries": {"64x4096x4096|w8a16|bf16|bias|tpu": {
         "bm": 64, "bn": 256, "bk": 512, "source": "measured"}}}

The cache is in memory unless ``REPRO_AUTOTUNE_CACHE`` names a file;
then measured winners persist there and later processes read them back.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import tempfile
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Hardware model constants
# ---------------------------------------------------------------------------

LANE = 128                       # last-dim tile width, every dtype
SUBLANE = {"int8": 32, "bf16": 16, "f32": 8}   # min second-to-last dim
DTYPE_BYTES = {"int8": 1, "bf16": 2, "f32": 4}

VMEM_BYTES = 16 * 2 ** 20        # per-core VMEM
# leave headroom for Pallas metadata / semaphores / the compiler's own
# staging buffers; candidates must fit working set in this budget.
DEFAULT_VMEM_BUDGET = 12 * 2 ** 20

MODES = ("w8a8", "w8a16")

BM_CANDIDATES = (8, 16, 32, 64, 128, 256, 512)
BN_CANDIDATES = (128, 256, 512)
BK_CANDIDATES = (128, 256, 512)


@dataclasses.dataclass(frozen=True)
class KernelHW:
    """Roofline constants for the analytic cost model (v4-class defaults).

    Only *ratios* matter for ranking; absolute values are not calibrated.
    """
    peak_flops: float = 275e12       # bf16/f32-accum MXU peak
    int8_speedup: float = 2.0        # paper §2: 8-bit ops at double rate
    hbm_bw: float = 1.2e12           # bytes/s
    grid_step_s: float = 3e-7        # per grid-step dispatch overhead


@dataclasses.dataclass(frozen=True)
class TileConfig:
    bm: int
    bn: int
    bk: int

    def as_kwargs(self) -> dict:
        return {"bm": self.bm, "bn": self.bn, "bk": self.bk}


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _round_up(a: int, b: int) -> int:
    return _cdiv(a, b) * b


def x_dtype_for(mode: str, act_dtype: str = "bf16") -> str:
    """The streamed-activation dtype of a quant mode."""
    return "int8" if mode == "w8a8" else act_dtype


# ---------------------------------------------------------------------------
# VMEM working-set model
# ---------------------------------------------------------------------------

def vmem_bytes(cfg: TileConfig, *, mode: str, x_dtype: str = "bf16",
               has_bias: bool = True, out_dtype: str = "f32") -> int:
    """Working-set bytes of one kernel step with double-buffered streams.

    Pallas pipelines every BlockSpec operand (and the output) with two
    buffers — the Weight-FIFO analogue — so streamed tiles count twice;
    the accumulator scratch is single-buffered and persistent.
    """
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}")
    xb = DTYPE_BYTES["int8"] if mode == "w8a8" else DTYPE_BYTES[x_dtype]
    x_tile = cfg.bm * cfg.bk * xb
    w_tile = cfg.bk * cfg.bn * DTYPE_BYTES["int8"]
    scales = cfg.bn * 4 + (4 if mode == "w8a8" else 0)   # col scales (+act)
    bias = cfg.bn * 4 if has_bias else 0
    out_tile = cfg.bm * cfg.bn * DTYPE_BYTES[out_dtype]
    acc = cfg.bm * cfg.bn * 4                            # int32 / f32 scratch
    return 2 * (x_tile + w_tile + scales + bias + out_tile) + acc


def _bm_align(mode: str, x_dtype: str, out_dtype: str) -> int:
    """bm alignment: both the streamed x tile (bm, bk) and the output tile
    (bm, bn) must be legal — the stricter sublane floor wins."""
    return max(SUBLANE[x_dtype_for(mode, x_dtype)], SUBLANE[out_dtype])


def is_legal(cfg: TileConfig, *, mode: str, x_dtype: str = "bf16",
             out_dtype: str = "f32", has_bias: bool = True,
             budget: int = DEFAULT_VMEM_BUDGET) -> bool:
    """Alignment + budget legality of a tile config (shape-independent).

    - bm must honour the sublane minimum of BOTH the streamed x dtype and
      the output dtype (int8 32, bf16 16, f32 8) — the (bm, bn) out tile
      is a real block too;
    - bn / bk must be lane-aligned (128); w8a8 K-tiles additionally pack
      two int8 per register lane, so bk must be a multiple of 256;
    - the double-buffered working set must fit the VMEM budget.
    """
    if cfg.bm <= 0 or cfg.bm % _bm_align(mode, x_dtype, out_dtype) != 0:
        return False
    if cfg.bn % LANE != 0 or cfg.bk % LANE != 0:
        return False
    if mode == "w8a8" and cfg.bk % 256 != 0:
        return False
    return vmem_bytes(cfg, mode=mode, x_dtype=x_dtype, out_dtype=out_dtype,
                      has_bias=has_bias) <= budget


def enumerate_candidates(m: int, k: int, n: int, *, mode: str = "w8a16",
                         x_dtype: str = "bf16", out_dtype: str = "f32",
                         has_bias: bool = True,
                         budget: int = DEFAULT_VMEM_BUDGET
                         ) -> List[TileConfig]:
    """All legal (bm, bn, bk) for a problem, pruned of dominated padding.

    A block strictly larger than the smallest block covering the whole
    dimension only adds padding (same grid extent of 1), so at most one
    such candidate per dimension survives.
    """

    def axis_pool(cands: Sequence[int], size: int, align: int) -> List[int]:
        pool = [c for c in cands if c % align == 0]
        # keep blocks that don't exceed the padded dim, plus the single
        # smallest block that covers the dim entirely
        keep = [c for c in pool if c < _round_up(size, align) * 2]
        covering = [c for c in pool if c >= size]
        if covering and min(covering) not in keep:
            keep.append(min(covering))
        return sorted(set(keep)) or [min(pool)]

    bms = axis_pool(BM_CANDIDATES, m, _bm_align(mode, x_dtype, out_dtype))
    bns = axis_pool(BN_CANDIDATES, n, LANE)
    bk_align = 256 if mode == "w8a8" else LANE
    bks = axis_pool(BK_CANDIDATES, k, bk_align)
    out = []
    for bm in bms:
        for bn in bns:
            for bk in bks:
                cfg = TileConfig(bm, bn, bk)
                if is_legal(cfg, mode=mode, x_dtype=x_dtype,
                            out_dtype=out_dtype, has_bias=has_bias,
                            budget=budget):
                    out.append(cfg)
    return out


# ---------------------------------------------------------------------------
# Analytic cost model
# ---------------------------------------------------------------------------

def predicted_cost(m: int, k: int, n: int, cfg: TileConfig, *,
                   mode: str = "w8a16", x_dtype: str = "bf16",
                   out_dtype: str = "f32",
                   hw: KernelHW = KernelHW()) -> float:
    """Modelled seconds for one kernel launch at this tile config.

    flops/bytes accounting mirrors ``core.hlo_cost``: the *padded* problem
    is what executes, x tiles are re-streamed once per N-tile column, w
    tiles once per M-tile row, and the roofline max of compute vs memory
    time plus a per-grid-step overhead ranks the candidates.
    """
    xd = x_dtype_for(mode, x_dtype)
    mp = _round_up(m, cfg.bm)
    kp = _round_up(k, cfg.bk)
    np_ = _round_up(n, cfg.bn)
    gi, gj, gk = mp // cfg.bm, np_ // cfg.bn, kp // cfg.bk

    flops = 2.0 * mp * kp * np_
    peak = hw.peak_flops * (hw.int8_speedup if mode == "w8a8" else 1.0)
    flop_time = flops / peak

    x_bytes = mp * kp * DTYPE_BYTES[xd] * gj        # x streamed per N tile
    w_bytes = kp * np_ * DTYPE_BYTES["int8"] * gi   # w streamed per M tile
    s_bytes = np_ * 4 * gi                          # col scales per M tile
    o_bytes = mp * np_ * DTYPE_BYTES[out_dtype]
    mem_time = (x_bytes + w_bytes + s_bytes + o_bytes) / hw.hbm_bw

    return max(flop_time, mem_time) + gi * gj * gk * hw.grid_step_s


def rank_candidates(m: int, k: int, n: int, *, mode: str = "w8a16",
                    x_dtype: str = "bf16", out_dtype: str = "f32",
                    has_bias: bool = True,
                    budget: int = DEFAULT_VMEM_BUDGET,
                    hw: KernelHW = KernelHW()) -> List[TileConfig]:
    """Legal candidates sorted best-first by the analytic model."""
    cands = enumerate_candidates(m, k, n, mode=mode, x_dtype=x_dtype,
                                 out_dtype=out_dtype, has_bias=has_bias,
                                 budget=budget)
    if not cands:
        raise ValueError(
            f"no legal tile config for {(m, k, n)} mode={mode} under "
            f"budget {budget}")
    return sorted(cands, key=lambda c: predicted_cost(
        m, k, n, c, mode=mode, x_dtype=x_dtype, out_dtype=out_dtype,
        hw=hw))


# ---------------------------------------------------------------------------
# Persistent JSON cache
# ---------------------------------------------------------------------------

SCHEMA_VERSION = 1


def default_cache_path() -> str:
    """``REPRO_AUTOTUNE_CACHE`` when set, else "" (an in-memory cache:
    nothing is read from or written to disk)."""
    return os.path.expanduser(os.environ.get("REPRO_AUTOTUNE_CACHE", ""))


class AutotuneCache:
    """JSON-backed (shape, mode, dtype, backend) -> TileConfig store.

    Concurrency discipline (same as ``checkpoint/manager.py``): every
    write goes to a same-directory ``*.tmp`` that is flushed, fsync'd and
    ``os.replace``d into place, so a reader can never observe a torn
    file.  Before replacing, the entries already on disk are re-read and
    merged, under the in-process lock plus a best-effort ``flock`` on a
    ``.lock`` sidecar — so two bench processes tuning different shapes
    keep each other's winners (last writer wins only on identical keys;
    where ``flock`` is unavailable the merge still narrows the lost-
    update window to the read-merge-replace itself).  A torn or
    stale-schema file on disk is discarded, not fatal — the analytic
    model refills it.  Writes are tolerated to fail on read-only
    filesystems; the cache is an accelerator, not a dependency.
    ``AutotuneCache(path="")`` gives a purely in-memory cache (tests).
    """

    def __init__(self, path: Optional[str] = None):
        self.path = default_cache_path() if path is None else path
        self._lock = threading.Lock()
        self._entries: Optional[Dict[str, dict]] = None

    @staticmethod
    def key(m: int, k: int, n: int, mode: str, x_dtype: str,
            out_dtype: str, has_bias: bool, backend: str) -> str:
        bias = "bias" if has_bias else "nobias"
        return f"{m}x{k}x{n}|{mode}|{x_dtype}>{out_dtype}|{bias}|{backend}"

    def _read_disk(self) -> Dict[str, dict]:
        """Entries currently on disk; {} for missing/torn/stale files."""
        try:
            with open(self.path) as f:
                data = json.load(f)
            if data.get("schema_version") == SCHEMA_VERSION:
                return dict(data.get("entries", {}))
        except (OSError, ValueError):
            pass
        return {}

    def _load(self) -> Dict[str, dict]:
        if self._entries is None:
            self._entries = self._read_disk() if self.path else {}
        return self._entries

    def get(self, key: str) -> Optional[TileConfig]:
        with self._lock:
            e = self._load().get(key)
        if not e:
            return None
        return TileConfig(int(e["bm"]), int(e["bn"]), int(e["bk"]))

    def put(self, key: str, cfg: TileConfig, source: str = "analytic"
            ) -> None:
        with self._lock:
            entries = self._load()
            entries[key] = {"bm": cfg.bm, "bn": cfg.bn, "bk": cfg.bk,
                            "source": source}
            if not self.path:               # in-memory only
                return
            try:
                d = os.path.dirname(self.path)
                if d:
                    os.makedirs(d, exist_ok=True)
                lockf = None
                try:                         # cross-PROCESS exclusion
                    import fcntl
                    lf = open(self.path + ".lock", "w")
                    try:
                        fcntl.flock(lf, fcntl.LOCK_EX)
                        lockf = lf
                    except OSError:          # e.g. ENOLCK on NFS
                        lf.close()
                except (ImportError, OSError):
                    pass                     # best-effort: merge below
                try:
                    # merge whatever landed on disk since we loaded, so
                    # a concurrent bench run's winners survive this write
                    merged = self._read_disk()
                    merged.update(entries)
                    self._entries = entries = merged
                    fd, tmp = tempfile.mkstemp(dir=d or ".", suffix=".tmp")
                    with os.fdopen(fd, "w") as f:
                        json.dump({"schema_version": SCHEMA_VERSION,
                                   "entries": entries}, f, indent=1)
                        f.flush()
                        os.fsync(f.fileno())
                    os.replace(tmp, self.path)
                finally:
                    if lockf is not None:
                        lockf.close()        # releases the flock
            except OSError:
                pass                         # read-only fs: stay in-memory


_default_cache: Optional[AutotuneCache] = None


def get_cache() -> AutotuneCache:
    global _default_cache
    if _default_cache is None:
        _default_cache = AutotuneCache()
    return _default_cache


# ---------------------------------------------------------------------------
# Timing backend (the explicit measured-tuning step, on a chip)
# ---------------------------------------------------------------------------

def measure_config(m: int, k: int, n: int, cfg: TileConfig, *,
                   mode: str = "w8a16", iters: int = 5) -> float:
    """Wall-clock one kernel launch at this config (compiled backends only;
    interpret-mode timings are meaningless).  Returns seconds/call.  Pass
    ``measure=lambda c: measure_config(m, k, n, c, mode=mode)`` to
    :func:`best_config` with a file-backed cache to tune explicitly."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.kernels import qmatmul as _k

    mp = _round_up(m, cfg.bm)
    kp = _round_up(k, cfg.bk)
    np_ = _round_up(n, cfg.bn)
    key = jax.random.PRNGKey(0)
    ws = jnp.ones((np_,), jnp.float32)
    w = jax.random.randint(key, (kp, np_), -127, 127, jnp.int8)
    if mode == "w8a8":
        x = jax.random.randint(jax.random.fold_in(key, 1), (mp, kp),
                               -127, 127, jnp.int8)
        fn = lambda: _k.qmatmul_w8a8(x, w, jnp.ones((), jnp.float32), ws,
                                     None, **cfg.as_kwargs())
    else:
        x = jax.random.normal(jax.random.fold_in(key, 1), (mp, kp),
                              jnp.float32).astype(jnp.bfloat16)
        fn = lambda: _k.qmatmul_w8a16(x, w, ws, None, **cfg.as_kwargs())
    fn().block_until_ready()                 # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def best_config(m: int, k: int, n: int, *, mode: str = "w8a16",
                x_dtype: str = "bf16", out_dtype: str = "f32",
                has_bias: bool = True,
                budget: int = DEFAULT_VMEM_BUDGET,
                backend: Optional[str] = None,
                measure: Optional[Callable[[TileConfig], float]] = None,
                top_k_measure: int = 4,
                cache: Optional[AutotuneCache] = None,
                hw: KernelHW = KernelHW()) -> TileConfig:
    """Tuned (bm, bn, bk) for a problem; cached per (shape, mode, dtype,
    backend).

    Without ``measure`` the analytic ranking decides alone — what the
    serving path does, so a run's tiles never depend on its timings.
    ``measure`` (``config -> seconds``) re-ranks the top
    ``top_k_measure`` analytic candidates by measurement: the explicit
    tuning step.  A candidate the compiler refuses raises.
    """
    if backend is None:
        import jax
        backend = jax.default_backend()
    cache = cache or get_cache()
    key = AutotuneCache.key(m, k, n, mode, x_dtype, out_dtype, has_bias,
                            backend)
    hit = cache.get(key)
    if hit is not None:
        return hit

    ranked = rank_candidates(m, k, n, mode=mode, x_dtype=x_dtype,
                             out_dtype=out_dtype, has_bias=has_bias,
                             budget=budget, hw=hw)
    source = "analytic"
    winner = ranked[0]
    if measure is not None:
        timed = [(measure(c), c) for c in ranked[:top_k_measure]]
        winner = min(timed, key=lambda t: t[0])[1]
        source = "measured"
    cache.put(key, winner, source=source)
    return winner


def arch_matmul_problems(cfg, m: int) -> List[Tuple[str, int, int, int]]:
    """The serving-path matmul problems of an ArchConfig at row count m.

    Rows are (name, M, K, N) — the projections every decode/prefill step
    runs through ``qlinear.linear``.  Used by the registry-wide budget
    tests and the bench's chosen-tiles report.
    """
    d = cfg.d_model
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rows = [
        ("wq", m, d, h * hd),
        ("wk", m, d, kv * hd),
        ("wv", m, d, kv * hd),
        ("wo", m, h * hd, d),
        ("w_up", m, d, cfg.d_ff),
        ("w_down", m, cfg.d_ff, d),
        ("unembed", m, d, cfg.vocab),
    ]
    if cfg.gated_mlp:
        rows.insert(5, ("w_gate", m, d, cfg.d_ff))
    return rows


def tune_arch(cfg, *, m_values: Sequence[int] = (8, 32, 128),
              modes: Sequence[str] = ("w8a16", "w8a8"),
              budget: int = DEFAULT_VMEM_BUDGET,
              cache: Optional[AutotuneCache] = None) -> List[dict]:
    """Tune every serving matmul of an arch at several decode/prefill row
    counts.  Returns report rows (consumed by benchmarks and tests)."""
    out = []
    for m in m_values:
        for name, mm, kk, nn in arch_matmul_problems(cfg, m):
            for mode in modes:
                # production serving dtypes: bf16 activations in and out
                tc = best_config(mm, kk, nn, mode=mode, x_dtype="bf16",
                                 out_dtype="bf16", budget=budget,
                                 cache=cache)
                out.append({
                    "op": name, "arch": cfg.name, "m": mm, "k": kk, "n": nn,
                    "mode": mode, "bm": tc.bm, "bn": tc.bn, "bk": tc.bk,
                    "vmem_bytes": vmem_bytes(tc, mode=mode,
                                             out_dtype="bf16"),
                })
    return out
