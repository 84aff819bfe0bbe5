"""Dispatch core + executor backends: backend-interface conformance
(both executors serve the same trace bit-for-bit through the same
DispatchCore), model hot-swap on a live engine (admit/retire with
drain + typed refusal), per-lane tick pricing, and the named watchdog.

The sharded executor runs here at tp=1 (a 1-device mesh), which pins
the interface and the shard_map plumbing in-process; the real
multi-device parity gates live in tests/test_sharded.py behind a
forced multi-device CPU mesh in a subprocess."""
import dataclasses

import jax
import pytest

from repro import engine as E
from repro.configs import get_config
from repro.models import registry as R
from repro.runtime.watchdog import StepWatchdog

KEY = jax.random.PRNGKey(0)


def _cfg(arch="starcoder2-3b", seed=0):
    cfg = dataclasses.replace(get_config(arch).reduced(), kv_quant=True)
    return cfg, R.init(jax.random.PRNGKey(seed), cfg)


@pytest.fixture(scope="module")
def dense_setup():
    return _cfg("starcoder2-3b", 0)


@pytest.fixture(scope="module")
def moe_setup():
    return _cfg("qwen2-moe-a2.7b", 1)


def _trace(tag, cfg, n, *, seed, rid_offset=0, shift=0.0):
    reqs = E.synthetic_requests(n, rate_per_s=2000.0, vocab=cfg.vocab,
                                prompt_len=4, max_new_tokens=5, seed=seed,
                                model=tag)
    return [dataclasses.replace(r, rid=r.rid + rid_offset,
                                arrival_s=r.arrival_s + shift)
            for r in reqs]


# ---------------------------------------------------------------------------
# backend interface conformance
# ---------------------------------------------------------------------------

def test_abstract_backend_provides_no_steps(dense_setup):
    """The base ExecutorBackend is an interface: every step provider
    must raise, and validate() must accept anything (it is the hook,
    not a gate, at this level)."""
    cfg, _ = dense_setup
    from repro.core.qlinear import W8A16
    b = E.ExecutorBackend()
    assert b.kind == "abstract" and b.tp == 1
    b.validate(object())               # no-op on the base class
    with pytest.raises(NotImplementedError):
        b.slot_step(cfg, mode=W8A16, temperature=0.0)
    with pytest.raises(NotImplementedError):
        b.chunk_step(cfg, mode=W8A16, chunk=4)
    with pytest.raises(NotImplementedError):
        b.prime_step(cfg, mode=W8A16)
    with pytest.raises(NotImplementedError):
        b.verify_step(cfg, mode=W8A16, k=2, temperature=0.0)
    with pytest.raises(NotImplementedError):
        b.propose_step(cfg, mode=W8A16, k=2)


def test_sharded_executor_rejects_bad_tp():
    with pytest.raises(ValueError, match="tp must be >= 1"):
        E.ShardedExecutor(tp=0)
    ndev = len(jax.devices())
    with pytest.raises(ValueError, match="exceeds"):
        E.ShardedExecutor(tp=ndev + 1)


def test_sharded_executor_validates_slot_divisibility(dense_setup):
    """A slot pool that does not divide across the mesh is rejected at
    Engine construction, before any step compiles."""
    cfg, params = dense_setup
    b = E.ShardedExecutor(tp=1)
    b.tp = 3                           # a mesh width 4 slots can't fill
    with pytest.raises(ValueError, match="must divide"):
        E.Engine(cfg, params, num_slots=4, max_seq=16, backend=b)


_COMPILES = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, secs, **kw: _COMPILES.append(event)
    if event == "/jax/core/compile/backend_compile_duration" else None)


@pytest.mark.parametrize("backend", ["default", "pinned", "sharded"])
def test_warmup_compiles_everything_serve_runs(dense_setup, backend):
    """Each backend lays its params, caches and pushed block tables out
    the way its steps take them, so after ``warmup`` a paged serve —
    fresh cache, step outputs, table pushes — compiles nothing.  A
    pinned replica or a sharded engine whose inputs changed layout from
    one tick to the next would recompile its step mid-serve."""
    cfg, params = dense_setup
    be = {"default": None,
          "pinned": E.SingleDeviceExecutor(device=jax.devices()[0]),
          "sharded": E.ShardedExecutor(tp=1)}[backend]
    eng = E.Engine(cfg, params, num_slots=4, max_seq=16, block_size=4,
                   backend=be)
    eng.warmup()
    before = len(_COMPILES)
    rep = eng.serve(E.synthetic_requests(
        8, rate_per_s=2000.0, vocab=cfg.vocab, prompt_len=4,
        max_new_tokens=5))
    assert len(rep.results) == 8
    assert len(_COMPILES) == before


def test_backends_are_bitwise_interchangeable(dense_setup):
    """The conformance gate: the same engine shape served through the
    SingleDeviceExecutor and through a ShardedExecutor(tp=1) produces
    bit-identical outputs and identical accounting — the backend seam
    carries steps, not behavior."""
    cfg, params = dense_setup
    reqs = E.synthetic_requests(16, rate_per_s=2000.0, vocab=cfg.vocab,
                                prompt_len=4, max_new_tokens=5)
    kw = dict(num_slots=4, max_seq=16, prefill_chunk=2, block_size=4)
    single = E.Engine(cfg, params, backend=E.SingleDeviceExecutor(), **kw)
    sharded = E.Engine(cfg, params, backend=E.ShardedExecutor(tp=1), **kw)
    assert single.backend.kind == "single"
    assert sharded.backend.kind == "sharded" and sharded.backend.tp == 1
    r1 = single.serve(reqs, tick_s=1e-3)
    r2 = sharded.serve(reqs, tick_s=1e-3)
    assert r1.outputs() == r2.outputs()
    assert r1.ticks == r2.ticks
    assert r1.leaked_blocks == r2.leaked_blocks == 0
    assert r1.outputs() == E.reference_outputs(cfg, params, reqs,
                                               max_seq=16)


def test_default_backend_is_single_device(dense_setup):
    cfg, params = dense_setup
    eng = E.Engine(cfg, params, num_slots=2, max_seq=16)
    assert isinstance(eng.backend, E.SingleDeviceExecutor)


# ---------------------------------------------------------------------------
# model hot-swap on a live engine
# ---------------------------------------------------------------------------

def test_retire_model_drains_inflight_and_refuses_late(dense_setup,
                                                       moe_setup):
    """retire_model mid-serve: in-flight requests on the retiring lane
    drain to completion with bit-identical outputs, later arrivals for
    that lane get a typed ``refused`` result, the drained lane is
    removed post-serve, and the surviving lane is undisturbed."""
    cfg_a, pa = dense_setup
    cfg_b, pb = moe_setup
    ta = _trace("a", cfg_a, 12, seed=11)
    tb = _trace("b", cfg_b, 12, seed=22, rid_offset=100)
    tb_late = _trace("b", cfg_b, 6, seed=33, rid_offset=200, shift=0.004)
    merged = sorted(ta + tb + tb_late, key=lambda r: r.arrival_s)

    def build():
        return E.Engine(models={"a": (cfg_a, pa), "b": (cfg_b, pb)},
                        num_slots=4, max_seq=16, prefill_chunk=2)

    eng = build()
    rep = eng.serve(merged, tick_s=1e-3,
                    control=[(0.004, lambda e: e.retire_model("b"))])
    assert len(rep.results) == len(merged)     # nothing lost
    ok_b = [r for r in rep.results if r.model == "b" and r.status == "ok"]
    ref_b = [r for r in rep.results
             if r.model == "b" and r.status == "refused"]
    assert ok_b and ref_b
    assert rep.refused == len(ref_b)
    assert all(r.tokens == [] and r.slot == -1 for r in ref_b)
    # the drained lane is gone; the survivor is not
    assert "b" not in eng.lanes and "a" in eng.lanes

    # same trace, no retire: the in-flight b outputs and all of lane a
    # must be bitwise what the control run produced
    base = build().serve(merged, tick_s=1e-3).outputs()
    assert all(base[r.rid] == r.tokens for r in ok_b)
    assert {r.rid: r.tokens for r in rep.results if r.model == "a"} == \
        {r.rid: base[r.rid] for r in ta}


def test_admit_model_joins_live_serve(dense_setup, moe_setup):
    """admit_model mid-serve: a lane admitted by a control op serves
    requests that arrived addressed to it, and its outputs are
    bit-identical to a dedicated engine over the same sub-trace."""
    cfg_a, pa = dense_setup
    cfg_b, pb = moe_setup
    ta = _trace("a", cfg_a, 12, seed=11)
    tc = _trace("c", cfg_b, 6, seed=44, rid_offset=300, shift=0.003)
    merged = sorted(ta + tc, key=lambda r: r.arrival_s)
    eng = E.Engine(models={"a": (cfg_a, pa)}, num_slots=4, max_seq=16,
                   prefill_chunk=2)
    rep = eng.serve(merged, tick_s=1e-3,
                    control=[(0.002,
                              lambda e: e.admit_model("c", cfg_b, pb))])
    okc = [r for r in rep.results if r.model == "c" and r.status == "ok"]
    assert len(okc) == len(tc)
    assert "c" in eng.lanes             # admitted lanes persist
    ded = E.Engine(cfg_b, pb, num_slots=4, max_seq=16, prefill_chunk=2)
    want = ded.serve([dataclasses.replace(r, model=None) for r in tc],
                     tick_s=1e-3).outputs()
    assert {r.rid: r.tokens for r in okc} == want


def test_admit_model_rejects_duplicates_and_single_model(dense_setup,
                                                         moe_setup):
    cfg_a, pa = dense_setup
    cfg_b, pb = moe_setup
    eng = E.Engine(models={"a": (cfg_a, pa)}, num_slots=2, max_seq=16)
    with pytest.raises(ValueError, match="already"):
        eng.admit_model("a", cfg_a, pa)
    single = E.Engine(cfg_a, pa, num_slots=2, max_seq=16)
    with pytest.raises(ValueError):
        single.admit_model("b", cfg_b, pb)


# ---------------------------------------------------------------------------
# per-lane tick pricing
# ---------------------------------------------------------------------------

def test_per_lane_tick_cost_prices_dispatched_lanes(dense_setup,
                                                    moe_setup):
    """A Mapping tick_s prices each tick as the sum of the DISPATCHED
    lanes' costs: outputs are untouched (pricing is pure accounting)
    but the expensive lane stretches the clock."""
    cfg_a, pa = dense_setup
    cfg_b, pb = moe_setup
    merged = sorted(_trace("a", cfg_a, 12, seed=11)
                    + _trace("b", cfg_b, 12, seed=22, rid_offset=100),
                    key=lambda r: r.arrival_s)

    def build():
        return E.Engine(models={"a": (cfg_a, pa), "b": (cfg_b, pb)},
                        num_slots=4, max_seq=16, prefill_chunk=2)

    priced = build().serve(merged, tick_s={"a": 1e-3, "b": 5e-3})
    flat = build().serve(merged, tick_s=1e-3)
    assert priced.outputs() == flat.outputs()
    assert priced.duration_s > flat.duration_s


def test_per_lane_tick_cost_validation(dense_setup, moe_setup):
    cfg_a, pa = dense_setup
    cfg_b, pb = moe_setup
    eng = E.Engine(models={"a": (cfg_a, pa), "b": (cfg_b, pb)},
                   num_slots=2, max_seq=16)
    reqs = _trace("a", cfg_a, 2, seed=1)
    with pytest.raises(ValueError, match="virtual"):
        eng.serve(reqs, clock="wall", tick_s={"a": 1e-3, "b": 1e-3})
    with pytest.raises(ValueError, match="every lane"):
        eng.serve(reqs, tick_s={"a": 1e-3})   # lane b unpriced


# ---------------------------------------------------------------------------
# named watchdog
# ---------------------------------------------------------------------------

def test_watchdog_name_labels_stragglers():
    """A named watchdog prefixes straggler warnings with its replica
    label; an anonymous one keeps the legacy message."""
    def provoke(wd):
        for _ in range(wd.warmup_steps):
            wd.record(1e-3)
        for _ in range(8):
            wd.record(1e-3)
        return wd.record(1.0)
    named = provoke(StepWatchdog(name="replica3"))
    assert named is not None and named.startswith("[replica3] straggler")
    anon = provoke(StepWatchdog())
    assert anon is not None and anon.startswith("straggler")


def test_engine_name_reaches_watchdog(dense_setup):
    cfg, params = dense_setup
    eng = E.Engine(cfg, params, num_slots=2, max_seq=16, name="r0")
    assert eng.name == "r0"
