"""Overload robustness: SLO-class admission, preemption with exact
resume, fault injection + recovery, and the typed retirement statuses.

The correctness bar everywhere is the engine's usual one — bit-for-bit
parity with the sequential per-token reference — now required to hold
*through* evictions, re-admissions, and injected faults."""
import dataclasses
import warnings

import jax
import pytest
from hypothesis import given, settings, strategies as st

from repro import engine as E
from repro.configs import get_config
from repro.core import batching as bt
from repro.engine.faults import FAULT_KINDS, Fault, FaultPlan
from repro.models import registry as R

KEY = jax.random.PRNGKey(0)


def _cfg():
    cfg = get_config("starcoder2-3b").reduced()
    return dataclasses.replace(cfg, kv_quant=True)


@pytest.fixture(scope="module")
def dense_setup():
    cfg = _cfg()
    return cfg, R.init(KEY, cfg)


@pytest.fixture(scope="module")
def trace(dense_setup):
    """A short two-class trace plus its sequential reference outputs."""
    cfg, params = dense_setup
    reqs = E.synthetic_requests(
        10, rate_per_s=2000.0, vocab=cfg.vocab, prompt_len=3,
        max_new_tokens=5,
        priority=lambda rid: "batch" if rid % 3 == 0 else "interactive")
    want = E.reference_outputs(cfg, params, reqs, max_seq=16)
    return reqs, want


# ---------------------------------------------------------------------------
# SLO-class admission
# ---------------------------------------------------------------------------

class TestClassAdmission:
    def test_scheduler_orders_class_first(self):
        def req(rid, deadline, cls):
            return E.EngineRequest(rid=rid, prompt=(1,), max_new_tokens=1,
                                   arrival_s=0.0, deadline_s=deadline,
                                   priority=cls)

        sched = E.SlotScheduler(bt.AdmissionPolicy(lambda b: 0.0,
                                                   max_batch=8))
        sched.push(req(0, 5.0, "batch"))
        sched.push(req(1, 9.0, "interactive"))
        sched.push(req(2, 1.0, "batch"))
        sched.push(req(3, 2.0, "interactive"))
        # interactive (rank 0) ahead of batch, deadline order within class
        assert [r.rid for r in sched.pending] == [3, 1, 2, 0]

    def test_quota_skips_over_blocked_class(self):
        policy = bt.AdmissionPolicy(lambda b: 0.0, max_batch=4,
                                    max_wait_s=0.0,
                                    class_quotas={"batch": 1})
        act = policy.decide(0.0, [1.0, 2.0, 3.0], capacity=3,
                            classes=["batch", "batch", "interactive"],
                            active_by_class={"batch": 1})
        # batch quota already consumed by an active slot: both pending
        # batch requests are skipped, the later interactive one admits
        assert act.launch and act.picks == (2,)

    def test_no_quota_no_classes_is_legacy_path(self):
        policy = bt.AdmissionPolicy(lambda b: 0.0, max_batch=4,
                                    max_wait_s=0.0)
        act = policy.decide(0.0, [1.0, 2.0], capacity=4)
        assert act.launch and act.batch == 2 and act.picks is None

    def test_unknown_class_ranks_last(self):
        assert bt.priority_rank("interactive") == 0
        assert bt.priority_rank("batch") == 1
        assert bt.priority_rank("mystery") == len(bt.PRIORITY_CLASSES)

    def test_quota_serve_parity(self, dense_setup, trace):
        """Quota-constrained admission reorders *when* requests run, but
        never what they produce."""
        cfg, params = dense_setup
        reqs, want = trace
        policy = bt.AdmissionPolicy(lambda b: 0.0, max_batch=4,
                                    max_wait_s=0.0,
                                    class_quotas={"batch": 1})
        eng = E.Engine(cfg, params, num_slots=4, max_seq=16,
                       prefill_chunk=2, policy=policy)
        rep = eng.serve(reqs, clock="virtual", tick_s=1e-3)
        assert rep.outputs() == want
        # batch never held more than its quota of slots at once
        assert all(r.status == "ok" for r in rep.results)


# ---------------------------------------------------------------------------
# preemption with exact resume
# ---------------------------------------------------------------------------

class TestPreemption:
    def test_block_pressure_preempts_and_resumes_exactly(
            self, dense_setup, trace):
        """A pool too small for the worst-case concurrent claim forces
        evictions; every resumed request is bit-for-bit its
        never-preempted self and the pool drains clean."""
        cfg, params = dense_setup
        reqs, want = trace
        eng = E.Engine(cfg, params, num_slots=4, max_seq=16,
                       prefill_chunk=2, block_size=4, num_blocks=9)
        rep = eng.serve(reqs, clock="virtual", tick_s=1e-3,
                        preemption=True)
        assert rep.outputs() == want
        assert rep.preempted > 0
        assert rep.leaked_blocks == 0
        assert any(r.preemptions > 0 for r in rep.results)

    def test_uniform_class_never_preempts(self, dense_setup):
        """Preemption only evicts a *strictly* lower class than the
        waiting head: a single-class trace can never preempt, with the
        flag on and resources ample."""
        cfg, params = dense_setup
        reqs = E.synthetic_requests(10, rate_per_s=2000.0,
                                    vocab=cfg.vocab, prompt_len=3,
                                    max_new_tokens=5)
        want = E.reference_outputs(cfg, params, reqs, max_seq=16)
        eng = E.Engine(cfg, params, num_slots=4, max_seq=16,
                       prefill_chunk=2, block_size=4)
        rep = eng.serve(reqs, clock="virtual", tick_s=1e-3,
                        preemption=True)
        assert rep.outputs() == want
        assert rep.preempted == 0 and rep.leaked_blocks == 0

    def test_sampled_resume_parity(self, dense_setup):
        """Position-derived sampling keys make resume exact for sampled
        decoding too, not just greedy."""
        cfg, params = dense_setup
        rng = jax.random.PRNGKey(7)
        reqs = E.synthetic_requests(
            8, rate_per_s=2000.0, vocab=cfg.vocab, prompt_len=3,
            max_new_tokens=4,
            priority=lambda rid: "batch" if rid % 2 else "interactive")
        want = E.reference_outputs(cfg, params, reqs, max_seq=16,
                                   temperature=0.8, rng=rng)
        eng = E.Engine(cfg, params, num_slots=4, max_seq=16,
                       prefill_chunk=2, block_size=4, num_blocks=9,
                       temperature=0.8, rng=rng)
        rep = eng.serve(reqs, clock="virtual", tick_s=1e-3,
                        preemption=True)
        assert rep.outputs() == want
        assert rep.preempted > 0 and rep.leaked_blocks == 0


# ---------------------------------------------------------------------------
# fault injection + recovery
# ---------------------------------------------------------------------------

class TestFaultInjection:
    def test_plan_is_deterministic_and_validated(self):
        a = FaultPlan.random(3, n_faults=6, num_slots=4)
        b = FaultPlan.random(3, n_faults=6, num_slots=4)
        assert a.faults == b.faults
        assert all(f.kind in FAULT_KINDS for f in a.faults)
        with pytest.raises(ValueError):
            Fault(tick=1, kind="meteor")
        with pytest.raises(ValueError):
            Fault(tick=-1, kind="dispatch")

    def test_transient_dispatch_fault_retries_to_parity(
            self, dense_setup, trace):
        cfg, params = dense_setup
        reqs, want = trace
        plan = FaultPlan([Fault(tick=4, kind="dispatch", slot=0,
                                repeat=2)])
        eng = E.Engine(cfg, params, num_slots=4, max_seq=16,
                       prefill_chunk=2)
        rep = eng.serve(reqs, clock="virtual", tick_s=1e-3,
                        preemption=True, fault_plan=plan)
        assert rep.outputs() == want
        assert rep.dispatch_retries == 2 and rep.failed == 0

    def test_persistent_dispatch_fault_fails_only_the_culprit(
            self, dense_setup, trace):
        cfg, params = dense_setup
        reqs, want = trace
        plan = FaultPlan([Fault(tick=4, kind="dispatch", slot=1,
                                repeat=99)])
        eng = E.Engine(cfg, params, num_slots=4, max_seq=16,
                       prefill_chunk=2)
        rep = eng.serve(reqs, clock="virtual", tick_s=1e-3,
                        preemption=True, fault_plan=plan,
                        max_retries=2)
        failed = [r for r in rep.results if r.status == "failed"]
        assert len(failed) == 1
        ok = {r.rid: r.tokens for r in rep.results if r.status == "ok"}
        assert all(ok[rid] == want[rid] for rid in ok)

    def test_nan_logits_recover_bitwise(self, dense_setup, trace):
        """A transient non-finite sample preempts the victim; the resume
        recomputes clean state and the output heals bit-for-bit."""
        cfg, params = dense_setup
        reqs, want = trace
        plan = FaultPlan([Fault(tick=5, kind="nan_logits", slot=2)])
        eng = E.Engine(cfg, params, num_slots=4, max_seq=16,
                       prefill_chunk=2)
        rep = eng.serve(reqs, clock="virtual", tick_s=1e-3,
                        preemption=True, fault_plan=plan)
        assert rep.outputs() == want
        assert rep.nonfinite_samples >= 1 and rep.failed == 0

    def test_torn_table_row_repaired_from_host_mirror(
            self, dense_setup, trace):
        cfg, params = dense_setup
        reqs, want = trace
        plan = FaultPlan([Fault(tick=5, kind="torn_table", slot=0)])
        eng = E.Engine(cfg, params, num_slots=4, max_seq=16,
                       prefill_chunk=2, block_size=4)
        rep = eng.serve(reqs, clock="virtual", tick_s=1e-3,
                        preemption=True, fault_plan=plan)
        assert rep.outputs() == want
        assert rep.torn_rows_repaired >= 1
        assert rep.leaked_blocks == 0


# ---------------------------------------------------------------------------
# typed retirement statuses + scheduler guards
# ---------------------------------------------------------------------------

class TestTypedStatuses:
    def test_tick_cap_retires_unfinished_with_warning(
            self, dense_setup, trace):
        cfg, params = dense_setup
        reqs, _ = trace
        eng = E.Engine(cfg, params, num_slots=2, max_seq=16)
        with pytest.warns(RuntimeWarning, match="tick cap"):
            rep = eng.serve(reqs, clock="virtual", tick_s=1e-3,
                            max_ticks=6)
        # nothing lost, nothing silently reported as served
        assert len(rep.results) == len(reqs)
        assert rep.unfinished > 0
        assert {r.status for r in rep.results} <= {"ok", "unfinished"}
        assert sum(r.status == "unfinished" for r in rep.results) == \
            rep.unfinished

    def test_every_request_retires_exactly_once(self, dense_setup, trace):
        cfg, params = dense_setup
        reqs, _ = trace
        plan = FaultPlan.random(5, n_faults=6, max_tick=60, num_slots=4)
        eng = E.Engine(cfg, params, num_slots=4, max_seq=16,
                       prefill_chunk=2, block_size=4, num_blocks=9)
        rep = eng.serve(reqs, clock="virtual", tick_s=1e-3,
                        preemption=True, fault_plan=plan)
        assert sorted(r.rid for r in rep.results) == \
            sorted(r.rid for r in reqs)

    def test_run_virtual_guards_stalled_policy(self):
        """A policy that declines a non-empty queue after the last
        arrival must surface as a clear error, not a None TypeError."""
        class Never(bt.AdmissionPolicy):
            def decide(self, *a, **k):
                return bt.Admission(False, wait_until=None)

        sched = E.SlotScheduler(Never(lambda b: 0.0, max_batch=4))
        reqs = [bt.Request(0.0, 1.0, 0)]
        with pytest.raises(RuntimeError, match="declined"):
            sched.run_virtual(reqs)


# ---------------------------------------------------------------------------
# per-class metrics + goodput
# ---------------------------------------------------------------------------

def test_per_class_metrics_and_goodput(dense_setup, trace):
    cfg, params = dense_setup
    reqs, _ = trace
    eng = E.Engine(cfg, params, num_slots=4, max_seq=16, prefill_chunk=2)
    rep = eng.serve(reqs, clock="virtual", tick_s=1e-3)
    assert set(rep.class_p99_latency_s) == {"interactive", "batch"}
    assert set(rep.class_mean_ttft_s) == {"interactive", "batch"}
    assert set(rep.class_p99_ttft_s) == {"interactive", "batch"}
    assert all(v > 0 for v in rep.class_p99_latency_s.values())
    # synthetic deadlines are infinite: everything is goodput
    assert rep.slo_attainment == 1.0
    assert rep.goodput_tokens_per_s == pytest.approx(rep.tokens_per_s)


# ---------------------------------------------------------------------------
# preemption storm: the property test
# ---------------------------------------------------------------------------

_STORM = {}


def _storm_setup():
    """Module-cached engine + trace + reference for the property test
    (the hypothesis shim's @given cannot consume pytest fixtures)."""
    if not _STORM:
        cfg = _cfg()
        params = R.init(KEY, cfg)
        reqs = E.synthetic_requests(
            12, rate_per_s=4000.0, vocab=cfg.vocab, prompt_len=3,
            max_new_tokens=4,
            priority=lambda rid: "batch" if rid % 2 else "interactive")
        want = E.reference_outputs(cfg, params, reqs, max_seq=16)
        eng = E.Engine(cfg, params, num_slots=4, max_seq=16,
                       prefill_chunk=2, block_size=4, num_blocks=9)
        _STORM["setup"] = (eng, reqs, want)
    return _STORM["setup"]


@given(st.integers(0, 1000))
@settings(max_examples=5, deadline=None)
def test_preemption_storm_property(seed):
    """Random fault plans over an under-provisioned pool: refcounts stay
    non-negative (BlockPool raises internally otherwise), the pool
    drains to its initial free count (no leaks), and every non-failed
    output is bit-for-bit the reference."""
    eng, reqs, want = _storm_setup()
    plan = FaultPlan.random(seed, n_faults=8, max_tick=120, num_slots=4)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        rep = eng.serve(reqs, clock="virtual", tick_s=1e-3,
                        preemption=True, fault_plan=plan)
    assert rep.leaked_blocks == 0
    assert sorted(r.rid for r in rep.results) == [r.rid for r in reqs]
    for r in rep.results:
        if r.status == "ok":
            assert r.tokens == want[r.rid], \
                f"rid {r.rid} diverged under fault seed {seed}"
