"""Replica router: the fleet front-end over N engine replicas.

The load-bearing property: the router NEVER routes an admission a
replica's own AdmissionPolicy would reject — every RouteDecision the
router records replays through ``policy.decide`` on exactly the
projected state the router consulted, and launches.  Plus: routed
serving is bit-for-bit the single-engine reference, refusals are typed
(never silent), plans are deterministic, and the report rolls up
per-replica accounting."""
import dataclasses

import jax
import pytest
from hypothesis import given, settings, strategies as st

from repro import engine as E
from repro.configs import get_config
from repro.core import batching as bt
from repro.models import registry as R

KEY = jax.random.PRNGKey(0)


def _cfg():
    return dataclasses.replace(get_config("starcoder2-3b").reduced(),
                               kv_quant=True)


@pytest.fixture(scope="module")
def dense_setup():
    cfg = _cfg()
    return cfg, R.init(KEY, cfg)


def _replicas(cfg, params, n=2, *, policy=None, num_slots=4, **kw):
    return [E.Engine(cfg, params, num_slots=num_slots, max_seq=16,
                     prefill_chunk=2,
                     policy=policy() if policy else None, **kw)
            for _ in range(n)]


# ---------------------------------------------------------------------------
# construction
# ---------------------------------------------------------------------------

def test_router_validates_construction(dense_setup):
    cfg, params = dense_setup
    with pytest.raises(ValueError, match="at least one"):
        E.ReplicaRouter([])
    engines = _replicas(cfg, params, 2)
    with pytest.raises(ValueError, match="names"):
        E.ReplicaRouter(_replicas(cfg, params, 2), names=["only-one"])
    with pytest.raises(ValueError, match="unique"):
        E.ReplicaRouter(_replicas(cfg, params, 2), names=["r", "r"])
    rt = E.ReplicaRouter(engines)
    assert rt.names == ["replica0", "replica1"]
    assert [e.name for e in engines] == rt.names   # names stick


def test_router_rejects_mismatched_lane_sets(dense_setup):
    cfg, params = dense_setup
    single = E.Engine(cfg, params, num_slots=4, max_seq=16)
    multi = E.Engine(models={"a": (cfg, params)}, num_slots=4, max_seq=16)
    with pytest.raises(ValueError, match="same model lanes"):
        E.ReplicaRouter([single, multi])


# ---------------------------------------------------------------------------
# the admission property
# ---------------------------------------------------------------------------

class TestRouterAdmissionProperty:
    @given(st.integers(0, 30), st.sampled_from([500.0, 5000.0, 50000.0]),
           st.sampled_from([None, 1, 2]))
    @settings(max_examples=10, deadline=None)
    def test_router_never_overrides_replica_policy(self, seed, rate,
                                                   batch_quota):
        """Replay every RouteDecision through the target replica's own
        AdmissionPolicy on exactly the projected state the router
        consulted: the policy must launch.  Quotas, capacity, and SLO
        classes all flow through ``decide`` — the router is a
        placement layer, never an admission override."""
        cfg = _cfg()
        quotas = ({"batch": batch_quota} if batch_quota is not None
                  else None)
        policies = [bt.AdmissionPolicy(lambda b: 1e-4, max_batch=4,
                                       max_wait_s=0.0,
                                       class_quotas=quotas)
                    for _ in range(2)]
        # route() needs engine shape + policy, not device state: a
        # light stand-in keeps 30 hypothesis examples cheap
        reps = [_FakeEngine(num_slots=4, policy=p) for p in policies]
        rt = E.ReplicaRouter.__new__(E.ReplicaRouter)
        rt.engines, rt.names = reps, ["r0", "r1"]
        reqs = E.synthetic_requests(
            40, rate_per_s=rate, vocab=256, prompt_len=3,
            max_new_tokens=4, seed=seed,
            priority=lambda rid: "batch" if rid % 2 else "interactive")
        plan = rt.route(reqs)
        by_rid = {r.rid: r for r in reqs}
        assert plan.decisions            # something was actually routed
        for dec in plan.decisions:
            r = by_rid[dec.rid]
            eng = reps[rt.names.index(dec.replica)]
            key = r.priority             # single-model engines
            act = eng.policy.decide(
                dec.now, [r.deadline_s], next_arrival=None,
                capacity=dec.capacity, classes=[key],
                active_by_class=dict(dec.active_by_class))
            assert act.launch and act.batch >= 1, (
                f"router admitted rid {dec.rid} on {dec.replica} where "
                f"its policy refuses: {dec}")
        # conservation: every request is assigned exactly once or refused
        routed = [r.rid for sub in plan.assignments.values() for r in sub]
        assert sorted(routed + [r.rid for r in plan.refused]) == \
            sorted(by_rid)


class _FakeEngine:
    """Just enough Engine surface for ReplicaRouter.route: the
    projection consults num_slots, multi, lanes, and policy only."""
    multi = False
    lanes = {None: None}
    name = None

    def __init__(self, *, num_slots, policy):
        self.num_slots = num_slots
        self.policy = policy


def test_hard_capped_quota_refuses_typed(dense_setup):
    """A class whose quota is zero on EVERY replica is permanently
    unroutable: route() returns it in ``refused`` (bounded — no
    spinning on the projection clock) and serve() synthesizes a typed
    ``refused`` result, never a silent drop."""
    cfg, params = dense_setup
    mk = lambda: bt.AdmissionPolicy(lambda b: 0.0, max_batch=4,
                                    max_wait_s=0.0,
                                    class_quotas={"batch": 0})
    rt = E.ReplicaRouter(_replicas(cfg, params, 2, policy=mk))
    reqs = E.synthetic_requests(
        8, rate_per_s=2000.0, vocab=cfg.vocab, prompt_len=3,
        max_new_tokens=4,
        priority=lambda rid: "batch" if rid % 2 else "interactive")
    plan = rt.route(reqs)
    assert {r.rid for r in plan.refused} == \
        {r.rid for r in reqs if r.priority == "batch"}
    rep = rt.serve(reqs, tick_s=1e-3)
    assert len(rep.results) == len(reqs)          # nothing lost
    statuses = {r.rid: r.status for r in rep.results}
    for r in reqs:
        want = "refused" if r.priority == "batch" else "ok"
        assert statuses[r.rid] == want
    assert rep.refused == len(plan.refused)
    ref = [r for r in rep.results if r.status == "refused"]
    assert all(r.tokens == [] and r.slot == -1 for r in ref)


# ---------------------------------------------------------------------------
# routed serving
# ---------------------------------------------------------------------------

def test_routed_outputs_match_reference_and_balance(dense_setup):
    """2 replicas, one hot trace: routed outputs are bit-for-bit the
    sequential reference (replicas share no state, so placement cannot
    change bits), both replicas take work, and the rollup report's
    accounting is consistent."""
    cfg, params = dense_setup
    rt = E.ReplicaRouter(_replicas(cfg, params, 2))
    reqs = E.synthetic_requests(40, rate_per_s=20000.0, vocab=cfg.vocab,
                                prompt_len=4, max_new_tokens=5)
    rep = rt.serve(reqs, tick_s=1e-3)
    want = E.reference_outputs(cfg, params, reqs, max_seq=16)
    assert {r.rid: r.tokens for r in rep.results
            if r.status == "ok"} == want
    assert rep.refused == 0
    assert min(rep.replica_requests.values()) > 0   # nobody starved
    assert sum(rep.replica_requests.values()) == len(reqs)
    assert set(rep.replicas) == {"replica0", "replica1"}
    assert rep.generated_tokens == sum(
        r.generated_tokens for r in rep.replicas.values())
    assert rep.duration_s == max(
        r.duration_s for r in rep.replicas.values())
    assert rep.leaked_blocks == 0
    assert rep.outputs() == {r.rid: r.tokens for r in rep.results}


def test_route_plan_is_deterministic(dense_setup):
    cfg, params = dense_setup
    reqs = E.synthetic_requests(30, rate_per_s=20000.0, vocab=cfg.vocab,
                                prompt_len=4, max_new_tokens=5)
    plans = [E.ReplicaRouter(_replicas(cfg, params, 3)).route(reqs)
             for _ in range(2)]
    a, b = plans
    assert {n: [r.rid for r in sub] for n, sub in a.assignments.items()} \
        == {n: [r.rid for r in sub] for n, sub in b.assignments.items()}
    assert [d.rid for d in a.decisions] == [d.rid for d in b.decisions]
    assert [d.replica for d in a.decisions] == \
        [d.replica for d in b.decisions]
