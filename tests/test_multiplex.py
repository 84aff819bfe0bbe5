"""Multi-model multiplexing: the cross-model differential harness.

One ``Engine(models={...})`` serves interleaved two-model traces; the
gate is that every model lane's outputs are BIT-FOR-BIT the outputs of a
dedicated single-model engine serving only that lane's requests — for
every family pair from {dense, moe, encdec}, greedy AND sampled, with
the paged KV cache, preemption, and fault injection in the loop.  Plus:
cross-model poison isolation (decode-contract rule 8), (model, class)
quota invariants property-tested against the PR-7 single-model
semantics, and golden-trace regressions pinning that the new ``model=``
/ ``models=`` trace knobs move nothing when unset."""
import collections
import dataclasses
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from benchmarks import traces as TR
from repro import engine as E
from repro.configs import get_config
from repro.core import batching as bt
from repro.models import registry as R

SAMPLE_RNG = jax.random.PRNGKey(5)

# every family pair from {dense, moe, encdec}
FAMILIES = {"dense": ("starcoder2-3b", 0),
            "moe": ("qwen2-moe-a2.7b", 1),
            "encdec": ("whisper-medium", 2)}
PAIRS = [("dense", "moe"), ("dense", "encdec"), ("moe", "encdec")]

# one engine geometry for the whole module: paged, tight per-lane block
# pools (13 blocks = 3 full 16-token rows + trash), 4 leased slots
ENGINE_KW = dict(num_slots=4, max_seq=16, prefill_chunk=4,
                 block_size=4, num_blocks=13)


@pytest.fixture(scope="module")
def families():
    out = {}
    for fam, (arch, seed) in FAMILIES.items():
        cfg = dataclasses.replace(get_config(arch).reduced(),
                                  kv_quant=True)
        out[fam] = (cfg, R.init(jax.random.PRNGKey(seed), cfg))
    return out


def _trace(tag, cfg, n, *, seed, rid_offset=0):
    """One lane's sub-trace: model-tagged, mixed SLO classes, inf
    deadlines (nothing drops — parity must hold on every request),
    sources attached for prime families, rids offset so the merged
    two-model trace keys uniquely."""
    reqs = E.synthetic_requests(
        n, rate_per_s=2000.0, vocab=cfg.vocab, prompt_len=4,
        max_new_tokens=5, seed=seed, model=tag,
        priority=lambda rid: "interactive" if rid % 3 else "batch",
        source_shape=((R.source_len(cfg), cfg.d_model)
                      if R.needs_prime(cfg) else None))
    return [dataclasses.replace(r, rid=r.rid + rid_offset) for r in reqs]


def _merged_pair(families, fa, fb, n_each=100):
    """A 2*n_each-request interleaved two-model trace plus each lane's
    (cfg, params)."""
    ca, pa = families[fa]
    cb, pb = families[fb]
    ta = _trace("a", ca, n_each, seed=11)
    tb = _trace("b", cb, n_each, seed=22, rid_offset=1000)
    merged = sorted(ta + tb, key=lambda r: r.arrival_s)
    return merged, {"a": (ca, pa), "b": (cb, pb)}


def _strip(reqs):
    return [dataclasses.replace(r, model=None) for r in reqs]


# ---------------------------------------------------------------------------
# the differential harness: multiplexed == dedicated, bitwise
# ---------------------------------------------------------------------------

class TestDifferential:
    @pytest.mark.parametrize("fa,fb", PAIRS)
    @pytest.mark.parametrize("temperature", [0.0, 0.7],
                             ids=["greedy", "sampled"])
    def test_multiplexed_matches_dedicated(self, families, fa, fb,
                                           temperature):
        """For every family pair, a 200-request interleaved two-model
        trace served multiplexed (paged KV, tight blocks, preemption,
        mixed SLO classes) produces per-model outputs bit-for-bit equal
        to dedicated single-model engines serving each lane's own
        sub-trace.  Holds sampled too: the position-derived key schedule
        makes tokens independent of cross-model admission timing."""
        merged, lanes = _merged_pair(families, fa, fb)
        kw = dict(ENGINE_KW, temperature=temperature,
                  rng=SAMPLE_RNG if temperature > 0 else None)
        mux = E.Engine(models=lanes, **kw)
        mrep = mux.serve(merged, clock="virtual", tick_s=1e-3,
                         preemption=True)
        assert len(mrep.results) == len(merged)
        assert all(r.status == "ok" for r in mrep.results)
        assert mrep.leaked_blocks == 0
        for tag, (cfg, params) in lanes.items():
            ded = E.Engine(cfg, params, **kw)
            sub = _strip([r for r in merged if r.model == tag])
            drep = ded.serve(sub, clock="virtual", tick_s=1e-3,
                             preemption=True)
            assert mrep.outputs_for(tag) == drep.outputs(), \
                f"lane {tag} ({fa if tag == 'a' else fb}) diverged"
        # per-model report partitions: lanes' outputs cover everything
        got = dict(mrep.outputs_for("a"))
        got.update(mrep.outputs_for("b"))
        assert got == mrep.outputs()
        assert set(mrep.model_mean_occupancy) == {"a", "b"}

    def test_chaos_arm(self, families):
        """The satellite chaos gate: a bursty mixed-model two-class
        trace with a seeded cross-lane fault plan AND forced preemption
        on under-provisioned per-lane pools — zero leaked blocks, no
        request lost, and every non-failed output exactly its own
        lane's sequential reference."""
        merged, lanes = _merged_pair(families, "dense", "moe")
        want = {tag: E.reference_outputs(
                    cfg, params,
                    _strip([r for r in merged if r.model == tag]),
                    max_seq=16)
                for tag, (cfg, params) in lanes.items()}
        eng = E.Engine(models=lanes, **ENGINE_KW)
        plan = E.FaultPlan.random(seed=42, n_faults=12, max_tick=400,
                                  num_slots=8)   # global ids, 2 lanes
        rep = eng.serve(merged, clock="virtual", tick_s=1e-3,
                        preemption=True, fault_plan=plan)
        assert len(rep.results) == len(merged)
        assert rep.leaked_blocks == 0
        assert rep.preempted > 0
        assert plan.fired
        bad = [r.rid for r in rep.results
               if r.status == "ok" and r.tokens != want[r.model][r.rid]]
        assert not bad, f"cross-model state leak: rids {bad[:8]}"

    def test_prefix_keys_are_model_fingerprinted(self, families):
        """The same token prompt hashes to DIFFERENT prefix-key chains
        on different lanes (and to the untagged single-model chain on
        neither), so paged sharing cannot cross models even before the
        lane-private BlockPools make it structurally impossible."""
        merged, lanes = _merged_pair(families, "dense", "moe", n_each=4)
        eng = E.Engine(models=lanes, **ENGINE_KW)
        single = E.Engine(*lanes["a"], **ENGINE_KW)
        probe = _strip([r for r in merged
                        if r.model == "a" and len(r.prompt) >= 4])[0]
        ka = eng.lanes["a"]._prefix_keys(probe)
        kb = eng.lanes["b"]._prefix_keys(probe)
        k0 = single.lanes[None]._prefix_keys(probe)
        assert ka and kb and k0
        assert ka != kb and ka != k0 and kb != k0


# ---------------------------------------------------------------------------
# cross-model poison: one lane's corruption is invisible to the other
# ---------------------------------------------------------------------------

class TestCrossModelPoison:
    def test_poisoned_lane_cannot_perturb_the_other(self, families):
        """Corrupt model A's fused dispatch so every sample is the -1
        sentinel: A's requests burn their retry budgets and retire as
        typed ``failed`` — and model B's outputs stay bitwise identical
        to the clean run.  Fault isolation is per-lane, not per-engine."""
        merged, lanes = _merged_pair(families, "dense", "moe", n_each=24)
        clean = E.Engine(models=lanes, **ENGINE_KW)
        baseline = clean.serve(merged, clock="virtual", tick_s=1e-3,
                               preemption=True).outputs_for("b")

        eng = E.Engine(models=lanes, **ENGINE_KW)
        orig = eng.lanes["a"]._fused

        def poisoned(tokens, cache, index, active):
            nxt, cache, new_index = orig(tokens, cache, index, active)
            return jnp.full_like(nxt, -1), cache, new_index

        eng.lanes["a"]._fused = poisoned
        rep = eng.serve(merged, clock="virtual", tick_s=1e-3,
                        preemption=True, max_retries=1)
        assert len(rep.results) == len(merged)
        a_res = [r for r in rep.results if r.model == "a"]
        assert a_res and all(r.status == "failed" for r in a_res)
        b_res = [r for r in rep.results if r.model == "b"]
        assert all(r.status == "ok" for r in b_res)
        assert rep.outputs_for("b") == baseline
        assert rep.leaked_blocks == 0        # failed slots drain clean

    def test_nan_in_one_cache_never_reaches_the_other_lanes_step(
            self, families):
        """Decode-contract rule 8 at the step level: fill lane A's
        device cache with NaN and lane B's very next fused dispatch is
        bitwise unchanged — no leaf of one model's state is ever an
        input to another model's step."""
        merged, lanes = _merged_pair(families, "dense", "moe", n_each=4)
        e1 = E.Engine(models=lanes, **ENGINE_KW)
        e2 = E.Engine(models=lanes, **ENGINE_KW)
        e2.lanes["a"].cache = jax.tree_util.tree_map(
            lambda x: (jnp.full_like(x, jnp.nan)
                       if jnp.issubdtype(x.dtype, jnp.floating) else x),
            e2.lanes["a"].cache)
        S = e1.num_slots
        tokens = jnp.ones((S, 1), jnp.int32)
        idx = jnp.zeros((S,), jnp.int32)
        active = jnp.ones((S,), bool)
        n1, _, i1 = e1.lanes["b"]._fused(tokens, e1.lanes["b"].cache,
                                         idx, active)
        n2, _, i2 = e2.lanes["b"]._fused(tokens, e2.lanes["b"].cache,
                                         idx, active)
        np.testing.assert_array_equal(np.asarray(n1), np.asarray(n2))
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


# ---------------------------------------------------------------------------
# (model, class) quota keys: property test + PR-7 boundary equivalence
# ---------------------------------------------------------------------------

QUOTA_CONFIGS = [
    {},                                       # uncapped
    {"batch": 2},                             # class-wide, cross-model
    {"a": 2},                                 # model-wide, cross-class
    {("a", "batch"): 1},                      # pinned intersection
    {"a": 3, "batch": 2, ("b", "interactive"): 1},   # all three kinds
]


def _meter_keys(m, c):
    """The keys one (model, class) request is metered against — the
    engine's admission loop and ``AdmissionPolicy._quota_keys`` agree."""
    return ((m, c), m, c)


class TestQuotaInvariants:
    @given(st.integers(0, 19), st.sampled_from(list(range(len(
        QUOTA_CONFIGS)))))
    @settings(max_examples=40, deadline=None)
    def test_never_exceed_and_never_barrier(self, seed, qi):
        """Drive SlotScheduler.admit with the multiplexed engine's
        ``key_fn`` through random push/admit/retire rounds: (1) no
        quota key's active count ever exceeds its quota; (2) blocked
        requests are skipped, never barriers — whenever admission
        leaves capacity unused, every request still pending is
        quota-blocked against the post-admission actives."""
        quotas = QUOTA_CONFIGS[qi]
        rng = random.Random(seed)
        policy = bt.AdmissionPolicy(lambda b: 0.0, max_batch=8,
                                    max_wait_s=0.0, class_quotas=quotas)
        sched = E.SlotScheduler(policy)
        S, rid = 6, 0
        active = []                       # (model, class) keys held
        for _ in range(12):
            for _ in range(rng.randrange(4)):
                req = E.EngineRequest(
                    rid=rid, prompt=(1,), max_new_tokens=1,
                    deadline_s=float("inf"),
                    priority=rng.choice(("interactive", "batch")),
                    model=rng.choice(("a", "b")))
                sched.push(req)
                rid += 1
            abc = collections.Counter()
            for m, c in active:
                for k in _meter_keys(m, c):
                    abc[k] += 1
            cap = S - len(active)
            got = sched.admit(0.0, cap, None, active_by_class=abc,
                              key_fn=lambda r: (r.model, r.priority))
            active.extend((r.model, r.priority) for r in got)
            assert len(active) <= S
            cnt = collections.Counter()
            for m, c in active:
                for k in _meter_keys(m, c):
                    cnt[k] += 1
            for k, q in quotas.items():
                assert cnt[k] <= q, f"quota key {k!r} over limit"
            if cap > 0 and len(got) < cap:
                for r in sched.pending:
                    keys = _meter_keys(r.model, r.priority)
                    assert any(k in quotas and cnt[k] >= quotas[k]
                               for k in keys), \
                        (f"rid {r.rid} is unblocked yet pending with "
                         f"{cap - len(got)} free slots — quota became "
                         f"a barrier")
            for i in reversed(range(len(active))):
                if rng.random() < 0.4:
                    active.pop(i)


def _pr7_decide_classes(policy, now, deadlines, next_arrival, cap,
                        costs, budget, classes, active_by_class):
    """PR-7's ``_decide_classes``, verbatim semantics: string quota keys
    only (a class meters exactly itself) and an int pool budget.  The
    boundary tests pin today's generalized tuple-key/mapping-budget code
    to this on every input the old code could see."""
    used = dict(active_by_class or {})
    sel = []
    for i, c in enumerate(classes):
        if len(sel) >= cap:
            break
        if policy.class_quotas.get(c) is not None \
                and used.get(c, 0) >= policy.class_quotas[c]:
            continue
        sel.append(i)
        used[c] = used.get(c, 0) + 1
    wait = bt.Admission(False, wait_until=(
        next_arrival if next_arrival is not None else now))
    if not sel:
        return wait
    earliest = min(deadlines[i] for i in sel)
    while len(sel) > 1 and now + policy.service_time(len(sel)) > earliest:
        sel.pop()
        earliest = min(deadlines[i] for i in sel)
    if costs is not None and budget is not None:
        while sel and sum(costs[i] for i in sel) > budget:
            sel.pop()
        if not sel:
            return wait
    can_wait = (
        len(sel) < cap and next_arrival is not None
        and next_arrival - now <= policy.max_wait_s
        and next_arrival + policy.service_time(
            min(len(sel) + 1, cap)) <= earliest)
    if can_wait:
        return bt.Admission(False, wait_until=next_arrival)
    return bt.Admission(True, batch=len(sel), picks=tuple(sel))


class TestPR7Boundary:
    @given(st.integers(0, 59))
    @settings(max_examples=60, deadline=None)
    def test_class_only_path_byte_identical_to_pr7(self, seed):
        """String-classed admission (what every PR-7 caller passes)
        through today's ``decide`` returns the exact Admission —
        including ``picks`` — the PR-7 procedure returns, across random
        quotas, deadlines, costs/budget, and wait windows."""
        rng = random.Random(seed)
        policy = bt.AdmissionPolicy(
            lambda b: 5e-4 * b, max_batch=8, max_wait_s=2e-3,
            class_quotas=rng.choice([{}, {"batch": 2},
                                     {"interactive": 3},
                                     {"batch": 1, "interactive": 4}]))
        n = rng.randrange(1, 10)
        now = rng.random()
        deadlines = [now + rng.uniform(1e-4, 2e-2) for _ in range(n)]
        classes = [rng.choice(("interactive", "batch"))
                   for _ in range(n)]
        abc = {c: rng.randrange(0, 3)
               for c in ("interactive", "batch")}
        use_budget = rng.random() < 0.5
        costs = [rng.randrange(1, 4) for _ in range(n)] \
            if use_budget else None
        budget = rng.randrange(0, 12) if use_budget else None
        next_arrival = rng.choice(
            [None, now + 5e-4, now + 5e-3])
        cap = rng.randrange(1, 9)
        got = policy.decide(now, deadlines, next_arrival,
                            capacity=cap, costs=costs, budget=budget,
                            classes=classes, active_by_class=abc)
        want = _pr7_decide_classes(
            policy, now, deadlines, next_arrival,
            min(cap, policy.max_batch), costs, budget, classes, abc)
        assert got == want

    @given(st.integers(0, 29))
    @settings(max_examples=30, deadline=None)
    def test_no_quota_tuple_path_reduces_to_legacy_prefix(self, seed):
        """With no quotas configured, tuple-classed admission (the
        multiplexed key_fn path) on a deadline-sorted queue picks
        exactly the legacy prefix cohort — same launch/batch/wait, and
        ``picks`` is literally ``range(batch)``."""
        rng = random.Random(seed)
        policy = bt.AdmissionPolicy(lambda b: 5e-4 * b, max_batch=8,
                                    max_wait_s=2e-3)
        n = rng.randrange(1, 10)
        now = rng.random()
        deadlines = sorted(now + rng.uniform(1e-4, 2e-2)
                           for _ in range(n))
        classes = [(rng.choice(("a", "b")), "interactive")
                   for _ in range(n)]
        use_budget = rng.random() < 0.5
        costs = [rng.randrange(1, 4) for _ in range(n)] \
            if use_budget else None
        budget = rng.randrange(1, 12) if use_budget else None
        next_arrival = rng.choice([None, now + 5e-4, now + 5e-3])
        cap = rng.randrange(1, 9)
        legacy = policy.decide(now, deadlines, next_arrival,
                               capacity=cap, costs=costs, budget=budget)
        tupled = policy.decide(now, deadlines, next_arrival,
                               capacity=cap, costs=costs, budget=budget,
                               classes=classes, active_by_class={})
        assert tupled.launch == legacy.launch
        assert tupled.batch == legacy.batch
        assert tupled.wait_until == legacy.wait_until
        if tupled.launch:
            assert tupled.picks == tuple(range(legacy.batch))

    def test_mapping_budget_sheds_only_the_starved_model(self):
        """A per-model budget mapping: the model with zero free blocks
        sheds its whole cohort, the other model admits through it —
        memory pressure on one lane never barriers the rest."""
        policy = bt.AdmissionPolicy(lambda b: 0.0, max_batch=8,
                                    max_wait_s=0.0)
        now = 0.0
        classes = [("b", "interactive"), ("a", "interactive"),
                   ("b", "interactive"), ("a", "interactive")]
        deadlines = [float("inf")] * 4
        costs = [2, 2, 2, 2]
        act = policy.decide(now, deadlines, None, capacity=4,
                            costs=costs, budget={"a": 8, "b": 0},
                            classes=classes, active_by_class={})
        assert act.launch and act.picks == (1, 3)


# ---------------------------------------------------------------------------
# per-model quota end to end: a model's lease never exceeds its cap
# ---------------------------------------------------------------------------

def test_model_quota_caps_lane_occupancy(families):
    """``class_quotas={'a': 2}`` on a multiplexed engine: lane a never
    holds more than 2 of the 4 leased slots on ANY tick, lane b is free
    to take the rest, and every request still completes."""
    ca, pa = families["dense"]
    cb, pb = families["moe"]
    # asymmetric demand: once a's short queue drains, b must be able to
    # grow past the 2 slots a's quota was reserving
    ta = _trace("a", ca, 8, seed=11)
    tb = _trace("b", cb, 32, seed=22, rid_offset=1000)
    merged = sorted(ta + tb, key=lambda r: r.arrival_s)
    lanes = {"a": (ca, pa), "b": (cb, pb)}
    policy = bt.AdmissionPolicy(lambda b: 0.0, max_batch=4,
                                max_wait_s=0.0, class_quotas={"a": 2})
    eng = E.Engine(models=lanes, num_slots=4, max_seq=16,
                   prefill_chunk=4, block_size=4, policy=policy)
    rep = eng.serve(merged, clock="virtual", tick_s=1e-3)
    assert len(rep.results) == len(merged)
    assert all(r.status == "ok" for r in rep.results)
    assert max(rep.model_occupancy["a"]) <= 2
    assert max(rep.model_occupancy["b"]) > 2   # b uses the freed lease
    assert rep.leaked_blocks == 0


# ---------------------------------------------------------------------------
# engine validation of the multi-model surface
# ---------------------------------------------------------------------------

class TestValidation:
    def test_unknown_model_tag_rejected(self, families):
        merged, lanes = _merged_pair(families, "dense", "moe", n_each=2)
        eng = E.Engine(models=lanes, **ENGINE_KW)
        bad = dataclasses.replace(merged[0], model="zzz")
        with pytest.raises(ValueError, match="not admitted"):
            eng.serve([bad])

    def test_tagged_request_rejected_on_single_model_engine(
            self, families):
        cfg, params = families["dense"]
        eng = E.Engine(cfg, params, num_slots=4, max_seq=16)
        req = E.EngineRequest(rid=0, prompt=(1, 2), max_new_tokens=2,
                              model="a")
        with pytest.raises(ValueError, match="not admitted"):
            eng.serve([req])

    def test_constructor_surface(self, families):
        cfg, params = families["dense"]
        with pytest.raises(ValueError, match="exactly one"):
            E.Engine(cfg, params, models={"a": (cfg, params)})
        with pytest.raises(ValueError, match="exactly one"):
            E.Engine()
        with pytest.raises(ValueError, match="at least one"):
            E.Engine(models={})
        with pytest.raises(ValueError, match="non-empty string"):
            E.Engine(models={"": (cfg, params)})


# ---------------------------------------------------------------------------
# golden-trace regressions: the model knobs move nothing when unset
# ---------------------------------------------------------------------------

class TestGoldenTraces:
    def test_synthetic_requests_defaults_pinned(self):
        """Literal golden pins (computed before the ``model=`` knob
        existed): the default trace may not move by a byte."""
        reqs = E.synthetic_requests(4, rate_per_s=1000.0, vocab=97,
                                    seed=3)
        assert [r.prompt for r in reqs] == [
            (1, 4, 7, 10), (8, 11, 14, 17),
            (15, 18, 21, 24), (22, 25, 28, 31)]
        assert [r.arrival_s for r in reqs] == pytest.approx(
            [0.000271762303, 0.001057527586,
             0.001519491884, 0.002445631049], rel=1e-9)
        assert all(r.model is None for r in reqs)
        assert all(r.priority == "interactive" for r in reqs)
        untagged = E.synthetic_requests(4, rate_per_s=1000.0, vocab=97,
                                        seed=3, model=None)
        assert untagged == reqs

    def test_two_class_trace_defaults_pinned(self):
        reqs = TR.two_class_trace(4, rate_per_s=500.0, vocab=97, seed=2)
        assert [r.prompt for r in reqs] == [
            (1, 4, 7), (8, 11, 14), (15, 18, 21), (22, 25)]
        assert [r.arrival_s for r in reqs] == pytest.approx(
            [0.023625595958, 0.024091302834,
             0.024800833454, 0.039239536565], rel=1e-9)
        assert [r.priority for r in reqs] == [
            "interactive", "batch", "interactive", "interactive"]
        assert [r.max_new_tokens for r in reqs] == [6, 3, 2, 2]
        assert all(r.model is None for r in reqs)
        untagged = TR.two_class_trace(4, rate_per_s=500.0, vocab=97,
                                      seed=2, models=None)
        assert untagged == reqs

    def test_model_tagging_changes_only_model_and_vocab(self):
        """Tagged traces keep arrivals/lengths/classes of the untagged
        trace; only the tag and the per-lane vocab drawing differ."""
        base = TR.two_class_trace(12, rate_per_s=500.0, vocab=97, seed=2)
        tagged = TR.two_class_trace(12, rate_per_s=500.0, vocab=0,
                                    seed=2, models=[("a", 97), ("b", 53)])
        assert [r.arrival_s for r in tagged] == \
            [r.arrival_s for r in base]
        assert [r.priority for r in tagged] == \
            [r.priority for r in base]
        assert [r.max_new_tokens for r in tagged] == \
            [r.max_new_tokens for r in base]
        assert [r.model for r in tagged] == ["a", "b"] * 6
        for r in tagged:
            v = 97 if r.model == "a" else 53
            assert all(1 <= t < v for t in r.prompt)
        # lane a draws in the same vocab as base -> identical prompts
        assert [r.prompt for r in tagged if r.model == "a"] == \
            [r.prompt for r in base if r.rid % 2 == 0]

    def test_synthetic_model_callable(self):
        reqs = E.synthetic_requests(
            6, rate_per_s=1000.0, vocab=97,
            model=lambda rid: "a" if rid % 2 == 0 else "b")
        assert [r.model for r in reqs] == ["a", "b"] * 3
        base = E.synthetic_requests(6, rate_per_s=1000.0, vocab=97)
        assert [dataclasses.replace(r, model=None) for r in reqs] == base
