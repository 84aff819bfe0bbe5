"""Continuous-batching engine: scheduler equivalence with the simulator,
slot-step isolation, and end-to-end bit-for-bit parity with the
sequential per-token reference loop on a 200-request Poisson trace."""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import engine as E
from repro.configs import get_config
from repro.core import batching as bt
from repro.models import registry as R
from repro.runtime import steps as ST

KEY = jax.random.PRNGKey(0)


def _cfg(kv_quant=True):
    cfg = get_config("starcoder2-3b").reduced()
    return dataclasses.replace(cfg, kv_quant=kv_quant)


@pytest.fixture(scope="module")
def dense_setup():
    cfg = _cfg()
    return cfg, R.init(KEY, cfg)


# ---------------------------------------------------------------------------
# scheduler: one admission policy, two backends
# ---------------------------------------------------------------------------

class TestSchedulerEquivalence:
    @given(st.integers(0, 40), st.sampled_from([2000.0, 20000.0, 50000.0]))
    @settings(max_examples=12, deadline=None)
    def test_simulator_and_engine_scheduler_agree(self, seed, rate):
        """BatchQueue (simulator backend) and the engine's SlotScheduler
        replay the SAME admission decisions on the same trace — the
        policy extraction is behavior-preserving."""
        reqs = bt.poisson_arrivals(rate, 200, deadline_s=7e-3, seed=seed)
        service = bt.TABLE4_TPU.service_time
        sim = bt.BatchQueue(service, max_batch=64).run(reqs)
        policy = bt.AdmissionPolicy(service, max_batch=64)
        live = E.SlotScheduler(policy).run_virtual(reqs)
        assert [(r.start_s, r.rids) for r in sim] == \
            [(r.start_s, r.rids) for r in live]

    def test_admit_respects_capacity(self):
        policy = bt.AdmissionPolicy(lambda b: 0.0, max_batch=64,
                                    max_wait_s=0.0)
        sched = E.SlotScheduler(policy)
        for rid in range(10):
            sched.push(bt.Request(0.0, float("inf"), rid))
        got = sched.admit(0.0, capacity=3)
        assert len(got) == 3 and len(sched.pending) == 7
        assert sched.admit(0.0, capacity=0) == []


# ---------------------------------------------------------------------------
# slot step: isolation of inactive rows
# ---------------------------------------------------------------------------

def test_inactive_slot_poison_cannot_leak(dense_setup):
    """Garbage in inactive slots' cache rows (and their token inputs)
    must not change active rows' outputs or cache writes, bitwise."""
    cfg, params = dense_setup
    step = ST.jit_slot_decode_step(ST.make_slot_decode_step(cfg))
    S, smax = 4, 32
    idx = jnp.array([2, 0, 3, 1], jnp.int32)
    active = jnp.array([True, False, True, False])
    tokens = jnp.array([[5], [1], [9], [2]], jnp.int32)

    def run(cache):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            return step(params, tokens, cache, idx, active)

    clean = R.init_cache(cfg, S, smax)
    n1, c1, i1 = run(jax.tree_util.tree_map(lambda x: x.copy(), clean))
    poisoned = jax.tree_util.tree_map(
        lambda x: x.at[:, 1].set(jnp.full_like(x[:, 1], 107))
                   .at[:, 3].set(jnp.full_like(x[:, 3], -9)), clean)
    poisoned_tokens = tokens.at[1, 0].set(400).at[3, 0].set(499)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        n2, c2, i2 = step(params, poisoned_tokens, poisoned, idx, active)

    np.testing.assert_array_equal(np.asarray(n1[active]),
                                  np.asarray(n2[active]))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    for a, b in zip(jax.tree_util.tree_leaves(c1),
                    jax.tree_util.tree_leaves(c2)):
        # active rows' cache contents identical under poisoning
        np.testing.assert_array_equal(np.asarray(a[:, active]),
                                      np.asarray(b[:, active]))
    # masked sampling: inactive rows emit 0 and do not advance
    assert int(n1[1]) == 0 and int(n1[3]) == 0
    np.testing.assert_array_equal(np.asarray(i1),
                                  np.asarray(idx + active.astype(jnp.int32)))


def test_slot_rows_match_batch1_decode(dense_setup):
    """Each active slot's sample equals a batch=1 lockstep decode of the
    same request — per-row positions don't perturb the math."""
    cfg, params = dense_setup
    step = ST.jit_slot_decode_step(ST.make_slot_decode_step(cfg))
    decode = jax.jit(ST.make_decode_step(cfg))
    S, smax = 4, 32
    cache = R.init_cache(cfg, S, smax)
    idx = jnp.zeros((S,), jnp.int32)
    active = jnp.array([True, True, False, True])
    tokens = jnp.array([[5], [9], [0], [3]], jnp.int32)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        nxt, cache, idx = step(params, tokens, cache, idx, active)
        nxt2, _, _ = step(params, nxt[:, None], cache, idx, active)
    for row, t0 in [(0, 5), (1, 9), (3, 3)]:
        c1 = R.init_cache(cfg, 1, smax)
        l1, c1 = decode(params, {"tokens": jnp.asarray([[t0]], jnp.int32),
                                 "cache_index": jnp.asarray(0, jnp.int32)},
                        c1)
        t1 = ST.greedy_sample(l1)
        assert int(t1[0]) == int(nxt[row])
        l2, _ = decode(params, {"tokens": t1[:, None],
                                "cache_index": jnp.asarray(1, jnp.int32)},
                       c1)
        assert int(ST.greedy_sample(l2)[0]) == int(nxt2[row])


# ---------------------------------------------------------------------------
# engine end-to-end
# ---------------------------------------------------------------------------

def test_engine_200_requests_bit_for_bit(dense_setup):
    """Acceptance: a 200-request pseudo-Poisson trace through the live
    engine (int8 KV slots, continuous admission, zero drain barriers)
    reproduces the sequential per-token reference loop bit-for-bit and
    reports p99 + occupancy."""
    cfg, params = dense_setup
    reqs = E.synthetic_requests(200, rate_per_s=3000.0, vocab=cfg.vocab,
                                prompt_len=3, max_new_tokens=5)
    eng = E.Engine(cfg, params, num_slots=8, max_seq=16)
    rep = eng.serve(reqs, clock="virtual", tick_s=1e-3)

    want = E.reference_outputs(cfg, params, reqs, max_seq=16)
    assert rep.outputs() == want            # greedy tokens, bit-for-bit
    assert len(rep.results) == 200
    # no drain barrier: admissions keep landing while older requests are
    # mid-generation, and slots turn over (more requests than slots)
    assert rep.admissions_while_busy > 0
    assert rep.num_slots == 8
    assert max(rep.occupancy) <= rep.num_slots
    assert rep.p99_latency_s > 0 and rep.tokens_per_s > 0
    assert 0 < rep.mean_occupancy <= 1
    assert rep.generated_tokens == 200 * 5


def test_engine_batch_never_exceeds_bucketed_slot_count(dense_setup):
    """Property: per-tick active slots and per-admission cohorts are
    bounded by the bucketed pool size, across loads."""
    cfg, params = dense_setup
    for rate, slots in ((500.0, 3), (20000.0, 5)):
        eng = E.Engine(cfg, params, num_slots=slots, max_seq=16)
        assert eng.num_slots == ST.bucket_batch(slots)
        reqs = E.synthetic_requests(40, rate_per_s=rate, vocab=cfg.vocab,
                                    prompt_len=2, max_new_tokens=4,
                                    seed=int(rate))
        rep = eng.serve(reqs, clock="virtual", tick_s=1e-3)
        assert max(rep.occupancy) <= eng.num_slots
        assert rep.outputs() == E.reference_outputs(cfg, params, reqs,
                                                    max_seq=16)


def test_engine_slot_reuse_after_retirement(dense_setup):
    """More requests than slots forces retire-then-reuse; results must
    still be exact (stale cache rows are invisible past the frontier)."""
    cfg, params = dense_setup
    reqs = E.synthetic_requests(12, rate_per_s=1e6, vocab=cfg.vocab,
                                prompt_len=4, max_new_tokens=6)
    eng = E.Engine(cfg, params, num_slots=2, max_seq=16)
    rep = eng.serve(reqs, clock="virtual", tick_s=1e-3)
    assert rep.outputs() == E.reference_outputs(cfg, params, reqs,
                                                max_seq=16)
    # 12 requests through a 2-slot pool: every slot served >= 1 tenant
    assert {r.slot for r in rep.results} == {0, 1}


def test_engine_fp_cache_and_wall_clock(dense_setup):
    """fp16-free path: bf16 KV cache engine + wall clock returns the same
    outputs as the virtual clock (timing never leaks into tokens)."""
    cfg = _cfg(kv_quant=False)
    params = R.init(KEY, cfg)
    reqs = E.synthetic_requests(10, rate_per_s=5000.0, vocab=cfg.vocab,
                                prompt_len=3, max_new_tokens=4)
    eng = E.Engine(cfg, params, num_slots=4, max_seq=16)
    a = eng.serve(reqs, clock="virtual", tick_s=1e-3)
    b = eng.serve(reqs, clock="wall")
    assert a.outputs() == b.outputs()
    assert a.outputs() == E.reference_outputs(cfg, params, reqs,
                                              max_seq=16)


def test_retired_mid_prefill_never_leaks_negative_ttft(dense_setup):
    """Regression (first_token_s = -1.0 sentinel): a request retired on a
    deadline miss BEFORE emitting any token must not poison the ttft
    aggregates with a negative value — they are computed only over
    requests that actually emitted."""
    cfg, params = dense_setup
    reqs = [
        # deadline passes at tick 3 of an 8-token prefill: dropped with
        # the sentinel still in place
        E.EngineRequest(rid=0, prompt=(1, 2, 3, 4, 5, 6, 7, 8),
                        max_new_tokens=4, deadline_s=2.5e-3),
        E.EngineRequest(rid=1, prompt=(3, 4), max_new_tokens=4),
        # already expired on arrival: retired at admission, before ever
        # taking a slot (no prime/prefill dispatch is wasted on it)
        E.EngineRequest(rid=2, prompt=(5,), max_new_tokens=2,
                        deadline_s=-1.0),
    ]
    eng = E.Engine(cfg, params, num_slots=2, max_seq=16)
    rep = eng.serve(reqs, clock="virtual", tick_s=1e-3,
                    drop_missed_deadlines=True)
    by_rid = {r.rid: r for r in rep.results}
    assert rep.dropped == 2
    assert by_rid[0].dropped and not by_rid[0].emitted
    assert by_rid[0].tokens == [] and by_rid[0].first_token_s == -1.0
    assert by_rid[2].dropped and by_rid[2].slot == -1   # never admitted
    # the sentinel never leaks: aggregates are >= 0 and equal the sole
    # emitting request's ttft
    assert rep.mean_ttft_s >= 0.0 and rep.p99_ttft_s >= 0.0
    assert rep.mean_ttft_s == pytest.approx(by_rid[1].ttft_s)
    assert rep.p99_ttft_s == pytest.approx(by_rid[1].ttft_s)
    # the surviving request's tokens are untouched by its neighbor's drop
    assert by_rid[1].tokens == E.reference_outputs(
        cfg, params, [reqs[1]], max_seq=16)[1]
    # dropped requests do not enter the completion-latency percentile
    assert rep.p99_latency_s == pytest.approx(by_rid[1].latency_s)


def test_engine_temperature_requires_rng(dense_setup):
    cfg, params = dense_setup
    with pytest.raises(ValueError, match="rng"):
        E.Engine(cfg, params, num_slots=2, max_seq=16, temperature=0.5)


def test_engine_rejects_oversized_request(dense_setup):
    cfg, params = dense_setup
    eng = E.Engine(cfg, params, num_slots=2, max_seq=8)
    assert eng.max_seq == 16            # rounds up to a 16-aligned cache
    reqs = [E.EngineRequest(rid=0, prompt=(1, 2, 3, 4), max_new_tokens=16)]
    with pytest.raises(ValueError, match="cache positions"):
        eng.serve(reqs, clock="virtual")
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.serve([E.EngineRequest(rid=0, prompt=(1,), max_new_tokens=0)],
                  clock="virtual")


def test_engine_warmup_does_not_change_outputs(dense_setup):
    cfg, params = dense_setup
    reqs = E.synthetic_requests(6, rate_per_s=5000.0, vocab=cfg.vocab,
                                prompt_len=3, max_new_tokens=4)
    eng = E.Engine(cfg, params, num_slots=4, max_seq=16)
    eng.warmup()
    rep = eng.serve(reqs, clock="virtual", tick_s=1e-3)
    assert rep.outputs() == E.reference_outputs(cfg, params, reqs,
                                                max_seq=16)


# ---------------------------------------------------------------------------
# all token-only decode families through the same slot engine
# ---------------------------------------------------------------------------

FAMILY_ARCHS = ["qwen2-moe-a2.7b", "mamba2-1.3b", "recurrentgemma-9b"]


@pytest.fixture(scope="module", params=FAMILY_ARCHS)
def family_setup(request):
    cfg = get_config(request.param).reduced()
    return cfg, R.init(KEY, cfg)


def test_engine_family_bit_for_bit(family_setup):
    """Acceptance: moe/ssm/hybrid registry configs serve through the slot
    engine with outputs bit-for-bit equal to the sequential per-token
    reference, through slot reuse (more requests than slots)."""
    cfg, params = family_setup
    reqs = E.synthetic_requests(16, rate_per_s=3000.0, vocab=cfg.vocab,
                                prompt_len=4, max_new_tokens=4)
    eng = E.Engine(cfg, params, num_slots=4, max_seq=16)
    rep = eng.serve(reqs, clock="virtual", tick_s=1e-3)
    assert rep.outputs() == E.reference_outputs(cfg, params, reqs,
                                                max_seq=16)
    assert len(rep.results) == 16
    assert rep.admissions_while_busy > 0     # continuous, no drain barrier
    assert {r.slot for r in rep.results} == set(range(4))  # reuse happened


def test_recurrent_state_isolated_from_inactive_rows(family_setup):
    """The recurrent families' slot contract: poisoned state in inactive
    rows never leaks into active rows, inactive rows' state is frozen
    bitwise, and a reused row is scrubbed by the reset-at-position-0
    rule (so the poison also cannot survive into a new tenancy)."""
    cfg, params = family_setup
    step = ST.jit_slot_decode_step(ST.make_slot_decode_step(cfg))
    S, smax = 4, 32
    axes = R.cache_batch_axes(cfg, R.init_cache(cfg, S, smax))
    idx = jnp.array([2, 0, 3, 1], jnp.int32)
    active = jnp.array([True, False, True, False])
    tokens = jnp.array([[5], [1], [9], [2]], jnp.int32)

    def poison_rows(x, axis):
        x = jnp.moveaxis(x, axis, 0)
        x = x.at[1].set(jnp.full_like(x[1], 107))
        x = x.at[3].set(jnp.full_like(x[3], -9))
        return jnp.moveaxis(x, 0, axis)

    def run(cache):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            return step(params, tokens, cache, idx, active)

    # warm the state so rows differ from init_cache zeros (makes the
    # freeze check meaningful)
    cache0 = R.init_cache(cfg, S, smax)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        _, cache0, _ = step(params, tokens, cache0,
                            jnp.zeros((S,), jnp.int32),
                            jnp.ones((S,), bool))
    n1, c1, i1 = run(jax.tree_util.tree_map(lambda x: x.copy(), cache0))
    poisoned = {k: poison_rows(v, axes[k]) for k, v in cache0.items()}
    # snapshot before run(): the jitted step donates its cache argument
    poisoned_np = {k: np.asarray(v) for k, v in poisoned.items()}
    n2, c2, i2 = run(poisoned)

    np.testing.assert_array_equal(np.asarray(n1[active]),
                                  np.asarray(n2[active]))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    for k in c1:
        a = np.moveaxis(np.asarray(c1[k]), axes[k], 0)
        b = np.moveaxis(np.asarray(c2[k]), axes[k], 0)
        # active rows' cache identical under poisoning of inactive rows
        np.testing.assert_array_equal(a[np.asarray(active)],
                                      b[np.asarray(active)])
        # inactive rows' poison is frozen, not half-updated
        pb = np.moveaxis(poisoned_np[k], axes[k], 0)
        if k in ("k", "v", "k_scale", "v_scale"):
            continue                         # positional: masked on read
        np.testing.assert_array_equal(b[1], pb[1])
        np.testing.assert_array_equal(b[3], pb[3])
    # masked sampling: inactive rows emit 0 and do not advance
    assert int(n1[1]) == 0 and int(n1[3]) == 0
    np.testing.assert_array_equal(np.asarray(i1),
                                  np.asarray(idx + active.astype(jnp.int32)))


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [2, 4, 8])
def test_chunked_prefill_bit_for_bit_across_buckets(dense_setup, chunk):
    """Chunked prefill == per-token prefill, bit-for-bit, for every chunk
    bucket (including remainders masked inside a padded bucket), and it
    cuts admission-to-first-token ticks."""
    cfg, params = dense_setup
    reqs = E.synthetic_requests(10, rate_per_s=3000.0, vocab=cfg.vocab,
                                prompt_len=11, max_new_tokens=3)
    want = E.reference_outputs(cfg, params, reqs, max_seq=16)
    plain = E.Engine(cfg, params, num_slots=4, max_seq=16)
    rep0 = plain.serve(reqs, clock="virtual", tick_s=1e-3)
    eng = E.Engine(cfg, params, num_slots=4, max_seq=16,
                   prefill_chunk=chunk)
    rep = eng.serve(reqs, clock="virtual", tick_s=1e-3)
    assert rep0.outputs() == want
    assert rep.outputs() == want
    # under a constant virtual tick, ttft is tick-exact: prompt_len=11 ->
    # per-token pays 11 ticks, chunked pays ceil(10/chunk) (the final
    # chunk tick doubles as the slot's first fused tick)
    tick = 1e-3
    assert abs(rep0.mean_ttft_s - 11 * tick) < 1e-9
    want_ticks = -(-10 // chunk)
    assert abs(rep.mean_ttft_s - want_ticks * tick) < 1e-9
    assert rep.mean_ttft_s < rep0.mean_ttft_s
    assert rep.ticks < rep0.ticks


def test_chunked_prefill_families(family_setup):
    """Chunked prefill stays bit-for-bit for the recurrent and moe
    families (state chunks written by the scan-over-decode step)."""
    cfg, params = family_setup
    reqs = E.synthetic_requests(8, rate_per_s=3000.0, vocab=cfg.vocab,
                                prompt_len=7, max_new_tokens=3)
    eng = E.Engine(cfg, params, num_slots=2, max_seq=16, prefill_chunk=4)
    rep = eng.serve(reqs, clock="virtual", tick_s=1e-3)
    assert rep.outputs() == E.reference_outputs(cfg, params, reqs,
                                                max_seq=16)
    assert rep.prefill_chunk == 4


def test_chunked_prefill_single_token_prompt(dense_setup):
    """prompt_len=1 has no teacher-forced prefix: the chunk path must
    degrade to the plain admission path."""
    cfg, params = dense_setup
    reqs = [E.EngineRequest(rid=0, prompt=(9,), max_new_tokens=4)]
    eng = E.Engine(cfg, params, num_slots=2, max_seq=16, prefill_chunk=8)
    rep = eng.serve(reqs, clock="virtual", tick_s=1e-3)
    assert rep.outputs() == E.reference_outputs(cfg, params, reqs,
                                                max_seq=16)


# ---------------------------------------------------------------------------
# temperature sampling in the engine
# ---------------------------------------------------------------------------

def test_engine_temperature_matches_decode_loop(dense_setup):
    """A single request through the engine at temperature t reproduces
    make_decode_loop's fold_in(rng, position) draws bit-for-bit — the
    ported key schedule, not a lookalike."""
    cfg, params = dense_setup
    rng = jax.random.PRNGKey(123)
    n_tok, temp = 6, 0.8
    loop = ST.jit_decode_loop(
        ST.make_decode_loop(cfg, num_tokens=n_tok, temperature=temp))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        want, _ = loop(params, jnp.asarray([[7]], jnp.int32),
                       R.init_cache(cfg, 1, 16), jnp.zeros((), jnp.int32),
                       rng)
    reqs = [E.EngineRequest(rid=0, prompt=(7,), max_new_tokens=n_tok)]
    eng = E.Engine(cfg, params, num_slots=4, max_seq=16,
                   temperature=temp, rng=rng)
    rep = eng.serve(reqs, clock="virtual", tick_s=1e-3)
    assert rep.outputs()[0] == np.asarray(want)[0].tolist()


def test_engine_temperature_multi_request_reference_parity(dense_setup):
    """Many interleaved sampled requests (with chunked prefill) still
    match the sequential reference under the shared key schedule, and
    the draws are rng-determined (same rng -> same stream, different
    rng -> different)."""
    cfg, params = dense_setup
    rng = jax.random.PRNGKey(5)
    reqs = E.synthetic_requests(12, rate_per_s=3000.0, vocab=cfg.vocab,
                                prompt_len=4, max_new_tokens=4)
    want = E.reference_outputs(cfg, params, reqs, max_seq=16,
                               temperature=0.9, rng=rng)
    eng = E.Engine(cfg, params, num_slots=4, max_seq=16, temperature=0.9,
                   rng=rng, prefill_chunk=2)
    rep = eng.serve(reqs, clock="virtual", tick_s=1e-3)
    assert rep.outputs() == want
    again = E.Engine(cfg, params, num_slots=4, max_seq=16,
                     temperature=0.9, rng=rng)
    assert again.serve(reqs, clock="virtual",
                       tick_s=1e-3).outputs() == want
    other = E.Engine(cfg, params, num_slots=4, max_seq=16,
                     temperature=0.9, rng=jax.random.PRNGKey(99))
    assert other.serve(reqs, clock="virtual",
                       tick_s=1e-3).outputs() != want


# ---------------------------------------------------------------------------
# encdec/vlm: per-slot primed cross-K/V through the same slot engine
# ---------------------------------------------------------------------------

PRIME_ARCHS = ["whisper-medium", "llama-3.2-vision-90b"]


@pytest.fixture(scope="module", params=PRIME_ARCHS)
def prime_setup(request):
    cfg = get_config(request.param).reduced()
    return cfg, R.init(KEY, cfg)


def _prime_requests(cfg, n, **kw):
    kw.setdefault("rate_per_s", 3000.0)
    return E.synthetic_requests(
        n, vocab=cfg.vocab, source_shape=R.source_shape(cfg), **kw)


def test_engine_prime_family_200_requests_bit_for_bit(prime_setup):
    """Acceptance: encdec/vlm serve LIVE through the slot engine (no
    simulator fallback) — a 200-request pseudo-Poisson trace with
    per-request sources of varying length, through slot reuse,
    reproduces the sequential per-token reference bit-for-bit."""
    cfg, params = prime_setup
    reqs = _prime_requests(cfg, 200, prompt_len=3, max_new_tokens=4)
    eng = E.Engine(cfg, params, num_slots=8, max_seq=16)
    rep = eng.serve(reqs, clock="virtual", tick_s=1e-3)
    assert rep.outputs() == E.reference_outputs(cfg, params, reqs,
                                                max_seq=16)
    assert len(rep.results) == 200
    assert rep.admissions_while_busy > 0     # continuous, no drain barrier
    assert {r.slot for r in rep.results} == set(range(8))  # reuse happened


def test_engine_prime_family_chunked_prefill(prime_setup):
    """Chunked prefill composes with the prime dispatch: the chunk step
    slices a slot row whose cross-K/V was already primed at admission,
    so outputs stay bit-for-bit."""
    cfg, params = prime_setup
    reqs = _prime_requests(cfg, 10, prompt_len=7, max_new_tokens=3)
    want = E.reference_outputs(cfg, params, reqs, max_seq=16)
    eng = E.Engine(cfg, params, num_slots=4, max_seq=16, prefill_chunk=4)
    rep = eng.serve(reqs, clock="virtual", tick_s=1e-3)
    assert rep.outputs() == want


def test_engine_prime_family_requires_source(prime_setup):
    """encdec/vlm requests must carry per-request source embeddings of a
    legal shape; the engine validates before admitting anything."""
    cfg, params = prime_setup
    eng = E.Engine(cfg, params, num_slots=2, max_seq=16)
    with pytest.raises(ValueError, match="source"):
        eng.serve([E.EngineRequest(rid=0, prompt=(1, 2), max_new_tokens=2)],
                  clock="virtual")
    too_long = np.zeros((R.source_len(cfg) + 1, cfg.d_model), np.float32)
    with pytest.raises(ValueError, match="source length"):
        eng.serve([E.EngineRequest(rid=0, prompt=(1, 2), max_new_tokens=2,
                                   source=too_long)], clock="virtual")


def test_primed_cross_kv_isolated_and_scrubbed_on_reuse(prime_setup):
    """The prime contract, mirroring the recurrent-state scrub test:
    (a) poisoned cross-K/V in inactive rows (a retired tenant's
    leftovers) never changes active rows' outputs or self-cache writes;
    (b) poison past an active row's own xlen frontier is invisible;
    (c) decode never writes cross state (poison is frozen bitwise);
    (d) re-priming a poisoned row — slot reuse — fully overwrites it:
    the new tenant decodes exactly as in a fresh pool."""
    cfg, params = prime_setup
    step = ST.jit_slot_decode_step(ST.make_slot_decode_step(cfg))
    prime = jax.jit(ST.make_prime_step(cfg))
    S, smax = 4, 32
    src_max = R.source_len(cfg)
    axes = R.cache_batch_axes(cfg, R.init_cache(cfg, S, smax))

    def src_for(seed, n):
        g = np.random.default_rng(seed)
        buf = np.zeros((1, src_max, cfg.d_model), np.float32)
        buf[0, :n] = g.standard_normal((n, cfg.d_model)).astype(np.float32)
        return jnp.asarray(buf, jnp.bfloat16)

    n0, n2 = src_max, max(1, src_max - 2)
    cache = R.init_cache(cfg, S, smax)
    cache = prime(params, src_for(7, n0), cache,
                  jnp.asarray(0, jnp.int32), jnp.asarray(n0, jnp.int32))
    cache = prime(params, src_for(8, n2), cache,
                  jnp.asarray(2, jnp.int32), jnp.asarray(n2, jnp.int32))
    idx = jnp.array([1, 0, 2, 1], jnp.int32)
    active = jnp.array([True, False, True, False])
    tokens = jnp.array([[5], [1], [9], [2]], jnp.int32)

    def run(c):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            return step(params, tokens,
                        jax.tree_util.tree_map(lambda x: x.copy(), c),
                        idx, active)

    n1, c1, i1 = run(cache)

    poisoned = {k: np.array(v) for k, v in cache.items()}
    for leaf in ("xk", "xv"):
        m = np.moveaxis(poisoned[leaf], axes[leaf], 0)
        m[1] = 107.0                        # dead rows: whole cross state
        m[3] = -9.0
        m[2][:, n2:] = 55.0                 # active short row: masked tail
    poisoned["xlen"][1] = 9999
    poisoned["xlen"][3] = -5
    poisoned = {k: jnp.asarray(v) for k, v in poisoned.items()}
    poisoned_np = {k: np.asarray(v) for k, v in poisoned.items()}
    n2_, c2, i2 = run(poisoned)

    np.testing.assert_array_equal(np.asarray(n1[active]),
                                  np.asarray(n2_[active]))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    for k in c1:
        if k in ("xk", "xv", "xlen"):
            # (c) cross state is a static operand: returned bitwise as
            # passed in, poison and all
            np.testing.assert_array_equal(np.asarray(c2[k]),
                                          poisoned_np[k])
            continue
        a = np.moveaxis(np.asarray(c1[k]), axes[k], 0)
        b = np.moveaxis(np.asarray(c2[k]), axes[k], 0)
        np.testing.assert_array_equal(a[np.asarray(active)],
                                      b[np.asarray(active)])

    # (d) slot reuse: re-prime the poisoned row 1 and decode it from
    # position 0 — must equal the same tenant in a fresh pool
    nB = max(1, src_max - 1)
    srcB = src_for(9, nB)
    reused = prime(params, srcB, c2,
                   jnp.asarray(1, jnp.int32), jnp.asarray(nB, jnp.int32))
    fresh = prime(params, srcB, R.init_cache(cfg, S, smax),
                  jnp.asarray(1, jnp.int32), jnp.asarray(nB, jnp.int32))
    tok2 = jnp.array([[5], [7], [9], [2]], jnp.int32)
    only1 = jnp.array([False, True, False, False])
    zero = jnp.zeros((S,), jnp.int32)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        nr, _, _ = step(params, tok2,
                        jax.tree_util.tree_map(lambda x: x.copy(), reused),
                        zero, only1)
        nf, _, _ = step(params, tok2,
                        jax.tree_util.tree_map(lambda x: x.copy(), fresh),
                        zero, only1)
    assert int(nr[1]) == int(nf[1])
