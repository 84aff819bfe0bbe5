"""Paged KV cache: BlockPool invariants (property-tested), typed
admission rejection, memory-aware admission, shared-prefix reuse, and
the poison test proving a recycled block's stale bytes are never read.

The engine-level parity tests here are the paged analogue of
test_engine.py's bit-for-bit discipline: the paged engine must produce
EXACTLY the sequential reference's outputs while slots AND blocks are
reused across tenants and prefix blocks are shared refcounted between
concurrently-live requests."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import engine as E
from repro.configs import get_config
from repro.core import batching as bt
from repro.core.qlinear import FP
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
# BlockPool invariants
# ---------------------------------------------------------------------------

class TestBlockPool:
    def test_trash_block_reserved(self):
        pool = E.BlockPool(4, 2)
        bids = [pool.alloc() for _ in range(3)]
        assert 0 not in bids and sorted(bids) == [1, 2, 3]

    @given(st.integers(2, 9))
    @settings(max_examples=8, deadline=None)
    def test_alloc_free_roundtrip_restores_pool(self, num_blocks):
        """Allocating the whole pool and releasing it restores the free
        list exactly; a fresh alloc succeeds again."""
        pool = E.BlockPool(num_blocks, 4)
        bids = [pool.alloc() for _ in range(num_blocks - 1)]
        assert pool.free_blocks == 0
        assert pool.used_blocks == num_blocks - 1
        for b in bids:
            pool.release(b)
        assert pool.free_blocks == num_blocks - 1
        assert all(rc == 0 for rc in pool.refcounts)
        assert pool.alloc() > 0

    @given(st.integers(2, 6))
    @settings(max_examples=6, deadline=None)
    def test_exhaustion_raises_without_corrupting(self, num_blocks):
        """An over-allocation raises; the pool state is untouched (no
        refcount moved, nothing popped)."""
        pool = E.BlockPool(num_blocks, 4)
        bids = [pool.alloc() for _ in range(num_blocks - 1)]
        before = list(pool.refcounts)
        with pytest.raises(RuntimeError, match="exhausted"):
            pool.alloc()
        assert pool.refcounts == before
        for b in bids:
            pool.release(b)
        assert pool.free_blocks == num_blocks - 1

    def test_refcount_never_negative(self):
        pool = E.BlockPool(4, 2)
        b = pool.alloc()
        pool.release(b)
        with pytest.raises(RuntimeError, match="never go negative"):
            pool.release(b)               # already free
        with pytest.raises(RuntimeError):
            pool.release(0)               # the trash block has no refs
        with pytest.raises(RuntimeError):
            pool.ref(b)                   # dead block cannot gain refs

    def test_sharing_lifecycle(self):
        """register -> lookup -> ref; the LAST release evicts the hash
        entry, so a recycled block can never be found by lookup."""
        pool = E.BlockPool(4, 2)
        b = pool.alloc()
        key = ((), (5, 6))
        pool.register(key, b)
        assert pool.lookup(key) == b
        pool.ref(b)                       # second tenant
        pool.release(b)                   # first tenant retires
        assert pool.lookup(key) == b      # still live: one ref left
        pool.release(b)                   # last ref
        assert pool.lookup(key) is None
        with pytest.raises(RuntimeError, match="dead"):
            pool.register(key, b)         # dead blocks cannot publish
        b2 = pool.alloc()                 # recycled
        assert pool.refcounts[b2] == 1

    @given(st.integers(0, 5))
    @settings(max_examples=6, deadline=None)
    def test_random_ops_keep_invariants(self, seed):
        """Any interleaving of alloc/ref/release keeps refcounts >= 0 and
        held + free == usable blocks."""
        rng = np.random.default_rng(seed)
        pool = E.BlockPool(6, 4)
        live = []                         # one entry per outstanding ref
        for _ in range(60):
            op = rng.integers(0, 3)
            if op == 0 and pool.free_blocks:
                live.append(pool.alloc())
            elif op == 1 and live:
                bid = live[rng.integers(len(live))]
                pool.ref(bid)
                live.append(bid)
            elif op == 2 and live:
                pool.release(live.pop(rng.integers(len(live))))
            assert all(rc >= 0 for rc in pool.refcounts)
            held = sum(1 for rc in pool.refcounts if rc > 0)
            assert held + pool.free_blocks == pool.num_blocks - 1


# ---------------------------------------------------------------------------
# typed admission rejection
# ---------------------------------------------------------------------------

class TestRequestTooLong:
    def test_is_a_value_error(self):
        assert issubclass(E.RequestTooLong, ValueError)

    def test_slot_pool_validates_max_seq(self):
        pool = E.SlotPool(2, max_seq=8)
        with pytest.raises(E.RequestTooLong, match="cache positions"):
            pool.alloc(0, tuple(range(1, 7)), 4, now=0.0, arrival_s=0.0)
        # within budget: fine
        st_ = pool.alloc(1, (1, 2, 3), 5, now=0.0, arrival_s=0.0)
        assert st_.rid == 1

    def test_engine_rejects_oversized_request(self, dense_setup):
        cfg, params = dense_setup
        eng = E.Engine(cfg, params, num_slots=2, max_seq=16)
        bad = [E.EngineRequest(rid=0, prompt=tuple(range(1, 15)),
                               max_new_tokens=8)]
        with pytest.raises(E.RequestTooLong, match="cache positions"):
            eng.serve(bad)

    def test_paged_engine_rejects_unservable_block_demand(self,
                                                          dense_setup):
        """A request needing more blocks than the whole pool holds can
        never be admitted (it would wait forever): typed rejection up
        front, not a hang."""
        cfg, params = dense_setup
        eng = E.Engine(cfg, params, num_slots=2, max_seq=16,
                       block_size=4, num_blocks=3)       # 2 usable blocks
        bad = [E.EngineRequest(rid=0, prompt=(1, 2, 3, 4, 5, 6),
                               max_new_tokens=6)]        # needs 3 blocks
        with pytest.raises(E.RequestTooLong, match="KV blocks"):
            eng.serve(bad)

    def test_engine_config_validation(self, dense_setup):
        cfg, params = dense_setup
        with pytest.raises(ValueError, match="power of two"):
            E.Engine(cfg, params, block_size=3)
        with pytest.raises(ValueError, match="block_size"):
            E.Engine(cfg, params, num_blocks=8)
        scfg = get_config("mamba2-1.3b").reduced()
        with pytest.raises(ValueError, match="paged"):
            E.Engine(scfg, R.init(KEY, scfg), block_size=4)


# ---------------------------------------------------------------------------
# memory-aware admission policy
# ---------------------------------------------------------------------------

class TestMemoryAwareAdmission:
    def _policy(self):
        return bt.AdmissionPolicy(lambda b: 0.0, max_batch=8,
                                  max_wait_s=0.0)

    def test_costs_shrink_batch_to_budget(self):
        act = self._policy().decide(0.0, [float("inf")] * 4,
                                    costs=[3, 3, 3, 3], budget=7)
        assert act.launch and act.batch == 2      # 3 + 3 <= 7 < 9

    def test_unaffordable_head_waits(self):
        act = self._policy().decide(0.0, [float("inf")] * 2,
                                    next_arrival=1.5,
                                    costs=[10, 1], budget=4)
        assert not act.launch and act.wait_until == 1.5

    def test_no_costs_is_unchanged(self):
        a = self._policy().decide(0.0, [float("inf")] * 4)
        b = self._policy().decide(0.0, [float("inf")] * 4,
                                  costs=None, budget=None)
        assert (a.launch, a.batch) == (b.launch, b.batch) == (True, 4)


# ---------------------------------------------------------------------------
# shared-prefix trace synthesis
# ---------------------------------------------------------------------------

class TestSharedPrefixTraces:
    def test_prefix_identical_across_requests(self):
        reqs = E.synthetic_requests(8, rate_per_s=100.0, vocab=64,
                                    prompt_len=6, shared_prefix_len=4)
        heads = {r.prompt[:4] for r in reqs}
        tails = {r.prompt[4:] for r in reqs}
        assert len(heads) == 1 and len(tails) == 8

    def test_default_reproduces_old_prompts(self):
        a = E.synthetic_requests(4, rate_per_s=100.0, vocab=64,
                                 prompt_len=5)
        b = E.synthetic_requests(4, rate_per_s=100.0, vocab=64,
                                 prompt_len=5, shared_prefix_len=0)
        assert [r.prompt for r in a] == [r.prompt for r in b]
        assert a[0].prompt == tuple(1 + (0 * 7 + 3 * j) % 63
                                    for j in range(5))

    def test_validation(self):
        with pytest.raises(ValueError, match="shared_prefix_len"):
            E.synthetic_requests(2, rate_per_s=1.0, vocab=8,
                                 prompt_len=4, shared_prefix_len=5)


# ---------------------------------------------------------------------------
# poison: stale bytes in recycled blocks are never read
# ---------------------------------------------------------------------------

class TestPoisonedBlocks:
    def test_new_tenant_never_reads_stale_block_bytes(self, dense_setup):
        """Fill EVERY physical block (trash included) with finite garbage
        — a previous tenant's worst-case leftovers — then serve one
        request through freshly 'allocated' blocks with the raw paged
        steps.  Greedy outputs must equal the sequential reference: every
        read past the row's frontier (and every trash-block byte) is
        masked, so the garbage is unreachable."""
        cfg, params = dense_setup
        prompt, gen = (3, 1, 4, 1, 5), 4
        req = E.EngineRequest(rid=0, prompt=prompt, max_new_tokens=gen)
        want = E.reference_outputs(cfg, params, [req], max_seq=16)[0]

        cache = dict(R.init_paged_cache(cfg, 2, 16, 4, 9))
        for k in cache:
            if k == "block_tables":
                continue
            poison = 77 if cache[k].dtype == jnp.int8 else 3.5
            cache[k] = jnp.full_like(cache[k], poison)
        tables = np.zeros((2, 4), np.int32)
        tables[0] = [1, 2, 3, 4]          # slot 0's "new" blocks
        cache["block_tables"] = jnp.asarray(tables)

        chunk = ST.jit_prefill_chunk_step(
            ST.make_prefill_chunk_step(cfg, mode=FP, chunk=4))
        step = ST.jit_slot_decode_step(ST.make_slot_decode_step(cfg))
        cache = chunk(params, jnp.asarray(prompt[:4], jnp.int32), cache,
                      jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
                      jnp.asarray(4, jnp.int32))
        tokens = np.zeros((2, 1), np.int32)
        tokens[0, 0] = prompt[4]
        index = jnp.asarray([4, 0], jnp.int32)
        active = jnp.asarray([True, False])
        got = []
        for _ in range(gen):
            nxt, cache, index = step(params, jnp.asarray(tokens), cache,
                                     index, active)
            tok = int(np.asarray(nxt)[0])
            got.append(tok)
            tokens[0, 0] = tok
        assert got == want


# ---------------------------------------------------------------------------
# engine-level paged parity
# ---------------------------------------------------------------------------

class TestPagedEngineParity:
    def test_shared_prefix_parity_with_live_sharers(self, dense_setup):
        """Paged engine vs sequential reference, bit-for-bit, on a trace
        where later requests share the earlier ones' prefix blocks WHILE
        those are still decoding — parity proves registered blocks are
        immutable under sharing (copy-on-extend, no mutation)."""
        cfg, params = dense_setup
        reqs = E.synthetic_requests(24, rate_per_s=2000.0, vocab=cfg.vocab,
                                    prompt_len=6, max_new_tokens=5,
                                    shared_prefix_len=4)
        want = E.reference_outputs(cfg, params, reqs, max_seq=16)
        eng = E.Engine(cfg, params, num_slots=4, max_seq=16,
                       prefill_chunk=4, block_size=4)
        rep = eng.serve(reqs, clock="virtual", tick_s=1e-3)
        assert rep.outputs() == want
        assert rep.shared_block_hits > 0
        assert rep.prefill_tokens_skipped == \
            rep.shared_block_hits * eng.block_size
        assert rep.block_size == 4 and rep.kv_hbm_bytes > 0
        assert 0.0 < rep.mean_block_util <= 1.0
        assert 0.0 < rep.shared_hit_rate < 1.0

    def test_blocks_limited_admission_completes(self, dense_setup):
        """More slots than the block budget can fill contiguously: the
        memory-aware policy holds requests until blocks drain, never
        overruns the pool, and still finishes the trace bit-for-bit."""
        cfg, params = dense_setup
        reqs = E.synthetic_requests(12, rate_per_s=5000.0, vocab=cfg.vocab,
                                    prompt_len=6, max_new_tokens=5)
        want = E.reference_outputs(cfg, params, reqs, max_seq=16)
        rep = E.Engine(cfg, params, num_slots=8, max_seq=16,
                       prefill_chunk=4, block_size=4,
                       num_blocks=17).serve(reqs, clock="virtual",
                                            tick_s=1e-3)
        assert rep.outputs() == want and len(rep.results) == 12
        assert rep.peak_blocks_used <= 16
        assert max(rep.occupancy) > 4     # beyond 4 contiguous rows' worth

    def test_moe_paged_parity(self):
        cfg = get_config("qwen2-moe-a2.7b").reduced()
        params = R.init(KEY, cfg)
        reqs = E.synthetic_requests(6, rate_per_s=2000.0, vocab=cfg.vocab,
                                    prompt_len=6, max_new_tokens=4)
        want = E.reference_outputs(cfg, params, reqs, max_seq=16)
        rep = E.Engine(cfg, params, num_slots=4, max_seq=16,
                       block_size=4).serve(reqs, clock="virtual",
                                           tick_s=1e-3)
        assert rep.outputs() == want

    def test_prime_family_shares_only_on_matching_source(self):
        """encdec prefixes are fingerprinted by the request SOURCE as
        well as the tokens: identical prompts with different sources must
        not share blocks (their self-KV differs through cross-attention),
        while identical sources do share — parity holds either way."""
        cfg = get_config("whisper-medium").reduced()
        params = R.init(KEY, cfg)
        shape = R.source_shape(cfg)
        reqs = E.synthetic_requests(6, rate_per_s=2000.0, vocab=cfg.vocab,
                                    prompt_len=6, max_new_tokens=4,
                                    shared_prefix_len=6,
                                    source_shape=shape)
        eng = E.Engine(cfg, params, num_slots=2, max_seq=16,
                       prefill_chunk=4, block_size=4)
        rep = eng.serve(reqs, clock="virtual", tick_s=1e-3)
        assert rep.outputs() == E.reference_outputs(cfg, params, reqs,
                                                    max_seq=16)
        assert rep.shared_block_hits == 0     # sources differ per rid
        same = [dataclasses.replace(r, source=np.asarray(reqs[0].source))
                for r in reqs]
        rep2 = eng.serve(same, clock="virtual", tick_s=1e-3)
        assert rep2.outputs() == E.reference_outputs(cfg, params, same,
                                                     max_seq=16)
        assert rep2.shared_block_hits > 0
