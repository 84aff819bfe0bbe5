"""Speculative decoding through the slot engine: bit-for-bit acceptance.

The whole contract is one sentence — with ``spec_k > 0`` the engine's
committed output stream is byte-identical to the non-speculative
engine's, whatever the draft proposes — so every test here is some form
of equality against the plain engine or the sequential reference:

- per opted-in family (dense, windowless moe), greedy AND sampled, on
  200-request continuous-batching traces;
- a full-depth self-draft (``draft_layers = n_layers``) IS the target,
  so every proposal must be accepted (the acceptance upper bound);
- a garbage draft (same arch, different init) whose proposals are
  teacher-forced into the target cache and then rejected proves the
  rejected tail's KV writes are dead (decode-contract rule 7), paged
  and contiguous;
- preemption mid-speculation and a seeded FaultPlan (hypothesis-driven)
  compose with exact resume: in-flight proposals are uncommitted state;
- families whose decode state cannot rewind (recurrent, windowed,
  primed) are refused at construction, and the new accounting columns
  (``accepted_per_dispatch``, ``latency_per_token_s``) are exact.
"""
import dataclasses

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import engine as E
from repro.configs import get_config
from repro.core import batching as bt
from repro.models import registry as R

KEY = jax.random.PRNGKey(0)
SPEC_FAMILIES = ["starcoder2-3b", "qwen2-moe-a2.7b"]


def _trace(cfg, n=200, rate=3000.0, prompt_len=4, max_new=6, seed=0,
           **kw):
    return E.synthetic_requests(n, rate_per_s=rate, vocab=cfg.vocab,
                                prompt_len=prompt_len,
                                max_new_tokens=max_new, seed=seed, **kw)


@pytest.fixture(scope="module", params=SPEC_FAMILIES)
def family_setup(request):
    cfg = get_config(request.param).reduced()
    return cfg, R.init(KEY, cfg)


@pytest.fixture(scope="module")
def dense_setup():
    cfg = get_config("starcoder2-3b").reduced()
    return cfg, R.init(KEY, cfg)


# ---------------------------------------------------------------------------
# registry hooks
# ---------------------------------------------------------------------------

class TestRegistryHooks:
    def test_speculation_support_is_positional_kv_only(self):
        """Exactly the families whose decode state is rewindable
        positional KV opt in; recurrent state, sliding windows, and
        primed cross-attention are out."""
        want = {"starcoder2-3b": True, "qwen2-moe-a2.7b": True,
                "mixtral-8x22b": False,        # sliding window
                "mamba2-1.3b": False,          # recurrent ssm state
                "recurrentgemma-9b": False,    # recurrent + windowed
                "whisper-medium": False,       # primed cross-attention
                "llama-3.2-vision-90b": False}
        for name, ok in want.items():
            cfg = get_config(name).reduced()
            assert R.supports_speculation(cfg) == ok, name
            assert R.supports_self_draft(cfg) == ok, name

    def test_draft_config_truncates_and_renames(self):
        cfg = get_config("starcoder2-3b").reduced()
        d = R.draft_config(cfg, 1)
        assert d.n_layers == 1 and d.vocab == cfg.vocab
        assert d.name == cfg.name + "-draft1"
        with pytest.raises(ValueError):
            R.draft_config(cfg, 0)
        with pytest.raises(ValueError):
            R.draft_config(cfg, cfg.n_layers + 1)

    def test_draft_params_is_a_shared_view(self, dense_setup):
        """The self-draft tree slices the stacked layers and shares the
        embed/norm/unembed leaves by reference — no second checkpoint,
        no copy of the kept weights."""
        cfg, params = dense_setup
        dp = R.draft_params(cfg, params, 1)
        assert dp["embed"] is params["embed"]
        assert dp["ln_f"] is params["ln_f"]
        for a, b in zip(jax.tree_util.tree_leaves(dp["layers"]),
                        jax.tree_util.tree_leaves(params["layers"])):
            assert a.shape[0] == 1 and b.shape[0] == cfg.n_layers

    def test_draft_params_refuses_non_speculative_families(self):
        cfg = get_config("mamba2-1.3b").reduced()
        with pytest.raises(ValueError, match="self-draft"):
            R.draft_params(cfg, {}, 1)


# ---------------------------------------------------------------------------
# construction contract
# ---------------------------------------------------------------------------

class TestConstruction:
    def test_spec_needs_exactly_one_draft_source(self, dense_setup):
        cfg, params = dense_setup
        with pytest.raises(ValueError, match="exactly one"):
            E.Engine(cfg, params, spec_k=2)
        with pytest.raises(ValueError, match="exactly one"):
            E.Engine(cfg, params, spec_k=2, draft_layers=1,
                     draft=(cfg, params))
        with pytest.raises(ValueError, match="spec_k"):
            E.Engine(cfg, params, spec_k=-1)
        with pytest.raises(ValueError, match="spec_k >= 1"):
            E.Engine(cfg, params, draft_layers=1)

    def test_rejects_unrewindable_targets_and_drafts(self, dense_setup):
        cfg, params = dense_setup
        for name in ("mixtral-8x22b", "mamba2-1.3b"):
            bad = get_config(name).reduced()
            bad_params = R.init(KEY, bad)
            with pytest.raises(ValueError, match="rewindable"):
                E.Engine(bad, bad_params, spec_k=2, draft_layers=1)
            with pytest.raises(ValueError, match="rewindable"):
                E.Engine(cfg, params, spec_k=2, draft=(bad, bad_params))

    def test_rejects_vocab_mismatch(self, dense_setup):
        cfg, params = dense_setup
        dcfg = dataclasses.replace(cfg, name="wrong-vocab",
                                   vocab=cfg.vocab * 2)
        with pytest.raises(ValueError, match="vocab"):
            E.Engine(cfg, params, spec_k=2,
                     draft=(dcfg, R.init(KEY, dcfg)))


# ---------------------------------------------------------------------------
# bit-for-bit acceptance, per opted-in family
# ---------------------------------------------------------------------------

class TestBitForBit:
    def test_greedy_200_requests(self, family_setup):
        """Acceptance: the speculative engine's outputs on a 200-request
        continuous-batching trace equal the plain engine's byte for
        byte, and speculation actually pays (fewer ticks, > 1 token per
        emitting dispatch)."""
        cfg, params = family_setup
        reqs = _trace(cfg)
        plain = E.Engine(cfg, params, num_slots=4, max_seq=16).serve(reqs)
        spec = E.Engine(cfg, params, num_slots=4, max_seq=16,
                        spec_k=3, draft_layers=1).serve(reqs)
        assert spec.outputs() == plain.outputs()
        assert len(spec.results) == 200
        assert all(r.status == "ok" for r in spec.results)
        assert spec.generated_tokens == plain.generated_tokens
        assert spec.accepted_per_dispatch > 1.0
        assert spec.ticks < plain.ticks

    def test_sampled_200_requests(self, family_setup):
        """The same equality under temperature sampling: the verify
        scan's per-position fold_in(rng, position) keys reproduce the
        slot step's draws exactly, so acceptance stays bitwise beyond
        greedy."""
        cfg, params = family_setup
        rng = jax.random.PRNGKey(11)
        reqs = _trace(cfg, seed=1)
        plain = E.Engine(cfg, params, num_slots=4, max_seq=16,
                         temperature=0.7, rng=rng).serve(reqs)
        spec = E.Engine(cfg, params, num_slots=4, max_seq=16,
                        temperature=0.7, rng=rng,
                        spec_k=2, draft_layers=1).serve(reqs)
        assert spec.outputs() == plain.outputs()
        assert all(r.status == "ok" for r in spec.results)

    def test_full_depth_self_draft_accepts_everything(self, dense_setup):
        """draft_layers = n_layers makes the draft the target: every
        proposal must be accepted, so with max_new divisible by k+1
        every emitting dispatch commits exactly k+1 tokens."""
        cfg, params = dense_setup
        k = 3
        reqs = _trace(cfg, n=24, max_new=8, seed=2)
        plain = E.Engine(cfg, params, num_slots=4, max_seq=16).serve(reqs)
        spec = E.Engine(cfg, params, num_slots=4, max_seq=16,
                        spec_k=k, draft_layers=cfg.n_layers).serve(reqs)
        assert spec.outputs() == plain.outputs()
        assert spec.accepted_per_dispatch == pytest.approx(k + 1)
        assert spec.ticks < plain.ticks

    def test_cross_model_draft(self, dense_setup):
        """A separate draft checkpoint (different arch dims, same vocab)
        — the starcoder2-3b-drafts-for-qwen2-moe configuration."""
        cfg = get_config("qwen2-moe-a2.7b").reduced()
        params = R.init(KEY, cfg)
        dcfg, dparams = dense_setup
        assert dcfg.vocab == cfg.vocab
        reqs = _trace(cfg, n=40, seed=3)
        plain = E.Engine(cfg, params, num_slots=4, max_seq=16).serve(reqs)
        spec = E.Engine(cfg, params, num_slots=4, max_seq=16,
                        spec_k=2, draft=(dcfg, dparams)).serve(reqs)
        assert spec.outputs() == plain.outputs()


# ---------------------------------------------------------------------------
# rejected speculative KV writes are dead (decode-contract rule 7)
# ---------------------------------------------------------------------------

class TestSpeculativePoison:
    @pytest.mark.parametrize("paged", [False, True])
    def test_garbage_draft_cannot_corrupt_the_target(self, dense_setup,
                                                     paged):
        """A draft initialized from a different seed proposes tokens the
        target mostly rejects — yet every proposal WAS teacher-forced
        into the target cache at positions past the committed frontier
        before being rewound.  Byte-equality of the committed stream is
        the proof those speculative writes are dead: overwritten before
        any read can see them, in private blocks only (never shared or
        registered ones)."""
        cfg, params = dense_setup
        garbage = R.init(jax.random.PRNGKey(666), cfg)
        kw = dict(block_size=4, prefill_chunk=4) if paged else {}
        reqs = _trace(cfg, n=60, seed=4, prompt_len=6 if paged else 4,
                      shared_prefix_len=4 if paged else 0)
        plain = E.Engine(cfg, params, num_slots=4, max_seq=16,
                         **kw).serve(reqs)
        spec = E.Engine(cfg, params, num_slots=4, max_seq=16, spec_k=3,
                        draft=(cfg, garbage), **kw).serve(reqs)
        assert spec.outputs() == plain.outputs()
        # every dispatch still commits its bonus token even when every
        # proposal is rejected — the floor of the accounting identity
        assert spec.accepted_per_dispatch >= 1.0
        if paged:
            assert spec.leaked_blocks == 0
            assert spec.shared_block_hits > 0


# ---------------------------------------------------------------------------
# composition: preemption mid-speculation, faults, exact resume
# ---------------------------------------------------------------------------

class TestChaosComposition:
    def test_preemption_mid_speculation_resumes_exactly(self, dense_setup):
        """Slot preemption lands between speculative rounds with the
        draft cache mid-stream; on resume the draft frontier is rebuilt
        from zero (alloc resets it) and the committed output is still
        the never-preempted output."""
        cfg, params = dense_setup
        reqs = _trace(cfg, n=30, rate=2000.0, prompt_len=3, max_new=5,
                      seed=5,
                      priority=lambda rid: ("batch" if rid % 3 == 0
                                            else "interactive"))
        want = E.reference_outputs(cfg, params, reqs, max_seq=16)
        eng = E.Engine(cfg, params, num_slots=4, max_seq=16,
                       block_size=4, num_blocks=9, prefill_chunk=2,
                       spec_k=3, draft_layers=1)
        rep = eng.serve(reqs, preemption=True)
        assert rep.preempted > 0
        assert any(r.preemptions > 0 for r in rep.results)
        assert rep.outputs() == want
        assert all(r.status == "ok" for r in rep.results)
        assert rep.leaked_blocks == 0

    @given(st.integers(0, 1000))
    @settings(max_examples=5, deadline=None)
    def test_fault_plan_chaos_stays_bit_for_bit(self, seed):
        """Seeded dispatch faults, non-finite logits, and torn block-
        table rows against the speculating engine: any fault inside a
        speculative round discards the WHOLE round (in-flight proposals
        are uncommitted state), recovery rebuilds from the last
        committed token, and every ok request still matches the
        sequential reference."""
        cfg = get_config("starcoder2-3b").reduced()
        params = R.init(KEY, cfg)
        reqs = _trace(cfg, n=30, rate=8000.0, seed=6,
                      priority=lambda rid: bt.PRIORITY_CLASSES[rid % 2])
        want = E.reference_outputs(cfg, params, reqs, max_seq=16)
        plan = E.FaultPlan.random(seed=seed, n_faults=10, max_tick=200,
                                  num_slots=4)
        eng = E.Engine(cfg, params, num_slots=4, max_seq=16,
                       block_size=4, num_blocks=13, prefill_chunk=4,
                       spec_k=2, draft_layers=1)
        rep = eng.serve(reqs, preemption=True, fault_plan=plan)
        assert len(rep.results) == 30
        for r in rep.results:
            if r.status == "ok":
                assert r.tokens == want[r.rid], r.rid
        assert rep.leaked_blocks == 0


# ---------------------------------------------------------------------------
# accounting
# ---------------------------------------------------------------------------

class TestAccounting:
    def test_non_speculative_identity(self, dense_setup):
        """Without speculation every emitting dispatch commits exactly
        one token: accepted_per_dispatch is 1.0 EXACTLY, and the
        per-token latency mean is positive and finite."""
        cfg, params = dense_setup
        rep = E.Engine(cfg, params, num_slots=4, max_seq=16).serve(
            _trace(cfg, n=20, seed=7))
        assert rep.spec_k == 0
        assert rep.accepted_per_dispatch == 1.0
        assert 0.0 < rep.latency_per_token_s < float("inf")
        ok = [r for r in rep.results if r.status == "ok" and r.tokens]
        want = float(np.mean([r.latency_s / len(r.tokens) for r in ok]))
        assert rep.latency_per_token_s == pytest.approx(want)

    def test_speculative_tokens_counted_once(self, dense_setup):
        """Throughput counts committed tokens only — a rejected proposal
        never inflates generated_tokens or tokens_per_s."""
        cfg, params = dense_setup
        reqs = _trace(cfg, n=20, seed=8)
        plain = E.Engine(cfg, params, num_slots=4, max_seq=16).serve(reqs)
        spec = E.Engine(cfg, params, num_slots=4, max_seq=16, spec_k=3,
                        draft_layers=1).serve(reqs)
        assert spec.generated_tokens == plain.generated_tokens
        assert spec.generated_tokens == \
            sum(len(r.tokens) for r in spec.results)
        assert spec.spec_k == 3
