"""granite-4.0-h-small's family (``models/hybrid_moe.py``), its expert
share and its held-expert kernel, on the CPU at the ``reduced`` size:
two periods of Mamba, attention and Mamba mixers, each layer ending in
the MoE block, 2 experts held of 4 routed (top 2).

The reference is the benchmark's own (``chipbench/configs/
granite-4.0-h-small.py``, float32 at ``highest`` precision, nothing of
the program), on int8 weights from a seed in the program's layout.
"""
import dataclasses
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.qlinear import FP, W8A8, W8A16
from repro.core.quant import QTensor
from repro.kernels import ops
from repro.models import moe
from repro.models import registry as R

ROOT = Path(__file__).resolve().parents[1]
CFG = get_config("granite-4.0-h-small").reduced()


def _reference():
    path = ROOT / "chipbench" / "configs" / "granite-4.0-h-small.py"
    spec = importlib.util.spec_from_file_location("granite_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _to_program(tree):
    if isinstance(tree, tuple) and tree and tree[0] == "int8":
        return QTensor(values=tree[1], scale=tree[2], bits=8)
    if isinstance(tree, dict):
        return {k: _to_program(v) for k, v in tree.items()}
    return tree


@pytest.fixture(scope="module")
def served():
    """The tiny configuration file, the reference, its weights and the
    program's view of the same buffers."""
    conf = json.loads((ROOT / "chipbench" / "tests" / "data"
                       / "tiny-granite.json").read_text())
    ref = _reference()
    w = ref.make_weights(jax.random.PRNGKey(3), conf)
    cfg = dataclasses.replace(CFG, vocab=conf["vocab_size"])
    return conf, ref, w, _to_program(ref.program_tree(w)), cfg


def _decode(cfg, params, cache, tokens, index, active, mode=W8A16):
    return R.apply_decode(params, cfg, {"tokens": tokens,
                                        "cache_index": index}, cache,
                          mode=mode, active=active)


def test_reduced_keeps_both_mixers_and_a_share():
    assert set(CFG.layer_types) == {"mamba", "attention"}
    assert CFG.n_experts >= 4 and 2 <= CFG.n_held < CFG.n_experts
    full = get_config("granite-4.0-h-small")
    assert full.layer_types.count("attention") == 4
    assert [i for i, t in enumerate(full.layer_types)
            if t == "attention"] == [5, 15, 25, 35]
    assert (full.n_experts, full.n_held, full.top_k) == (72, 9, 10)


def test_prompt_then_decode_through_the_slot_cache_matches_reference(served):
    """Teacher-forced through the slot step's decode, one token a tick in
    row 0 of three (row 1 idle, row 2 a copy of row 0), the logits of
    every position against the reference's forward over the sequence.

    Reading: at each position the largest logit error over the
    reference's largest logit, averaged over the 24 positions.  Over
    weight seeds 3 to 10 the served W8A16 path (bf16 activations) reads
    0.011 to 0.023 (0.023 on this seed) and the program's W8A8 mode
    0.044 to 0.058 (0.045 here).  The limit 0.032 sits between the two
    with about 1.4x room on each side.  The mean, not the worst
    position: routing that flips at a near tie moves single positions of
    either mode by up to 0.2."""
    conf, ref, w, params, cfg = served
    n = 24
    toks = jax.random.randint(jax.random.PRNGKey(4), (n,), 0, cfg.vocab)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda w, t: ref.logits(w, t, conf))(
            w, toks))
    errs = {}
    for name, mode in (("w8a16", W8A16), ("w8a8", W8A8)):
        cache = R.init_cache(cfg, 3, 32)
        step = jax.jit(lambda p, t, ca, i, a, mode=mode:
                       _decode(cfg, p, ca, t, i, a, mode))
        got = []
        active = jnp.array([True, False, True])
        for t in range(n):
            tok = jnp.stack([toks[t], 0, toks[t]])[:, None]
            lg, cache = step(params, tok, cache,
                             jnp.array([t, 0, t], jnp.int32), active)
            np.testing.assert_array_equal(np.asarray(lg[0]),
                                          np.asarray(lg[2]))
            got.append(np.asarray(lg[0, 0], np.float32))
        errs[name] = (np.abs(np.stack(got) - want).max(-1).mean()
                      / np.abs(want).max())
    assert errs["w8a16"] < 0.032, errs
    assert errs["w8a8"] > 0.032, errs


def test_expert_shares_sum_to_the_uncut_layer():
    """4 ranks each holding 1 of 4 routed experts: their MoE outputs, the
    shared MLP (which every rank computes) counted once, add up to the
    layer that holds all 4.  Float32 weights and rows; the tolerance
    covers the order of float32 sums."""
    cfg = dataclasses.replace(CFG, experts_held=4, expert_offset=0)
    p = jax.tree_util.tree_map(
        lambda a: a[None], moe.init_expert_share(jax.random.PRNGKey(0),
                                                 cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, cfg.d_model))
    layer = jnp.int32(0)
    full = moe.expert_share(p, layer, x, cfg, mode=FP)
    shared = moe.shared_mlp(jax.tree_util.tree_map(lambda a: a[0],
                                                   p["shared"]),
                            x.reshape(-1, cfg.d_model), FP)
    total = -(cfg.n_experts - 1) * shared.reshape(x.shape)
    for r in range(cfg.n_experts):
        part = dict(p, experts=jax.tree_util.tree_map(
            lambda a: a[:, r:r + 1], p["experts"]))
        total = total + moe.expert_share(
            part, layer, x, dataclasses.replace(cfg, experts_held=1,
                                                expert_offset=r), mode=FP)
    np.testing.assert_allclose(np.asarray(total), np.asarray(full),
                               rtol=1e-5, atol=1e-5)


def test_share_drops_no_routed_token():
    """Every row gets every held expert it chose, however many rows pick
    the same one: the combine weights of the held experts are the
    top-k softmax's, at any number of rows."""
    cfg = dataclasses.replace(CFG, experts_held=4)
    router = jax.random.normal(jax.random.PRNGKey(2),
                               (cfg.d_model, cfg.n_experts))
    # 64 rows all routed alike: far past any capacity of 2 per expert
    x = jnp.tile(jax.random.normal(jax.random.PRNGKey(3),
                                   (1, cfg.d_model)), (64, 1))
    comb = moe.route_held(router, x, cfg)
    np.testing.assert_allclose(np.asarray(comb.sum(-1)), 1.0, rtol=1e-6)
    assert int((comb > 0).sum(-1).min()) == cfg.top_k


def _poisoned_cache(cfg, rows, s_max, seed):
    cache = R.init_cache(cfg, rows, s_max)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(cache))
    return {k: jax.random.normal(kk, v.shape).astype(v.dtype)
            for (k, v), kk in zip(sorted(cache.items()), keys)}


def test_inactive_row_keeps_state_conv_and_kv_bitwise(served):
    _, _, _, params, cfg = served
    cache = _poisoned_cache(cfg, 4, 16, 7)
    kept = jax.tree_util.tree_map(np.asarray, cache)
    active = jnp.array([True, False, True, False])
    _, new = _decode(cfg, params, cache, jnp.array([[5], [6], [7], [8]]),
                     jnp.array([3, 4, 0, 0], jnp.int32), active)
    for name, leaf in new.items():
        leaf, old = np.asarray(leaf), kept[name]
        for row in (1, 3):
            np.testing.assert_array_equal(leaf[:, row], old[:, row],
                                          err_msg=name)
        assert not np.array_equal(leaf[:, 0], old[:, 0]), name


def test_reused_slot_at_position_zero_sees_no_previous_tenant(served):
    """A row at position 0 gives the same logits whether its slot holds a
    previous tenant's state, conv tail and K/V or nothing."""
    _, _, _, params, cfg = served
    tok = jnp.array([[11], [11]])
    idx = jnp.zeros((2,), jnp.int32)
    active = jnp.ones((2,), bool)
    clean, _ = _decode(cfg, params, R.init_cache(cfg, 2, 16), tok, idx,
                       active)
    dirty, _ = _decode(cfg, params, _poisoned_cache(cfg, 2, 16, 9), tok,
                       idx, active)
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))


@pytest.mark.parametrize("per_expert_rows", [False, True])
def test_moe_w8a16_kernel_matches_oracle(per_expert_rows):
    """The Pallas kernel under the interpreter against its oracle, with
    one expert that has no rows (it gives zeros), from a stack of
    layers.  Both multiply bf16 rows by int8 weights exactly into
    float32 and differ only in the order of the sums."""
    nl, e, m, k, n = 3, 3, 5, 256, 384
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    w = QTensor(values=jax.random.randint(keys[0], (nl, e, k, n), -127,
                                          128).astype(jnp.int8),
                scale=jax.random.uniform(keys[1], (nl, e, 1, n)) / 1e3,
                bits=8)
    shape = (e, m, k) if per_expert_rows else (m, k)
    x = jax.random.normal(keys[2], shape).astype(jnp.bfloat16)
    counts = jnp.array([2, 0, 5])
    got = ops.moe_w8a16(x, w, 1, counts, interpret=True)
    want = ops.moe_w8a16(x, w, 1, counts)
    assert got.shape == (e, m, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(got[1]).any()


def test_decode_through_the_kernel_matches_the_oracle_path(served):
    """One decode step with the held-expert and w8a16 linear kernels
    under the interpreter equals the same step on their oracles."""
    _, _, _, params, cfg = served
    cache = _poisoned_cache(cfg, 2, 16, 11)
    args = (jnp.array([[3], [4]]), jnp.array([2, 0], jnp.int32),
            jnp.array([True, True]))
    interp = dataclasses.replace(W8A16, interpret=True)
    outs = []
    for mode in (W8A16, interp):
        lg, new = R.apply_decode(params, cfg, {"tokens": args[0],
                                               "cache_index": args[1]},
                                 cache, mode=mode, active=args[2])
        outs.append((np.asarray(lg, np.float32), new))
    np.testing.assert_allclose(outs[0][0], outs[1][0], rtol=2e-2,
                               atol=2e-2 * np.abs(outs[0][0]).max())


def test_engine_serves_and_reports_cache_bytes_by_kind(served):
    """The family serves through the normal engine (slot step, dispatch,
    registry) with slots reused, and the warm-up report gives its cache
    by kind: SSD state, conv tails and K/V."""
    from repro.engine import Engine, EngineRequest
    _, _, _, params, cfg = served
    eng = Engine(cfg, params, mode=W8A16, num_slots=2, max_seq=32)
    warm = eng.warmup()
    (tag, kinds), = warm.cache_bytes.items()
    n_m = cfg.layer_types.count("mamba")
    n_a = cfg.layer_types.count("attention")
    assert kinds == {
        "ssd_state": n_m * 2 * cfg.ssm_heads * cfg.ssm_headdim
        * cfg.ssm_state * 4,
        "conv": n_m * 2 * 3 * (cfg.d_inner + 2 * cfg.ssm_state) * 2,
        "kv": 2 * n_a * 2 * 32 * cfg.n_kv_heads * cfg.head_dim * 2}
    reqs = [EngineRequest(rid=i, prompt=tuple(range(3 + i, 8 + 2 * i)),
                          max_new_tokens=4 + i) for i in range(5)]
    rep = eng.serve(reqs)
    assert rep.compiles == 0
    assert sorted(len(r.tokens) for r in rep.results) == [4, 5, 6, 7, 8]
