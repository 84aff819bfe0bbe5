"""Autotuner validation: legality invariants, VMEM-budget discipline for
every registry arch (reduced mode), cache persistence, and interpret-mode
parity of tuned small-M tiles vs the kernels/ref.py oracles."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config, list_archs
from repro.core.quant import quantize, quantize_weight
from repro.kernels import autotune as AT
from repro.kernels import ops, ref


PROBLEMS = [
    # (m, k, n) spanning decode (small M) to prefill/train (large M)
    (8, 256, 128), (16, 4096, 4096), (32, 512, 1024),
    (64, 1024, 256), (128, 256, 128), (200, 300, 500),
    (1, 128, 128), (2048, 4096, 8192),
]


# ---------------------------------------------------------------------------
# legality invariants
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", AT.MODES)
@pytest.mark.parametrize("m,k,n", PROBLEMS)
def test_candidates_are_legal(m, k, n, mode):
    """Every enumerated candidate is lane/sublane aligned and fits the
    double-buffered working set in the VMEM budget; bk divides K (the
    kernels take a ragged block on N only), and is lane-aligned unless it
    spans the whole of K."""
    xd = AT.x_dtype_for(mode)
    cands = AT.enumerate_candidates(m, k, n, mode=mode)
    assert cands, f"no candidates for {(m, k, n)} {mode}"
    for c in cands:
        assert c.bm % AT.SUBLANE[xd] == 0, (c, xd)
        assert c.bn % AT.LANE == 0, c
        assert k % c.bk == 0, c
        if c.bk != k:
            assert c.bk % (256 if mode == "w8a8" else AT.LANE) == 0, c
        assert AT.vmem_bytes(c, mode=mode) <= AT.DEFAULT_VMEM_BUDGET, c
        assert AT.is_legal(c, mode=mode, k=k), c


@pytest.mark.parametrize("mode,x_dtype,m,want_bm", [
    ("w8a16", "f32", 8, 8),      # f32 acts: 8-sublane floor -> true GEMV tile
    ("w8a16", "bf16", 8, 16),    # bf16 acts: 16-sublane floor
    ("w8a8", "bf16", 32, 32),    # int8 acts: 32-sublane floor
    ("w8a16", "f32", 32, 32),
])
def test_ranked_best_respects_budget_and_beats_padding(mode, x_dtype, m,
                                                       want_bm):
    """The winner never exceeds the budget, and for decode-sized M it
    picks the smallest legal row tile instead of padding to 128 rows (the
    whole point of the small-M path)."""
    ranked = AT.rank_candidates(m, 4096, 4096, mode=mode, x_dtype=x_dtype)
    best = ranked[0]
    assert AT.vmem_bytes(best, mode=mode, x_dtype=x_dtype) \
        <= AT.DEFAULT_VMEM_BUDGET
    assert best.bm == want_bm, \
        f"decode M={m} should pick a {want_bm}-row tile, got {best}"


def test_out_dtype_tightens_bm_floor():
    """The (bm, bn) output tile is a real block: a bf16 output forbids
    8-row tiles even when the streamed x is f32."""
    best_f32 = AT.rank_candidates(8, 4096, 4096, mode="w8a16",
                                  x_dtype="f32", out_dtype="f32")[0]
    best_bf16 = AT.rank_candidates(8, 4096, 4096, mode="w8a16",
                                   x_dtype="f32", out_dtype="bf16")[0]
    assert best_f32.bm == 8
    assert best_bf16.bm == 16
    assert not AT.is_legal(AT.TileConfig(8, 128, 128), mode="w8a16",
                           x_dtype="f32", out_dtype="bf16")
    # distinct cache keys: a winner tuned for f32 output is never reused
    # for bf16 output
    assert AT.AutotuneCache.key(8, 4096, 4096, "w8a16", "f32", "f32",
                                True, "tpu") != \
        AT.AutotuneCache.key(8, 4096, 4096, "w8a16", "f32", "bf16",
                             True, "tpu")


def test_budget_excludes_oversized_configs():
    huge = AT.TileConfig(2048, 1024, 1024)
    assert AT.vmem_bytes(huge, mode="w8a16", x_dtype="f32") \
        > AT.DEFAULT_VMEM_BUDGET
    assert not AT.is_legal(huge, mode="w8a16", x_dtype="f32")
    # every enumerated shape stays inside VMEM even before the budget cap:
    # the candidate pools are sized so the working set can never approach
    # the physical 16 MiB, but the budget check is still the hard gate
    worst = AT.TileConfig(max(AT.BM_CANDIDATES), max(AT.BN_CANDIDATES),
                          max(AT.BK_CANDIDATES))
    assert AT.vmem_bytes(worst, mode="w8a16", x_dtype="f32") < AT.VMEM_BYTES


def test_registry_archs_within_vmem_budget(tmp_path):
    """For every registry arch (reduced mode) and every serving matmul at
    decode/prefill row counts, the autotuner never selects a config
    exceeding the VMEM budget — the ISSUE's acceptance criterion."""
    cache = AT.AutotuneCache(str(tmp_path / "autotune.json"))
    for name in list_archs():
        cfg = get_config(name).reduced()
        for row in AT.tune_arch(cfg, m_values=(8, 32), cache=cache):
            assert row["vmem_bytes"] <= AT.DEFAULT_VMEM_BUDGET, row
            tc = AT.TileConfig(row["bm"], row["bn"], row["bk"])
            assert AT.is_legal(tc, mode=row["mode"], k=row["k"]), row


# ---------------------------------------------------------------------------
# cost model sanity
# ---------------------------------------------------------------------------

def test_cost_model_penalizes_padding():
    """A 128-row tile on an 8-row problem costs strictly more than an
    8-row tile (16x the padded flops and x-bytes)."""
    c_small = AT.TileConfig(8, 256, 512)
    c_big = AT.TileConfig(128, 256, 512)
    assert AT.predicted_cost(8, 4096, 4096, c_small, x_dtype="f32") \
        < AT.predicted_cost(8, 4096, 4096, c_big, x_dtype="f32")


def test_cost_model_prefers_weight_reuse_at_large_m():
    """At train-sized M, tiny row tiles re-stream the weights M/bm times;
    the model must prefer larger bm."""
    best = AT.rank_candidates(2048, 4096, 4096, mode="w8a16")[0]
    assert best.bm >= 64, best


# ---------------------------------------------------------------------------
# JSON cache
# ---------------------------------------------------------------------------

def test_cache_roundtrip_and_schema(tmp_path):
    path = tmp_path / "autotune.json"
    cache = AT.AutotuneCache(str(path))
    tc = AT.best_config(8, 256, 128, mode="w8a16", x_dtype="f32",
                        backend="cpu", cache=cache)
    data = json.loads(path.read_text())
    assert data["schema_version"] == AT.SCHEMA_VERSION
    key = AT.AutotuneCache.key(8, 256, 128, "w8a16", "f32", "f32", True,
                               "cpu")
    assert data["entries"][key]["bm"] == tc.bm
    # a fresh cache object reads the persisted winner back
    again = AT.AutotuneCache(str(path)).get(key)
    assert again == tc


def test_cache_recovers_from_injected_partial_write(tmp_path):
    """A torn cache file (a writer that died mid-file, or a
    pre-atomic-discipline interleaving) is discarded on load — never
    fatal — and the next atomic put leaves a valid file again."""
    import os
    path = tmp_path / "autotune.json"
    tc = AT.TileConfig(16, 128, 128)
    AT.AutotuneCache(str(path)).put("k1", tc)
    text = path.read_text()
    path.write_text(text[:len(text) // 2])          # inject the tear
    torn = AT.AutotuneCache(str(path))
    assert torn.get("k1") is None                   # discarded, no crash
    torn.put("k2", tc)
    reread = json.loads(path.read_text())           # valid JSON again
    assert reread["schema_version"] == AT.SCHEMA_VERSION
    assert AT.AutotuneCache(str(path)).get("k2") == tc
    # the tmp staging file was replaced, not left behind (the .lock
    # sidecar for cross-process exclusion is expected)
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]


def test_cache_concurrent_writers_merge_not_clobber(tmp_path):
    """Two processes tuning different shapes against one cache file:
    the second writer's read-merge-replace must keep the first's entry
    (a plain rewrite of its own stale snapshot would drop it)."""
    path = str(tmp_path / "autotune.json")
    a, b = AT.AutotuneCache(path), AT.AutotuneCache(path)
    assert b.get("anything") is None       # b snapshots the (empty) file
    a.put("ka", AT.TileConfig(16, 128, 128))
    b.put("kb", AT.TileConfig(32, 256, 256))
    fresh = AT.AutotuneCache(path)
    assert fresh.get("ka") == AT.TileConfig(16, 128, 128)
    assert fresh.get("kb") == AT.TileConfig(32, 256, 256)


def test_cache_hit_skips_ranking(tmp_path, monkeypatch):
    cache = AT.AutotuneCache(str(tmp_path / "autotune.json"))
    first = AT.best_config(16, 512, 512, backend="cpu", cache=cache)
    monkeypatch.setattr(AT, "rank_candidates",
                        lambda *a, **k: pytest.fail("cache miss"))
    second = AT.best_config(16, 512, 512, backend="cpu", cache=cache)
    assert first == second


def test_measured_refinement_uses_timing_backend(tmp_path):
    """A timing backend re-ranks the analytic top candidates: make the
    analytically-worst of the top group the measured winner."""
    cache = AT.AutotuneCache(str(tmp_path / "autotune.json"))
    ranked = AT.rank_candidates(64, 1024, 1024, mode="w8a16")
    want = ranked[min(2, len(ranked) - 1)]
    times = {c: (0.0 if c == want else 1.0) for c in ranked}
    got = AT.best_config(64, 1024, 1024, mode="w8a16", backend="cpu",
                         cache=cache, measure=lambda c: times[c],
                         top_k_measure=3)
    assert got == want


# ---------------------------------------------------------------------------
# interpret-mode parity: tuned tiles vs the jnp oracles
# ---------------------------------------------------------------------------

def _rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


@given(st.sampled_from([8, 16, 32]),
       st.sampled_from([(256, 128), (384, 256), (512, 512)]))
@settings(max_examples=9, deadline=None)
def test_w8a16_tuned_small_m_matches_ref(m, kn):
    """Tuned small-M (GEMV-style) tiles through the real kernel body (the
    Pallas interpreter) agree with the oracle."""
    k, n = kn
    tc = AT.best_config(m, k, n, mode="w8a16", x_dtype="f32",
                        backend="interpret", cache=AT.AutotuneCache(""))
    keys = jax.random.split(jax.random.PRNGKey(m * 31 + k + n), 3)
    x = _rand(keys[0], (m, k))
    w = quantize_weight(_rand(keys[1], (k, n)))
    b = _rand(keys[2], (n,))
    got = ops.qmatmul(x, w, b, interpret=True, out_dtype=jnp.float32,
                      **tc.as_kwargs())
    want = ref.qmatmul_w8a16_ref(x, w.values, w.scale.reshape(-1), b,
                                 out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@given(st.sampled_from([8, 16, 32]),
       st.sampled_from([(256, 128), (512, 256)]))
@settings(max_examples=6, deadline=None)
def test_w8a8_tuned_small_m_matches_ref(m, kn):
    k, n = kn
    tc = AT.rank_candidates(m, k, n, mode="w8a8")[0]
    keys = jax.random.split(jax.random.PRNGKey(m + 7 * k + n), 3)
    x = _rand(keys[0], (m, k))
    xq = quantize(x, bits=8, axis=None)
    w = quantize_weight(_rand(keys[1], (k, n)))
    b = _rand(keys[2], (n,))
    got = ops.qmatmul(x, w, b, x_q=xq, interpret=True,
                      out_dtype=jnp.float32, **tc.as_kwargs())
    want = ref.qmatmul_w8a8_ref(xq.values, w.values, xq.scale,
                                w.scale.reshape(-1), b,
                                out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("m,k,n", [(128, 256, 128), (256, 512, 384)])
def test_w8a16_tuned_aligned_matches_ref(m, k, n):
    """128-aligned shapes through the default (autotuned) dispatch."""
    keys = jax.random.split(jax.random.PRNGKey(m + k + n), 2)
    x = _rand(keys[0], (m, k))
    w = quantize_weight(_rand(keys[1], (k, n)))
    got = ops.qmatmul(x, w, None, interpret=True, out_dtype=jnp.float32)
    want = ref.qmatmul_w8a16_ref(x, w.values, w.scale.reshape(-1), None,
                                 out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_bias_free_path_streams_no_bias_tile():
    """The conditional-operand rework: with bias=None the kernel call
    receives no bias operand at all (one fewer VMEM stream per tile)."""
    from repro.kernels import qmatmul as K
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    x = _rand(keys[0], (64, 256))
    w = quantize_weight(_rand(keys[1], (256, 128)))
    got = K.qmatmul_w8a16(x.astype(jnp.float32), w.values[None],
                          w.scale.reshape(1, 1, -1), jnp.int32(0), None,
                          bm=64, bn=128, bk=256, interpret=True,
                          out_dtype=jnp.float32)
    want = ref.qmatmul_w8a16_ref(x, w.values, w.scale.reshape(-1), None,
                                 out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
