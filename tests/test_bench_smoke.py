"""Guard for every future perf PR: `benchmarks/run.py --smoke --bench-out`
exits 0 offline and the BENCH JSON schema is stable."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

REQUIRED_KINDS = {"tokens_per_s", "service_time", "chosen_tile",
                  "kernel_bench", "engine"}
ROW_KEYS = {
    "tokens_per_s": {"arch", "batch", "num_tokens", "tokens_per_s",
                     "seconds"},
    "service_time": {"arch", "batch", "seconds"},
    "chosen_tile": {"arch", "op", "m", "k", "n", "mode", "bm", "bn", "bk",
                    "vmem_bytes"},
    "kernel_bench": {"name", "us_per_call", "derived"},
    "engine": {"arch", "family", "rate", "n_requests", "num_slots",
               "p99_s", "tokens_per_s", "mean_occupancy", "ticks",
               "admissions_while_busy", "occupancy_curve",
               "prefill_chunk", "mean_ttft_s", "p99_ttft_s",
               "block_size", "num_blocks", "kv_hbm_bytes",
               "peak_blocks_used", "mean_block_util", "shared_block_hits",
               "shared_hit_rate", "prefill_tokens_skipped",
               "effective_concurrency",
               # overload robustness: per-SLO-class tails + goodput
               "class_p99_latency_s", "class_mean_ttft_s",
               "class_p99_ttft_s", "goodput_tokens_per_s",
               "slo_attainment", "preempted", "dropped", "failed",
               "unfinished",
               # speculative decoding: draft-and-verify accounting
               "spec_k", "draft_layers", "accepted_per_dispatch",
               "latency_per_token_s",
               # multi-model multiplexing: the row's lane label plus
               # per-model tail/goodput/occupancy columns (empty dicts
               # on single-model rows)
               "model", "model_p99_s", "model_mean_ttft_s",
               "model_goodput_tokens_per_s", "model_mean_occupancy",
               # scale-out: replica/tensor-parallel fleet columns
               # (1/1/{} on ordinary single-engine rows)
               "replicas", "tp", "replica_occupancy"},
}


@pytest.fixture(scope="module")
def bench_doc(tmp_path_factory):
    out = tmp_path_factory.mktemp("bench") / "BENCH_serving.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(REPO, "src"), REPO,
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    env.setdefault("REPRO_AUTOTUNE_CACHE",
                   str(tmp_path_factory.mktemp("cache") / "autotune.json"))
    r = subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"), "--smoke",
         "--bench-out", str(out)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"--smoke failed:\n{r.stdout}\n{r.stderr}"
    assert "smoke OK" in r.stdout
    # satellite: kernel_bench rows ride along in the --smoke output
    assert "kernel/qmatmul_" in r.stdout
    # satellite: --smoke runs one short continuous-batching engine trace
    # (sequential-reference parity + append-path kernel parity, offline)
    assert "[engine] smoke:" in r.stdout
    assert "parity OK" in r.stdout
    # satellite: --smoke runs the speculative gate (full-depth self-draft
    # chaos arm + garbage draft + non-spec control, all bit-for-bit)
    assert "[spec] smoke:" in r.stdout
    # satellite: --smoke runs the multi-model gate (two families on one
    # engine under chaos, per-model parity + occupancy consolidation)
    assert "[multiplex] smoke:" in r.stdout
    # satellite: --smoke runs the fleet gate (2 replicas x 2 models
    # behind the router, per-model parity, zero leaked blocks)
    assert "[router] smoke:" in r.stdout
    # satellite: --smoke runs the sharded-executor parity gate (tp=1
    # conformance always; multi-device skips gracefully on 1 device)
    assert "[sharded] smoke:" in r.stdout
    return json.loads(out.read_text())


def test_schema_stable(bench_doc):
    assert bench_doc["schema_version"] == 1
    assert "backend" in bench_doc
    rows = bench_doc["rows"]
    kinds = {row["kind"] for row in rows}
    assert REQUIRED_KINDS <= kinds, kinds
    for row in rows:
        want = ROW_KEYS.get(row["kind"])
        if want:
            assert want <= set(row), (row["kind"], row)


def test_rows_are_sane(bench_doc):
    from repro.kernels import autotune as AT
    for row in bench_doc["rows"]:
        if row["kind"] == "tokens_per_s":
            assert row["tokens_per_s"] > 0
        elif row["kind"] == "service_time":
            assert row["seconds"] > 0
        elif row["kind"] == "chosen_tile":
            # the autotuner never ships a config exceeding the VMEM budget
            assert row["vmem_bytes"] <= AT.DEFAULT_VMEM_BUDGET
            tc = AT.TileConfig(row["bm"], row["bn"], row["bk"])
            assert AT.is_legal(tc, mode=row["mode"], k=row["k"]), row
        elif row["kind"] == "engine":
            assert row["p99_s"] > 0 and row["tokens_per_s"] > 0
            assert 0 < row["mean_occupancy"] <= 1
            assert row["admissions_while_busy"] >= 0
            assert all(0 <= a <= row["num_slots"]
                       for a in row["occupancy_curve"])
            assert 0 < row["mean_ttft_s"] <= row["p99_s"]
            assert row["kv_hbm_bytes"] > 0
            assert row["effective_concurrency"] > 0
            if row["block_size"]:             # a paged-engine row
                assert row["num_blocks"] >= 2
                assert 0 < row["peak_blocks_used"] < row["num_blocks"]
                assert 0 < row["mean_block_util"] <= 1
                assert 0 <= row["shared_hit_rate"] < 1
            else:
                assert row["peak_blocks_used"] == 0
                assert row["shared_block_hits"] == 0
            # speculative accounting: apd is exactly 1.0 without a
            # draft (one committed token per dispatch, by construction)
            # and can only improve on it with one
            assert row["latency_per_token_s"] > 0
            if row["spec_k"] == 0:
                assert row["accepted_per_dispatch"] == 1.0
                assert row["draft_layers"] == 0
            else:
                assert row["accepted_per_dispatch"] >= 1.0
                assert row["draft_layers"] >= 1


def test_paged_engine_row_present(bench_doc):
    """The paged-KV trajectory row: block-table decode with a shared
    system prompt, so block reuse shows up in the memory columns."""
    paged = [row for row in bench_doc["rows"]
             if row["kind"] == "engine" and row["block_size"]]
    assert paged, "no paged engine row in the trajectory JSON"
    assert any(row["shared_block_hits"] > 0 for row in paged)
    assert any(row["prefill_tokens_skipped"] > 0 for row in paged)


def test_speculative_rows_beat_their_pair(bench_doc):
    """The perf story this PR ships: the speculative rows share their
    trace with a non-speculative row at the same (arch, rate), so the
    ticks column is directly comparable — a self-draft config must
    commit > 1 token per verify dispatch and finish the trace in
    strictly fewer engine ticks."""
    eng = [r for r in bench_doc["rows"] if r["kind"] == "engine"]
    spec = [r for r in eng if r["spec_k"] > 0]
    assert spec, "no speculative engine row in the trajectory JSON"
    assert any(r["accepted_per_dispatch"] > 1.0 for r in spec)
    for row in spec:
        pair = [r for r in eng
                if r["spec_k"] == 0 and r["arch"] == row["arch"]
                and r["rate"] == row["rate"]
                and r["n_requests"] == row["n_requests"]
                and not r["block_size"] and "+" not in r["arch"]]
        assert pair, f"speculative row has no non-spec pair: {row['arch']}"
        if row["accepted_per_dispatch"] > 1.0:
            assert row["ticks"] < min(r["ticks"] for r in pair), row


def test_multiplexed_rows_consolidate_occupancy(bench_doc):
    """The multi-model trajectory rows: two ``+dedicated`` rows (one
    engine per lane) and at least one ``+2model`` row (both lanes
    multiplexed) at the SAME per-model offered rates.  The multiplexed
    row must carry per-model columns for both lanes and beat either
    dedicated row's occupancy — the consolidation the shared slot lease
    exists for."""
    eng = [r for r in bench_doc["rows"] if r["kind"] == "engine"]
    ded = [r for r in eng if r["arch"].endswith("+dedicated")]
    mux = [r for r in eng if r["arch"].endswith("+2model")]
    assert mux, "no multiplexed engine row in the trajectory JSON"
    assert {r["model"] for r in ded} == {"a", "b"}
    for row in ded:
        # dedicated single-model engines have no per-model breakdown
        assert row["model_mean_occupancy"] == {}
        assert row["model_p99_s"] == {}
    for row in mux:
        assert row["model"] == "a+b"
        assert set(row["model_mean_occupancy"]) == {"a", "b"}
        assert set(row["model_p99_s"]) == {"a", "b"}
        assert all(v > 0 for v in row["model_p99_s"].values())
        assert all(v > 0 for v in
                   row["model_goodput_tokens_per_s"].values())
        # per-lane occupancy fractions share the leased-slot
        # denominator, so they sum to the row's combined occupancy
        assert sum(row["model_mean_occupancy"].values()) == \
            pytest.approx(row["mean_occupancy"])
        assert row["mean_occupancy"] > max(r["mean_occupancy"]
                                           for r in ded)
    # ordinary single-model rows stay unlabelled
    assert all(r["model"] is None for r in eng
               if "+dedicated" not in r["arch"]
               and "+2model" not in r["arch"])


def test_router_row_carries_fleet_columns(bench_doc):
    """The ``+router`` trajectory row: the same engine trace behind the
    replica router.  It must carry the fleet columns (replicas, tp,
    per-replica occupancy for every replica) while every ordinary row
    keeps the single-engine defaults — the schema change is invisible
    outside the fleet rows."""
    eng = [r for r in bench_doc["rows"] if r["kind"] == "engine"]
    routed = [r for r in eng if r["arch"].endswith("+router")]
    assert routed, "no +router engine row in the trajectory JSON"
    for row in routed:
        assert row["replicas"] >= 2 and row["tp"] >= 1
        assert len(row["replica_occupancy"]) == row["replicas"]
        assert all(0 < v <= 1 for v in row["replica_occupancy"].values())
        assert row["p99_s"] > 0 and row["tokens_per_s"] > 0
    for row in eng:
        if not row["arch"].endswith("+router"):
            assert row["replicas"] == 1 and row["tp"] == 1
            assert row["replica_occupancy"] == {}


def test_engine_rows_cover_all_decode_families(bench_doc):
    """The paper's all-NN-families serving argument: EVERY registry
    family serves through the slot engine and lands in the trajectory
    JSON — including encdec/vlm, whose rows decode behind per-slot
    primed cross-K/V (their ttft includes the prime dispatch)."""
    fams = {row["family"] for row in bench_doc["rows"]
            if row["kind"] == "engine"}
    assert {"dense", "moe", "ssm", "hybrid", "encdec", "vlm"} <= fams, fams
