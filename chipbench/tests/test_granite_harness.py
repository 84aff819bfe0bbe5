"""``correct`` on a CPU-sized granite-4.0-h-small: true for the program
as it is, false when the timed path is broken underneath.

The runs drive the whole harness (weights, engine, warm-up, the timed
serve, the reference check) with the look for a chip skipped, on the
``reduced`` variant of the configuration (two periods of Mamba,
attention and Mamba mixers, 2 experts held of 4 routed) checked by the
configuration's own reference, ``configs/granite-4.0-h-small.py``.
Faults are planted in the engine after warm-up, so they sit on the path
the window drives.

The limit (``tests/data/tiny-granite.json``): over eight seeds on the
CPU the program's widest gap read 0.00012 to 0.0101 (a routing flip at
a near tie gives the largest), so 0.03 leaves it three times its worst
reading; a planted fault reads the gap to an unrelated token, tens of
times that.
"""
import time
from pathlib import Path

import pytest

import harness
from test_correct import state_unchanged, token_altered

DATA = Path(__file__).resolve().parent / "data"
SPEC = {
    "configs": [{"name": "tiny-granite",
                 "file": "chipbench/tests/data/tiny-granite.json"}],
    "workloads": [{"name": "tiny-granite.mix", "config": "tiny-granite",
                   "traffic": "tiny-mix", "chips": 1}],
    "end_to_end": [{"name": "ttft_p95_s", "unit": "s"},
                   {"name": "setup_s", "unit": "s"}],
    "per_layer": [],
}


def run(seed, patch=None):
    return harness.run(["--workload", "tiny-granite.mix", "--seed",
                        str(seed), "--seconds", "1.5", "--trace", "0"],
                       t_start=time.perf_counter(), require_chip=False,
                       spec=SPEC, traffic_dir=DATA, patch=patch)


def test_sound_program_is_correct():
    out = run(2**31 + 99)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 10
    assert set(out["metrics"]) == {"ttft_p95_s", "setup_s"}


@pytest.mark.parametrize("fault", [state_unchanged, token_altered])
def test_broken_path_is_not_correct(fault):
    out = run(5, patch=fault)
    assert not out["correct"], out["checks"]
    gap = out["checks"]["widest_logit_gap"]
    assert gap["value"] > gap["limit"]


def _moe_reader():
    return harness.load_module(
        harness.metric_file("moe_w8a16_roofline.lat")).read


def test_moe_roofline_reads_held_expert_bytes():
    """Two passes over 2 layers of 3 held experts, each pass 16 rows:
    8 calls (up and down projection per layer per pass), their least
    time the experts' int8 weights, float32 scales and bf16 rows over
    HBM bandwidth (both projections are bound by bytes here)."""
    from peaks import peaks
    pk = peaks("TPU v5 lite")
    linears = [("mamba.in_proj", 64, 64)] + [
        lin for e in range(3) for lin in ((f"expert{e}.gate_up", 512, 256),
                                          (f"expert{e}.down", 128, 512))]
    run = {"linears": linears, "layers": 2, "peaks": pk,
           "trace": {"tokens": 32, "op_count": {"moe_w8a16.3": 4,
                                                "moe_w8a16.7": 4,
                                                "qmatmul_w8a16.1": 9},
                     "op_time_s": {"moe_w8a16.3": 1e-3, "moe_w8a16.7": 1e-3,
                                   "qmatmul_w8a16.1": 5.0}}}
    per_pass = 2 * 3 * sum(k * n + 4 * n + 2 * 16 * (k + n)
                           for k, n in ((512, 256), (128, 512)))
    want = 2 * per_pass / pk["hbm_bytes_per_s"] / 2e-3 * 100
    assert _moe_reader()(run) == pytest.approx(want)


def test_moe_roofline_left_out_without_its_kernel_or_experts():
    run = {"linears": [("expert0.gate_up", 8, 8)], "layers": 1,
           "peaks": {}, "trace": {"tokens": 4, "op_count": {},
                                  "op_time_s": {}}}
    assert _moe_reader()(run) is None
    run["linears"] = [("in_proj", 8, 8)]
    run["trace"]["op_count"] = {"moe_w8a16.1": 2}
    run["trace"]["op_time_s"] = {"moe_w8a16.1": 1e-3}
    assert _moe_reader()(run) is None
    assert _moe_reader()({"trace": None}) is None


def test_reference_counts_the_chips_share():
    """The published configuration's widths as the reference reads them:
    36 Mamba and 4 attention layers, 9 held of 72, top 10; the routed
    FLOPs a token are top-k x held / E experts'."""
    import json as _json
    root = Path(__file__).resolve().parents[1]
    conf = _json.loads((root / "configs" / "granite-4.0-h-small.json")
                       .read_text())
    ref = harness.load_module(root / "configs" / "granite-4.0-h-small.py")
    d = ref.dims(conf)
    assert (d["L"], d["Lm"], d["La"], d["E"], d["held"], d["k"]) == (
        40, 36, 4, 72, 9, 10)
    experts = [ln for ln in ref.linears(conf) if ln[0].startswith("expert")]
    assert len(experts) == 18
    assert sum(k * n for _, k, n in experts) == 9 * 3 * 4096 * 768
    held_share = ref.token_flops(conf, 0) - ref.token_flops(
        dict(conf, num_local_experts=0), 0)
    assert held_share == pytest.approx(
        40 * 10 * 9 / 72 * 2 * 3 * 4096 * 768)
