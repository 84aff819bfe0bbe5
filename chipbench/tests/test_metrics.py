"""The metric readers and the work arithmetic on hand-built records."""
import json
from pathlib import Path

import pytest

import harness
import work
from peaks import PEAKS, peaks

METRICS = Path(__file__).resolve().parents[1] / "metrics"
CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def reader(name):
    return harness.load_module(harness.metric_file(name)).read


def res(arrival, admit, first, finish, n, status="ok"):
    return dict(arrival_s=arrival, admit_s=admit, first_token_s=first,
                finish_s=finish, n_tokens=n, status=status)


# 20 requests: request i arrives at i, is admitted 0.1*i later, gets its
# first token 1 + i/10 s after arrival and then i+2 more tokens 10 ms apart
RUN = {"setup_s": 12.5, "results": [
    res(i, i + 0.1 * i, i + 1 + i / 10, i + 1 + i / 10 + 0.01 * (i + 2),
        i + 3) for i in range(20)]}


def test_ttft_p95_nearest_rank():
    # 20 values 1.0 .. 2.9: the 19th smallest is the 95th percentile
    assert reader("ttft_p95_s")(RUN) == pytest.approx(2.8)


def test_ttft_counts_unserved_as_infinite():
    run = {"results": RUN["results"][:19] + [res(0, 0, -1.0, 5, 0,
                                                   "failed")]}
    assert reader("ttft_p95_s")(run) == pytest.approx(2.8)
    run["results"] = run["results"][1:] + [res(0, 0, -1.0, 5, 0, "failed")]
    assert reader("ttft_p95_s")(run) == float("inf")


def test_tpot_p95_ms():
    # every request: (i+2) gaps of 10 ms over i+2 tokens after the first
    assert reader("tpot_p95_ms")(RUN) == pytest.approx(10.0)
    one = {"results": [res(0, 0, 1, 1, 1)]}
    assert reader("tpot_p95_ms")(one) is None


def test_queue_wait_p95():
    assert reader("queue_wait_p95_s")(RUN) == pytest.approx(1.8)


def test_tokens_per_s():
    toks = sum(i + 3 for i in range(20))
    span = 19 + 1 + 1.9 + 0.01 * 21
    assert reader("tokens_per_s")(RUN) == pytest.approx(toks / span)


def test_setup():
    assert reader("setup_s")(RUN) == 12.5


TRACE = {"busy_s": 0.8, "window_s": 1.0, "tokens": 3200,
         "flops": 0.8 * 197e12 * 0.25,
         "op_time_s": {"qmatmul_w8a16.3": 0.2, "qmatmul_w8a16.1": 0.2,
                       "fusion.2": 0.1},
         "op_count": {"qmatmul_w8a16.3": 100, "qmatmul_w8a16.1": 100,
                      "fusion.2": 100}}
# one layer of two linear layers: 100 calls of each are 100 passes
LINEARS = [("a", 4096, 4096), ("b", 4096, 4096)]


def test_trace_metrics():
    p = peaks("TPU v5 lite")
    run = {"trace": TRACE, "peaks": p, "linears": LINEARS, "layers": 1}
    assert reader("device_us_per_token.lat")(run) == pytest.approx(250.0)
    assert reader("device_idle_share.tput")(run) == pytest.approx(20.0)
    assert reader("step_mfu.lat")(run) == pytest.approx(25.0)
    least = 100 * work.linear_least_s(LINEARS, 1, 32, p)
    assert reader("qmatmul_roofline.lat")(run) == pytest.approx(
        least / 0.4 * 100)


def test_trace_metrics_silent_without_trace():
    for name in ("device_us_per_token", "device_idle_share", "step_mfu",
                 "qmatmul_roofline"):
        assert reader(name)({"trace": None, "peaks": None}) is None
    no_kernel = dict(TRACE, op_time_s={"fusion.2": 0.1},
                     op_count={"fusion.2": 100})
    assert reader("qmatmul_roofline")({"trace": no_kernel}) is None


def test_kernel_least_time_counts_passes_over_the_weights():
    p = peaks("TPU v5 lite")
    lins = [("a", 3072, 3072), ("b", 3072, 12288)]
    # 30 layers, 2 linear layers: 600 calls are 10 passes of 64 rows
    t = work.kernel_least_s(lins, 30, 600, 640, p)
    assert t == pytest.approx(10 * work.linear_least_s(lins, 30, 64, p))


def test_unknown_chip_raises():
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
    assert PEAKS["TPU v5 lite"]["bf16_flops"] == 197e12


def conf(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_starcoder2_work_counts():
    ref = harness.load_module(CONFIGS / "starcoder2-3b.py")
    c = conf("starcoder2-3b")
    per_layer = 2 * (3072 * 3072 * 2 + 3072 * 256 * 2 + 3072 * 12288 * 2)
    assert ref.token_flops(c, 0) == 30 * per_layer + 2 * 3072 * 49152
    assert ref.token_flops(c, 10) - ref.token_flops(c, 0) == \
        10 * 4 * 30 * 24 * 128
    a, b = ref.token_flops(c, 0), ref.token_flops(c, 1) - ref.token_flops(c, 0)
    # a prompt of 4 admitted at 0, first token at 4, then 3 more tokens
    # by 7: passes at 1, 2, 3, 4 (contexts 1..4), 5, 6, 7 (contexts 5..7)
    r = dict(status="ok", prompt_len=4, n_tokens=4, admit_s=0.0,
             first_token_s=4.0, finish_s=7.0)
    w = work.window_work(ref, c, [r], (2.0, 6.0))
    assert w["tokens"] == 4                       # passes at 2, 3, 4, 5
    assert w["flops"] == pytest.approx(4 * a + (2 + 3 + 4 + 5) * b)
    w = work.window_work(ref, c, [r], (0.0, 100.0))
    assert w["tokens"] == 7
    assert w["flops"] == pytest.approx(7 * a + 28 * b)
    failed = dict(r, status="failed", first_token_s=-1.0)
    assert work.window_work(ref, c, [failed], (0.0, 100.0))["tokens"] == 0


def test_linear_least_time_is_bandwidth_bound_at_decode():
    p = peaks("TPU v5 lite")
    lins = [("w", 3072, 12288)]
    t = work.linear_least_s(lins, 1, 64, p)
    nbytes = 3072 * 12288 + 4 * 12288 + 2 * 64 * (3072 + 12288)
    assert t == pytest.approx(nbytes / p["hbm_bytes_per_s"])
    # at many rows the same layer is compute bound
    t = work.linear_least_s(lins, 1, 4096, p)
    assert t == pytest.approx(2 * 4096 * 3072 * 12288 / p["bf16_flops"])


def test_mamba2_work_counts():
    ref = harness.load_module(CONFIGS / "mamba2-1.3b.py")
    c = conf("mamba2-1.3b")
    d = ref.dims(c)
    assert (d["din"], d["H"], d["N"]) == (4096, 64, 128)
    assert ref.linears(c) == [("in_proj", 2048, 2 * 4096 + 2 * 128 + 64),
                              ("out_proj", 4096, 2048)]
    assert ref.token_flops(c, 5) == ref.token_flops(c, 500)
