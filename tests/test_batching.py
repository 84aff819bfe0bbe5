"""Latency-aware batching: Table 4 reproduction + scheduler properties."""
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import batching as bt


class TestTable4:
    def test_tpu_batch200_at_7ms(self):
        b, lat, ips, frac = bt.table4_row(bt.TABLE4_TPU, 7e-3, max_batch=250)
        assert b == 200                       # paper: batch 200
        assert lat == pytest.approx(7e-3, rel=0.01)
        assert ips == pytest.approx(225000, rel=0.01)
        assert frac == pytest.approx(0.80, abs=0.02)   # "80%"

    def test_cpu_gpu_forced_to_small_batches(self):
        bc, _, _, fc = bt.table4_row(bt.TABLE4_CPU, 7e-3, max_batch=64)
        bg, _, _, fg = bt.table4_row(bt.TABLE4_GPU, 7e-3, max_batch=64)
        assert bc <= 16 and bg <= 32          # paper: both use 16
        assert fc < 0.5 and fg < 0.6          # 42% / 37% of max IPS

    def test_ordering_tpu_best(self):
        fr = {m.name: bt.table4_row(m, 7e-3, max_batch=250)[3]
              for m in (bt.TABLE4_CPU, bt.TABLE4_GPU, bt.TABLE4_TPU)}
        assert fr["TPU"] > fr["Haswell"] and fr["TPU"] > fr["K80"]


class TestChooseBatch:
    @given(st.floats(1e-3, 50e-3), st.integers(1, 512))
    @settings(max_examples=30, deadline=None)
    def test_never_exceeds_deadline(self, deadline, max_batch):
        b = bt.choose_batch(bt.TABLE4_TPU, deadline, max_batch)
        if b:
            assert bt.TABLE4_TPU.p99_latency(b) <= deadline + 1e-12
            assert b <= max_batch

    @given(st.floats(1e-3, 50e-3))
    @settings(max_examples=30, deadline=None)
    def test_maximal(self, deadline):
        b = bt.choose_batch(bt.TABLE4_TPU, deadline, 4096)
        if 0 < b < 4096:
            assert bt.TABLE4_TPU.p99_latency(b + 1) > deadline


class TestBatchQueue:
    def _run(self, rate, n=500, deadline=7e-3, max_batch=200, seed=0):
        reqs = bt.poisson_arrivals(rate, n, deadline, seed)
        q = bt.BatchQueue(bt.TABLE4_TPU.service_time, max_batch=max_batch)
        return reqs, q.run(reqs)

    def test_all_requests_served_once(self):
        reqs, recs = self._run(rate=20000)
        served = [r for rec in recs for r in rec.rids]
        assert sorted(served) == list(range(len(reqs)))

    def test_deadlines_met_at_moderate_load(self):
        _, recs = self._run(rate=20000)
        met = sum(r.deadlines_met for r in recs) / len(recs)
        assert met > 0.95

    def test_batches_grow_with_load(self):
        _, light = self._run(rate=2000)
        _, heavy = self._run(rate=50000)
        mean = lambda rs: sum(len(r.rids) for r in rs) / len(rs)
        assert mean(heavy) > mean(light) * 2

    @given(st.integers(0, 100))
    @settings(max_examples=10, deadline=None)
    def test_no_batch_exceeds_max(self, seed):
        _, recs = self._run(rate=30000, n=300, seed=seed)
        assert all(len(r.rids) <= 200 for r in recs)

    def test_virtual_time_monotone(self):
        _, recs = self._run(rate=20000)
        for a, b in zip(recs, recs[1:]):
            assert b.start_s >= a.finish_s - 1e-12


class TestP99:
    """Nearest-rank p99: ceil(0.99 n)-th order statistic, exactly."""

    @pytest.mark.parametrize("n,want_rank", [
        (1, 1),      # a single sample IS its own p99
        (99, 99),    # ceil(98.01) = 99 -> the max, correctly
        (100, 99),   # the regression: int(0.99*100)=100th (max) was wrong
        (101, 100),  # ceil(99.99) = 100 -> second-largest
    ])
    def test_boundary_ranks(self, n, want_rank):
        xs = [float(i) for i in range(n)]
        assert bt.p99(xs) == xs[want_rank - 1]

    def test_n100_is_not_the_max(self):
        """The off-by-one this fixes: at n=100 the old int(0.99*n)
        indexing returned the maximum, overstating tail latency by a
        whole rank."""
        xs = [1.0] * 99 + [1000.0]
        assert bt.p99(xs) == 1.0

    def test_input_order_irrelevant_and_empty(self):
        import random
        xs = [float(i) for i in range(101)]
        random.Random(3).shuffle(xs)
        assert bt.p99(xs) == 99.0
        assert bt.p99([]) == 0.0


def test_perfmodel_integration():
    """batching consumes core.perfmodel service times end-to-end."""
    from repro.core import perfmodel as pm
    app = pm.APP_BY_NAME["MLP0"]
    service = lambda b: pm.service_time(app, batch=b)
    q = bt.BatchQueue(service, max_batch=200)
    reqs = bt.poisson_arrivals(50000, 400, deadline_s=7e-3)
    recs = q.run(reqs)
    assert recs and all(len(r.rids) <= 200 for r in recs)
