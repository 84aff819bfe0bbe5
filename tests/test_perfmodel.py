"""Validation of the TPU v1 analytical model against the paper's numbers."""
import math

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import perfmodel as pm


class TestHardwareConstants:
    def test_peak_tops(self):
        # 65,536 MACs x 700 MHz x 2 ops = 92 TOPS (paper headline)
        assert pm.TPU_V1.peak_ops / 1e12 == pytest.approx(92, rel=0.01)

    def test_ridge_point(self):
        # "operations per byte ... is ~1350" (paper §2 and Fig. 5)
        assert pm.TPU_V1.ridge_ops_per_byte == pytest.approx(1350, rel=0.01)

    def test_tile_fetch_is_ridge(self):
        # one 64 KiB tile fetch = the ridge in cycles — same quantity
        assert pm.TPU_V1.tile_fetch_cycles == pytest.approx(1350, rel=0.01)


class TestTable1:
    @pytest.mark.parametrize("app", pm.PAPER_APPS, ids=lambda a: a.name)
    def test_weight_counts(self, app):
        targets = {"MLP0": 20e6, "MLP1": 5e6, "LSTM0": 52e6,
                   "LSTM1": 34e6, "CNN0": 8e6, "CNN1": 100e6}
        assert app.weight_bytes == pytest.approx(targets[app.name],
                                                 rel=0.20)

    @pytest.mark.parametrize("app", pm.PAPER_APPS, ids=lambda a: a.name)
    def test_ops_per_byte(self, app):
        # Table 1 column "TPU Ops/Weight Byte"
        targets = {"MLP0": 200, "MLP1": 168, "LSTM0": 64, "LSTM1": 96,
                   "CNN0": 2888, "CNN1": 1750}
        assert app.ops_per_weight_byte == pytest.approx(
            targets[app.name], rel=0.05)


class TestTable3:
    def test_row9_tops_mean_error(self):
        """Model vs Table 3 row 9; paper's own model was within 8%
        (Table 7) — ours must be within 20% mean abs error."""
        errs = [abs(pm.simulate(a).tops / a.paper_tops - 1)
                for a in pm.PAPER_APPS]
        assert sum(errs) / len(errs) < 0.20

    def test_memory_bound_apps_have_high_stall(self):
        for name in ("MLP0", "MLP1", "LSTM0", "LSTM1"):
            r = pm.simulate(pm.APP_BY_NAME[name])
            assert r.stall_frac > 0.4, name      # Table 3 row 4: 44-62%
            assert r.active_frac < 0.2, name     # row 1: 8-13%

    def test_cnn0_compute_bound(self):
        r = pm.simulate(pm.APP_BY_NAME["CNN0"])
        assert r.active_frac > 0.6                # row 1: 78.2%
        assert r.stall_frac < 0.1                 # row 4: 0%


class TestFig11:
    def test_memory_is_biggest_lever(self):
        sw = pm.fig11_sweep()
        at4 = {k: dict(v)[4.0] for k, v in sw.items()}
        # "performance improves 3X on average when memory increases 4X"
        assert 2.5 < at4["memory"] < 4.0
        # "clock rate has little benefit"
        assert at4["clock"] < 1.3
        assert at4["clock+"] < 1.4
        # "average performance slightly degrades when the matrix unit
        # expands" (2x or 4x)
        assert at4["matrix"] < 1.0
        assert at4["matrix+"] < 1.0

    def test_lstm1_fragmentation_example(self):
        """Paper: 600-wide LSTM1 matrices tile worse on a 512 unit."""
        app = pm.APP_BY_NAME["LSTM1"]
        t256 = pm.simulate(app, pm.TPU_V1).time_s
        t512 = pm.simulate(app, pm.TPU_V1.scaled(matrix=2,
                                                 accumulators=4)).time_s
        assert t512 > t256 * 0.9   # no speedup from the bigger array


class TestTPUPrime:
    def test_gddr5_gains(self):
        g = pm.tpu_prime_gains()
        # paper: GM 2.6, WM 3.9 from GDDR5 alone (we accept a band)
        assert 2.0 < g["gddr5_gm"] < 3.5
        assert 3.0 < g["gddr5_wm"] < 5.5
        # clock alone: "almost no change"
        assert g["clock1.5_wm"] < 1.3
        # both: WM not much better than memory alone ("TPU' just has
        # faster memory")
        assert g["both_wm"] < g["gddr5_wm"] * 1.25

    def test_ridge_shift(self):
        # "shifting its roofline ridge point from 1350 to 250"
        assert pm.TPU_PRIME.ridge_ops_per_byte == pytest.approx(250, rel=0.02)


class TestModelProperties:
    @given(st.sampled_from([a.name for a in pm.PAPER_APPS]),
           st.floats(0.25, 4.0))
    @settings(max_examples=25, deadline=None)
    def test_more_bandwidth_never_hurts(self, name, s):
        app = pm.APP_BY_NAME[name]
        base = pm.simulate(app, pm.TPU_V1).time_s
        fast = pm.simulate(app, pm.TPU_V1.scaled(memory=s)).time_s
        if s >= 1:
            assert fast <= base * 1.001
        else:
            assert fast >= base * 0.999

    @given(st.integers(1, 2040))
    @settings(max_examples=25, deadline=None)
    def test_throughput_monotone_in_batch(self, b):
        """Monotone below the 2048-row accumulator capacity (the paper
        sized the UB 'to allow MLPs to run at batch sizes up to 2048')."""
        import dataclasses
        app = dataclasses.replace(pm.APP_BY_NAME["MLP0"], batch=b)
        app2 = dataclasses.replace(app, batch=b + 1)
        ips1 = pm.simulate(app).ips
        ips2 = pm.simulate(app2).ips
        assert ips2 >= ips1 * 0.999   # bigger batch never reduces IPS

    def test_accumulator_capacity_cliff(self):
        """Crossing 2048 rows forces a second chunk + weight re-fetch —
        the modeled analogue of overflowing the double-buffered
        accumulators."""
        import dataclasses
        at = pm.simulate(dataclasses.replace(pm.APP_BY_NAME["MLP0"],
                                             batch=2048)).ips
        over = pm.simulate(dataclasses.replace(pm.APP_BY_NAME["MLP0"],
                                               batch=2049)).ips
        assert over < at

    def test_roofline_attainable_bounds_achieved(self):
        for app in pm.PAPER_APPS:
            intensity, attain, achieved = pm.roofline_point(app)
            assert achieved <= attain * 1.001

    def test_counter_fractions_sum_to_one(self):
        for app in pm.PAPER_APPS:
            r = pm.simulate(app)
            total = (r.active_frac + r.stall_frac + r.shift_frac
                     + r.nonmatrix_frac)
            assert total == pytest.approx(1.0, abs=1e-6)


def test_unified_buffer_within_capacity():
    """Table 8: every app fits the 24 MiB Unified Buffer."""
    for app in pm.PAPER_APPS:
        assert pm.unified_buffer_mib(app) < 24.0
