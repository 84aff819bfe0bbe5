"""HLO cost model: trip counts, slice-aware bytes, collective accounting.

Two tiers: golden-fixture tests parse checked-in HLO text (milliseconds, no
JAX compilation — see tests/fixtures/hlo/regen.py), while the compiled-module
tests lower real programs through the installed XLA as integration checks.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hlo_cost as HC
from repro.core import roofline as RL

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "hlo")


def _fixture(name):
    with open(os.path.join(FIXTURES, name)) as f:
        return f.read()


def _compiled(f, *specs):
    return jax.jit(f).lower(*specs).compile()


class TestGoldenFixtures:
    """Core parsing cases against checked-in post-SPMD HLO text."""

    def test_scan_flops_exact(self):
        t = HC.analyze(_fixture("scan_matmul.hlo"))
        assert t.flops == 2 * 64 * 128 * 128 * 8
        assert t.unparsed_whiles == 0

    def test_scan_weight_slices_not_full_stack(self):
        t = HC.analyze(_fixture("scan_matmul.hlo"))
        stack_bytes = 8 * 128 * 128 * 4
        assert t.bytes < 6 * stack_bytes

    def test_nested_scan_flops(self):
        assert HC.analyze(_fixture("nested_scan.hlo")).flops == \
            2 * 64 * 128 * 128 * 24

    def test_fusion_with_dot(self):
        t = HC.analyze(_fixture("fusion_dot.hlo"))
        assert t.flops == 2 * 32 * 64 * 16
        # all flops attributed to the dot in the per-op breakdown
        assert t.by_op["dot"].flops == t.flops
        # the gelu+bias tail is an elementwise-only fusion: free bytes
        assert "fusion" not in t.by_op

    def test_dus_charged_at_update_region(self):
        t = HC.analyze(_fixture("dus_donated.hlo"))
        update_bytes = 4 * 1 * 64 * 4
        full_cache = 4 * 1024 * 64 * 4
        assert t.bytes == 2 * update_bytes
        assert t.bytes < full_cache

    def test_psum_bytes_and_count(self):
        t = HC.analyze(_fixture("psum.hlo"))
        # all-reduce over the f32[128,128] partial product
        assert t.collective_bytes == 128 * 128 * 4
        assert t.collective_counts["all-reduce"] == 1
        assert t.collective_bytes_by_op["all-reduce"] == t.collective_bytes

    def test_collective_inside_scan_trip_multiplied(self):
        t = HC.analyze(_fixture("scan_psum.hlo"))
        # one f32[16,64] all-reduce per iteration, 8 iterations
        assert t.collective_counts["all-reduce"] == 8
        assert t.collective_bytes == 8 * 16 * 64 * 4

    def test_by_op_totals_are_consistent(self):
        for name in ("scan_matmul.hlo", "fusion_dot.hlo", "psum.hlo",
                     "scan_psum.hlo", "dus_donated.hlo",
                     "nested_scan.hlo"):
            t = HC.analyze(_fixture(name))
            assert sum(oc.flops for oc in t.by_op.values()) == \
                pytest.approx(t.flops)
            assert sum(oc.bytes for oc in t.by_op.values()) == \
                pytest.approx(t.bytes)

    def test_structural_parse_resolves_operand_shapes(self):
        """The regex line-walker split `f32[64,128]` at the inner comma and
        lost the dot contraction; the structural parser must not."""
        module = HC.parse_hlo(_fixture("scan_matmul.hlo"))
        dots = [(comp, ins) for comp in module.computations.values()
                for ins in comp.instrs.values() if ins.opcode == "dot"]
        assert dots
        comp, dot = dots[0]
        lhs = comp.shapes_of(dot.operands[0])
        assert lhs and lhs[0].dims == (64, 128)


class TestTripCounts:
    def test_scan_flops_exact(self):
        def f(x, w):
            def body(x, wi):
                return jnp.tanh(x @ wi), None
            return jax.lax.scan(body, x, w)[0]
        c = _compiled(f, jax.ShapeDtypeStruct((64, 128), jnp.float32),
                      jax.ShapeDtypeStruct((8, 128, 128), jnp.float32))
        t = HC.analyze(c.as_text())
        assert t.flops == 2 * 64 * 128 * 128 * 8
        assert t.unparsed_whiles == 0

    def test_nested_scan(self):
        def g(x, w):
            def outer(x, wi):
                def inner(x, _):
                    return jnp.tanh(x @ wi), None
                return jax.lax.scan(inner, x, None, length=3)[0], None
            return jax.lax.scan(outer, x, w)[0]
        c = _compiled(g, jax.ShapeDtypeStruct((64, 128), jnp.float32),
                      jax.ShapeDtypeStruct((8, 128, 128), jnp.float32))
        assert HC.analyze(c.as_text()).flops == 2 * 64 * 128 * 128 * 24

    def test_unrolled_matches_scan(self):
        x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
        w = jax.ShapeDtypeStruct((4, 64, 64), jnp.float32)

        def scan_f(x, w):
            return jax.lax.scan(lambda c, wi: (c @ wi, None), x, w)[0]

        def unroll_f(x, w):
            for i in range(4):
                x = x @ w[i]
            return x
        fs = HC.analyze(_compiled(scan_f, x, w).as_text()).flops
        fu = HC.analyze(_compiled(unroll_f, x, w).as_text()).flops
        assert fs == fu == 2 * 32 * 64 * 64 * 4


class TestSliceAwareBytes:
    def test_scan_weight_slices_not_full_stack(self):
        """Each iteration reads ONE (128,128) weight slice, not the whole
        (64,128,128) stack; total weight bytes ~ stack size, not 64x it."""
        def f(x, w):
            return jax.lax.scan(lambda c, wi: (c @ wi, None), x, w)[0]
        c = _compiled(f, jax.ShapeDtypeStruct((8, 128), jnp.float32),
                      jax.ShapeDtypeStruct((64, 128, 128), jnp.float32))
        t = HC.analyze(c.as_text())
        stack_bytes = 64 * 128 * 128 * 4
        # bound: weights once + activations; far below 64x the stack
        assert t.bytes < 6 * stack_bytes

    def test_dynamic_update_slice_charged_at_update(self):
        def f(cache, new):
            return jax.lax.dynamic_update_slice(cache, new, (0, 5, 0))
        # donated buffer -> in-place update, no defensive copy (this is how
        # the decode path runs; without donation XLA inserts a full copy,
        # which IS real traffic and is charged)
        c = jax.jit(f, donate_argnums=(0,)).lower(
            jax.ShapeDtypeStruct((4, 1024, 64), jnp.float32),
            jax.ShapeDtypeStruct((4, 1, 64), jnp.float32)).compile()
        t = HC.analyze(c.as_text())
        full = 4 * 1024 * 64 * 4
        assert t.bytes < full  # must NOT charge the full cache


class TestCollectives:
    def test_psum_counted(self):
        import subprocess, sys, os, textwrap
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import sys; sys.path.insert(0, "src")
            import jax, jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.core import hlo_cost as HC
            mesh = jax.make_mesh((4,), ("x",))
            def f(a, b):
                return (a @ b)
            a = jax.ShapeDtypeStruct((128, 256), jnp.float32)
            b = jax.ShapeDtypeStruct((256, 128), jnp.float32)
            sa = NamedSharding(mesh, P(None, "x"))
            sb = NamedSharding(mesh, P("x", None))
            with mesh:
                c = jax.jit(f, in_shardings=(sa, sb),
                            out_shardings=NamedSharding(mesh, P())) \
                    .lower(a, b).compile()
            t = HC.analyze(c.as_text())
            assert t.collective_bytes > 0, "contraction over sharded dim \
needs an all-reduce"
            print("OK")
        """)
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True,
                           cwd=os.path.dirname(os.path.dirname(
                               os.path.abspath(__file__))), timeout=120)
        assert r.returncode == 0, r.stderr[-1500:]

    def test_collective_inside_scan_multiplied(self):
        """The loop multiplies collective bytes AND counts: a single
        all-reduce instruction in an 8-trip while body counts 8 times."""
        t = HC.analyze(_fixture("scan_psum.hlo"))
        single = HC.analyze(_fixture("psum.hlo"))
        assert t.collective_counts["all-reduce"] == 8
        assert single.collective_counts["all-reduce"] == 1
        assert t.collective_bytes == 8 * (16 * 64 * 4)


class TestRooflineTerms:
    def test_terms_and_bound(self):
        def f(a, b):
            return (a @ b).sum()
        c = _compiled(f, jax.ShapeDtypeStruct((256, 512), jnp.float32),
                      jax.ShapeDtypeStruct((512, 128), jnp.float32))
        t = RL.from_compiled("tiny", c, chips=1,
                             model_flops=2 * 256 * 512 * 128)
        assert t.compute_s > 0 and t.memory_s > 0
        assert t.bound in ("compute", "memory", "collective")
        assert 0.9 < t.useful_flops_frac <= 1.05
        d = t.to_dict()
        assert d["cell"] == "tiny"

    def test_flops_match_model_flops_exactly_for_pure_matmul(self):
        def f(a, b):
            return a @ b
        c = _compiled(f, jax.ShapeDtypeStruct((64, 128), jnp.float32),
                      jax.ShapeDtypeStruct((128, 32), jnp.float32))
        t = RL.from_compiled("mm", c, chips=1, model_flops=2 * 64 * 128 * 32)
        assert t.hlo_flops == t.model_flops

    def test_peaks_are_keyed_by_device_kind(self):
        """The v5e's published peaks, with their source; a device kind
        with no published peaks is an error, never a default."""
        v5e = RL.chip_peaks("TPU v5 lite")
        assert (v5e.bf16_flops, v5e.int8_ops, v5e.hbm_bw) == \
            (197e12, 393e12, 819e9)
        assert "TPU v5e" in v5e.source
        with pytest.raises(KeyError, match="no published peaks"):
            RL.chip_peaks("cpu")


def test_watchdog_detects_stragglers():
    from repro.runtime.watchdog import StepWatchdog
    w = StepWatchdog(warmup_steps=0, threshold=2.0)
    for _ in range(10):
        assert w.record(0.1) is None
    msg = w.record(0.5)
    assert msg is not None and "straggler" in msg
    assert w.slow_steps == 1
    # normal step after the spike: no warning
    assert w.record(0.11) is None
