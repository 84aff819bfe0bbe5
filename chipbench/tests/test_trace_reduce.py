"""trace_reduce on recorded traces with a known busy and idle split."""
import gzip
from pathlib import Path

import pytest

import trace_reduce

DATA = Path(__file__).resolve().parent / "data"

# an op holding a kernel, a 2 us gap under a host span, one more op:
# busy = [1, 4] + [6, 7] us = 4 us of a 10 us window
SPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 1000000 }
  }
  lines {
    id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 9000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8] fusion(f32[8] %p)" } }
  event_metadata { key: 2 value { id: 2 name: "%qmatmul_w8a16.3 = bf16[8] custom-call(bf16[8] %x)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines {
    id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 2500000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 3500000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "engine.dispatch" } }
  event_metadata { key: 2 value { id: 2 name: "inner" } }
}
"""


def space(txt):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(txt)


def test_union():
    total, merged = trace_reduce.union_ns([(5, 6), (0, 2), (1, 3), (3, 4)])
    assert total == 5
    assert merged == [[0, 4], [5, 6]]


def test_known_split():
    red = trace_reduce.reduce_space(space(SPACE), window_s=10e-6)
    assert red["busy_s"] == pytest.approx(4e-6)
    assert red["window_s"] == 10e-6
    assert red["op_time_s"]["fusion.1"] == pytest.approx(4e-6)
    assert red["op_time_s"]["qmatmul_w8a16.3"] == pytest.approx(1e-6)
    assert "jit_step" not in red["op_time_s"]       # modules are not ops
    assert red["op_count"]["qmatmul_w8a16.3"] == 1
    # the breakdown ranks self time: fusion.1 less the kernel it holds
    assert red["breakdown"]["device_ops"] == [
        ["fusion.1", pytest.approx(3e-6)],
        ["qmatmul_w8a16.3", pytest.approx(1e-6)]]
    # the gap [4, 6] us is named by the innermost host span over its middle
    assert red["breakdown"]["idle_gaps"] == [["inner", pytest.approx(2e-6)]]


def test_self_time_of_nested_ops():
    evs = [(0, 10, "while.1"), (1, 3, "a"), (4, 8, "b"), (5, 6, "c"),
           (12, 13, "d")]
    got = dict(trace_reduce.self_times(evs))
    assert got == {"while.1": 4, "a": 2, "b": 3, "c": 1, "d": 1}


def test_two_chips_average():
    two = SPACE.replace('name: "/host:CPU"', 'name: "/device:TPU:1"')
    two = two.replace('name: "python"', 'name: "XLA Ops"')
    red = trace_reduce.reduce_space(space(two), window_s=10e-6)
    assert red["n_chips"] == 2
    # chip 0 busy 4 us; chip 1 busy [3.5, 6.5] us = 3 us
    assert red["busy_s"] == pytest.approx(3.5e-6)


def test_no_device_plane_raises():
    host_only = SPACE[SPACE.index("planes {\n  id: 2"):]
    with pytest.raises(ValueError):
        trace_reduce.reduce_space(space(host_only), window_s=1.0)


def test_recorded_chip_trace():
    """A trace recorded on one TPU v5e (five runs of a jitted matmul, 20 ms
    sleeps between): the reduction's busy time equals the union of the
    device's ops in the profiler's own Perfetto export."""
    import json
    from jax.profiler import ProfileData
    pb = DATA / "tiny_tpu.xplane.pb.gz"
    red = trace_reduce.reduce_space(
        ProfileData.from_serialized_xspace(gzip.decompress(pb.read_bytes())),
        window_s=1.0)
    ev = json.loads(gzip.decompress(
        (DATA / "tiny_tpu.perfetto.json.gz").read_bytes()))["traceEvents"]
    pids = {e["pid"] for e in ev if e.get("ph") == "M"
            and e.get("name") == "process_name"
            and e["args"]["name"].startswith("/device:TPU:0")}
    tids = {(e["pid"], e["tid"]) for e in ev if e.get("ph") == "M"
            and e.get("name") == "thread_name" and e["pid"] in pids
            and e["args"]["name"] == "XLA Ops"}
    ivs = [(e["ts"], e["ts"] + e["dur"]) for e in ev
           if e.get("ph") == "X" and (e["pid"], e["tid"]) in tids]
    busy_us, _ = trace_reduce.union_ns(ivs)
    assert busy_us > 0
    assert red["busy_s"] == pytest.approx(busy_us * 1e-6, rel=1e-3)
    # five matmuls separated by sleeps: at least four idle gaps of ~20 ms
    gaps = [g for _, g in red["breakdown"]["idle_gaps"] if g > 0.015]
    assert len(gaps) >= 4
