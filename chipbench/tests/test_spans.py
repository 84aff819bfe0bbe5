"""spans.py: the engine's spans read against the device, on a trace with
a known split and on one recorded on the chip."""
import gzip
import json
from pathlib import Path

import pytest

import spans
import trace_reduce

DATA = Path(__file__).resolve().parent / "data"

US = 1_000_000                         # ps per us


def _ev(meta, start_us, end_us, stats=""):
    start, end = round(start_us * US), round(end_us * US)
    return (f"    events {{ metadata_id: {meta} offset_ps: {start} "
            f"duration_ps: {end - start} {stats}}}\n")


def _stats(step, rows, prompt_rows):
    return (f"stats {{ metadata_id: 1 int64_value: {step} }} "
            f"stats {{ metadata_id: 2 int64_value: {rows} }} "
            f"stats {{ metadata_id: 3 int64_value: {prompt_rows} }} ")


# Two ticks and a wait, times in us.  The device runs ops [1, 4], [6, 9]
# and [12, 13]; the first and last are slot steps, the middle a chunk.
#   tick 0 [0, 10]: admit [0, 1] holding a prime prefill [0.2, 0.6],
#     dispatch [1, 2], pull [2, 7], bookkeep [7, 10];
#     idle [0, 1] (prefill 0.4, admit 0.6), [4, 6] pull, [9, 10] bookkeep
#   wait [10, 11]: its idle time is in no tick
#   tick 1 [11, 15]: dispatch [11, 12], pull [12, 13.5], nothing after;
#     idle [11, 12] dispatch, and the gap [13, 15] split: 0.5 pull and
#     1.5 the tick's own time (its midpoint, 14, lies in the latter)
HOST = ("".join([
    _ev(1, 0, 10, _stats(0, 5, 2)), _ev(2, 0, 1), _ev(3, 0.2, 0.6),
    _ev(4, 1, 2), _ev(7, 1.1, 1.9), _ev(5, 2, 7), _ev(6, 7, 10),
    _ev(8, 10, 11),
    _ev(1, 11, 15, _stats(1, 3, 0)), _ev(4, 11, 12), _ev(5, 12, 13.5)]))
SPACE = f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{
    id: 1 name: "XLA Ops" timestamp_ns: 0
{_ev(1, 1, 4)}{_ev(2, 2, 3)}{_ev(1, 6, 9)}{_ev(1, 12, 13)}  }}
  lines {{
    id: 2 name: "XLA Modules" timestamp_ns: 0
{_ev(3, 1, 4)}{_ev(4, 6, 9)}{_ev(3, 12, 13)}  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.1 = f32[8] fusion(f32[8] %p)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%qmatmul_w8a16.3 = bf16[8] custom-call(bf16[8] %x)" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "jit_slot_decode(1234)" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "jit_prefill_chunk(99)" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{
    id: 1 name: "python" timestamp_ns: 0
{HOST}  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "engine.tick" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "engine.admit" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "engine.prefill" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "engine.dispatch" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "engine.pull" }} }}
  event_metadata {{ key: 6 value {{ id: 6 name: "engine.bookkeep" }} }}
  event_metadata {{ key: 7 value {{ id: 7 name: "PjitFunction(slot_decode)" }} }}
  event_metadata {{ key: 8 value {{ id: 8 name: "engine.wait" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "step_num" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "rows" }} }}
  stat_metadata {{ key: 3 value {{ id: 3 name: "prompt_rows" }} }}
}}
"""


def space(txt):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(txt)


def test_idle_split_by_innermost_span():
    red = spans.reduce_spans(space(SPACE))
    idle = {k: v * 1e6 for k, v in red["idle_by_span"].items()}
    assert idle == pytest.approx({
        "engine.admit": 0.6, "engine.prefill": 0.4, "engine.pull": 2.5,
        "engine.bookkeep": 1.0, "engine.dispatch": 1.0, "engine.tick": 1.5})
    assert red["ticks"] == 2
    assert red["tick_s"] == pytest.approx(14e-6)
    assert red["tick_busy_s"] == pytest.approx(7e-6)
    # the parts sum to the ticks' idle time: their length less busy time
    assert sum(red["idle_by_span"].values()) == pytest.approx(
        red["tick_s"] - red["tick_busy_s"])


def test_span_and_module_summaries():
    red = spans.reduce_spans(space(SPACE))
    s = red["spans"]
    assert set(s) == {"engine.tick", "engine.admit", "engine.prefill",
                      "engine.dispatch", "engine.pull", "engine.bookkeep",
                      "engine.wait"}
    assert s["engine.tick"]["count"] == 2
    assert s["engine.tick"]["total_s"] == pytest.approx(14e-6)
    assert s["engine.tick"]["self_s"] == pytest.approx(1.5e-6)
    assert s["engine.tick"]["rows"] == 8
    assert s["engine.tick"]["prompt_rows"] == 2
    assert s["engine.admit"]["self_s"] == pytest.approx(0.6e-6)
    assert s["engine.pull"]["count"] == 2
    assert s["engine.pull"]["total_s"] == pytest.approx(6.5e-6)
    assert s["engine.wait"]["count"] == 1
    mods = red["modules"]
    assert set(mods) == {"jit_slot_decode", "jit_prefill_chunk"}
    assert mods["jit_slot_decode"]["count"] == 2
    assert mods["jit_slot_decode"]["time_s"] == pytest.approx(4e-6)
    assert mods["jit_prefill_chunk"]["time_s"] == pytest.approx(3e-6)


def test_per_tick_readings():
    got = spans.per_tick(spans.reduce_spans(space(SPACE)))
    assert got == pytest.approx({
        "pull_gap_us": 2.5 / 2, "host_work_gap_us": 4.5 / 2,
        "device_us_per_row": 7 / 8, "slot_step_ms": 0.002})


def test_trace_reduce_reads_the_same_trace():
    """The existing reduction is unchanged by the spans beside it: busy
    time, and the gap [9, 12] named by the host span over its middle."""
    red = trace_reduce.reduce_space(space(SPACE), window_s=15e-6)
    assert red["busy_s"] == pytest.approx(7e-6)
    assert red["breakdown"]["idle_gaps"] == [
        ["engine.wait", pytest.approx(3e-6)],
        ["engine.pull", pytest.approx(2e-6)]]


def test_two_chips_average():
    two = SPACE.replace('id: 2 name: "/host:CPU"',
                        'id: 3 name: "/device:TPU:1"\n'
                        '  lines { id: 1 name: "XLA Ops" timestamp_ns: 0\n'
                        + _ev(1, 0, 15) + '  }\n'
                        '  event_metadata { key: 1 value { id: 1 name: '
                        '"%fusion.2 = f32[8] fusion(f32[8] %p)" } }\n'
                        '}\nplanes {\n  id: 2 name: "/host:CPU"')
    red = spans.reduce_spans(space(two))
    # chip 1 is busy through both ticks: half of chip 0's idle time
    assert sum(red["idle_by_span"].values()) == pytest.approx(3.5e-6)
    assert red["tick_busy_s"] == pytest.approx((7e-6 + 14e-6) / 2)


def test_silent_without_engine_spans():
    """A program that writes no spans (an older tree) reads nothing, and
    raises nothing."""
    pb = DATA / "tiny_tpu.xplane.pb.gz"
    from jax.profiler import ProfileData
    red = spans.reduce_spans(ProfileData.from_serialized_xspace(
        gzip.decompress(pb.read_bytes())))
    assert red["ticks"] == 0 and red["spans"] == {}
    assert red["modules"]["jit__lambda"]["count"] == 5
    assert spans.per_tick(red) == dict.fromkeys(
        ["pull_gap_us", "host_work_gap_us", "device_us_per_row",
         "slot_step_ms"])
    host_only = SPACE[SPACE.index("planes {\n  id: 2"):]
    red = spans.reduce_spans(space(host_only))
    assert red["ticks"] == 2 and red["tick_busy_s"] is None
    assert spans.per_tick(red)["pull_gap_us"] is None


def test_recorded_engine_trace():
    """The reduced mamba2-1.3b engine, six requests on one TPU v5e
    (``data/record_engine_trace.py``): one span per tick the engine
    counted, rows that sum to the positions its requests advanced,
    slot steps and chunks named on the device's module line, and idle
    parts that sum to the ticks' idle time."""
    from jax.profiler import ProfileData
    want = json.loads((DATA / "engine_tiny_tpu.json").read_text())
    red = spans.reduce_spans(ProfileData.from_serialized_xspace(
        gzip.decompress((DATA / "engine_tiny_tpu.xplane.pb.gz")
                        .read_bytes())))
    assert red["ticks"] == want["ticks"]
    tick = red["spans"]["engine.tick"]
    assert tick["rows"] == want["positions"]
    assert tick["prompt_rows"] == want["prompt_positions"]
    assert red["modules"]["jit_slot_decode"]["count"] >= want["ticks"] - 2
    assert red["modules"]["jit_prefill_chunk"]["count"] > 0
    assert 0 < red["tick_busy_s"] < red["tick_s"]
    assert sum(red["idle_by_span"].values()) == pytest.approx(
        red["tick_s"] - red["tick_busy_s"])
    got = spans.per_tick(red)
    assert all(v is not None and v > 0 for v in got.values())
