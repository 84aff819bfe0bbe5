#!/usr/bin/env python3
"""The engine's own spans in a profiler trace, read against the device.

``reduce_spans(space)`` reads the planes of one ``.xplane.pb``
(``jax.profiler.ProfileData``).  The engine writes its spans through
``jax.profiler`` on the host's ``python`` line, on the device trace's
clock: one ``engine.tick`` per dispatching iteration of its tick loop,
with the stats ``rows`` (positions advanced) and ``prompt_rows``
(those teacher-forced from a prompt), holding one span per host phase
(``engine.admit``, ``engine.prefill``, ``engine.draft``,
``engine.dispatch``, ``engine.pull``, ``engine.bookkeep``), and
``engine.wait`` while no slot is active.  It returns:

- ``spans``: per span name, the count and the total and self time in s
  (self: less the spans nested in it); for ``engine.tick`` also the
  summed ``rows`` and ``prompt_rows``;
- ``idle_by_span``: the device's idle time inside the ticks, in s, split
  by the innermost span over each instant of it (an idle gap that
  crosses from one phase into the next is split between them, so the
  parts sum to the idle time of the ticks); ``engine.tick`` keeps what
  no phase covers;
- ``ticks``, ``tick_s`` (their summed length) and ``tick_busy_s`` (the
  device's busy time inside them);
- ``modules``: per ``XLA Modules`` name with its ``(hash)`` stripped
  (``jit_slot_decode``, ``jit_prefill_chunk``, ...), the count and the
  device time in s.

Device times are the mean over the trace's ``/device:TPU:<n>`` planes,
busy time the union of each plane's ``XLA Ops`` intervals, as in
``trace_reduce``.  A trace without engine spans (a program that writes
none) reduces to empty ``spans`` and ``idle_by_span`` and no ticks, and
``per_tick`` then reads None.

Run on a trace kept by ``run.py --keep-trace <dir>``:

    python chipbench/spans.py <dir>

or run one traced run of a cell, keep its trace, and print its result
line with the reduction and the engine's compile counts:

    python chipbench/spans.py --workload <name> --seed <n> \
        --seconds <s> --keep-trace <dir>
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import bisect  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

from trace_reduce import (DEVICE_PREFIX, OPS_LINE, self_times,  # noqa: E402
                          union_ns)

PREFIX = "engine."
TICK = "engine.tick"
PULL = "engine.pull"
MODULES_LINE = "XLA Modules"
SLOT_MODULE = "jit_slot_decode"


def module_name(event_name: str) -> str:
    """``jit_slot_decode(1234)`` -> ``jit_slot_decode``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def _engine_spans(space) -> List[Tuple[float, float, str, dict]]:
    out = []
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append((ev.start_ns, ev.end_ns, ev.name,
                                dict(ev.stats)))
    return sorted(out, key=lambda s: (s[0], -s[1]))


def _busy_in(merged: List[list], starts: List[float], a: float,
             b: float) -> float:
    """Length of ``[a, b]`` covered by sorted disjoint intervals."""
    t = 0.0
    i = max(0, bisect.bisect_right(starts, a) - 1)
    while i < len(merged) and merged[i][0] < b:
        t += max(0.0, min(b, merged[i][1]) - max(a, merged[i][0]))
        i += 1
    return t


def _segments(tick, spans):
    """The tick's time cut at every boundary of the spans inside it,
    each piece labelled by the innermost span over it."""
    s0, e0 = tick[0], tick[1]
    inner = [s for s in spans if s[2] != TICK and s[0] < e0 and s[1] > s0]
    cuts = sorted({s0, e0} | {min(max(x, s0), e0)
                              for s in inner for x in s[:2]})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        over = [s for s in inner if s[0] <= a and b <= s[1]]
        label = (min(over, key=lambda s: s[1] - s[0])[2] if over
                 else TICK)
        out.append((a, b, label))
    return out


def reduce_spans(space) -> Dict:
    spans = _engine_spans(space)
    planes = [p for p in space.planes if p.name.startswith(DEVICE_PREFIX)]
    n = max(1, len(planes))
    busy, modules = [], {}
    for plane in planes:
        ivs = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ivs += [(ev.start_ns, ev.end_ns) for ev in line.events]
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    m = modules.setdefault(module_name(ev.name),
                                           {"count": 0, "time_s": 0.0})
                    m["count"] += 1 / n
                    m["time_s"] += ev.duration_ns * 1e-9 / n
        _, merged = union_ns(ivs)
        busy.append((merged, [s for s, _ in merged]))

    summary: Dict[str, dict] = {}
    for s, e, name, _ in spans:
        d = summary.setdefault(name, {"count": 0, "total_s": 0.0,
                                      "self_s": 0.0})
        d["count"] += 1
        d["total_s"] += (e - s) * 1e-9
    for name, self_ns in self_times([s[:3] for s in spans]):
        summary[name]["self_s"] += self_ns * 1e-9
    ticks = [s for s in spans if s[2] == TICK]
    if ticks:
        summary[TICK]["rows"] = sum(t[3].get("rows", 0) for t in ticks)
        summary[TICK]["prompt_rows"] = sum(t[3].get("prompt_rows", 0)
                                           for t in ticks)

    idle: Dict[str, float] = {}
    tick_busy = 0.0
    for tick in ticks:
        for a, b, label in _segments(tick, spans):
            for merged, starts in busy:
                got = _busy_in(merged, starts, a, b)
                tick_busy += got * 1e-9 / n
                idle[label] = (idle.get(label, 0.0)
                               + (b - a - got) * 1e-9 / n)
    return {
        "spans": summary,
        "idle_by_span": idle,
        "ticks": len(ticks),
        "tick_s": sum(e - s for s, e, _, _ in ticks) * 1e-9,
        "tick_busy_s": tick_busy if planes else None,
        "modules": modules,
    }


def per_tick(red: Dict) -> Dict[str, Optional[float]]:
    """The reduction's per-tick readings: the device's idle time in the
    pull and in the other host phases of a tick, in us; device busy time
    per row advanced, in us; the mean device time of a slot step, in
    ms.  None where the trace holds nothing to read."""
    n = red["ticks"] if red["tick_busy_s"] is not None else 0
    rows = red["spans"].get(TICK, {}).get("rows", 0)
    idle = red["idle_by_span"]
    pull = idle.get(PULL, 0.0)
    slot = red["modules"].get(SLOT_MODULE)
    return {
        "pull_gap_us": pull / n * 1e6 if n else None,
        "host_work_gap_us": ((sum(idle.values()) - pull) / n * 1e6
                             if n else None),
        "device_us_per_row": (red["tick_busy_s"] / rows * 1e6
                              if n and rows else None),
        "slot_step_ms": (slot["time_s"] / slot["count"] * 1e3
                         if slot and slot["count"] else None),
    }


def reduce_dir(trace_dir: str) -> Dict:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace under {trace_dir}, "
                         f"found {len(files)}")
    red = reduce_spans(ProfileData.from_file(files[0]))
    red["per_tick"] = per_tick(red)
    return red


def run_traced(argv: List[str], t_start: float, **kw) -> Dict:
    """One ``--trace 1`` run of a cell through ``harness.run_cell``
    (``kw`` passed on), its trace kept under ``--keep-trace`` and reduced
    here.  Beside the result line: the run's end-to-end metrics, read
    from its record as a ``--trace 0`` run reports them (the cost of the
    profiler), what ``Engine.warmup`` reported, the compiles of
    ``Engine.serve``, and how long the profiler took to start and to
    stop (the serve waits for both)."""
    import harness
    from repro.engine import Engine
    seen: Dict = {}
    warmup, serve = Engine.warmup, Engine.serve
    start, stop = harness.TraceWindow.start, harness.TraceWindow.stop

    def warmup_(self):
        seen["warmup"] = w = warmup(self)
        return w

    def serve_(self, *a, **k):
        rep = serve(self, *a, **k)
        seen["serve_compiles"] = getattr(rep, "compiles", None)
        return rep

    def timed(name, fn):
        def call(self, *a):
            t = time.perf_counter()
            fn(self, *a)
            seen.setdefault(name, time.perf_counter() - t)
        return call

    Engine.warmup, Engine.serve = warmup_, serve_
    harness.TraceWindow.start = timed("profiler_start_s", start)
    harness.TraceWindow.stop = timed("profiler_stop_s", stop)
    try:
        out, record = harness.run_cell(argv + ["--trace", "1"],
                                       t_start=t_start, **kw)
    finally:
        Engine.warmup, Engine.serve = warmup, serve
        harness.TraceWindow.start, harness.TraceWindow.stop = start, stop
    args = harness.parse(argv)
    spec = kw.get("spec") or harness.load_spec(kw.get("root", harness.ROOT))
    e2e = {m["name"]: harness.load_module(
               harness.metric_file(m["name"])).read(record)
           for m in harness.cell_metrics(spec, args.workload, False)}
    w = seen.get("warmup")
    return {"result": out, "end_to_end": e2e,
            "spans": reduce_dir(args.keep_trace),
            "engine": {"warmup": w and vars(w),
                       "serve_compiles": seen.get("serve_compiles")},
            "profiler": {k: seen.get(k) for k in ("profiler_start_s",
                                                  "profiler_stop_s")}}


def main(argv: List[str]) -> int:
    if len(argv) == 1 and not argv[0].startswith("-"):
        print(json.dumps(reduce_dir(argv[0])))
        return 0
    if "--keep-trace" not in argv:
        print("spans.py: give --keep-trace <dir> with --workload",
              file=sys.stderr)
        return 2
    import harness
    harness.setup()
    print(json.dumps(run_traced(argv, T_START)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
