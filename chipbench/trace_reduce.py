"""From a profiler trace to device busy time, idle gaps and op times.

``reduce_space(space, window_s)`` reads the planes of one ``.xplane.pb``
(``jax.profiler.ProfileData``): each ``/device:TPU:<n>`` plane's
``XLA Ops`` line holds one event per operation run on that chip, named
by its HLO text (``%qmatmul_w8a16.40 = bf16[64,12288]... custom-call``);
an op is keyed by its instruction name (``qmatmul_w8a16.40``).  Ops
nest: a ``while`` holds the ops of its body.  Busy time is the union of
the op intervals; the traced window is the host's time between
starting and stopping the profiler; idle is the rest.  With several
chips, busy time is the mean over their planes.  Op time is inclusive;
the breakdown ranks ops by self time (their time less that of the ops
they hold), and ``op_count`` counts each op's events.  Each idle gap
between device operations is named by the innermost host event (any
thread) that covers its middle.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def union_ns(intervals: List[Tuple[float, float]]) -> Tuple[float, list]:
    """Total length of the union of ``(start, end)`` intervals, and the
    merged intervals in order."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def op_name(event_name: str) -> str:
    """``%name.3 = type op(...)`` -> ``name.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def self_times(events):
    """Self time per event of one line: its duration less that of the
    events nested in it.  ``events`` are (start, end, name)."""
    out, stack = [], []
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        rec = [s, e, name, e - s]
        if stack:
            stack[-1][3] -= e - s
        stack.append(rec)
        out.append(rec)
    return [(name, t) for _, _, name, t in out]


def _host_events(space):
    out = []
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.duration_ns > 0:
                    out.append((ev.start_ns, ev.end_ns, ev.name))
    return out


def _name_gap(host, mid):
    best = None
    for s, e, name in host:
        if s <= mid <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "no host event"


def reduce_space(space, window_s: float, top: int = 10) -> Dict:
    planes = [p for p in space.planes if p.name.startswith(DEVICE_PREFIX)]
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    busy, op_ns, op_n, self_ns, gaps = [], {}, {}, {}, []
    host = _host_events(space)
    for plane in planes:
        evs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                name = op_name(ev.name)
                evs.append((ev.start_ns, ev.end_ns, name))
                op_ns[name] = op_ns.get(name, 0.0) + ev.duration_ns
                op_n[name] = op_n.get(name, 0) + 1
        for name, t in self_times(evs):
            self_ns[name] = self_ns.get(name, 0.0) + t
        total, merged = union_ns([(s, e) for s, e, _ in evs])
        busy.append(total)
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            gaps.append((s1 - e0, (e0 + s1) / 2))
    gaps.sort(reverse=True)
    n = len(planes)
    ops = sorted(self_ns.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": sum(busy) / n * 1e-9,
        "window_s": window_s,
        "n_chips": n,
        "op_time_s": {k: v / n * 1e-9 for k, v in op_ns.items()},
        "op_count": {k: v / n for k, v in op_n.items()},
        "breakdown": {
            "device_ops": [[k, v / n * 1e-9] for k, v in ops[:top]],
            "idle_gaps": [[_name_gap(host, mid), g * 1e-9]
                          for g, mid in gaps[:top]],
        },
    }


def reduce_dir(trace_dir: str, window) -> Dict:
    """Reduce the one ``.xplane.pb`` under ``trace_dir``; ``window`` is
    the host's (start, stop) of the profiler in seconds."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace under {trace_dir}, "
                         f"found {len(files)}")
    return reduce_space(ProfileData.from_file(files[0]),
                        window[1] - window[0])
