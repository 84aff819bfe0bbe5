"""One run of one cell: build, warm up, serve a timed window, check, report.

Everything about a cell is found by name: the workload in
``BENCHMARK.json`` names a configuration (``configs/<config>.json`` with
its reference ``configs/<config>.py`` beside it) and a traffic mix
(``traffic/<mix>.json``); each metric is read by ``metrics/<name>.py``,
or by ``metrics/<base>.py`` for a name ``<base>.<variant>``.  Adding a
cell or a metric adds files and edits none.

The run: weights from ``--seed`` on the device (one jitted call of the
configuration's reference module), the program's ``Engine`` built at the
cell's sizes and warmed through ``Engine.warmup``, the traffic generated
from ``--seed``, then ``Engine.serve(requests, clock="wall")`` as an
open loop over ``--seconds`` of arrivals, drained to the last request.
After the window the engine is freed, and a sample of the finished
requests is compared with the configuration's reference (``correct.py``).
``calibrate.py`` and ``sweep.py`` drive this same run, with the
program's lower-precision path switched on or the traffic's rate and
base seed changed.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import sys
import time
import warnings
from pathlib import Path
from typing import Callable, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def setup():
    """The checkout's compile cache and the program's import path.  The
    cache lives inside the checkout at a fixed path; JAX's size-bounded
    cache writes into the directory but does not create it."""
    import os
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.makedirs(ROOT / ".jax_cache", exist_ok=True)
    for path in (HERE, ROOT / "src"):
        if str(path) not in sys.path:
            sys.path.insert(0, str(path))


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_files(spec: dict, workload: str, root: Path = ROOT,
               traffic_dir: Optional[Path] = None) -> dict:
    """The cell's entry, its configuration entry and the files both name.
    The reference sits beside the configuration file, with its stem, or
    where the file's ``reference`` key points (relative to it)."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    cell = cells[workload]
    confs = {c["name"]: c for c in spec["configs"]}
    conf = confs[cell["config"]]
    cfile = root / conf["file"]
    refname = json.loads(cfile.read_text()).get("reference")
    return {"cell": cell, "conf": conf, "config": cfile,
            "reference": (cfile.parent / refname if refname
                          else cfile.with_suffix(".py")),
            "traffic": (traffic_dir or root / "chipbench" / "traffic")
            / (cell["traffic"] + ".json")}


def metric_file(name: str, root: Path = ROOT) -> Optional[Path]:
    d = root / "chipbench" / "metrics"
    for stem in (name, name.split(".")[0]):
        p = d / (stem + ".py")
        if p.exists():
            return p
    return None


def cell_metrics(spec: dict, workload: str, trace: bool) -> List[dict]:
    """The metrics a run of ``workload`` reports: its end-to-end metrics
    with ``--trace 0``, its per-layer metrics with ``--trace 1``."""
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def find_chips(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    return devs[:chips]


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


# ---------------------------------------------------------------------------
# building the system under test
# ---------------------------------------------------------------------------

def program_config(conf: dict):
    """The program's ArchConfig for the configuration file, checked
    against every width the file states."""
    import dataclasses
    from repro.configs import get_config
    p = conf["program"]
    cfg = get_config(p["registry"])
    if p.get("variant") == "reduced":
        cfg = cfg.reduced()
    cfg = dataclasses.replace(cfg, **p.get("overrides", {}))
    for key, attr in p["match"].items():
        want = conf
        for part in key.split("."):
            want = want[part]
        if getattr(cfg, attr) != want:
            raise ValueError(f"{conf['name']}: the program's {attr} is "
                             f"{getattr(cfg, attr)!r}, the file's {key} is "
                             f"{want!r}")
    return cfg


def to_program(tree):
    """``("int8", values, scale)`` leaves become the program's QTensor."""
    from repro.core.quant import QTensor
    if isinstance(tree, tuple) and tree and tree[0] == "int8":
        return QTensor(values=tree[1], scale=tree[2], bits=8)
    if isinstance(tree, dict):
        return {k: to_program(v) for k, v in tree.items()}
    return tree


def seed_key(seed: int):
    """A PRNG key that depends on every bit of a seed up to 64 bits."""
    import jax
    k = jax.random.key(0)
    k = jax.random.fold_in(k, seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def build_engine(conf: dict, cfg, params, max_seq: int):
    """The program's engine at the configuration's serving settings.
    ``w8a16`` is what the cells serve; ``w8a8`` is the program's own
    lower-precision path, switched on only as the check's control."""
    from repro.core.qlinear import W8A8, W8A16
    from repro.engine import Engine
    s = conf["serving"]
    modes = {"w8a16": W8A16, "w8a8": W8A8}
    if s["quant"] not in modes:
        raise ValueError(f"serving quant {s['quant']!r}: not one of "
                         f"{sorted(modes)}")
    return Engine(cfg, params, mode=modes[s["quant"]],
                  num_slots=s["num_slots"], max_seq=max_seq,
                  block_size=s.get("block_size"),
                  prefill_chunk=s.get("prefill_chunk"))


class TraceWindow:
    """The profiler's window, started and stopped at fixed times of the
    serve through the engine's own schedule of control operations
    (``Engine.serve(control=...)``), with the host times of both."""

    def __init__(self, trace_dir: str, start_s: float, stop_s: float):
        self.dir = trace_dir
        self.window = None           # (host start, host stop), perf_counter
        self.schedule = [(start_s, self.start), (stop_s, self.stop)]

    def start(self, _engine=None):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.window = [time.perf_counter(), None]

    def stop(self, _engine=None):
        import jax
        if self.window is not None and self.window[1] is None:
            self.window[1] = time.perf_counter()
            jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="keep the profiler trace in this directory")
    return ap.parse_args(argv)


def run_cell(argv, *, t_start: float, require_chip: bool = True,
             root: Path = ROOT, spec: Optional[dict] = None,
             traffic_dir: Optional[Path] = None,
             patch: Optional[Callable] = None,
             overrides: Optional[dict] = None, check: bool = True):
    """One run; returns the result line's object and the run's record.
    ``patch(engine)`` may alter the built engine (the fault tests break
    the timed path with it); ``overrides`` replaces keys of the
    configuration's ``serving`` and of the traffic file (the control's
    path, a sweep's rate); ``require_chip=False`` skips the look for a
    chip; ``check=False`` skips the comparison with the reference."""
    args = parse(argv)
    spec = spec if spec is not None else load_spec(root)
    files = cell_files(spec, args.workload, root, traffic_dir)
    overrides = overrides or {}
    conf = json.loads(files["config"].read_text())
    conf["serving"] = {**conf["serving"], **overrides.get("serving", {})}
    traffic = {**json.loads(files["traffic"].read_text()),
               **overrides.get("traffic", {})}
    metrics = cell_metrics(spec, args.workload, bool(args.trace))

    import jax
    if require_chip:
        devs = find_chips(files["cell"]["chips"])
        from repro.launch.cache import use_compile_cache
        use_compile_cache()
    else:
        devs = jax.devices()[:1]

    import correct
    import gen
    import trace_reduce
    import work
    from peaks import peaks

    ref = load_module(files["reference"])
    cfg = program_config(conf)
    max_seq = gen.max_seq(traffic)

    weights = ref.make_weights(seed_key(args.seed), conf)
    jax.block_until_ready(weights)
    eng = build_engine(conf, cfg, to_program(ref.program_tree(weights)),
                       max_seq)
    eng.warmup()
    jax.block_until_ready([ln.cache for ln in eng.lanes.values()])
    if patch is not None:
        patch(eng)

    from repro.engine import EngineRequest
    reqs = [EngineRequest(rid=r.rid, prompt=r.prompt,
                          max_new_tokens=r.max_new, arrival_s=r.arrival_s)
            for r in gen.requests(traffic, args.seed, args.seconds,
                                  conf["vocab_size"])]

    trace = None
    if args.trace:
        import tempfile
        start_s = 0.5 * args.seconds
        trace = TraceWindow(
            args.keep_trace or tempfile.mkdtemp(prefix="chipbench-"),
            start_s, start_s + min(3.0, 0.25 * args.seconds))

    setup_s = time.perf_counter() - t_start
    t_serve = time.perf_counter()
    try:
        with warnings.catch_warnings():
            # the engine's straggler watchdog warns per slow tick; its
            # count stays in the report, the lines would bury the check's
            warnings.filterwarnings("ignore", message="engine tick")
            rep = eng.serve(reqs, clock="wall",
                            control=trace.schedule if trace else ())
    finally:
        if trace is not None:
            trace.stop()
    dev = device_info(devs)

    by_rid = {r.rid: r for r in reqs}
    results = [dict(rid=r.rid, arrival_s=r.arrival_s, admit_s=r.admit_s,
                    first_token_s=r.first_token_s, finish_s=r.finish_s,
                    n_tokens=len(r.tokens), status=r.status,
                    prompt_len=len(by_rid[r.rid].prompt),
                    max_new=by_rid[r.rid].max_new_tokens)
               for r in rep.results]
    record = {
        "setup_s": setup_s, "results": results,
        "serve": {"ticks": rep.ticks, "wall_s": rep.wall_s},
        "peaks": peaks(dev["kind"]) if require_chip else None,
        "linears": ref.linears(conf), "layers": ref.dims(conf)["L"],
        "trace": None, "cell": files["cell"]["name"],
    }
    if trace is not None:
        red = trace_reduce.reduce_dir(trace.dir, trace.window)
        # the traced window in the serve's own time, which stamps use
        red.update(work.window_work(ref, conf, results,
                                    [t - t_serve for t in trace.window]))
        record["trace"] = red
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
        if args.keep_trace is None:
            import shutil
            shutil.rmtree(trace.dir, ignore_errors=True)

    out_metrics = {}
    for m in metrics:
        path = metric_file(m["name"], root)
        if path is None:
            raise FileNotFoundError(f"no reader for metric {m['name']!r}")
        val = load_module(path).read(record)
        if val is not None:
            # a request never served reads as an unbounded time
            val = val if math.isfinite(val) else 1e30
            out_metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}

    # the check, after the window: the program's state is freed first
    outputs = {r.rid: list(r.tokens) for r in rep.results
               if r.status == "ok"}
    failed = sum(1 for r in rep.results if r.status != "ok") + (
        len(reqs) - len(rep.results))
    record["outputs"] = outputs
    del eng, rep
    gc.collect()
    checks = {}
    if check:
        checks, record["readings"] = correct.check(
            ref, conf, weights, by_rid, outputs, seed=args.seed,
            max_seq=max_seq, failed=failed)
    out = {"correct": bool(checks) and all(
               c["value"] <= c["limit"] for c in checks.values()),
           "attempted": len(reqs), "failed": failed,
           "metrics": out_metrics, "device": dev}
    if record["trace"] is not None:
        out["breakdown"] = record["trace"]["breakdown"]
    out["checks"] = checks
    return out, record


def run(argv, **kw) -> dict:
    """One run; the result line's object (see ``run_cell``)."""
    return run_cell(argv, **kw)[0]


def main(argv, t_start: float) -> int:
    try:
        out, record = run_cell(argv, t_start=t_start)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    print("readings " + json.dumps(record.get("readings")), file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0
