#!/usr/bin/env python3
"""Record ``engine_tiny_tpu.xplane.pb.gz`` and its ``.json`` beside it:
the reduced mamba2-1.3b engine serving five short requests under the
profiler on one chip, for the span tests (``test_spans.py``).

    python chipbench/tests/data/record_engine_trace.py <out dir>

Run from the root of a checkout on a machine with a TPU.  The ``.json``
holds what the engine reported: its ticks and the positions its
requests advanced.  The trace keeps the device planes' ``XLA Ops`` and
``XLA Modules`` lines and, of the host's, the engine's spans and its
steps' dispatches; the runtime's other events, most of a trace's bytes,
are dropped.
"""
import glob
import gzip
import importlib.util
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT / "src"))


KEEP = ("engine.", "PjitFunction(")
DEVICE_LINES = ("XLA Ops", "XLA Modules")


def strip(raw: bytes) -> bytes:
    """The trace with only the device planes' op and module lines and,
    on the host planes, the events named ``engine.*`` or
    ``PjitFunction(...)``.  The XSpace
    message is read with the profiler's own proto module, loaded from
    its file so that nothing else of the package that ships it is
    imported."""
    import google.protobuf
    site = Path(google.protobuf.__file__).resolve().parents[2]
    path = site / "tensorflow/tsl/profiler/protobuf/xplane_pb2.py"
    spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    pb2 = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pb2)
    space = pb2.XSpace()
    space.ParseFromString(raw)
    keep = [p for p in space.planes
            if p.name.startswith(("/device:TPU:", "/host:CPU"))]
    for p in keep:
        if not p.name.startswith("/host:"):
            lines = [ln for ln in p.lines if ln.name in DEVICE_LINES]
            del p.lines[:]
            p.lines.extend(lines)
            continue
        names = {k: m.name for k, m in p.event_metadata.items()}
        for ln in p.lines:
            events = [ev for ev in ln.events
                      if names.get(ev.metadata_id, "").startswith(KEEP)]
            del ln.events[:]
            ln.events.extend(events)
        lines = [ln for ln in p.lines if ln.events]
        del p.lines[:]
        p.lines.extend(lines)
    out = pb2.XSpace()
    out.planes.extend(keep)
    return out.SerializeToString()


def main(out: Path) -> int:
    import jax
    from repro import engine as E
    from repro.configs import get_config
    from repro.core.qlinear import W8A16
    from repro.models import registry as R

    if jax.devices()[0].platform != "tpu":
        print("record_engine_trace: no TPU", file=sys.stderr)
        return 3
    cfg = get_config("mamba2-1.3b").reduced()
    params = R.init_quantized(jax.random.PRNGKey(0), cfg)
    eng = E.Engine(cfg, params, mode=W8A16, num_slots=4, max_seq=32,
                   prefill_chunk=4)
    reqs = E.synthetic_requests(5, rate_per_s=200.0, vocab=cfg.vocab,
                                prompt_len=6, max_new_tokens=4, seed=1)
    eng.warmup()
    tmp = tempfile.mkdtemp(prefix="engine-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        rep = eng.serve(reqs, clock="wall")
    finally:
        jax.profiler.stop_trace()
    (pb,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    out.mkdir(parents=True, exist_ok=True)
    (out / "engine_tiny_tpu.xplane.pb.gz").write_bytes(
        gzip.compress(strip(Path(pb).read_bytes()), mtime=0))
    prompt = {r.rid: len(r.prompt) for r in reqs}
    (out / "engine_tiny_tpu.json").write_text(json.dumps({
        "ticks": rep.ticks,
        "positions": sum(prompt[r.rid] + len(r.tokens) - 1
                         for r in rep.results),
        "prompt_positions": sum(n - 1 for n in prompt.values()),
        "device_kind": jax.devices()[0].device_kind}, indent=1) + "\n")
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(Path(sys.argv[1])))
