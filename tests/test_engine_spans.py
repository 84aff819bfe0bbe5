"""The engine's own instruments on the profiler's clock: the tick loop's
spans, the step kinds' names, and the compile counters of ``serve`` and
``warmup`` (docs/serving.md, "Profiling a serve")."""
import dataclasses
import glob
import os

import jax
import pytest
from jax._src import monitoring
from jax.profiler import ProfileData

from repro import engine as E
from repro.configs import get_config
from repro.models import registry as R

PHASES = ("engine.admit", "engine.prefill", "engine.draft",
          "engine.dispatch", "engine.pull", "engine.bookkeep")


@pytest.fixture(scope="module")
def dense():
    cfg = dataclasses.replace(get_config("starcoder2-3b").reduced(),
                              kv_quant=True)
    return cfg, R.init(jax.random.PRNGKey(0), cfg)


def _requests(cfg, late_s=0.3):
    """Four requests at once, then one after the pool has drained, so
    the serve waits between them."""
    reqs = E.synthetic_requests(4, rate_per_s=4000.0, vocab=cfg.vocab,
                                prompt_len=9, max_new_tokens=4, seed=3)
    return reqs + [dataclasses.replace(reqs[0], rid=99, arrival_s=late_s)]


def _traced_serve(eng, reqs, tmp_path):
    eng.warmup()
    with jax.profiler.trace(str(tmp_path)):
        rep = eng.serve(reqs, clock="wall")
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    events = [ev for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events]
    return rep, events


def _spans(events, name):
    return [ev for ev in events if ev.name == name]


def _positions(reqs, rep):
    """Positions the served requests advanced: every prompt token and
    every output token but the last is fed once."""
    prompt = {r.rid: len(r.prompt) for r in reqs}
    return sum(prompt[r.rid] + len(r.tokens) - 1 for r in rep.results)


@pytest.mark.parametrize("chunk", [None, 4])
def test_one_tick_span_per_tick_and_rows(dense, tmp_path, chunk):
    cfg, params = dense
    eng = E.Engine(cfg, params, num_slots=4, max_seq=16,
                   prefill_chunk=chunk)
    reqs = _requests(cfg)
    rep, events = _traced_serve(eng, reqs, tmp_path)
    assert all(r.status == "ok" for r in rep.results)
    ticks = _spans(events, "engine.tick")
    assert len(ticks) == rep.ticks
    stats = [dict(ev.stats) for ev in ticks]
    assert sorted(s["step_num"] for s in stats) == list(range(rep.ticks))
    # every phase lies inside a tick, and no tick overlaps a wait
    bounds = [(ev.start_ns, ev.end_ns) for ev in ticks]
    for name in PHASES:
        for ev in _spans(events, name):
            assert any(s <= ev.start_ns and ev.end_ns <= e
                       for s, e in bounds), name
    waits = _spans(events, "engine.wait")
    assert waits                               # the late request waited
    for w in waits:
        assert all(w.end_ns <= s or e <= w.start_ns for s, e in bounds)
    # rows: every position advanced, once; prompt rows all but the last
    # prompt token of each request (its sample is the first output)
    assert sum(s["rows"] for s in stats) == _positions(reqs, rep)
    assert sum(s["prompt_rows"] for s in stats) == sum(
        len(r.prompt) - 1 for r in reqs)
    for name in ("engine.admit", "engine.dispatch", "engine.pull",
                 "engine.bookkeep"):
        assert _spans(events, name), name
    assert not _spans(events, "engine.draft")
    names = {ev.name for ev in events}
    assert "PjitFunction(slot_decode)" in names
    assert ("PjitFunction(prefill_chunk)" in names) == (chunk is not None)
    if chunk:
        assert _spans(events, "engine.prefill")


def test_speculative_steps_named(dense, tmp_path):
    cfg, params = dense
    eng = E.Engine(cfg, params, num_slots=4, max_seq=16, prefill_chunk=4,
                   spec_k=2, draft_layers=1)
    reqs = _requests(cfg)
    rep, events = _traced_serve(eng, reqs, tmp_path)
    names = {ev.name for ev in events}
    assert {"PjitFunction(verify)", "PjitFunction(draft_propose)",
            "PjitFunction(prefill_chunk)"} <= names
    assert "PjitFunction(slot_decode)" not in names
    ticks = _spans(events, "engine.tick")
    assert len(ticks) == rep.ticks
    assert _spans(events, "engine.draft")
    stats = [dict(ev.stats) for ev in ticks]
    assert sum(s["rows"] for s in stats) == _positions(reqs, rep)
    assert sum(s["prompt_rows"] for s in stats) == sum(
        len(r.prompt) - 1 for r in reqs)


def _listeners():
    return (list(monitoring.get_event_duration_listeners()),
            list(monitoring.get_event_listeners()))


def test_compile_counters(dense):
    cfg, params = dense
    before = _listeners()
    # a cache length no other engine in this module compiles
    eng = E.Engine(cfg, params, num_slots=4, max_seq=48, prefill_chunk=4)
    first = eng.warmup()
    assert isinstance(first, E.WarmupReport)
    # the slot step and the chunk buckets 1, 2 and 4, at the least
    assert first.compiles >= 4
    assert 0 < first.compile_s <= first.seconds
    assert first.cache_hits <= first.compiles
    again = eng.warmup()                   # every program is in memory
    assert (again.compiles, again.cache_hits) == (0, 0)
    reqs = _requests(cfg, late_s=0.0)
    assert eng.serve(reqs).compiles == 0
    # a pool size warm-up never saw compiles inside the serve
    cold = E.Engine(cfg, params, num_slots=2, max_seq=48)
    assert cold.serve(reqs).compiles >= 1
    assert _listeners() == before


def test_no_listener_left_when_serve_raises(dense):
    cfg, params = dense
    before = _listeners()
    eng = E.Engine(cfg, params, num_slots=4, max_seq=16)

    def boom(_engine):
        raise RuntimeError("control op failed")

    with pytest.raises(RuntimeError, match="control op failed"):
        eng.serve(_requests(cfg, late_s=0.0), control=[(0.0, boom)])
    assert _listeners() == before
