"""``correct`` on CPU-sized cells: true for the program as it is, false
when the timed path is broken underneath.

The runs drive the whole harness (weights, engine, warm-up, the timed
serve, the reference check) with the look for a chip skipped, on tiny
configurations of both families whose references are the benchmark's
own.  Faults are planted in the engine after warm-up, so they sit on
the path the window drives.
"""
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import harness

DATA = Path(__file__).resolve().parent / "data"
ROOT = Path(__file__).resolve().parents[2]
SPEC = {
    "configs": [
        {"name": "tiny-dense", "file": "chipbench/tests/data/tiny-dense.json"},
        {"name": "tiny-ssm", "file": "chipbench/tests/data/tiny-ssm.json"}],
    "workloads": [
        {"name": "tiny-dense.mix", "config": "tiny-dense",
         "traffic": "tiny-mix", "chips": 1},
        {"name": "tiny-ssm.mix", "config": "tiny-ssm",
         "traffic": "tiny-mix", "chips": 1}],
    "end_to_end": [{"name": "ttft_p95_s", "unit": "s"},
                   {"name": "setup_s", "unit": "s"}],
    "per_layer": [],
}
CELLS = ["tiny-dense.mix", "tiny-ssm.mix"]


def run(cell, seed, patch=None):
    return harness.run(["--workload", cell, "--seed", str(seed),
                        "--seconds", "1.5", "--trace", "0"],
                       t_start=time.perf_counter(), require_chip=False,
                       spec=SPEC, traffic_dir=DATA, patch=patch)


def state_unchanged(eng):
    """The fused step computes, but hands back the state it was given."""
    lane = next(iter(eng.lanes.values()))
    inner = lane.step

    def step(params, tokens, cache, index, active):
        kept = jax.tree_util.tree_map(jnp.copy, cache)   # the step donates
        nxt, _, new_index = inner(params, tokens, cache, index, active)
        return nxt, kept, new_index
    lane.step = step


def token_altered(eng):
    """Every sampled token is replaced by its neighbour in the vocabulary
    where the step produces it."""
    lane = next(iter(eng.lanes.values()))
    inner, vocab = lane.step, lane.cfg.vocab

    def step(params, tokens, cache, index, active):
        nxt, cache, new_index = inner(params, tokens, cache, index, active)
        return (nxt + 1) % vocab, cache, new_index
    lane.step = step


@pytest.mark.parametrize("cell", CELLS)
def test_sound_program_is_correct(cell):
    out = run(cell, 2**31 + 99)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 10
    assert set(out["metrics"]) == {"ttft_p95_s", "setup_s"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", [state_unchanged, token_altered])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_path_is_not_correct(cell, fault):
    out = run(cell, 5, patch=fault)
    assert not out["correct"], out["checks"]
    gap = out["checks"]["widest_logit_gap"]
    assert gap["value"] > gap["limit"]
