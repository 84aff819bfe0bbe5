"""Sharded executor parity: the tensor-parallel backend must be
BIT-FOR-BIT the single-device engine on 200-request traces, per decode
family, greedy AND sampled, plus paged + preemption under block
pressure.

XLA only honors ``--xla_force_host_platform_device_count`` before the
first jax import, so the 4-way CPU mesh runs in a subprocess (same
discipline as tests/test_bench_smoke.py); the in-process tp=1
conformance gate lives in tests/test_dispatch.py.  Every parity check
compares the sharded engine against the single-device engine serving
the SAME trace in the SAME process — the strictest comparison: any
reassociated float add, lost slot write, or mis-merged paged block
flips a bit.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                           + os.environ.get("XLA_FLAGS", ""))
import dataclasses, json, sys
import jax

from repro import engine as E
from repro.configs import get_config
from repro.models import registry as R

TP = 4
out = {"devices": len(jax.devices()), "checks": {}}
if out["devices"] < TP:
    print("RESULT " + json.dumps(out))
    sys.exit(0)


def parity(name, cfg, params, reqs, engine_kw, serve_kw):
    single = E.Engine(cfg, params, **engine_kw)
    sharded = E.Engine(cfg, params, backend=E.ShardedExecutor(tp=TP),
                       **engine_kw)
    r1 = single.serve(reqs, tick_s=1e-3, **serve_kw)
    r2 = sharded.serve(reqs, tick_s=1e-3, **serve_kw)
    out["checks"][name] = {
        "n": len(reqs),
        "results": len(r1.results),
        "bit_identical": r1.outputs() == r2.outputs(),
        "same_result_count": len(r1.results) == len(r2.results),
        "generated_tokens": r1.generated_tokens,
        "tokens_match": r1.generated_tokens == r2.generated_tokens,
        "preempted": (r1.preempted, r2.preempted),
        "leaked_blocks": (r1.leaked_blocks, r2.leaked_blocks),
    }


FAMILIES = [
    ("dense", "starcoder2-3b", True),
    ("moe", "qwen2-moe-a2.7b", True),
    ("encdec", "whisper-medium", False),
]
for fam, arch, kvq in FAMILIES:
    cfg = get_config(arch).reduced()
    if kvq:
        cfg = dataclasses.replace(cfg, kv_quant=True)
    params = R.init(jax.random.PRNGKey(0), cfg)
    src = R.source_shape(cfg)
    reqs = E.synthetic_requests(200, rate_per_s=2000.0, vocab=cfg.vocab,
                                prompt_len=4, max_new_tokens=5,
                                source_shape=src)
    kw = dict(num_slots=8, max_seq=16)
    parity(fam + "/greedy", cfg, params, reqs, kw, {})
    parity(fam + "/sampled", cfg, params, reqs,
           dict(kw, temperature=0.8, rng=jax.random.PRNGKey(7)), {})

# paged + preemption + sampled under block pressure (the
# tests/test_robustness.py recipe, scaled to the 200-request trace):
# stash/exact-resume must survive the shard merge bit-for-bit
cfg = dataclasses.replace(get_config("starcoder2-3b").reduced(),
                          kv_quant=True)
params = R.init(jax.random.PRNGKey(0), cfg)
reqs = E.synthetic_requests(
    200, rate_per_s=2000.0, vocab=cfg.vocab, prompt_len=3,
    max_new_tokens=4,
    priority=lambda rid: "batch" if rid % 2 else "interactive")
parity("dense/paged_preempt_sampled", cfg, params, reqs,
       dict(num_slots=4, max_seq=16, prefill_chunk=2, block_size=4,
            num_blocks=9, temperature=0.8, rng=jax.random.PRNGKey(7)),
       dict(preemption=True))

# chunked + paged greedy (block-table decode through the shard merge)
reqs = E.synthetic_requests(200, rate_per_s=2000.0, vocab=cfg.vocab,
                            prompt_len=6, max_new_tokens=5,
                            shared_prefix_len=4)
parity("dense/paged_chunked", cfg, params, reqs,
       dict(num_slots=8, max_seq=16, prefill_chunk=4, block_size=4), {})

print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def shard_doc(tmp_path_factory):
    """Run every parity check once, in one subprocess (one jax import,
    one compile set), and hand the JSON record to the tests."""
    tmp = tmp_path_factory.mktemp("sharded")
    script = tmp / "worker.py"
    script.write_text(WORKER)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    env.setdefault("REPRO_AUTOTUNE_CACHE", str(tmp / "autotune.json"))
    proc = subprocess.run([sys.executable, str(script)], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, (
        f"sharded parity worker failed\nstdout:\n{proc.stdout}\n"
        f"stderr:\n{proc.stderr[-4000:]}")
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT ")][-1]
    doc = json.loads(line[len("RESULT "):])
    if doc["devices"] < 4:
        pytest.skip(f"no 4-way host mesh here ({doc})")
    return doc


ALL_CHECKS = ["dense/greedy", "dense/sampled", "moe/greedy",
              "moe/sampled", "encdec/greedy", "encdec/sampled",
              "dense/paged_preempt_sampled", "dense/paged_chunked"]


def test_all_parity_checks_ran(shard_doc):
    assert sorted(shard_doc["checks"]) == sorted(ALL_CHECKS)
    for name, c in shard_doc["checks"].items():
        assert c["n"] == 200, name
        assert c["results"] == 200, (name, c)


@pytest.mark.parametrize("name", ALL_CHECKS)
def test_sharded_is_bit_identical(shard_doc, name):
    c = shard_doc["checks"][name]
    assert c["bit_identical"], (
        f"{name}: sharded outputs diverge from single-device "
        f"({c})")
    assert c["same_result_count"] and c["tokens_match"], (name, c)


def test_preemption_fired_and_matched(shard_doc):
    """The paged-pressure arm must actually preempt (otherwise the
    stash/resume path was never sharded) and both backends must count
    the SAME preemptions — scheduling is host-side and backend-blind."""
    p1, p2 = shard_doc["checks"]["dense/paged_preempt_sampled"]["preempted"]
    assert p1 > 0 and p1 == p2
    for name in ("dense/paged_preempt_sampled", "dense/paged_chunked"):
        l1, l2 = shard_doc["checks"][name]["leaked_blocks"]
        assert l1 == 0 and l2 == 0, (name, l1, l2)
