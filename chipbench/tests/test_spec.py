"""BENCHMARK.json resolves to files, and its names keep to the contract."""
import json
import re
from pathlib import Path

import pytest

import gen
import harness

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["chipbench"]
    assert SPEC["command"][1] == "chipbench/run.py"
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    files = harness.cell_files(SPEC, cell, ROOT)
    for key in ("config", "reference", "traffic"):
        assert files[key].exists(), files[key]
    conf = json.loads(files["config"].read_text())
    assert conf["name"] == files["cell"]["config"]
    assert conf["reduced"] == files["conf"]["reduced"]
    traffic = json.loads(files["traffic"].read_text())
    assert traffic["rate_per_s"] > 0
    assert gen.max_seq(traffic) > 0
    lims = conf["check"]["limits"]
    assert lims and all(isinstance(v, float) and v > 0
                        for v in lims.values())
    # every cell reports setup_s, one more end-to-end metric and one
    # per-layer metric
    e2e = [m["name"] for m in harness.cell_metrics(SPEC, cell, False)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.cell_metrics(SPEC, cell, True)


def test_metrics_have_readers_and_legal_names():
    names = set()
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert harness.metric_file(m["name"], ROOT) is not None, m["name"]
        assert m["name"] not in names
        names.add(m["name"])
        for w in m.get("workloads", []):
            assert w in CELLS
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for w in m["workloads"]:
            assert w in next(e for e in SPEC["end_to_end"]
                             if e["name"] == m["moves"])["workloads"]
    layers = {}
    for m in SPEC["per_layer"]:
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_names():
    for c in SPEC["configs"]:
        assert NAME.match(c["name"])
        for k in c["reduced"]:
            assert NAME.match(k)
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    assert len({w["name"] for w in SPEC["workloads"]}) == len(CELLS)
    pairs = {(w["config"], w["traffic"]) for w in SPEC["workloads"]}
    assert len(pairs) == len(CELLS)


def test_each_config_used_and_file_own():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    for f in files:
        assert f.startswith("chipbench/")


def test_no_tpu_exits_without_result(tmp_path):
    """With no TPU the run exits non-zero and prints no result line."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
