"""The control of the correctness check at a size a test run holds.

On the chip the control runs at each cell's own size (``calibrate.py``);
``PERF.md`` gives those readings and the limits set from them.  Here the
same procedure runs on tiny configurations of both families: the
program served through the harness, then its control, the program with
its own W8A8 path switched on, put in the program's place and checked by
the harness's own comparison (``harness.run_cell`` -> ``correct.check``).
The tiny limits are set from CPU readings of eight seeds (dense model:
program widest gap up to 0.045 and mean gap up to 0.00027, control from
0.023 and 0.00035; SSM: program up to 0.033 and 0.00017, control from
0.039 and 0.00025).  At this size the control sits close to the
program, and W8A8's scale is shared by the rows that happen to share a
tick, so its readings move from run to run: the control must come out
not correct on at least half of the seeds, and read above the program
over all of them.
"""
import json
from pathlib import Path

import pytest

import calibrate

DATA = Path(__file__).resolve().parent / "data"


@pytest.mark.parametrize("cell", ["tiny-dense.mix", "tiny-ssm.mix"])
def test_control_reads_above_the_program(cell, tmp_path):
    spec = tmp_path / "spec.json"
    spec.write_text(json.dumps({
        "configs": [
            {"name": "tiny-dense",
             "file": "chipbench/tests/data/tiny-dense.json"},
            {"name": "tiny-ssm", "file": "chipbench/tests/data/tiny-ssm.json"}],
        "workloads": [
            {"name": "tiny-dense.mix", "config": "tiny-dense",
             "traffic": "tiny-mix", "chips": 1},
            {"name": "tiny-ssm.mix", "config": "tiny-ssm",
             "traffic": "tiny-mix", "chips": 1}],
        "end_to_end": [], "per_layer": []}))
    rows = calibrate.main(["--workload", cell, "--seeds", "1,2,3,4",
                           "--seconds", "4", "--no-chip", "--spec",
                           str(spec), "--traffic-dir", str(DATA)])
    prog = [r for r in rows if r["side"] == "program"]
    ctrl = [r for r in rows if r["side"] == "control"]
    assert len(prog) == len(ctrl) == 4
    assert all(r["finished"] == r["requests"] for r in rows)
    assert all(r["correct"] for r in prog)
    assert sum(not r["correct"] for r in ctrl) >= 2
    for key in ("widest_logit_gap", "mean_logit_gap", "off_reference_pct"):
        assert sum(r[key] for r in ctrl) > sum(r[key] for r in prog)
