#!/usr/bin/env python3
"""Readings that a correctness limit is set from, on the chip.

    python chipbench/calibrate.py --workload <name> --seeds 1,2,3 \
        --seconds 10

One process; per seed two runs of the cell through the harness
(``harness.run_cell``), each at the cell's own sizes and load over
``--seconds`` of arrivals, drained, and checked by ``correct.py`` as a
benchmark run is:

- ``program``: the program as the cell serves it;
- ``control``: the program with its own lower-precision path switched
  on, as the configuration's ``check.control`` names it (``{"quant":
  "w8a8"}``: every linear layer's input rounded to int8, below the
  bfloat16 activations that W8A16 states).

Per run it prints one JSON line: every reading of ``correct.readings``
and whether the run came out ``correct``.  The lower reading of a limit
is the largest ``program`` reading over a dozen seeds or more, the upper
one the smallest ``control`` reading; the control has to come out not
correct.  The benchmark's own runs never run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

import harness


def main(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--sides", default="program,control")
    ap.add_argument("--no-chip", action="store_true",
                    help="skip the look for a chip (CPU tests)")
    ap.add_argument("--spec", default=None,
                    help="a BENCHMARK.json-shaped file (CPU tests)")
    ap.add_argument("--traffic-dir", default=None)
    args = ap.parse_args(argv)

    spec = (json.loads(Path(args.spec).read_text()) if args.spec
            else harness.load_spec())
    traffic_dir = Path(args.traffic_dir) if args.traffic_dir else None
    files = harness.cell_files(spec, args.workload, traffic_dir=traffic_dir)
    control = json.loads(files["config"].read_text())["check"]["control"]
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        for side in args.sides.split(","):
            row = {"workload": args.workload, "seed": seed, "side": side}
            try:
                out, rec = harness.run_cell(
                    ["--workload", args.workload, "--seed", str(seed),
                     "--seconds", str(args.seconds), "--trace", "0"],
                    t_start=time.perf_counter(),
                    require_chip=not args.no_chip, spec=spec,
                    traffic_dir=traffic_dir,
                    overrides={"serving": control} if side == "control"
                    else None)
            except harness.NoChip:
                raise
            except Exception as e:      # noqa: BLE001
                # a control that crashes has failed, and gives no reading
                row.update(correct=False, error=repr(e)[:500])
            else:
                row.update(correct=out["correct"],
                           requests=out["attempted"],
                           finished=len(rec["outputs"]), **rec["readings"])
            print(json.dumps(row), flush=True)
            rows.append(row)
    return rows


if __name__ == "__main__":
    harness.setup()
    main(sys.argv[1:])
