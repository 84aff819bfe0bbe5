#!/usr/bin/env python3
"""Find a cell's knee: serve its traffic at several fixed rates.

    python chipbench/sweep.py --workload <name> --rates 4,6,8 \
        --base-seeds 1,2 --seconds 40

Each (rate, base seed) is one run of the cell through the harness
(``harness.run_cell``, without the correctness check), with the traffic
file's ``rate_per_s`` and ``base_seed`` replaced: another base seed
draws another arrival path and other lengths of the same mix.  Per run
it prints one JSON line: the offered and completed request rates, the
p95 time to first token, the mean queue wait of the first and the last
third of the arrivals (a backlog that grows shows as the last third
waiting longer), and output tokens per second.  The knee is the highest
rate with no growing backlog on any base seed; the cell's traffic file
then holds its rate as a number.  The benchmark's own runs never sweep.
"""
import argparse
import json
import sys
import time

import harness


def third_waits(results):
    rs = sorted(results, key=lambda r: r["arrival_s"])
    k = max(1, len(rs) // 3)
    mean = lambda xs: sum(r["admit_s"] - r["arrival_s"] for r in xs) / len(xs)
    return mean(rs[:k]), mean(rs[-k:])


def main(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--base-seeds", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=101)
    args = ap.parse_args(argv)

    for base in [int(b) for b in args.base_seeds.split(",")]:
        for rate in [float(r) for r in args.rates.split(",")]:
            t0 = time.perf_counter()
            out, rec = harness.run_cell(
                ["--workload", args.workload, "--seed", str(args.seed),
                 "--seconds", str(args.seconds), "--trace", "0"],
                t_start=t0, check=False,
                overrides={"traffic": {"rate_per_s": rate,
                                       "base_seed": base}})
            res = rec["results"]
            ttft = sorted(r["first_token_s"] - r["arrival_s"] for r in res)
            first, last = third_waits(res)
            end = max(r["finish_s"] for r in res)
            print(json.dumps({
                "workload": args.workload, "rate": rate, "base_seed": base,
                "n": len(res), "offered_per_s": len(res) / args.seconds,
                "completed_per_s": len(res) / end,
                "ttft_p95_s": ttft[int(0.95 * (len(ttft) - 1))],
                "wait_first_third_s": first, "wait_last_third_s": last,
                "tokens_per_s": sum(r["n_tokens"] for r in res) / end,
                "ms_per_tick": rec["serve"]["wall_s"]
                / max(1, rec["serve"]["ticks"]) * 1e3,
                "run_s": time.perf_counter() - t0}), flush=True)


if __name__ == "__main__":
    harness.setup()
    main(sys.argv[1:])
