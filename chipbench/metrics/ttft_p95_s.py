"""95th percentile of time to first token, from when each request was due.

``first_token_s - arrival_s`` of every request, over all requests of the
run (nearest rank).  A request that never produced a token counts as
never served: its time is infinite.
"""
import math


def p95(xs):
    xs = sorted(xs)
    return xs[max(0, math.ceil(0.95 * len(xs)) - 1)] if xs else None


def read(run):
    return p95([r["first_token_s"] - r["arrival_s"]
                if r["first_token_s"] >= 0 else math.inf
                for r in run["results"]])
