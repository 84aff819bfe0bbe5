"""95th percentile of the wait in the engine's queue: admission minus
arrival, over every admitted request (nearest rank)."""
import math


def read(run):
    xs = sorted(r["admit_s"] - r["arrival_s"] for r in run["results"]
                if r["admit_s"] >= 0)
    return xs[max(0, math.ceil(0.95 * len(xs)) - 1)] if xs else None
