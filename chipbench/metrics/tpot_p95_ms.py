"""95th percentile of time per output token after the first, in ms.

Per request ``(finish_s - first_token_s) / (tokens - 1)``, over every
request that produced at least two tokens (nearest rank).
"""
import math


def read(run):
    xs = sorted((r["finish_s"] - r["first_token_s"]) / (r["n_tokens"] - 1)
                * 1e3 for r in run["results"]
                if r["status"] == "ok" and r["n_tokens"] >= 2)
    return xs[max(0, math.ceil(0.95 * len(xs)) - 1)] if xs else None
