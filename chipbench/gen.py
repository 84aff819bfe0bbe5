"""Traffic generation: one general generator, driven by a traffic file.

A traffic file (``chipbench/traffic/<mix>.json``) gives the arrival
process and its rate, and bounded-Pareto ranges for prompt and output
lengths.  Every seed serves the same work:

- The arrival times and the (prompt, output) lengths of the requests are
  drawn from the file's ``base_seed`` alone, for a window of
  ``seconds``.  Every run of a cell sends the same requests at the same
  times, whatever its seed, so runs differ only by the machine's noise.
- ``--seed`` draws the prompt token ids over the vocabulary (and the
  weights, elsewhere).

The arrival processes and the bounded-Pareto inverse CDF are copies of
``benchmarks/traces.py`` (``mmpp_process``, ``heavy_tailed_lengths``),
kept here so the yardstick cannot move with the program.
"""
from __future__ import annotations

import dataclasses
import math
import random
from typing import List, Sequence, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    rid: int
    prompt: Tuple[int, ...]
    max_new: int
    arrival_s: float


def bounded_pareto(n: int, *, lo: int, hi: int, alpha: float,
                   rng: random.Random) -> List[int]:
    """Bounded-Pareto integer lengths in ``[lo, hi]`` by the inverse CDF."""
    if not 1 <= lo <= hi:
        raise ValueError(f"need 1 <= lo <= hi, got lo={lo}, hi={hi}")
    if alpha <= 0:
        raise ValueError(f"alpha must be > 0, got {alpha}")
    la, ha = lo ** -alpha, hi ** -alpha
    out = []
    for _ in range(n):
        u = rng.random()
        x = (la - u * (la - ha)) ** (-1.0 / alpha)
        out.append(min(hi, max(lo, int(round(x)))))
    return out


def bounded_pareto_mean(lo: int, hi: int, alpha: float) -> float:
    """Mean of the continuous bounded Pareto on ``[lo, hi]``."""
    norm = 1.0 - (lo / hi) ** alpha
    if alpha == 1.0:
        return lo * math.log(hi / lo) / norm
    return (lo ** alpha / norm * alpha / (alpha - 1.0)
            * (lo ** (1.0 - alpha) - hi ** (1.0 - alpha)))


def poisson_arrivals(rate: float, span: float,
                     rng: random.Random) -> List[float]:
    """Poisson arrival times on ``[0, span)``."""
    out, t = [], rng.expovariate(rate)
    while t < span:
        out.append(t)
        t += rng.expovariate(rate)
    return out


def mmpp_arrivals(rate: float, span: float, modulation: Sequence[float],
                  dwell_s: Sequence[float],
                  rng: random.Random) -> List[float]:
    """2-state MMPP arrival times on ``[0, span)`` (copy of
    ``benchmarks/traces.py::mmpp_process``): state ``k`` emits at
    ``rate * modulation[k]`` for an exponential time of mean
    ``dwell_s[k]``; a draw that crosses the boundary is discarded and
    redrawn at the next state's rate, which is exact for exponential
    gaps."""
    if len(modulation) != 2 or len(dwell_s) != 2:
        raise ValueError("the MMPP takes exactly two states")
    out, t, state = [], 0.0, 0
    while t < span:
        end = min(span, t + rng.expovariate(1.0 / dwell_s[state]))
        while True:
            dt = rng.expovariate(rate * modulation[state])
            if t + dt > end:
                break
            t += dt
            out.append(t)
        t, state = end, 1 - state
    return out


def arrivals(traffic: dict, rate: float, seconds: float) -> List[float]:
    """Arrival times on ``[0, seconds)``, from the file's ``base_seed``."""
    rng = random.Random(traffic["base_seed"])
    kind = traffic["arrival"]
    if kind == "poisson":
        return poisson_arrivals(rate, seconds, rng)
    if kind == "mmpp":
        return mmpp_arrivals(rate, seconds, traffic["mmpp"]["modulation"],
                             traffic["mmpp"]["dwell_s"], rng)
    raise ValueError(f"unknown arrival process {kind!r}")


def requests(traffic: dict, seed: int, seconds: float,
             vocab: int) -> List[Req]:
    """The cell's requests for one run: ``seconds`` of arrivals at the
    file's rate, lengths from the file's ranges (both from its
    ``base_seed``), prompt ids from ``seed``."""
    times = arrivals(traffic, traffic["rate_per_s"], seconds)
    n = len(times)
    rng = random.Random(traffic["base_seed"] + 1)
    p, o = traffic["prompt"], traffic["output"]
    plens = bounded_pareto(n, lo=p["lo"], hi=p["hi"], alpha=p["alpha"],
                           rng=rng)
    olens = bounded_pareto(n, lo=o["lo"], hi=o["hi"], alpha=o["alpha"],
                           rng=rng)
    g = np.random.default_rng([seed, 2])
    out = []
    for rid, t in enumerate(times):
        ids = g.integers(1, vocab, size=plens[rid])
        out.append(Req(rid=rid, prompt=tuple(int(x) for x in ids),
                       max_new=olens[rid], arrival_s=t))
    return out


def max_seq(traffic: dict) -> int:
    """Cache positions the longest request of the mix needs."""
    return traffic["prompt"]["hi"] + traffic["output"]["hi"]
