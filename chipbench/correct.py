"""The comparison that decides ``correct``.

After the window, a sample of the requests the engine finished is drawn
from the seed, the longest (prompt plus served tokens) always in it,
until it holds the configuration's ``check.served_tokens`` served
tokens.  The configuration's reference runs once over each prompt with
its served tokens, teacher-forced, at the precision its module states
(``highest`` for any float32 matmul).  Two readings are taken over the
sample's served tokens:

- ``widest_logit_gap``: the widest gap by which a served token's logit
  lies below the reference's best logit at its position: 0 where the
  engine's greedy choice is the reference's, small where rounding flips
  a near tie, large where the served path computes something else;
- ``mean_logit_gap``: the same gap averaged over every served token.
  A flip needs a near tie no wider than the served path's error, and
  its gap is of that error's size, so the mean grows as the square of
  the error where the widest gap grows as the error;
- ``off_reference_pct``: the share of served tokens, in %, that are not
  the reference's first choice.

Each reading that the configuration's ``check.limits`` names is
compared with its limit; so is the count of requests not finished,
whose limit is 0.

Every sequence is padded at the end to the cell's cache length rounded
up to 256 positions (the reference is causal, so padding changes
nothing before it): one compiled reference program per cell.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

BUCKET = 256


def sample(by_rid: dict, outputs: Dict[int, List[int]], seed: int,
           served_tokens: int) -> List[int]:
    """Request ids to compare: the longest finished request, then others
    in an order drawn from ``seed`` until ``served_tokens`` are held."""
    done = sorted(rid for rid, toks in outputs.items() if toks)
    if not done:
        return []
    size = lambda rid: len(by_rid[rid].prompt) + len(outputs[rid])
    longest = max(done, key=lambda rid: (size(rid), -rid))
    rest = [r for r in done if r != longest]
    order = np.random.default_rng([seed, 3]).permutation(len(rest))
    picked, n = [longest], len(outputs[longest])
    for i in order:
        if n >= served_tokens:
            break
        picked.append(rest[i])
        n += len(outputs[rest[i]])
    return picked


def padded(prompt: Sequence[int], served: Sequence[int], max_seq: int):
    """(inputs, targets, first scored position, length) padded to
    ``max_seq`` rounded up to a multiple of 256."""
    seq = list(prompt) + list(served)
    n = len(seq) - 1
    t = -(-max_seq // BUCKET) * BUCKET
    inp = np.zeros((t,), np.int32)
    tgt = np.zeros((t,), np.int32)
    inp[:n] = seq[:-1]
    tgt[:n] = seq[1:]
    return inp, tgt, len(prompt) - 1, n


_FNS: dict = {}


def _gap_fn(ref, conf: dict):
    """The jitted gap of one configuration's reference (one per process)."""
    import jax
    import jax.numpy as jnp
    key = (id(ref), conf["name"])
    if key not in _FNS:
        def fn(w, inp, tgt):
            with jax.default_matmul_precision("highest"):
                lg = ref.logits(w, inp, conf)
            return lg.max(-1) - jnp.take_along_axis(lg, tgt[:, None],
                                                    -1)[:, 0]
        _FNS[key] = jax.jit(fn)
    return _FNS[key]


def readings(ref, conf: dict, weights, by_rid: dict,
             outputs: Dict[int, List[int]], rids: Sequence[int],
             max_seq: int) -> Dict[str, float]:
    """Both readings over the served tokens of ``rids``."""
    fn = _gap_fn(ref, conf)
    gaps = []
    for rid in rids:
        inp, tgt, first, n = padded(by_rid[rid].prompt, outputs[rid],
                                    max_seq)
        gaps.append(np.asarray(fn(weights, inp, tgt))[first:n])
    g = np.concatenate(gaps)
    return {"widest_logit_gap": float(g.max()),
            "mean_logit_gap": float(g.mean()),
            "off_reference_pct": float((g > 0).mean() * 100.0)}


def check(ref, conf: dict, weights, by_rid: dict,
          outputs: Dict[int, List[int]], *, seed: int, max_seq: int,
          failed: int):
    """Every number compared, beside its limit, and all the readings.
    A limit of ``None`` (not set from readings yet) fails every run; no
    finished request to compare reads as numbers no limit admits."""
    rids = sample(by_rid, outputs, seed, conf["check"]["served_tokens"])
    read = (readings(ref, conf, weights, by_rid, outputs, rids, max_seq)
            if rids else dict.fromkeys(
                ("widest_logit_gap", "mean_logit_gap", "off_reference_pct"),
                1e30))
    checks = {"requests_not_finished": {"value": failed, "limit": 0}}
    for name, lim in conf["check"]["limits"].items():
        checks[name] = {"value": read[name],
                        "limit": lim if lim is not None else -1.0}
    return checks, read
