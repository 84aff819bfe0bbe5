"""The work of the traced window, counted from the configuration's widths.

Operations and bytes come from the configuration file and the served
requests' own stamps, never from the program's shapes or its dispatch
calls, so a later change to the implementation (chunked prefill, another
step) is read against the same work.  The configuration's reference
module supplies the widths (``linears(c)``: the int8 linear layers of
one layer) and the forward FLOPs of one token at a context length
(``token_flops(c, ctx)``).

A request with ``P`` prompt tokens and ``n`` output tokens makes
``P + n - 1`` forward passes: the prompt's ``P`` between its admission
and its first token, the other ``n - 1`` between its first token and its
finish.  The passes of each stretch are taken as evenly spaced in it;
pass ``j`` of the prompt sees ``j`` positions, pass ``j`` after the
first token ``P + j``.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple


def linear_least_s(linears: Sequence[Tuple[str, int, int]], layers: int,
                   rows: float, peaks: dict) -> float:
    """The least time of one dispatch's int8 linear layers at ``rows``
    rows: per layer the larger of FLOPs over the bf16 peak (W8A16
    multiplies in bf16) and bytes over HBM bandwidth.  Bytes are the
    int8 weights, their float32 column scales, and the bf16 rows read
    and written."""
    t = 0.0
    for _, k, n in linears:
        flops = 2.0 * rows * k * n
        nbytes = k * n + 4 * n + 2 * rows * (k + n)
        t += max(flops / peaks["bf16_flops"],
                 nbytes / peaks["hbm_bytes_per_s"])
    return layers * t


def kernel_least_s(linears, layers: int, calls: int, tokens: float,
                   peaks: dict) -> float:
    """The least time of ``calls`` runs of the int8 linear kernel that
    carried ``tokens`` tokens through every linear layer of every layer:
    each call runs one linear layer of one layer, so the calls make
    ``calls / (len(linears) * layers)`` whole passes over the weights,
    at the mean rows per pass.  Taking the mean rows can only lower the
    least time (it is convex in the rows)."""
    passes = calls / (len(linears) * layers)
    return passes * linear_least_s(linears, layers, tokens / passes, peaks)


def _in_window(a: float, b: float, k: int, w0: float, w1: float):
    """Of ``k`` passes evenly spaced in ``(a, b]`` (pass ``j`` at
    ``a + j (b - a) / k``, j = 1..k), the first and last ``j`` inside
    ``[w0, w1)``; (1, 0) where none is."""
    if k <= 0:
        return 1, 0
    dt = (b - a) / k
    if dt <= 0:
        return (1, k) if w0 <= b < w1 else (1, 0)
    lo = max(1, math.ceil((w0 - a) / dt))
    hi = min(k, math.ceil((w1 - a) / dt) - 1)
    return lo, hi


def window_work(ref, conf: dict, results: List[dict], window) -> Dict:
    """Forward passes (tokens advanced) and model FLOPs inside the
    ``window`` (start, stop), in the serve's time, from the stamps of
    every finished request."""
    a0 = ref.token_flops(conf, 0)
    b = ref.token_flops(conf, 1) - a0
    w0, w1 = window
    tokens, flops = 0, 0.0
    for r in results:
        if r["status"] != "ok" or r["first_token_s"] < 0:
            continue
        p, n = r["prompt_len"], r["n_tokens"]
        for start, end, k, base in (
                (r["admit_s"], r["first_token_s"], p, 0),
                (r["first_token_s"], r["finish_s"], n - 1, p)):
            lo, hi = _in_window(start, end, k, w0, w1)
            if hi < lo:
                continue
            count = hi - lo + 1
            tokens += count
            # context of pass j is base + j; summed over j = lo..hi
            flops += count * a0 + b * (count * base + (lo + hi) * count / 2)
    return {"tokens": tokens, "flops": flops}
