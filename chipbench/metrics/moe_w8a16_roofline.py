"""The held-expert kernel's share of its roofline in the traced window,
in %.

The least time of the kernel's calls in the window over their device
time.  A call computes one projection (``gate_up`` or ``down``) of every
expert held here for one layer, so the calls make ``calls / (projections
x layers)`` passes over the held experts, carrying the window's tokens.
Per pass and per held expert's projection the least time is the larger
of its FLOPs over the bf16 peak and its bytes (int8 weights, float32
column scales, bf16 rows in and out) over HBM bandwidth
(``work.linear_least_s``).  Widths come from the ``expert<e>.<proj>``
entries of the configuration's ``linears``, never from the program.  The
calls are the custom calls named after the kernel, ``moe_w8a16.<n>``;
where none is found, or the configuration holds no experts, the metric
is left out.
"""
import work

KERNEL = "moe_w8a16."


def read(run):
    t = run["trace"]
    if not t or not t["tokens"]:
        return None
    experts = [ln for ln in run["linears"] if ln[0].startswith("expert")]
    calls = sum(v for k, v in t["op_count"].items() if k.startswith(KERNEL))
    kernel_s = sum(v for k, v in t["op_time_s"].items()
                   if k.startswith(KERNEL))
    if not experts or not calls or kernel_s <= 0:
        return None
    projections = {name.split(".", 1)[1] for name, _, _ in experts}
    passes = calls / (len(projections) * run["layers"])
    least = passes * work.linear_least_s(experts, run["layers"],
                                         t["tokens"] / passes, run["peaks"])
    return least / kernel_s * 100.0
