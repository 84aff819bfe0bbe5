"""The w8a16 kernel's share of its roofline in the traced window, in %.

The least time of the kernel's calls in the window (``work.
kernel_least_s``: each call one int8 linear layer of one layer, at the
configuration's widths, carrying the window's tokens), over the device
time of the calls.  The calls are the custom calls that the compiler
names after the kernel's function, ``qmatmul_w8a16.<n>``; where none is
found the metric is left out.
"""
import work

KERNEL = "qmatmul_w8a16."


def read(run):
    t = run["trace"]
    if not t or not t["tokens"]:
        return None
    calls = sum(v for k, v in t["op_count"].items() if k.startswith(KERNEL))
    kernel_s = sum(v for k, v in t["op_time_s"].items()
                   if k.startswith(KERNEL))
    if not calls or kernel_s <= 0:
        return None
    least = work.kernel_least_s(run["linears"], run["layers"], calls,
                                t["tokens"], run["peaks"])
    return least / kernel_s * 100.0
