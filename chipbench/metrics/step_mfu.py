"""Model FLOPs of every token advanced in the traced window, over the
device's busy time times the chip's bf16 peak, in %.

FLOPs come from the configuration's widths and the served requests'
stamps and lengths (``work.py``), never from the program's shapes.
"""


def read(run):
    t = run["trace"]
    if not t or not t["flops"] or t["busy_s"] <= 0:
        return None
    return t["flops"] / (t["busy_s"] * run["peaks"]["bf16_flops"]) * 100.0
