"""Device busy time per token advanced in the traced window, in us: the
union of the device's operation intervals over the forward passes that
the served requests made inside the window (``work.window_work``, from
their stamps), however the program dispatched them."""


def read(run):
    t = run["trace"]
    if not t or not t["tokens"]:
        return None
    return t["busy_s"] / t["tokens"] * 1e6
