"""Share of the traced window in which no operation ran on the device,
in %."""


def read(run):
    t = run["trace"]
    if not t or t["window_s"] <= 0:
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * 100.0
