"""device_idle_pct (%, device trace): 1 - the union of device operation
intervals over the traced window, averaged over the cell's chips."""


def read(run):
    t = run.trace
    if not t or t["window_s"] <= 0:
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * 100.0
