"""kernel_ms (ms, device trace): device time per call of the app's
Mosaic kernels (core/fusion.py lower_group_pallas), one frame a call."""


def read(run):
    t = run.trace
    if not t or not t["calls"] or not t["kernel_s"]:
        return None
    return t["kernel_s"] / t["calls"] * 1e3
