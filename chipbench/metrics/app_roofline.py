"""app_roofline (%, device trace): the least time a frame needs (each
input read from HBM once and each output written once at the chip's
peak bandwidth, or the stated arithmetic at its peak rate if longer)
over the device time of everything one call of the app runs."""


def read(run):
    t = run.trace
    if not t or not t["calls"]:
        return None
    busy = (t["kernel_s"] + t["other_s"]) / t["calls"]
    if busy <= 0:
        return None
    return run.min_frame_s / busy * 100.0
