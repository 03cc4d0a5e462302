"""wrapper_ms (ms, device trace): device time per call of everything
else the app's program runs: the pad before each kernel and the crop
after it (core/fusion.py run()), and copies."""


def read(run):
    t = run.trace
    if not t or not t["calls"]:
        return None
    return t["other_s"] / t["calls"] * 1e3
