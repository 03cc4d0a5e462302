"""stage_ms (ms, program span): mean per batch of the host staging
copy and the dispatch, transfer to the device included
(runtime/batching.py, report() phases stack + launch).  Read for every
``stage_ms.<suffix>``."""
from harness.stats import engine_phase_ms


def read(run):
    return engine_phase_ms(run, "stack", "launch")
