"""queue_wait_ms (ms, program span): mean time a frame waits in the
engine's queue before a batch takes it (runtime/engine.py, report()
phase queue_wait).  Read for every ``queue_wait_ms.<suffix>``."""
from harness.stats import engine_phase_ms


def read(run):
    return engine_phase_ms(run, "queue_wait")
