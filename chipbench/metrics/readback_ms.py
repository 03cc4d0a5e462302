"""readback_ms (ms, program span): mean per batch of the wait for a
batch's outputs and their copy back to the host (runtime/engine.py
_retire, report() phase readback).  Read for every
``readback_ms.<suffix>``."""
from harness.stats import engine_phase_ms


def read(run):
    return engine_phase_ms(run, "readback")
