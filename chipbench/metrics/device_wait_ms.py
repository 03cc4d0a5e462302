"""device_wait_ms (ms, program span): mean per batch of the engine's
wait for a batch's outputs to be ready on the device: H2D, the kernel,
pad and crop, after the worker turns to retire the batch
(runtime/engine.py _retire, span engine.wait, report() phase wait).
Read for every ``device_wait_ms.<suffix>``; None from a program without
the phase."""
from harness.stats import engine_phase_ms


def read(run):
    return engine_phase_ms(run, "wait")
